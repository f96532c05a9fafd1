"""Cost-model-driven serving placement — the online Mensa oracle.

This module closes the loop between the paper's characterization machinery
(`core/characterize`, `core/clustering`, `core/costmodel`, the accelerator
configs in `core/accelerators`) and the live serving engine.  Instead of one
global set of execution knobs, the `ExecutionOracle`:

  1. builds one `LayerSpec` per served layer at the engine's actual geometry
     (prefill chunks at batch 1, lockstep decode at `slots` x 1 token against
     `max_len` of KV) and characterizes each via `characterize_layer`;
  2. clusters the layers with the paper's `rule_cluster` boxes and verifies
     the grouping against a seeded `kmeans_cluster` run (the agreement score
     is recorded on the plan, so a drifting k-means can't silently change
     decisions);
  3. prices every layer on its cluster's designated Mensa accelerator with
     `layer_cost` and emits one `ExecutionPolicy` per cluster — kernel
     variant (Pallas vs the XLA reference path), prefill chunk size, bucket
     ladder, preferred mesh sharding axis — rolled up into a whole-engine
     `PlacementPlan` with predicted per-phase latency.

Policies decide *how* the engine executes, never *what* it computes: a plan
only selects among token-identical implementations, is resolved entirely
before `warmup()`, and is immutable afterwards, so the compiled-program
inventory stays closed (the zero-recompile invariant).  Pallas kernel
variants are only selected when the backend can lower them natively
(`jax.default_backend() == "tpu"`); on CPU CI the oracle resolves to the XLA
path and `--policy auto` is bitwise-identical to the fixed-knob engine.  On
a mesh every selected kernel runs under shard_map; the one that cannot (the
paged decode kernel, `_MESH_XLA`) stays on XLA with its reason in the plan.

`benchmarks/calibrate.py` fits the plan's predictions against measured
engine phase times and gates the residual in CI — see docs/placement.md.
"""
from __future__ import annotations

import json
from dataclasses import dataclass, field

from ..core.accelerators import CLUSTER_TO_ACCELERATOR
from ..core.characterize import LayerCharacteristics, characterize_layer
from ..core.clustering import agreement, kmeans_cluster, rule_cluster
from ..core.costmodel import layer_cost
from ..core.layerspec import LayerKind, LayerSpec
from ..models.model_config import ArchConfig

BYTES = 2.0  # serving runs bf16 activations/params (matches core/strategy.py)

# Block kinds whose execution the policy can switch between a Pallas kernel
# and the XLA reference path, and the ArchConfig knob that carries the choice
# into the model code.  "ssm" is deliberately absent for serving: the fused
# pavlov_ssm kernel returns outputs only (no final state), and serving
# prefill must hand the scan state to decode — the oracle therefore keeps
# SSM blocks on the XLA scan and records the reason on the policy.
# "mamba2" has no kernel at all: its mixer is XLA chunked SSD.
KIND_TO_IMPL_KNOB = {
    "attn": "attn_impl",
    "local": "attn_impl",
    "dec": "attn_impl",
    "enc": "attn_impl",
    "rec": "rglru_impl",
}

# Concrete kernels behind each knob, per phase — display metadata for stats,
# --policy-dump, and docs; the model code routes on (knob, mode) itself.
_PALLAS_VARIANTS = {
    "attn_impl": {"prefill": "pallas_flash", "decode": "pallas_paged"},
    "rglru_impl": {"prefill": "pallas_rglru", "decode": "pallas_rglru"},
}

# (knob, phase) kernels a mesh engine cannot run, with the reason the plan
# records.  Every other kernel runs under shard_map on a mesh
# (models/layout.MeshLayout.shard).
_MESH_XLA = {
    ("attn_impl", "decode"):
        "decode attention stays on the XLA paged gather on a mesh: the "
        "paged kernel reads a slot's blocks from one device's pool, but "
        "the pool's block axis is striped over the data axes and a slot's "
        "blocks may sit on any shard",
}


def _bucket_ladder(max_len: int, min_bucket: int = 16,
                   max_bucket: int | None = None) -> tuple[int, ...]:
    top = min(max_bucket, max_len) if max_bucket else max_len
    # engine.prefill_buckets is the single source of truth for the ladder
    # shape; imported lazily because serve/engine.py consumes this module.
    from .engine import prefill_buckets
    return prefill_buckets(top, min_bucket)


# ------------------------------------------------------------------ policies
@dataclass(frozen=True)
class ExecutionPolicy:
    """Per-cluster execution decision: how one group of layers should run."""

    cluster: int                      # Mensa cluster id (1..5)
    kinds: tuple[str, ...]            # block kinds governed by this policy
    accelerator: str                  # designated Mensa accelerator (paper map)
    kernel: str                       # "pallas" | "xla"
    variants: tuple[str, ...]         # concrete kernels, e.g. "pallas_flash"
    prefill_chunk: int                # chunk width this cluster wants per tick
    buckets: tuple[int, ...]          # prompt bucket ladder
    sharding_axis: str | None         # preferred mesh axis ("data"/"model")
    predicted_prefill_s: float        # summed layer_cost, one prefill chunk
    predicted_decode_s: float         # summed layer_cost, one decode step
    note: str = ""                    # why a kernel was (not) selected

    def summary(self) -> dict:
        out = {
            "cluster": self.cluster,
            "kinds": list(self.kinds),
            "accelerator": self.accelerator,
            "kernel": self.kernel,
            "variants": list(self.variants),
            "prefill_chunk": self.prefill_chunk,
            "sharding_axis": self.sharding_axis,
            "predicted_prefill_s": self.predicted_prefill_s,
            "predicted_decode_s": self.predicted_decode_s,
        }
        if self.note:
            out["note"] = self.note
        return out


@dataclass(frozen=True)
class PlacementPlan:
    """Whole-engine resolution of per-cluster policies.

    Frozen and tuple-valued on purpose: a plan is resolved once, before the
    engine compiles anything, and two plans for the same (arch, geometry,
    backend) compare equal — the determinism the tests pin down.
    """

    arch: str
    source: str                       # "auto" (oracle) | "fixed" (constructor knobs)
    backend: str                      # backend kernels were resolved against
    policies: tuple[ExecutionPolicy, ...] = ()
    layer_kinds: tuple[str, ...] = ()
    layer_clusters: tuple[int, ...] = ()   # cluster id per model layer
    buckets: tuple[int, ...] = ()
    prefill_chunk: int = 0
    sharding_axis: str | None = None
    # ArchConfig override items ({knob: impl}), per phase — all RUNTIME_SAFE
    prefill_overrides: tuple[tuple[str, str], ...] = ()
    decode_overrides: tuple[tuple[str, str], ...] = ()
    predicted_prefill_s: float = 0.0  # whole model, one full prefill chunk
    predicted_decode_s: float = 0.0   # whole model, one lockstep decode step
    rule_kmeans_agreement: float = 0.0
    # per-role engine knobs for disaggregated serving (serve/disagg.py):
    # (("prefill", (("buckets", (...)), ("prefill_chunk", n))), ("decode", ()))
    # — a dedicated prefill submesh has no decoders to protect, so its chunk
    # is freed from the decode-latency bound the interleaved chunk obeys
    role_knobs: tuple = ()

    @property
    def per_role(self) -> dict:
        """``{"prefill": {...}, "decode": {...}}`` view of ``role_knobs``."""
        return {role: dict(kv) for role, kv in self.role_knobs}

    @property
    def prefill_cfg_overrides(self) -> dict:
        return dict(self.prefill_overrides)

    @property
    def decode_cfg_overrides(self) -> dict:
        return dict(self.decode_overrides)

    def policy_for(self, kind: str) -> ExecutionPolicy | None:
        for p in self.policies:
            if kind in p.kinds:
                return p
        return None

    def summary(self) -> dict:
        """JSON-able view — EngineStats `placement` section / --policy-dump."""
        return {
            "arch": self.arch,
            "source": self.source,
            "backend": self.backend,
            "buckets": list(self.buckets),
            "prefill_chunk": self.prefill_chunk,
            "sharding_axis": self.sharding_axis,
            "layer_clusters": list(self.layer_clusters),
            "layer_kinds": list(self.layer_kinds),
            "policies": [p.summary() for p in self.policies],
            "prefill_overrides": dict(self.prefill_overrides),
            "decode_overrides": dict(self.decode_overrides),
            "predicted": {
                "prefill_chunk_s": self.predicted_prefill_s,
                "decode_step_s": self.predicted_decode_s,
            },
            "rule_kmeans_agreement": self.rule_kmeans_agreement,
            "role_knobs": {role: dict(kv) for role, kv in self.role_knobs},
        }

    def dumps(self, indent: int = 2) -> str:
        return json.dumps(self.summary(), indent=indent, sort_keys=True)


def fixed_plan(cfg: ArchConfig, *, buckets: tuple[int, ...],
               prefill_chunk: int, backend: str = "") -> PlacementPlan:
    """The no-oracle plan: records the constructor-global knobs so EngineStats
    always has a placement section, but decides nothing."""
    return PlacementPlan(
        arch=cfg.name, source="fixed", backend=backend,
        layer_kinds=tuple(cfg.layer_kinds),
        buckets=tuple(buckets), prefill_chunk=int(prefill_chunk))


# -------------------------------------------------------------------- oracle
@dataclass
class ExecutionOracle:
    """Characterize -> cluster -> cost -> per-cluster `ExecutionPolicy`.

    Pure given its inputs: the same (cfg, geometry, backend, seed) always
    resolves to the same `PlacementPlan` — `resolve()` touches no global
    state and no clocks, so CI decisions are reproducible.
    """

    cfg: ArchConfig
    slots: int = 4
    max_len: int = 512
    min_bucket: int = 16
    max_bucket: int | None = None
    mesh_axes: tuple[str, ...] = ()   # e.g. ("data", "model"); () = no mesh
    backend: str | None = None        # None: ask jax.default_backend()
    seed: int = 0                     # k-means verification seed
    _chars: list = field(default_factory=list, repr=False)

    # ---------------------------------------------------------- layer specs
    def _spec(self, kind: str, *, seq: int, batch: int,
              kv_len: int = 0) -> LayerSpec:
        """One LayerSpec for one block class at an explicit serving geometry
        (mirrors core/strategy._block_specs, but phase-aware: decode runs
        seq=1 against kv_len of context)."""
        cfg = self.cfg
        B = dict(bytes_per_param=BYTES, bytes_per_act=BYTES, batch=batch)
        if kind in ("attn", "local", "dec", "enc"):
            return LayerSpec(
                name=kind, kind=LayerKind.ATTENTION, hidden=cfg.d_model,
                heads=cfg.num_heads, kv_heads=cfg.num_kv_heads,
                head_dim=cfg.head_dim, seq_len=seq, kv_len=kv_len,
                window=cfg.window if kind == "local" else 0,
                in_features=cfg.d_model, **B)
        if kind == "rec":
            return LayerSpec(name="rec", kind=LayerKind.RGLRU,
                             in_features=cfg.d_model, hidden=cfg.d_rnn,
                             seq_len=seq, **B)
        if kind in ("ssm", "mamba2"):
            return LayerSpec(name=kind, kind=LayerKind.SSM,
                             in_features=cfg.d_model, hidden=cfg.d_inner,
                             state=cfg.d_state, seq_len=seq, **B)
        if kind == "ffn":
            if cfg.ffn_kind == "moe":
                return LayerSpec(name="moe", kind=LayerKind.MOE,
                                 in_features=cfg.d_model, hidden=cfg.d_ff,
                                 experts=cfg.num_experts, top_k=cfg.top_k,
                                 seq_len=seq, **B)
            width = 3 * cfg.d_ff if cfg.ffn_kind == "glu" else 2 * cfg.d_ff
            return LayerSpec(name="ffn", kind=LayerKind.FC,
                             in_features=cfg.d_model, out_features=width,
                             **{**B, "batch": batch * seq})
        if kind == "embed":
            return LayerSpec(name="embed", kind=LayerKind.EMBEDDING,
                             vocab=cfg.vocab_padded, out_features=cfg.d_model,
                             seq_len=seq, **B)
        raise ValueError(f"unknown block kind {kind!r}")

    def _phase_specs(self, *, seq: int, batch: int,
                     kv_len: int = 0) -> list[tuple[int, str, LayerSpec]]:
        """(layer index, block kind, spec) for every schedulable unit: one
        primary block per layer, the FFN that follows every non-SSM layer,
        plus the embedding table."""
        cfg = self.cfg
        out: list[tuple[int, str, LayerSpec]] = []
        for i, kind in enumerate(cfg.layer_kinds):
            out.append((i, kind, self._spec(kind, seq=seq, batch=batch,
                                            kv_len=kv_len)))
            if kind != "ssm" and cfg.ffn_kind != "none":
                out.append((i, "ffn", self._spec("ffn", seq=seq, batch=batch)))
        out.append((-1, "embed", self._spec("embed", seq=seq, batch=batch)))
        return out

    # ------------------------------------------------------------ resolution
    def _characterize(self) -> list[tuple[int, str, LayerCharacteristics]]:
        """Characterize at full-context geometry (one request, max_len tokens)
        — the per-inference view the paper clusters on."""
        if not self._chars:
            for i, kind, spec in self._phase_specs(seq=self.max_len, batch=1):
                self._chars.append(
                    (i, kind, characterize_layer(self.cfg.name, i, spec)))
        return self._chars

    def _cluster_of(self, kind: str) -> int:
        for _, k, c in self._characterize():
            if k == kind:
                return rule_cluster(c).cluster
        raise KeyError(kind)

    def _kernel_for(self, kinds: tuple[str, ...]
                    ) -> tuple[str, dict, list, str]:
        """(kernel, {phase: knobs run as Pallas}, variants, note)."""
        backend = self.backend
        pallas_ok = backend == "tpu"
        knobs = sorted({KIND_TO_IMPL_KNOB[k] for k in kinds
                        if k in KIND_TO_IMPL_KNOB})
        xla_only = {
            "ssm": "ssm kernel yields no carry state — serving stays on "
                   "the XLA scan",
            "mamba2": "mamba2 has no kernel — its mixer runs as XLA "
                      "chunked SSD in prefill and one recurrence step in "
                      "decode"}
        if not knobs:
            return "xla", {}, [], "; ".join(
                why for k, why in xla_only.items() if k in kinds)
        if not pallas_ok:
            return "xla", {}, [], \
                f"backend {backend!r} lowers via XLA reference path"
        off = {kp: why for kp, why in _MESH_XLA.items()
               if self.mesh_axes and kp[0] in knobs}
        if "attn_impl" in knobs and {"attn", "dec"} & set(kinds):
            import jax.numpy as jnp

            from ..kernels.paged_attention.kernel import lowers
            cfg = self.cfg
            if not lowers(cfg.num_kv_heads, cfg.head_dim,
                          jnp.dtype(cfg.compute_dtype).itemsize):
                off[("attn_impl", "decode")] = (
                    f"decode attention stays on the XLA paged gather: the "
                    f"paged kernel cannot copy pages of {cfg.num_kv_heads} "
                    f"kv heads x {cfg.head_dim} out of the pool")
        phases = {ph: [k for k in knobs if (k, ph) not in off]
                  for ph in ("prefill", "decode")}
        variants = sorted({_PALLAS_VARIANTS[k][ph]
                           for ph, ks in phases.items() for k in ks})
        if not variants:
            return "xla", {}, [], "; ".join(off.values())
        return "pallas", phases, variants, "; ".join(off.values())

    def _sharding_axis(self, compute_centric: bool) -> str | None:
        if not self.mesh_axes:
            return None
        # compute-centric clusters want their GEMMs split on the model axis;
        # memory-centric clusters scale by replicating over data (slots)
        want = "model" if compute_centric else "data"
        if want in self.mesh_axes:
            return want
        return self.mesh_axes[0]

    def _chunk_for(self, cluster_kinds: tuple[str, ...],
                   ladder_top: int) -> int:
        """Recurrent clusters bound the per-tick scan length (decode latency
        for running slots is gated on one chunk's scan); everything else
        takes the widest chunk (fewest chunk program invocations)."""
        if any(k in ("rec", "ssm", "mamba2") for k in cluster_kinds):
            return max(self.min_bucket, min(ladder_top, self.cfg.scan_chunk))
        return ladder_top

    def resolve(self) -> PlacementPlan:
        cfg = self.cfg
        if self.backend is None:
            import jax
            self.backend = jax.default_backend()
        buckets = _bucket_ladder(self.max_len, self.min_bucket, self.max_bucket)
        chars = self._characterize()

        # rule clustering, verified against the seeded k-means run
        assignments = {}
        for i, kind, c in chars:
            assignments[(i, kind)] = rule_cluster(c).cluster
        km_agreement = agreement([c for _, _, c in chars]) if len(chars) >= 2 \
            else 1.0
        layer_clusters = tuple(assignments[(i, kind)]
                               for i, kind in enumerate(cfg.layer_kinds))

        # group block kinds by cluster id
        by_cluster: dict[int, list[str]] = {}
        for (_, kind), cid in assignments.items():
            by_cluster.setdefault(cid, [])
            if kind not in by_cluster[cid]:
                by_cluster[cid].append(kind)

        # phase geometries: one prefill chunk at batch 1; one lockstep decode
        # step over every slot against the full KV context.  The engine chunk
        # is the tightest recommendation across clusters (recurrent clusters
        # bound the per-tick scan; everything else accepts the widest chunk).
        chunk = self._chunk_for(tuple(set(cfg.layer_kinds)), buckets[-1])
        prefill_specs = self._phase_specs(seq=chunk, batch=1)
        decode_specs = self._phase_specs(seq=1, batch=self.slots,
                                         kv_len=self.max_len)

        def _phase_cost(specs, kinds) -> float:
            total = 0.0
            for i, kind, spec in specs:
                if kind not in kinds:
                    continue
                acc = CLUSTER_TO_ACCELERATOR[assignments[(i, kind)]]
                total += layer_cost(spec, acc).latency_s
            return total

        policies = []
        prefill_over: dict[str, str] = {}
        decode_over: dict[str, str] = {}
        for cid in sorted(by_cluster):
            kinds = tuple(sorted(by_cluster[cid]))
            kernel, phases, variants, note = self._kernel_for(kinds)
            for knob in phases.get("prefill", ()):
                prefill_over[knob] = "pallas"
            for knob in phases.get("decode", ()):
                decode_over[knob] = "pallas"
            compute_centric = any(c.compute_centric for (_, k, c) in chars
                                  if k in kinds)
            policies.append(ExecutionPolicy(
                cluster=cid, kinds=kinds,
                accelerator=CLUSTER_TO_ACCELERATOR[cid].name,
                kernel=kernel, variants=tuple(variants),
                prefill_chunk=self._chunk_for(kinds, buckets[-1]),
                buckets=buckets,
                sharding_axis=self._sharding_axis(compute_centric),
                predicted_prefill_s=_phase_cost(prefill_specs, set(kinds)),
                predicted_decode_s=_phase_cost(decode_specs, set(kinds)),
                note=note))

        all_kinds = {k for _, k, _ in chars}
        plan_axis = None
        if self.mesh_axes:
            axes = [p.sharding_axis for p in policies if p.sharding_axis]
            plan_axis = ("model" if "model" in axes else
                         (axes[0] if axes else self.mesh_axes[0]))
        # per-role knobs for the disaggregated pair: the interleaved chunk
        # above is bounded by the recurrent scan so a long prompt can't
        # freeze running decoders — a dedicated prefill submesh has none, so
        # its chunk widens to the full ladder top (fewest chunk invocations;
        # token-identical by the chunked==unchunked prefill invariant).  The
        # decode role takes no prefill knobs at all.
        role_knobs = (("prefill", (("buckets", buckets),
                                   ("prefill_chunk", buckets[-1]))),
                      ("decode", ()))
        return PlacementPlan(
            arch=cfg.name, source="auto", backend=self.backend,
            policies=tuple(policies),
            layer_kinds=tuple(cfg.layer_kinds),
            layer_clusters=layer_clusters,
            buckets=buckets, prefill_chunk=chunk,
            sharding_axis=plan_axis,
            prefill_overrides=tuple(sorted(prefill_over.items())),
            decode_overrides=tuple(sorted(decode_over.items())),
            predicted_prefill_s=_phase_cost(prefill_specs, all_kinds),
            predicted_decode_s=_phase_cost(decode_specs, all_kinds),
            rule_kmeans_agreement=km_agreement,
            role_knobs=role_knobs)


def resolve_policy(cfg: ArchConfig, **kw) -> PlacementPlan:
    """Convenience wrapper: one-shot oracle resolution."""
    return ExecutionOracle(cfg, **kw).resolve()


def verify_kmeans_agreement(cfg: ArchConfig, *, max_len: int = 512,
                            seed: int = 0, min_agreement: float = 0.5) -> float:
    """Assert the rule clusters are recoverable by the seeded k-means run for
    a served arch — the reproducibility check the tests pin per arch."""
    oracle = ExecutionOracle(cfg, max_len=max_len, seed=seed, backend="cpu")
    chars = [c for _, _, c in oracle._characterize()]
    labels_a, _ = kmeans_cluster(chars, seed=seed)
    labels_b, _ = kmeans_cluster(chars, seed=seed)
    if list(labels_a) != list(labels_b):
        raise AssertionError("kmeans_cluster is not deterministic under a seed")
    score = agreement(chars)
    if score < min_agreement:
        raise AssertionError(
            f"rule-vs-kmeans agreement {score:.2f} < {min_agreement} "
            f"for {cfg.name}")
    return score
