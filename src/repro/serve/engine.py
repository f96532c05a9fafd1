"""Batched serving engine: continuous batching over a fixed slot pool.

The engine owns per-slot KV/recurrent state; requests are admitted into free
slots, prefilled, then advanced in lockstep decode steps.  Finished slots
(EOS or max_tokens) are evicted and refilled — the standard continuous-
batching pattern (vLLM-style), with a static slot count so every jitted shape
is fixed.

Prefill is *bucketed, batched, and jitted*: prompts are right-padded to a
small set of power-of-two buckets, same-bucket admissions in one tick are
stacked into one ``(N, bucket)`` prefill program (N itself bucketed to powers
of two up to ``max_prefill_batch``), and the padded prefill + splice-into-
slots runs as one compiled call — lengths and target slots are traced, so the
program inventory is exactly |buckets| x |batch buckets|.

Prompts longer than the largest bucket take the *chunked* path: the prompt is
split into ``prefill_chunk``-wide pieces that run one per tick, interleaved
with decode steps, each resuming from the slot's spliced state (cache
continuation for causal/sliding-window attention, conv + RG-LRU/SSM carry for
the recurrent families).  Decode latency for already-running slots therefore
stays bounded by one chunk, not one full long prompt.

KV storage is optionally *paged* (``kv_block_size=...``): full-attention
layers keep their KV in a global pool of fixed-size blocks addressed through
per-slot block tables (serve/kvpool.py), so KV memory scales with actual
sequence lengths instead of ``slots x max_len``, finished requests release
their blocks the same tick they retire, and a radix-tree prefix cache maps
prompts sharing a token prefix onto shared read-only blocks — the shared
portion skips prefill entirely (it resumes through the chunk-continuation
program at ``offset = matched``), with a single block clone (copy-on-write)
when the divergence falls inside a block.  Sliding-window layers keep their
dense ring (already right-sized at ``window``) and recurrent/SSM layers their
fixed-size state — per-layer-class memory organization, the Mensa reading of
the paper's memory-handling pitfall.

Sampling is per-request (temperature / top-k / top-p / seed carried in the
slot pool) and happens inside the jitted programs; greedy requests take the
exact argmax path, bit-for-bit identical to a sampling-free engine.

Serving is optionally *sharded* (``mesh=...``): model weights route through
the Mensa cluster specs in ``launch/shardings.py``, per-slot serving state
shards its slot axis over the mesh's data axes (``serve_state_specs``), and a
paged block pool shards its BLOCK axis the same way — each shard owns a
contiguous stripe of physical blocks, mirrored host-side by the pool's
per-shard accounting.  Every program is jitted with ``NamedSharding``-pinned
state outputs, so the compiled inventory stays closed (zero recompiles) on
1, 2, or 8 devices alike; on a pure data-parallel mesh no per-slot reduction
ever crosses a shard and generated tokens are bitwise identical to the
single-device engine.

``step`` interleaves work per tick — in-flight chunks advance, then at most
``max_prefill_per_step`` admissions, then one lockstep decode step whose
``active`` mask freezes dead and mid-prefill slots bit-for-bit.

Per the Mensa reading: prefill steps are compute-centric (Pascal cluster) and
decode steps memory-centric (Jacquard/Pavlov clusters); the engine keeps them
as separate jitted programs so each lowers with its own strategy — pass
``prefill_model`` / ``decode_model`` built from per-phase
``core.executor.execution_profile`` overrides to specialize each program.

Serving is optionally *disaggregated* (``role=...``): a ``role="prefill"``
engine runs bucketed/chunked prefill only and stages finished slots for
export; a ``role="decode"`` engine owns admission of finished prefills via
:meth:`adopt`, which remaps fresh blocks in its own pool and scatters the
visiting "suitcase" (the slot's state row plus its KV block contents) into
them — a device-to-device block copy, never a re-layout.  The two roles pin
to disjoint submeshes of one device set (``launch.mesh.make_role_meshes``),
so a prefill burst can no longer inflate decode latency — the DistServe
reading of the paper's one-size-fits-none argument, applied to request
phases.  ``serve.disagg.DisaggEngine`` couples the pair.  Each role warms
only its own closed program inventory (prefill + export vs decode + import),
keeping zero-recompile guarantees per submesh.

The engine is *observable by default* (repro/obs): every request's lifecycle
(submit → admit → prefill/chunk → decode → stall → finish/abort) lands in a
ring-buffered :class:`~repro.obs.Tracer` — one track per slot, per-tick
counter tracks, exportable as Chrome trace-event JSON via
:meth:`ServeEngine.save_trace` — and every section, program call or host
phase of a tick, goes through the tracer's one span call
(:meth:`~repro.obs.Tracer.section`): a :class:`~repro.obs.Timed` whose
program sections block on their outputs first (JAX dispatch is async; an
unsynchronized stamp times the enqueue, not the compute), one ring event,
and, when profiling, a profiler annotation of the same name.  The engine
never reads ``time.perf_counter`` directly: all stamps come from the
tracer's clock, so spans, stats, and TTFTs share one timeline (statically
enforced by jitlint JL008).  Aggregates go to the
``EngineStats.metrics`` registry (log2 histograms + counters), serialized as
the versioned ``obs`` section of ``summary()``.
"""
from __future__ import annotations

import warnings
from collections import deque
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from ..core.accelerators import host_chip
from ..models.attention import PagedKVCache
from ..models.layout import MeshLayout
from ..models.transformer import Model
from ..obs import MetricsRegistry, ProgramRegistry, Tracer
from ..obs.drift import drift_report, plan_predictions
from .kvpool import PagedKVManager
from .sampling import sample_tokens

#: tracer track ids: queue-level request events on 0, slot ``i`` on ``1 + i``,
#: engine-wide spans (decode ticks, warmup) on ``1 + slots``
TRACK_REQUESTS = 0


# ------------------------------------------------------------------- buckets
def prefill_buckets(max_len: int, min_bucket: int = 16) -> tuple[int, ...]:
    """Power-of-two prompt buckets up to max_len: one compile per bucket.
    When max_len is not itself a power of two, a final max_len-sized bucket
    covers the gap so no prompt below the cache size is rejected."""
    out = []
    b = min_bucket
    while b <= max_len:
        out.append(b)
        b *= 2
    if not out:
        raise ValueError(f"max_len {max_len} < min_bucket {min_bucket}")
    if out[-1] < max_len:
        out.append(max_len)
    return tuple(out)


def bucket_for(n: int, buckets: tuple[int, ...]) -> int:
    """Smallest bucket that fits an n-token prompt."""
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(f"prompt length {n} exceeds largest bucket {buckets[-1]}")


# --------------------------------------------------------------------- stats
@dataclass
class EngineStats:
    """Engine-side serving metrics, accumulated across ticks."""
    requests_completed: int = 0
    requests_aborted: int = 0           # unfinished when run() hit max_steps
    tokens_generated: int = 0
    prefills: int = 0                   # requests prefilled (all paths)
    prefills_chunked: int = 0           # requests prefilled via the chunked path
    prefill_calls: int = 0              # compiled batched-prefill invocations
    prefill_chunks: int = 0             # chunk-continuation invocations
    prefill_prompt_tokens: int = 0
    # prompt tokens actually run through a prefill program — prefix-cache
    # hits skip the shared portion, so computed < prompt when the cache hits
    prefill_tokens_computed: int = 0
    prefill_padded_tokens: int = 0
    prefill_time_s: float = 0.0
    decode_steps: int = 0
    decode_time_s: float = 0.0
    # TTFT: count/sum/max are exact streaming aggregates; percentiles come
    # from the fixed-size log2 histogram in ``metrics`` (O(1) memory on
    # long-lived engines, within one bucket width of exact)
    ttft_count: int = 0
    ttft_sum: float = 0.0
    ttft_max: float = 0.0
    # counters + log2 histograms (TTFT, per-tick decode latency, tokens/tick,
    # prefill padding waste) — the versioned ``obs`` section of summary()
    metrics: MetricsRegistry = field(default_factory=MetricsRegistry)
    occupancy_sum: float = 0.0          # sum over ticks of busy/slots
    ticks: int = 0
    bucket_counts: dict = field(default_factory=dict)
    batch_counts: dict = field(default_factory=dict)   # rows per prefill call
    prefill_compiles: int = 0           # jit cache entries (incl. chunk prog)
    decode_compiles: int = 0
    wall_time_s: float = 0.0
    # ---- paged KV pool (all zero on dense engines) ----
    kv_pool_blocks: int = 0             # physical blocks in the pool
    kv_block_size: int = 0
    kv_blocks_in_use: int = 0           # referenced blocks, end of last tick
    kv_blocks_peak: int = 0
    kv_blocks_cached: int = 0           # evictable prefix-cache blocks
    kv_occupancy_sum: float = 0.0       # sum over ticks of in_use/pool
    prefix_queries: int = 0
    prefix_hits: int = 0
    prefix_tokens_reused: int = 0
    blocks_copied: int = 0              # copy-on-write clones
    blocks_evicted: int = 0             # LRU evictions of cached blocks
    # the KV manager's own work: radix nodes looked at by LRU eviction and
    # walked by the admission supply check (kvpool work counters)
    evict_scanned: int = 0
    reclaim_walked: int = 0
    decode_stalls: int = 0              # slot-ticks frozen waiting for blocks
    # ---- recurrent slot state (conv + RG-LRU/SSM/Mamba-2 carries) ----
    # {kind: {"layers", "bytes_per_slot"}}; empty for pure-attention stacks
    rec_state_kinds: dict = field(default_factory=dict)
    rec_state_slots: int = 0            # slots the state is held for
    rec_slots_live: int = 0             # slots holding a request's state,
    #                                     end of last tick
    rec_chunks_resumed: int = 0         # chunk calls that carried a state in
    prefix_cache_note: str = ""         # why the prefix cache is off
    # ---- sharded pool (mesh engines; kv_shards == 1 otherwise) ----
    kv_shards: int = 1
    kv_in_use_per_shard: list = field(default_factory=list)
    kv_peak_per_shard: list = field(default_factory=list)   # sums to peak
    # ---- disaggregated handoff (role engines; all zero interleaved) ----
    handoffs: int = 0                   # slots exported (prefill role) or
    #                                     adopted (decode role)
    handoff_time_s: float = 0.0         # export/import program time
    handoff_stalls: int = 0             # adoptions deferred: no free slot or
    #                                     no blocks on the decode pool
    # ---- placement (serve/placement.py plan summary; set by the engine) ----
    placement: dict = field(default_factory=dict)
    # ---- program cost registry (obs/programs.py; attached by the engine) ----
    programs: ProgramRegistry | None = None

    def record_ttft(self, v: float) -> None:
        self.ttft_count += 1
        self.ttft_sum += v
        if v > self.ttft_max:
            self.ttft_max = v
        self.metrics.histogram("ttft_s").record(v)

    def summary(self) -> dict:
        dec_ms = 1e3 * self.decode_time_s / max(self.decode_steps, 1)
        out = {
            "requests_completed": self.requests_completed,
            "requests_aborted": self.requests_aborted,
            "tokens_generated": self.tokens_generated,
            "tokens_per_s": self.tokens_generated / self.wall_time_s
            if self.wall_time_s else 0.0,
            "ttft_ms": {
                "mean": 1e3 * self.ttft_sum / self.ttft_count
                if self.ttft_count else 0.0,           # exact
                "p50": 1e3 * self.metrics.histogram("ttft_s").quantile(0.5),
                "max": 1e3 * self.ttft_max,            # exact
            },
            "decode_step_ms": dec_ms,
            "decode_steps": self.decode_steps,
            "prefills": self.prefills,
            "prefills_chunked": self.prefills_chunked,
            "prefill_calls": self.prefill_calls,
            "prefill_chunks": self.prefill_chunks,
            "prefill_prompt_tokens": self.prefill_prompt_tokens,
            "prefill_tokens_computed": self.prefill_tokens_computed,
            "prefill_time_s": self.prefill_time_s,
            "prefill_padding_overhead": (
                self.prefill_padded_tokens / self.prefill_prompt_tokens - 1.0
                if self.prefill_prompt_tokens else 0.0),
            "bucket_counts": dict(self.bucket_counts),
            "prefill_batch_counts": dict(self.batch_counts),
            "slot_occupancy": self.occupancy_sum / max(self.ticks, 1),
            "prefill_compiles": self.prefill_compiles,
            "decode_compiles": self.decode_compiles,
            "wall_time_s": self.wall_time_s,
        }
        if self.handoffs or self.handoff_stalls:
            out["handoff"] = {
                "handoffs": self.handoffs,
                "handoff_time_s": self.handoff_time_s,
                "handoff_stalls": self.handoff_stalls,
            }
        if self.kv_pool_blocks:
            out["kv"] = {
                "pool_blocks": self.kv_pool_blocks,
                "block_size": self.kv_block_size,
                "blocks_in_use": self.kv_blocks_in_use,
                "blocks_peak": self.kv_blocks_peak,
                "blocks_cached": self.kv_blocks_cached,
                "occupancy": self.kv_occupancy_sum / max(self.ticks, 1),
                "prefix_queries": self.prefix_queries,
                "prefix_hits": self.prefix_hits,
                "prefix_hit_rate": self.prefix_hits / self.prefix_queries
                if self.prefix_queries else 0.0,
                "prefix_tokens_reused": self.prefix_tokens_reused,
                "blocks_copied": self.blocks_copied,
                "blocks_evicted": self.blocks_evicted,
                "evict_scanned": self.evict_scanned,
                "reclaim_walked": self.reclaim_walked,
                "decode_stalls": self.decode_stalls,
            }
            if self.kv_shards > 1:
                out["kv"]["shards"] = self.kv_shards
                out["kv"]["in_use_per_shard"] = list(self.kv_in_use_per_shard)
                out["kv"]["peak_per_shard"] = list(self.kv_peak_per_shard)
        if self.rec_state_kinds:
            kinds = {k: {**v, "bytes": v["bytes_per_slot"]
                         * self.rec_state_slots}
                     for k, v in self.rec_state_kinds.items()}
            per_slot = sum(v["bytes_per_slot"] for v in kinds.values())
            out["state"] = {
                "kinds": kinds,
                "bytes_per_slot": per_slot,
                "bytes": per_slot * self.rec_state_slots,
                "slots_live": self.rec_slots_live,
                "chunks_resumed": self.rec_chunks_resumed,
            }
            if self.prefix_cache_note:
                out["state"]["prefix_cache"] = self.prefix_cache_note
        if self.placement:
            # plan (predicted) + measured + drift, side by side — the triple
            # benchmarks/calibrate.py fits the cost model against (same
            # obs.drift arithmetic, so the numbers agree exactly)
            p = dict(self.placement)
            p["measured"] = {
                "prefill_call_s": self.prefill_time_s
                / max(self.prefill_calls + self.prefill_chunks, 1),
                "prefill_token_s": self.prefill_time_s
                / max(self.prefill_tokens_computed, 1),
                "decode_step_s": self.decode_time_s
                / max(self.decode_steps, 1),
            }
            p["drift"] = drift_report(plan_predictions(p), p["measured"])
            if p["drift"] and self.programs is not None:
                # per-cluster measured-vs-predicted: the program registry's
                # phase totals attributed over the plan's clusters, next to
                # the whole-engine drift the calibration gate consumes
                clusters = self.programs.cluster_rollup()
                if clusters:
                    p["drift"]["clusters"] = clusters
            out["placement"] = p
        if self.programs is not None:
            out["programs"] = self.programs.summary()
        out["obs"] = self.metrics.to_dict()
        return out


@dataclass
class Request:
    rid: int
    prompt: list[int]
    max_new_tokens: int = 32
    eos_id: int = -1
    # sampling: temperature <= 0 is exact greedy argmax (the default);
    # seed None derives a per-request stream from rid
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    seed: int | None = None
    generated: list[int] = field(default_factory=list)
    done: bool = False
    aborted: bool = False               # unfinished when run() gave up
    t_submit: float = 0.0
    t_first_token: float = 0.0
    t_done: float = 0.0


class ServeEngine:
    def __init__(self, model: Model, params, *, slots: int = 4,
                 max_len: int = 512, greedy: bool = True,
                 buckets: tuple[int, ...] | None = None,
                 min_bucket: int = 16,
                 max_prefill_per_step: int = 1,
                 max_prefill_batch: int = 4,
                 prefill_chunk: int | None = None,
                 kv_block_size: int | None = None,
                 kv_blocks: int | None = None,
                 prefix_cache: bool = True,
                 mesh=None,
                 param_strategy: str = "tp",
                 prefill_model: Model | None = None,
                 decode_model: Model | None = None,
                 policy=None,
                 role: str = "both",
                 track_base: int = 0,
                 tracer: Tracer | None = None,
                 profile: bool = False,
                 program_memory: bool = False):
        """``greedy`` is a legacy knob: sampling is now per-request
        (Request.temperature/top_k/top_p/seed) and greedy stays the exact
        default, so both values are accepted and equivalent.

        ``kv_block_size``: enable the paged KV pool with this many tokens per
        block (must divide max_len).  ``kv_blocks``: physical blocks in the
        pool (default: the dense equivalent, slots * max_len / block_size —
        pass less to actually cap KV memory).  ``prefix_cache``: share
        same-prefix KV blocks across requests via the radix tree; requires
        every layer to be a full-attention layer (block-sharable state) and
        silently disables itself otherwise.

        ``mesh``: optional ``jax.sharding.Mesh`` with (data, model) axes
        (``launch.mesh.make_serve_mesh``).  Weights shard through
        ``launch.shardings.param_specs`` (``param_strategy``: "tp" for the
        Mensa cluster templates, "dp" for replicated blocks), serving state
        through ``serve_state_specs`` (slots and — paged — pool blocks over
        the data axes; heads/recurrence width over ``model`` when they
        divide it).  Axes that don't divide evenly fall back to replicated,
        so any mesh serves any shape.  Program outputs are pinned to the
        canonical state sharding, keeping the compiled inventory closed.

        ``policy``: optional ``serve.placement.PlacementPlan`` from the
        ExecutionOracle.  A plan supplies the bucket ladder and prefill
        chunk (explicit constructor arguments still win) and is recorded in
        ``EngineStats.placement``; its per-phase kernel-variant overrides
        are applied by the caller when building ``prefill_model`` /
        ``decode_model`` (see ``launch.serve.build_engine``).  Plans are
        resolved before any program compiles and never consulted per tick,
        so the zero-recompile invariant is untouched.

        ``param_strategy``: "tp" (Mensa cluster templates), "dp"
        (replicated blocks), or "auto" — route each block family's
        parameters by its cluster's ``ExecutionPolicy.sharding_axis`` from
        the plan (memory-centric clusters replicate, compute-centric ones
        take the TP templates).

        ``role``: "both" (default, interleaved engine), "prefill" (runs
        bucketed/chunked prefill only; finished slots queue on ``ready``
        for :meth:`export_slot` + :meth:`release_handoff`), or "decode"
        (never admits from the queue; sequences arrive via :meth:`adopt`).
        Role engines warm only their own program inventory and carry a
        handoff program each (export/import).  ``track_base`` offsets this
        engine's tracer tracks so two role engines share one timeline
        without colliding; role engines also prefix their track and counter
        names with the role.

        ``tracer``: a :class:`repro.obs.Tracer`; default is a fresh enabled
        one (pass ``Tracer(enabled=False)`` to opt out).  ``profile=True``
        (an attribute, read per span) also opens every span — program calls
        and the tick's host phases, through :meth:`Tracer.section` — as a
        ``jax.profiler.TraceAnnotation``, so XLA profiles carry the same
        spans as the ring.

        ``program_memory=True`` additionally AOT-compiles each program at
        warmup for its ``memory_analysis()`` temp/argument/output watermarks
        in the ``programs`` stats section (roughly doubles warmup compile
        time; the static FLOPs/bytes cost registry is on either way and
        costs one extra lowering per program)."""
        del greedy                      # superseded by per-request sampling
        if role not in ("both", "prefill", "decode"):
            raise ValueError(f"role {role!r} not in "
                             f"('both', 'prefill', 'decode')")
        self.role = role
        self.track_base = track_base
        self.tracer = tracer if tracer is not None else Tracer()
        self.profile = profile
        self.model = model
        self.mesh = mesh
        self.slots = slots
        self.max_len = max_len
        # where programs' arrays lie on the mesh: read by the paged K/V
        # gathers and by every Pallas kernel call (shard_map)
        self._layout = None
        if mesh is not None:
            from ..launch.mesh import data_axes
            self._layout = MeshLayout(mesh, data_axes(mesh))
        # number of data shards the mesh carries (1 = unsharded)
        self._nd = self._layout.nd if self._layout is not None else 1
        if not buckets and policy is not None and policy.buckets:
            buckets = policy.buckets
        self.buckets = tuple(sorted(buckets)) if buckets \
            else prefill_buckets(max_len, min_bucket)
        if self.buckets[-1] > max_len:
            raise ValueError(f"bucket {self.buckets[-1]} > max_len {max_len}")
        self.max_prefill_per_step = max(1, max_prefill_per_step)
        # batch-bucket the admission group size so the compiled-program
        # inventory stays |buckets| x |batch_buckets|, not one per group size
        self.max_prefill_batch = max(1, min(max_prefill_batch, slots))
        self.batch_buckets = prefill_buckets(self.max_prefill_batch,
                                             min_bucket=1)
        if not prefill_chunk and policy is not None and policy.prefill_chunk:
            prefill_chunk = policy.prefill_chunk
        self.prefill_chunk = int(prefill_chunk) if prefill_chunk \
            else self.buckets[-1]
        if not 1 <= self.prefill_chunk <= max_len:
            raise ValueError(f"prefill_chunk {self.prefill_chunk} outside "
                             f"[1, max_len {max_len}]")
        # every engine carries a plan: either the oracle's resolution or a
        # "fixed" record of the constructor knobs (EngineStats.placement)
        if policy is None:
            from .placement import fixed_plan
            policy = fixed_plan(model.cfg, buckets=self.buckets,
                                prefill_chunk=self.prefill_chunk,
                                backend=jax.default_backend())
        self.policy = policy
        # per-phase programs (Mensa: compute-centric prefill vs memory-centric
        # decode lower as separate jitted functions)
        self.prefill_model = prefill_model or model
        self.decode_model = decode_model or model
        # ------------------------------------------------- paged KV pool
        self.kv: PagedKVManager | None = None
        self._state_kw: dict = {}
        self._prefix_note = ""
        if kv_block_size is not None:
            blocks_per_slot = -(-max_len // kv_block_size)
            if kv_blocks is None:
                kv_blocks = slots * blocks_per_slot
            if kv_blocks < blocks_per_slot:
                # a pool smaller than one request's worst case could never
                # admit a long prompt: admission would requeue it forever on
                # an otherwise idle engine
                raise ValueError(
                    f"kv_blocks {kv_blocks} < max_len/kv_block_size "
                    f"{blocks_per_slot}: the pool must cover at least one "
                    f"request's worst case")
            # prefix reuse needs every layer's per-token state to live in
            # sharable blocks: full-attention stacks only (window rings and
            # recurrent states are not block-addressable)
            kinds = tuple(model.pattern) + tuple(model.tail_kinds)
            prefix_ok = bool(kinds) and all(k == "attn" for k in kinds)
            if prefix_cache and not prefix_ok:
                self._prefix_note = (
                    "off: the prefix cache shares KV blocks only; "
                    f"{sorted(set(kinds) - {'attn'})} layers carry per-slot "
                    "state that is not snapshotted at block boundaries")
            # the device pool shards its block axis over the data axes only
            # when the stripes come out equal — the host-side accounting
            # mirrors exactly that layout
            shards = self._nd if self._nd > 1 \
                and kv_blocks % self._nd == 0 else 1
            self.kv = PagedKVManager(
                slots=slots, max_len=max_len, block_size=kv_block_size,
                num_blocks=kv_blocks,
                prefix_cache=prefix_cache and prefix_ok,
                shards=shards)
            self._state_kw = dict(kv_block_size=kv_block_size,
                                  kv_blocks=kv_blocks)
        # ------------------------------------------------- mesh placement
        self._state_shardings = None
        if mesh is not None:
            from ..launch import shardings as shard_lib
            specs = shard_lib.serve_state_specs(
                model, mesh, slots, max_len, **self._state_kw)
            self._state_shardings = shard_lib.to_named(specs, mesh)
            params = jax.device_put(
                params, shard_lib.to_named(
                    shard_lib.param_specs(model.cfg, params,
                                          strategy=param_strategy,
                                          plan=self.policy), mesh))
        self.params = params
        self.states = model.init_states(slots, max_len, **self._state_kw,
                                        shardings=self._state_shardings)
        self.memory = None
        self.requests: list[Request | None] = [None] * slots
        self.positions = np.zeros(slots, np.int32)
        # per-slot sampling knobs, applied inside the jitted programs
        self.samp_temp = np.zeros(slots, np.float32)
        self.samp_topk = np.zeros(slots, np.int32)
        self.samp_topp = np.ones(slots, np.float32)
        self.samp_seed = np.zeros(slots, np.int32)
        # donate the pool state: every program updates slots in place instead
        # of copying the whole pool each call.  On a mesh, pin the state
        # outputs to the canonical sharding — otherwise XLA's propagated
        # choice could differ from the input placement and the next call
        # would recompile on the changed sharding.
        if mesh is None:
            out_sh = dict(decode=None, prefill=None, chunk=None, copy=None,
                          export=None, imp=None)
        else:
            repl = NamedSharding(mesh, PartitionSpec())
            st = self._state_shardings
            out_sh = dict(decode=(repl, st), prefill=(repl, st),
                          chunk=(repl, st), copy=st, export=repl, imp=st)
        self._decode = jax.jit(self._decode_and_sample, donate_argnums=(2,),
                               out_shardings=out_sh["decode"])
        self._prefill = jax.jit(self._prefill_and_splice,
                                donate_argnums=(4,),
                                out_shardings=out_sh["prefill"])
        self._chunk = jax.jit(self._chunk_and_splice, donate_argnums=(5,),
                              out_shardings=out_sh["chunk"])
        self._copy = jax.jit(self._copy_blocks, donate_argnums=(0,),
                             out_shardings=out_sh["copy"]) \
            if self.kv is not None else None
        # disaggregated handoff pair (role engines only): export packs a slot
        # into a self-contained suitcase replicated on the prefill submesh,
        # import scatters a visiting suitcase into this engine's pool
        self._export = jax.jit(self._export_slot,
                               out_shardings=out_sh["export"]) \
            if role == "prefill" else None
        self._import = jax.jit(self._import_slot, donate_argnums=(0,),
                               out_shardings=out_sh["imp"]) \
            if role == "decode" else None
        self._queue: deque[Request] = deque()
        self._prefilling: dict[int, int] = {}   # slot -> prompt tokens consumed
        # prefill role: slots whose prefill finished, awaiting export by the
        # coordinator (blocks stay pinned until release_handoff)
        self.ready: deque[int] = deque()
        # decode-tick device caches: the full block table and sampling arrays
        # change only on admission/extension/retirement, not every tick
        self._bt_cache = None
        self._bt_version = -1
        self._samp_cache = None
        # trace track layout: queue events, one track per slot, engine-wide —
        # all offset by track_base so cooperating role engines share one
        # tracer timeline; role engines prefix their track + counter names
        pfx = "" if role == "both" else f"{role}/"
        self._ctr_prefix = pfx
        self._trk_req = track_base + TRACK_REQUESTS
        self.tracer.set_track(self._trk_req, f"{pfx}requests")
        for s in range(slots):
            self.tracer.set_track(self._slot_track(s), f"{pfx}slot {s}")
        self._trk_engine = track_base + 1 + slots
        self.tracer.set_track(self._trk_engine, f"{pfx}engine")
        # ------------------------------------------- program cost registry
        device = mesh.devices.flat[0] if mesh is not None else jax.devices()[0]
        self.programs = ProgramRegistry(chip=host_chip(device.device_kind),
                                        plan_summary=self.policy.summary())
        self._program_memory = program_memory
        # static device-memory telemetry: the state tree realizes
        # serve_state_specs, so its leaf sizes ARE the per-slot footprint;
        # paged K/V leaves belong to the pool, everything else to the slots
        pool_bytes, state_bytes = self._state_byte_stats()
        self._slot_state_bytes = state_bytes // slots
        self._rec_kinds = self._rec_state_bytes()
        if self.kv is not None:
            self.kv.set_block_bytes(pool_bytes // self.kv.pool.num_blocks)
        self.stats = EngineStats()
        # the KV manager's work counters at the last counter sample, and the
        # resumed chunk calls
        self._kv_work_seen = (0, 0)
        self._rec_resumed_seen = 0
        self._init_kv_stats()

    def _span(self, name: str, tid: int | None = None, args: tuple = ()):
        """A section through the tracer's one span call (a synced-capable
        ``Timed`` on its clock, one ring event on track ``tid``), with a
        profiler annotation while the engine profiles: ``profile`` is read
        per call, since a harness may set it after construction."""
        return self.tracer.section(name, tid, args, profile=self.profile)

    def _slot_track(self, slot: int) -> int:
        """Tracer track id of ``slot`` (track_base-relative)."""
        return self.track_base + 1 + slot

    def _state_byte_stats(self) -> tuple[int, int]:
        """(paged pool K/V bytes, per-slot state bytes) of the state tree."""
        pool_b = state_b = 0
        for leaf in jax.tree.leaves(self.states, is_leaf=_is_paged):
            if _is_paged(leaf):
                pool_b += leaf.k.nbytes + leaf.v.nbytes
            elif hasattr(leaf, "nbytes"):
                state_b += leaf.nbytes
        return pool_b, state_b

    def _rec_state_bytes(self) -> dict:
        """Recurrent slot state (conv and recurrence carries) by block kind:
        {kind: {"layers", "bytes_per_slot"}}, read off the state tree."""
        out: dict = {}

        def add(kind, st, layers):
            if st.rec is None:
                return
            b = sum(a.nbytes for a in jax.tree.leaves(st.rec)) // self.slots
            e = out.setdefault(kind, {"layers": 0, "bytes_per_slot": 0})
            e["layers"] += layers
            e["bytes_per_slot"] += b

        m = self.model
        if m.n_groups > 0:
            for j, kind in enumerate(m.pattern):
                add(kind, self.states["groups"][str(j)], m.n_groups)
        for kind, st in zip(m.tail_kinds, self.states["tail"]):
            add(kind, st, 1)
        return out

    def _init_kv_stats(self) -> None:
        self.stats.rec_state_kinds = {k: dict(v)
                                      for k, v in self._rec_kinds.items()}
        self.stats.rec_state_slots = self.slots
        self.stats.prefix_cache_note = self._prefix_note
        if self.kv is not None:
            self.stats.kv_pool_blocks = self.kv.pool.num_blocks
            self.stats.kv_block_size = self.kv.block_size
            self.stats.kv_shards = self.kv.shards
        self.stats.placement = self.policy.summary()
        self.stats.programs = self.programs
        # static memory gauges (the per-tick values update in _tick_counters)
        m = self.stats.metrics
        m.gauge("slot_state_bytes", "bytes").set(self._slot_state_bytes)
        if self.kv is not None:
            m.gauge("kv_pool_capacity_bytes", "bytes").set(
                self.kv.pool.num_blocks * self.kv.block_bytes)
        tmp = self.programs.temp_bytes_peak()
        if tmp:
            m.gauge("program_temp_bytes_peak", "bytes").set(tmp)

    def reset_stats(self) -> None:
        self.stats = EngineStats()
        self._kv_work_seen = (0, 0)
        self._rec_resumed_seen = 0
        if self.kv is not None:
            self.kv.reset_stats()
        self.programs.reset_observed()
        self._init_kv_stats()
        self._sync_compile_stats()
        self._sync_kv_stats()

    def _sync_compile_stats(self) -> None:
        # _cache_size (a private jit attribute) counts the compiled variants
        # of each program: the zero-recompile gate reads its growth
        def size(fn):
            return 0 if fn is None else fn._cache_size()
        self.stats.prefill_compiles = size(self._prefill) \
            + size(self._chunk) + size(self._copy) + size(self._export)
        self.stats.decode_compiles = size(self._decode) + size(self._import)

    def _sync_kv_stats(self) -> None:
        if self.kv is None:
            return
        st, mgr = self.stats, self.kv
        st.kv_blocks_in_use = mgr.in_use
        st.kv_in_use_per_shard = mgr.in_use_by_shard
        # the pool tracks its high-water mark at alloc/retain time, so the
        # peak sees blocks that were allocated and released within one tick;
        # the per-shard snapshot is the distribution AT that peak, so it sums
        # to kv_blocks_peak exactly
        if mgr.pool.peak_in_use >= st.kv_blocks_peak:
            st.kv_peak_per_shard = mgr.peak_by_shard
        st.kv_blocks_peak = max(st.kv_blocks_peak, mgr.pool.peak_in_use)
        st.kv_blocks_cached = mgr.cached
        st.prefix_queries = mgr.stats.prefix_queries
        st.prefix_hits = mgr.stats.prefix_hits
        st.prefix_tokens_reused = mgr.stats.prefix_tokens_reused
        st.blocks_copied = mgr.stats.blocks_copied
        st.blocks_evicted = mgr.blocks_evicted
        st.evict_scanned = mgr.evict_scanned
        st.reclaim_walked = mgr.reclaim_walked

    def _tick_counters(self, ts: float, busy: int) -> None:
        """Per-tick counter-track samples (queue depth, slot occupancy,
        paged KV-pool in-use/cached and the radix nodes the KV manager
        scanned or walked since the last sample, slots holding recurrent
        state and the chunk calls that resumed one since the last sample,
        device-memory bytes) plus the memory gauges — gauges update even
        untraced so ``summary()`` always carries the latest occupancy in
        bytes."""
        m = self.stats.metrics
        if self._rec_kinds:
            self.stats.rec_slots_live = busy
        state_bytes = busy * self._slot_state_bytes
        m.gauge("active_state_bytes", "bytes").set(state_bytes)
        if self.kv is not None:
            m.gauge("kv_pool_bytes", "bytes").set(self.kv.bytes_in_use)
            m.gauge("kv_pool_bytes_peak", "bytes").set(self.kv.bytes_peak)
        tr, p = self.tracer, self._ctr_prefix
        if not tr.enabled:
            return
        tr.counter(p + "queue_depth", ts, (("queued", len(self._queue)),))
        tr.counter(p + "slots", ts, (("busy", busy),
                                     ("free", self.slots - busy)))
        if self.kv is not None:
            work = (self.kv.evict_scanned, self.kv.reclaim_walked)
            seen, self._kv_work_seen = self._kv_work_seen, work
            tr.counter(p + "kv_blocks", ts, (
                ("in_use", self.kv.in_use), ("cached", self.kv.cached),
                ("evict_scanned", work[0] - seen[0]),
                ("reclaim_walked", work[1] - seen[1])))
            if self.kv.shards > 1:
                tr.counter(p + "kv_in_use_by_shard", ts, tuple(
                    (f"shard{i}", v)
                    for i, v in enumerate(self.kv.in_use_by_shard)))
        if self._rec_kinds:
            resumed = self.stats.rec_chunks_resumed
            seen, self._rec_resumed_seen = self._rec_resumed_seen, resumed
            tr.counter(p + "rec_state", ts, (("live_slots", busy),
                                             ("chunks_resumed",
                                              resumed - seen)))
        series = [("slot_state", state_bytes)]
        if self.kv is not None:
            series.append(("kv_pool", self.kv.bytes_in_use))
        tr.counter(p + "device_memory_bytes", ts, tuple(series))

    def save_trace(self, path) -> None:
        """Write the Chrome trace-event JSON for everything traced so far,
        with the stats summary's placement section (plan + measured + drift)
        and the metrics registry embedded under ``otherData``."""
        summary = self.stats.summary()
        other = {"obs": summary["obs"]}
        if "placement" in summary:
            other["placement"] = summary["placement"]
        if "programs" in summary:
            other["programs"] = summary["programs"]
        self.tracer.save(path, other_data=other)

    # ------------------------------------------------------------- admission
    def submit(self, req: Request) -> None:
        if not req.prompt:
            raise ValueError("empty prompt: nothing to condition on")
        if req.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1 (prefill always "
                             "samples the first token)")
        if len(req.prompt) > self.max_len - 1:
            # a max_len-token prompt fills the cache completely: the first
            # decode write would land past the last slot and be dropped
            raise ValueError(f"prompt length {len(req.prompt)} leaves no "
                             f"cache room to decode (max_len {self.max_len})")
        if req.temperature < 0:
            raise ValueError("temperature must be >= 0 (0 = greedy)")
        if not 0 < req.top_p <= 1:
            raise ValueError("top_p must be in (0, 1]")
        if req.top_k < 0:
            raise ValueError("top_k must be >= 0 (0 = no top-k filter)")
        req.t_submit = self.tracer.now()
        self.tracer.instant("submit", self._trk_req, req.t_submit,
                            (("rid", req.rid),
                             ("prompt_tokens", len(req.prompt))))
        self._queue.append(req)

    def _set_sampling(self, slot: int, req: Request) -> None:
        self.samp_temp[slot] = req.temperature
        self.samp_topk[slot] = req.top_k
        self.samp_topp[slot] = req.top_p
        self.samp_seed[slot] = req.seed if req.seed is not None \
            else req.rid & 0x7FFFFFFF
        self._samp_cache = None

    def _decode_args(self):
        """Cached device copies of the full block table + per-slot sampling
        arrays — rebuilt only when admission/extension/retirement touched
        them, not on every decode tick."""
        if self.kv is None:
            bt = None
        else:
            if self._bt_cache is None or self._bt_version != self.kv.version:
                self._bt_cache = jnp.asarray(
                    np.asarray(self.kv.table, np.int32))
                self._bt_version = self.kv.version
            bt = self._bt_cache
        if self._samp_cache is None:
            self._samp_cache = (jnp.asarray(self.samp_temp),
                                jnp.asarray(self.samp_topk),
                                jnp.asarray(self.samp_topp),
                                jnp.asarray(self.samp_seed))
        return bt, self._samp_cache

    def _admit(self, budget: int) -> int:
        free = [s for s in range(self.slots) if self.requests[s] is None]
        take = min(budget, len(free), len(self._queue))
        if take <= 0:
            return 0
        groups: dict[int, list[tuple[int, Request]]] = {}
        admitted = 0
        while admitted < take:
            req = self._queue[0]
            slot = free[0]
            matched = 0
            copy = None
            if self.kv is not None:
                with self._span("kv_admit", self._trk_engine):
                    plan = self.kv.admit(slot, req.prompt)
                if plan is None:
                    # pool can't cover the prompt yet: keep FIFO order and
                    # retry next tick (decode frees blocks as requests end)
                    break
                matched = plan.matched_tokens
                copy = plan.copy
            self._queue.popleft()
            free.pop(0)
            self.requests[slot] = req
            self._set_sampling(slot, req)
            now = self.tracer.now()
            self.tracer.begin(f"req {req.rid}", self._slot_track(slot), now,
                              (("rid", req.rid),
                               ("prompt_tokens", len(req.prompt)),
                               ("prefix_hit_tokens", matched),
                               ("queue_wait_s", round(now - req.t_submit, 6))))
            if copy is not None:
                self.tracer.instant("cow_copy", self._slot_track(slot), now,
                                    (("rid", req.rid), ("src", copy[0]),
                                     ("dst", copy[1])))
                self._run_copy(*copy)
            admitted += 1
            if matched > 0 or len(req.prompt) > self.buckets[-1]:
                # chunked path: long prompts, and prefix-cache hits of any
                # length — the hit resumes prefill at offset=matched through
                # the same chunk-continuation program
                self._prefilling[slot] = matched
                self._advance_chunk(slot)
            else:
                b = bucket_for(len(req.prompt), self.buckets)
                groups.setdefault(b, []).append((slot, req))
        for b in sorted(groups):
            members = groups[b]
            for i in range(0, len(members), self.max_prefill_batch):
                self._prefill_group(b, members[i:i + self.max_prefill_batch])
        return admitted

    # ------------------------------------------------------ compiled programs
    def _decode_and_sample(self, params, tokens, pool_states, positions,
                           memory, active, block_table, temp, topk, topp,
                           seed):
        """The decode program: one lockstep step over the slot pool + in-jit
        per-slot sampling of the next token (greedy rows take exact argmax)."""
        logits, states = self.decode_model.decode_step(
            params, tokens, pool_states, positions, memory, active,
            block_table, layout=self._layout)
        nxt = sample_tokens(logits[:, 0], temp, topk, topp, seed,
                            positions + 1)
        return nxt, states

    def _prefill_and_splice(self, params, tokens, lengths, slot_ids,
                            pool_states, block_tables, temp, topk, topp,
                            seed):
        """One compiled program per (batch-bucket, bucket) shape: padded
        (N, bucket) prefill, splice each row into the pool at ``slot_ids[i]``,
        return the N first sampled tokens.  Padding rows (group smaller than
        the batch bucket) carry slot_ids[0]; rows splice in REVERSE order so
        the real row that shares a padding row's target lands last and wins.
        In paged mode the padding rows' block-table entries are the sentinel,
        so their KV writes drop instead."""
        n = tokens.shape[0]
        states_n = self.prefill_model.init_states(n, self.max_len,
                                                  **self._state_kw)
        if self.kv is not None:
            states_n = _adopt_pool_kv(states_n, pool_states)
        logits, states_n, _ = self.prefill_model.prefill(
            params, tokens, states_n, length=lengths,
            block_table=block_tables, layout=self._layout)
        for i in reversed(range(n)):
            row = _state_row(states_n, i)
            pool_states = _splice_states(pool_states, row, slot_ids[i])
        first = sample_tokens(logits[:, 0], temp, topk, topp, seed, lengths)
        return first, pool_states

    def _chunk_and_splice(self, params, tokens, offset, length, slot,
                          pool_states, block_table, temp, topk, topp, seed):
        """One compiled program for every chunk of every long prompt (and for
        every prefix-cache-hit suffix): gather the slot's state, resume
        prefill at ``offset`` with the (1, C) chunk, splice back, return the
        sampled token (meaningful on the final chunk only)."""
        row = _gather_slot(pool_states, slot)
        logits, row, _ = self.prefill_model.prefill(
            params, tokens, row, length=length[None], offset=offset[None],
            block_table=block_table, layout=self._layout)
        pool = _splice_states(pool_states, row, slot)
        tok = sample_tokens(logits[:, -1], temp, topk, topp, seed,
                            (offset + length)[None])
        return tok[0], pool

    def _copy_blocks(self, pool_states, src, dst):
        """Clone physical block ``src`` into ``dst`` across every paged
        layer — the copy-on-write step for a partial-block prefix hit."""
        def tail_copy(x):
            if isinstance(x, PagedKVCache):
                return x._replace(k=x.k.at[dst].set(x.k[src]),
                                  v=x.v.at[dst].set(x.v[src]))
            return x

        def group_copy(x):
            if isinstance(x, PagedKVCache):
                return x._replace(k=x.k.at[:, dst].set(x.k[:, src]),
                                  v=x.v.at[:, dst].set(x.v[:, src]))
            return x

        return {"groups": jax.tree.map(group_copy, pool_states["groups"],
                                       is_leaf=_is_paged),
                "tail": jax.tree.map(tail_copy, pool_states["tail"],
                                     is_leaf=_is_paged)}

    def _run_copy(self, src: int, dst: int) -> None:
        with self._span("kv_copy", self._trk_engine,
                        (("src", src), ("dst", dst))) as tm:
            self.states = self._copy(self.states,
                                     jnp.asarray(src, jnp.int32),
                                     jnp.asarray(dst, jnp.int32))
            tm.sync(self.states)
        self.programs.observe("copy", tm.dur, phase="kv", program="_copy")

    def _export_slot(self, pool_states, slot, table_row):
        """Pack slot ``slot`` into a self-contained handoff suitcase: the
        batch-1 state row (dense caches, window rings, RG-LRU/SSM carries —
        everything ``serve_state_specs`` describes) plus, paged, the slot's
        own KV block *contents* gathered through its block-table row.  The
        suitcase shape depends on blocks-per-slot only, never on this pool's
        size, so it travels between pools of different capacities.  Sentinel
        rows (unowned tail of the table) clip to a valid block and gather
        garbage — the import side's sentinel destination rows drop exactly
        those writes."""
        row = _gather_slot(pool_states, slot)
        if self.kv is None:
            return row
        idx = jnp.clip(table_row, 0, self.kv.pool.num_blocks - 1)

        def tail(a):
            return a._replace(k=a.k[idx], v=a.v[idx]) if _is_paged(a) else a

        def grp(a):
            return a._replace(k=a.k[:, idx], v=a.v[:, idx]) \
                if _is_paged(a) else a

        return {"groups": jax.tree.map(grp, row["groups"], is_leaf=_is_paged),
                "tail": jax.tree.map(tail, row["tail"], is_leaf=_is_paged)}

    def _import_slot(self, pool_states, row, slot, table_row):
        """Unpack a visiting suitcase into slot ``slot``: scatter its block
        contents into the pool rows mapped by ``table_row`` (a device-to-
        device block copy between pool stripes — never a re-layout), then
        splice the batch-1 state row.  Sentinel table entries are out of
        bounds by exactly one, so ``mode="drop"`` discards the suitcase's
        garbage tail the same way padded prefill rows drop their writes."""
        if self.kv is not None:
            def tail(pool, new):
                if _is_paged(pool):
                    return new._replace(
                        k=pool.k.at[table_row].set(
                            new.k.astype(pool.k.dtype), mode="drop"),
                        v=pool.v.at[table_row].set(
                            new.v.astype(pool.v.dtype), mode="drop"))
                return new

            def grp(pool, new):
                if _is_paged(pool):
                    return new._replace(
                        k=pool.k.at[:, table_row].set(
                            new.k.astype(pool.k.dtype), mode="drop"),
                        v=pool.v.at[:, table_row].set(
                            new.v.astype(pool.v.dtype), mode="drop"))
                return new

            row = {"groups": jax.tree.map(grp, pool_states["groups"],
                                          row["groups"], is_leaf=_is_paged),
                   "tail": jax.tree.map(tail, pool_states["tail"],
                                        row["tail"], is_leaf=_is_paged)}
        return _splice_states(pool_states, row, slot)

    # -------------------------------------------------------- host-side args
    def _tables_for(self, slot_ids: list[int], rows: int) -> jax.Array | None:
        """(rows, blocks_per_slot) block-table rows for the given slots;
        padding rows (beyond ``slot_ids``) are all-sentinel so the compiled
        program drops their writes."""
        if self.kv is None:
            return None
        bt = np.full((rows, self.kv.blocks_per_slot), self.kv.sentinel,
                     np.int32)
        for i, s in enumerate(slot_ids):
            bt[i] = self.kv.table[s]
        return jnp.asarray(bt)

    def _samp_rows(self, slot_ids: list[int], rows: int):
        t = np.zeros(rows, np.float32)
        k = np.zeros(rows, np.int32)
        p = np.ones(rows, np.float32)
        s = np.zeros(rows, np.int32)
        for i, sl in enumerate(slot_ids):
            t[i] = self.samp_temp[sl]
            k[i] = self.samp_topk[sl]
            p[i] = self.samp_topp[sl]
            s[i] = self.samp_seed[sl]
        return jnp.asarray(t), jnp.asarray(k), jnp.asarray(p), jnp.asarray(s)

    # -------------------------------------------------------------- prefill
    def _prefill_group(self, bucket: int, members: list) -> None:
        n = len(members)
        nb = bucket_for(n, self.batch_buckets)
        toks = np.zeros((nb, bucket), np.int32)
        lens = np.ones((nb,), np.int32)
        slot_ids = np.full((nb,), members[0][0], np.int32)
        for i, (slot, req) in enumerate(members):
            toks[i, :len(req.prompt)] = req.prompt
            lens[i] = len(req.prompt)
            slot_ids[i] = slot
        slots_real = [slot for slot, _ in members]
        bt = self._tables_for(slots_real, nb)
        samp = self._samp_rows(slots_real, nb)
        # one program call, one ring span per member slot (below)
        with self._span("prefill") as tm:
            first, self.states = self._prefill(
                self.params, jnp.asarray(toks), jnp.asarray(lens),
                jnp.asarray(slot_ids), self.states, bt, *samp)
            first = tm.sync(first)           # device sync BEFORE the stamp
        first = np.asarray(first)
        now = tm.t1
        st = self.stats
        st.prefill_calls += 1
        st.prefill_time_s += tm.dur
        self.programs.observe(f"prefill[{nb}x{bucket}]", tm.dur,
                              phase="prefill", program="_prefill")
        st.batch_counts[n] = st.batch_counts.get(n, 0) + 1
        waste = st.metrics.counter("prefill_waste_tokens", "tokens")
        for i, (slot, req) in enumerate(members):
            tok = int(first[i])
            self.positions[slot] = len(req.prompt)
            req.generated.append(tok)
            req.t_first_token = now
            st.prefills += 1
            st.prefill_prompt_tokens += len(req.prompt)
            st.prefill_tokens_computed += len(req.prompt)
            st.prefill_padded_tokens += bucket
            waste.inc(bucket - len(req.prompt))
            self.tracer.span("prefill", self._slot_track(slot), tm.t0, tm.t1,
                             (("rid", req.rid), ("bucket", bucket),
                              ("rows", n)))
            st.record_ttft(now - req.t_submit)
            st.bucket_counts[bucket] = st.bucket_counts.get(bucket, 0) + 1
            self._publish(slot, req.prompt)
            if len(req.generated) >= req.max_new_tokens or tok == req.eos_id:
                self._finish(slot, now)
            elif self.role == "prefill":
                self._stage_ready(slot, now)

    def _publish(self, slot: int, tokens: list[int]) -> None:
        """Publish a freshly prefilled prompt's blocks for prefix hits."""
        if self.kv is not None:
            with self._span("kv_publish", self._trk_engine):
                self.kv.publish(slot, tokens)

    def _advance_chunk(self, slot: int) -> None:
        req = self.requests[slot]
        off = self._prefilling[slot]
        c = self.prefill_chunk
        piece = req.prompt[off:off + c]
        n = len(piece)
        toks = np.zeros((1, c), np.int32)
        toks[0, :n] = piece
        bt = self._tables_for([slot], 1)
        samp = self._samp_rows([slot], 1)
        # every chunk syncs on its sampled token before the stamp: the old
        # no-sync fast path on intermediate chunks recorded dispatch time as
        # prefill time (the async-dispatch under-reporting bug) and hid the
        # chunk's real cost from the per-tick timeline
        with self._span("prefill_chunk", self._slot_track(slot),
                        (("rid", req.rid), ("offset", off), ("n", n))) as tm:
            tok, self.states = self._chunk(
                self.params, jnp.asarray(toks), jnp.asarray(off, jnp.int32),
                jnp.asarray(n, jnp.int32), jnp.asarray(slot, jnp.int32),
                self.states, bt, *samp)
            tok = tm.sync(tok)
        st = self.stats
        st.prefill_chunks += 1
        if off > 0 and self._rec_kinds:
            st.rec_chunks_resumed += 1
        st.prefill_padded_tokens += c
        st.prefill_tokens_computed += n
        st.prefill_time_s += tm.dur
        self.programs.observe("chunk", tm.dur, phase="prefill",
                              program="_chunk")
        st.metrics.counter("prefill_waste_tokens", "tokens").inc(c - n)
        if off + n < len(req.prompt):
            self._prefilling[slot] = off + n
            return
        tok = int(tok)                       # final chunk: sample first token
        now = tm.t1
        del self._prefilling[slot]
        self.positions[slot] = len(req.prompt)
        req.generated.append(tok)
        req.t_first_token = now
        st.prefills += 1
        st.prefills_chunked += 1
        st.prefill_prompt_tokens += len(req.prompt)
        st.record_ttft(now - req.t_submit)
        self._publish(slot, req.prompt)
        if len(req.generated) >= req.max_new_tokens or tok == req.eos_id:
            self._finish(slot, now)
        elif self.role == "prefill":
            self._stage_ready(slot, now)

    def _finish(self, slot: int, now: float) -> None:
        req = self.requests[slot]
        req.done = True
        req.aborted = False
        req.t_done = now
        self.tracer.end(f"req {req.rid}", self._slot_track(slot), now,
                        (("rid", req.rid),
                         ("tokens", len(req.generated))))
        self.requests[slot] = None
        if self.kv is not None:
            # same-tick reclamation: publish the finished sequence for future
            # prefix hits, then release every block the slot held.  The LAST
            # generated token was sampled but never fed back through decode,
            # so its KV was never written — publish only the written prefix
            # or a block-aligned sequence would share a garbage position.
            with self._span("kv_release", self._trk_engine):
                self.kv.finish(slot, req.prompt + req.generated[:-1])
        self.stats.requests_completed += 1
        self.stats.tokens_generated += len(req.generated)

    # --------------------------------------------------------------- handoff
    def _stage_ready(self, slot: int, now: float) -> None:
        """Prefill role: the slot's prompt is fully prefilled and its first
        token sampled — park it on ``ready`` for the coordinator.  The slot
        keeps its blocks pinned until :meth:`release_handoff`; the sequence
        was already published, so future same-prefix admissions hit it."""
        self.ready.append(slot)
        self.tracer.instant("prefill_done", self._slot_track(slot), now,
                            (("rid", self.requests[slot].rid),))

    def export_slot(self, slot: int):
        """Prefill role: run the export program for a ready slot, returning
        the suitcase (still on this engine's devices — the decode engine's
        :meth:`stage_in` moves it)."""
        req = self.requests[slot]
        trow = jnp.asarray(np.asarray(self.kv.table[slot], np.int32)) \
            if self.kv is not None else None
        with self._span("handoff_export", self._slot_track(slot),
                        (("rid", req.rid),)) as tm:
            out = self._export(self.states, jnp.asarray(slot, jnp.int32),
                               trow)
            tm.sync(out)
        st = self.stats
        st.handoffs += 1
        st.handoff_time_s += tm.dur
        self.programs.observe("export", tm.dur, phase="handoff",
                              program="_export")
        return out

    def release_handoff(self, slot: int) -> None:
        """Prefill role: the suitcase left — free the slot and its block
        references (the prefix tree keeps the published blocks cached)."""
        req = self.requests[slot]
        now = self.tracer.now()
        self.tracer.end(f"req {req.rid}", self._slot_track(slot), now,
                        (("rid", req.rid), ("handoff", 1)))
        self.requests[slot] = None
        if self.kv is not None:
            with self._span("kv_release", self._trk_engine):
                self.kv.release(slot)
        self._sync_kv_stats()

    def stage_in(self, suitcase):
        """Decode role: land a visiting suitcase on this engine's submesh,
        replicated — one fixed committed sharding, because the import
        program's jit cache keys on it, and this is the single transfer
        point warm and runtime suitcases share.  Meshless engines pass
        through untouched: a device_put would *commit* the arrays and split
        the cache key from the uncommitted warm path."""
        if self.mesh is None:
            return suitcase
        return jax.device_put(suitcase,
                              NamedSharding(self.mesh, PartitionSpec()))

    def adopt(self, req: Request, suitcase, n_tokens: int) -> int | None:
        """Decode role: admit a finished prefill from a peer engine — map a
        free slot, remap fresh blocks covering its ``n_tokens`` written
        positions (:meth:`PagedKVManager.adopt`), scatter the suitcase into
        them, and start decoding from ``req.generated[-1]``.  Returns the
        slot, or None — with no side effects beyond a stall counter — when
        no slot or no blocks are free (the coordinator retries next tick)."""
        free = [s for s in range(self.slots) if self.requests[s] is None]
        if not free:
            self.stats.handoff_stalls += 1
            return None
        slot = free[0]
        if self.kv is not None:
            with self._span("kv_admit", self._trk_engine):
                adopted = self.kv.adopt(slot, n_tokens)
            if not adopted:
                self.stats.handoff_stalls += 1
                return None
        trow = jnp.asarray(np.asarray(self.kv.table[slot], np.int32)) \
            if self.kv is not None else None
        with self._span("handoff_import") as tm:
            self.states = self._import(self.states, suitcase,
                                       jnp.asarray(slot, jnp.int32), trow)
            tm.sync(self.states)
        st = self.stats
        st.handoffs += 1
        st.handoff_time_s += tm.dur
        self.programs.observe("import", tm.dur, phase="handoff",
                              program="_import")
        self.requests[slot] = req
        self.positions[slot] = n_tokens
        self._set_sampling(slot, req)
        now = tm.t1
        self.tracer.begin(f"req {req.rid}", self._slot_track(slot), now,
                          (("rid", req.rid),
                           ("prompt_tokens", len(req.prompt))))
        self.tracer.instant(
            "handoff", self._slot_track(slot), now,
            (("rid", req.rid), ("tokens", n_tokens),
             ("blocks", self.kv.owned[slot] if self.kv is not None else 0)))
        self._sync_kv_stats()
        return slot

    # ---------------------------------------------------------------- warmup
    def warmup(self) -> None:
        """Pre-compile every program the engine can ever run — all
        (batch-bucket, bucket) prefill shapes, the chunk-continuation program
        (when any admissible prompt is longer than the largest bucket, or a
        prefix cache can shortcut into it), the block-clone program (paged),
        and the decode program — then reset the pool.  After this, any trace
        triggers zero recompiles regardless of arrival pattern."""
        if self._queue or self._prefilling \
                or any(r is not None for r in self.requests):
            raise RuntimeError("warmup() requires an idle engine")
        zs = lambda n: (jnp.zeros((n,), jnp.float32),
                        jnp.zeros((n,), jnp.int32),
                        jnp.ones((n,), jnp.float32),
                        jnp.zeros((n,), jnp.int32))
        # every program registers its static cost (lowered-HLO FLOPs/bytes,
        # optionally compiled memory watermarks) immediately before its
        # warmup call — same args, so the registered shape IS the warmed one
        reg, mem = self.programs, self._program_memory
        with self._span("warmup", self._trk_engine) as tm:
            if self.role != "decode":
                for b in self.buckets:
                    for nb in self.batch_buckets:
                        args = (self.params, jnp.zeros((nb, b), jnp.int32),
                                jnp.ones((nb,), jnp.int32),
                                jnp.asarray(np.arange(nb) % self.slots,
                                            np.int32),
                                self.states, self._warm_table(nb), *zs(nb))
                        reg.register(f"prefill[{nb}x{b}]", self._prefill,
                                     args, phase="prefill",
                                     program="_prefill", memory=mem)
                        _, self.states = self._prefill(*args)
                # chunk continuation: reachable for prompts beyond the
                # largest bucket, and (paged) for any prefix-cache hit
                if self.max_len - 1 > self.buckets[-1] \
                        or (self.kv is not None and self.kv.prefix_enabled):
                    args = (self.params,
                            jnp.zeros((1, self.prefill_chunk), jnp.int32),
                            jnp.asarray(0, jnp.int32),
                            jnp.asarray(1, jnp.int32),
                            jnp.asarray(0, jnp.int32), self.states,
                            self._warm_table(1), *zs(1))
                    reg.register("chunk", self._chunk, args, phase="prefill",
                                 program="_chunk", memory=mem)
                    _, self.states = self._chunk(*args)
                if self._copy is not None:
                    args = (self.states, jnp.asarray(0, jnp.int32),
                            jnp.asarray(0, jnp.int32))
                    reg.register("copy", self._copy, args, phase="kv",
                                 program="_copy", memory=mem)
                    self.states = self._copy(*args)
            if self.role != "prefill":
                args = (self.params, jnp.zeros((self.slots, 1), jnp.int32),
                        self.states, jnp.asarray(self.positions),
                        self.memory, jnp.zeros((self.slots,), bool),
                        self._warm_table(self.slots), *zs(self.slots))
                reg.register("decode", self._decode, args, phase="decode",
                             program="_decode", memory=mem)
                _, self.states = self._decode(*args)
            self._warm_handoff(reg, mem)
            self.states = self.model.init_states(
                self.slots, self.max_len, **self._state_kw,
                shardings=self._state_shardings)
            tm.sync(self.states)
        if self.kv is not None:
            # the device pool was just re-zeroed: drop every cached prefix
            # that described its old contents
            self.kv.clear()
        self.positions[:] = 0
        self._sync_compile_stats()
        tmp = self.programs.temp_bytes_peak()
        if tmp:
            self.stats.metrics.gauge("program_temp_bytes_peak",
                                     "bytes").set(tmp)
            if self.tracer.enabled:
                self.tracer.counter(self._ctr_prefix + "program_temp_bytes",
                                    tm.t1, (("peak", tmp),))

    def _warm_table(self, rows: int) -> jax.Array | None:
        """All-sentinel block tables: warmup calls drop every KV write."""
        if self.kv is None:
            return None
        return jnp.full((rows, self.kv.blocks_per_slot), self.kv.sentinel,
                        jnp.int32)

    def _warm_handoff(self, reg, mem) -> None:
        """Compile this role's half of the handoff pair.  The prefill side
        exports an idle slot through an all-sentinel table row.  The decode
        side builds a warm suitcase eagerly from its *own* idle states (the
        wire format's pytree structure depends on the model and blocks-per-
        slot only, both shared with the peer), stages it through the same
        :meth:`stage_in` path as runtime — the committed input sharding is
        part of the jit cache key — and imports against an all-sentinel
        destination row, so every paged write drops; the spliced garbage
        lands in slot 0 of states that warmup re-initializes right after."""
        wt = self._warm_table(1)
        trow = wt[0] if wt is not None else None
        if self._export is not None:
            args = (self.states, jnp.asarray(0, jnp.int32), trow)
            reg.register("export", self._export, args, phase="handoff",
                         program="_export", memory=mem)
            self._export(*args)
        if self._import is not None:
            suitcase = self.stage_in(self._export_slot(
                self.states, jnp.asarray(0, jnp.int32), trow))
            args = (self.states, suitcase, jnp.asarray(0, jnp.int32), trow)
            reg.register("import", self._import, args, phase="handoff",
                         program="_import", memory=mem)
            self.states = self._import(*args)

    # ---------------------------------------------------------------- decode
    def step(self) -> None:
        """One engine tick: advance each in-flight chunked prefill by one
        chunk, admit up to ``max_prefill_per_step`` queued requests, then
        advance every decoding slot by one lockstep decode step (dead and
        mid-prefill slots are frozen by the ``active`` mask).  Paged engines
        extend each slot's block table before the write and stall (freeze) a
        slot for the tick when the pool has no block for it.

        Every stretch of the tick is a span on the engine track: ``tick``
        around it all, the scheduler's ``schedule`` / ``decode_prep`` /
        ``retire`` / ``tick_stats``, the KV manager's ``kv_admit`` /
        ``kv_extend`` / ``kv_publish`` / ``kv_release`` inside them, and the
        program calls."""
        with self._span("tick", self._trk_engine) as tick:
            tick.args = self._tick(tick.t0)

    def _extend(self, active: list[int]) -> list[int]:
        """Extend each active slot's block table over this tick's write;
        returns the slots that got their block (the rest stall a tick)."""
        ok = []
        with self._span("kv_extend", self._trk_engine):
            for i in active:
                # the write this tick lands at position[i]: the table must
                # cover position[i] + 1 tokens
                if self.kv.extend(i, int(self.positions[i]) + 1):
                    ok.append(i)
                else:
                    self.stats.decode_stalls += 1
                    self.tracer.instant(
                        "stall", self._slot_track(i), self.tracer.now(),
                        (("rid", self.requests[i].rid),))
        if not ok and not self._prefilling:
            # nothing can decode and nothing mid-prefill will retire: no
            # block can ever free — fail loudly instead of spinning
            raise RuntimeError(
                f"KV pool exhausted: {self.kv.in_use} of "
                f"{self.kv.pool.num_blocks} blocks referenced, every active "
                f"slot stalled and nothing can retire — size the pool for "
                f"at least one request's worst case "
                f"(kv_blocks >= max_len / kv_block_size)")
        return ok

    def _tick(self, t_tick: float) -> tuple:
        """The body of :meth:`step`; returns the ``tick`` span's args."""
        trk, st = self._trk_engine, self.stats
        chunks0, admitted, stalled = st.prefill_chunks, 0, 0
        with self._span("schedule", trk):
            if self.role != "decode":
                for slot in list(self._prefilling):
                    self._advance_chunk(slot)
                admitted = self._admit(self.max_prefill_per_step)
            busy = [i for i, r in enumerate(self.requests) if r is not None]
            # a prefill-role engine never decodes: ready slots wait for export
            active = [] if self.role == "prefill" \
                else [i for i in busy if i not in self._prefilling]
        tm = None
        if active:
            with self._span("decode_prep", trk):
                if self.kv is not None:
                    ok = self._extend(active)
                    stalled, active = len(active) - len(ok), ok
                toks = np.zeros((self.slots, 1), np.int32)
                mask = np.zeros((self.slots,), bool)
                for i in active:
                    mask[i] = True
                    req = self.requests[i]
                    toks[i, 0] = req.generated[-1] if req.generated \
                        else req.prompt[-1]
                bt, samp = self._decode_args() if active else (None, None)
        if active:
            with self._span("decode", trk, (("active", len(active)),)) as tm:
                nxt, self.states = self._decode(
                    self.params, jnp.asarray(toks), self.states,
                    jnp.asarray(self.positions), self.memory,
                    jnp.asarray(mask), bt, *samp)
                nxt = tm.sync(nxt)           # device sync BEFORE the stamp
            with self._span("retire", trk):
                nxt = np.asarray(nxt, np.int32)
                for i in active:
                    req = self.requests[i]
                    self.positions[i] += 1
                    req.generated.append(int(nxt[i]))
                    if (len(req.generated) >= req.max_new_tokens
                            or int(nxt[i]) == req.eos_id
                            or self.positions[i] >= self.max_len - 1):
                        self._finish(i, tm.t1)
        with self._span("tick_stats", trk):
            st.ticks += 1
            st.occupancy_sum += len(busy) / self.slots
            if tm is not None:
                st.decode_steps += 1
                st.decode_time_s += tm.dur
                self.programs.observe("decode", tm.dur, phase="decode",
                                      program="_decode")
                st.metrics.histogram("decode_tick_s").record(tm.dur)
                st.metrics.histogram(
                    "tokens_per_tick", base=1.0, unit="tokens").record(
                        len(active))
            self._sync_compile_stats()
            self._sync_kv_stats()
            st.kv_occupancy_sum += (
                self.kv.in_use / self.kv.pool.num_blocks
                if self.kv is not None else 0.0)
            end = self.tracer.now()
            if tm is not None:
                # time-between-tokens as a running slot experiences it: the
                # whole tick's wall, chunk-prefill and admission interference
                # included — on a dedicated decode submesh the tick carries
                # only the decode program, which is exactly the latency win
                # the --disagg gate measures (interleaved p99 carries chunk
                # ticks; disagg p99 doesn't)
                st.metrics.histogram("decode_tbt_s").record(end - t_tick)
            self._tick_counters(end, sum(r is not None
                                         for r in self.requests))
            # wall time accumulates per tick so tokens_per_s stays
            # meaningful for callers driving submit()+step() directly
            st.wall_time_s += end - t_tick
        return (("active", len(active)), ("admitted", admitted),
                ("chunks", st.prefill_chunks - chunks0),
                ("stalled", stalled))

    def run(self, requests: list[Request], max_steps: int = 10_000,
            on_truncate: str = "warn") -> list[Request]:
        """Serve ``requests`` to completion (or ``max_steps`` ticks).

        ``on_truncate``: what to do when max_steps is exhausted with work
        still in flight — "warn" (default), "raise", or "ignore".  Survivors
        are always marked ``req.aborted`` and counted in
        ``stats.requests_aborted`` (a later run() that finishes them clears
        the flag)."""
        if on_truncate not in ("warn", "raise", "ignore"):
            raise ValueError(f"on_truncate {on_truncate!r} not in "
                             f"('warn', 'raise', 'ignore')")
        for r in requests:
            self.submit(r)
        steps = 0
        while (self._queue or any(r is not None for r in self.requests)) \
                and steps < max_steps:
            self.step()
            steps += 1
        leftovers = [r for r in self.requests if r is not None] \
            + list(self._queue)
        if leftovers:
            # count each distinct request once, even across repeated
            # truncated run() calls over the same survivors
            self.stats.requests_aborted += sum(
                1 for r in leftovers if not r.aborted)
            t_abort = self.tracer.now()
            for r in leftovers:
                if not r.aborted:
                    self.tracer.instant("abort", self._trk_req, t_abort,
                                        (("rid", r.rid),))
                r.aborted = True
            msg = (f"run() exhausted max_steps={max_steps} with "
                   f"{len(leftovers)} unfinished requests "
                   f"(rids {[r.rid for r in leftovers][:8]}...) — they remain "
                   f"queued/in-slot and are marked aborted")
            if on_truncate == "raise":
                raise RuntimeError(msg)
            if on_truncate == "warn":
                warnings.warn(msg, RuntimeWarning, stacklevel=2)
        return requests


# --------------------------------------------------------- state pool surgery
def _is_paged(x) -> bool:
    return isinstance(x, PagedKVCache)


def _adopt_pool_kv(fresh, pool):
    """Swap the paged-KV leaves of a freshly initialized batch-N state tree
    for the live pool's (the block arrays are global — the fresh zeros are
    dead code the compiler drops); everything else keeps its fresh batch-N
    leaves.  ``fresh.length`` (zeros) is kept: prefill rows start empty."""
    def pick(f, p):
        if _is_paged(f):
            return PagedKVCache(p.k, p.v, f.length)
        return f
    return jax.tree.map(pick, fresh, pool, is_leaf=_is_paged)


def _state_row(states, i: int):
    """Batch-1 view of row ``i`` (a static index) of a batch-N state tree.
    Batch is the first axis for tail states, the second for stacked
    (scan-group) states.  Paged KV leaves have no batch axis on k/v (they're
    the global pool) — only their per-slot ``length`` is sliced."""
    def grp(a):
        return a._replace(length=a.length[:, i:i + 1]) if _is_paged(a) \
            else a[:, i:i + 1]

    def tail(a):
        return a._replace(length=a.length[i:i + 1]) if _is_paged(a) \
            else a[i:i + 1]

    return {"groups": jax.tree.map(grp, states["groups"], is_leaf=_is_paged),
            "tail": jax.tree.map(tail, states["tail"], is_leaf=_is_paged)}


def _gather_slot(pool_states, slot):
    """Batch-1 copy of slot ``slot`` (may be a traced scalar) of the pool."""

    def tail(a):
        if _is_paged(a):
            return a._replace(
                length=jax.lax.dynamic_slice(a.length, (slot,), (1,)))
        return jax.lax.dynamic_slice(
            a, (slot,) + (0,) * (a.ndim - 1), (1,) + a.shape[1:])

    def grp(a):
        if _is_paged(a):
            return a._replace(length=jax.lax.dynamic_slice(
                a.length, (0, slot), (a.length.shape[0], 1)))
        return jax.lax.dynamic_slice(
            a, (0, slot) + (0,) * (a.ndim - 2),
            (a.shape[0], 1) + a.shape[2:])

    return {"groups": jax.tree.map(grp, pool_states["groups"],
                                   is_leaf=_is_paged),
            "tail": jax.tree.map(tail, pool_states["tail"],
                                 is_leaf=_is_paged)}


def _splice_states(pool_states, one_states, slot):
    """Write batch-1 `one_states` into slot `slot` of the pooled states.
    Batch is the first axis for tail states and the second for stacked
    (scan-group) states.  ``slot`` may be a traced scalar.  Paged KV leaves
    carry the updated global pool in k/v (taken wholesale) and a per-slot
    length (spliced)."""

    def splice(pool, new):
        if _is_paged(pool):
            return PagedKVCache(new.k, new.v, jax.lax.dynamic_update_slice(
                pool.length, new.length.astype(pool.length.dtype), (slot,)))
        if pool.ndim == new.ndim:          # tail state: batch axis 0
            return jax.lax.dynamic_update_slice(
                pool, new.astype(pool.dtype),
                (slot,) + (0,) * (pool.ndim - 1))
        raise ValueError((pool.shape, new.shape))

    def splice_stacked(pool, new):
        if _is_paged(pool):
            return PagedKVCache(new.k, new.v, jax.lax.dynamic_update_slice(
                pool.length, new.length.astype(pool.length.dtype), (0, slot)))
        # pool: (G, B, ...), new: (G, 1, ...)
        return jax.lax.dynamic_update_slice(
            pool, new.astype(pool.dtype),
            (0, slot) + (0,) * (pool.ndim - 2))

    out_groups = jax.tree.map(splice_stacked, pool_states["groups"],
                              one_states["groups"], is_leaf=_is_paged)
    out_tail = jax.tree.map(splice, pool_states["tail"], one_states["tail"],
                            is_leaf=_is_paged)
    return {"groups": out_groups, "tail": out_tail}
