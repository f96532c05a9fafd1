"""Sharding strategies — the Mensa clusters mapped to mesh layouts (Level B).

Each parameter gets a PartitionSpec from its Mensa strategy cluster:

* Pascal (compute-centric attn/FFN matmuls): Megatron column->row pairing —
  only one collective per block on the forward pass.
* Jacquard (huge-footprint, low-reuse): vocab/embedding tables and MoE expert
  banks sharded on `model` and NEVER gathered; compute moves to the shard.
* Pavlov (recurrent): recurrence width (d_rnn / d_inner) sharded on `model`,
  sequence kept local so the time scan has no cross-device dependency;
  weights stay resident across the whole scan.

Batch is sharded on (pod, data).  KV caches for decode shard the *sequence*
axis on `model` (context parallelism): softmax reductions over the sharded
axis lower to small all-reduces instead of gathering the cache.
"""
from __future__ import annotations

from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.model_config import ArchConfig
from ..models.transformer import Model
from .mesh import data_axes

PyTree = Any


# ------------------------------------------------------------------ parameters
def _base_spec(names: list[str], leaf, is_moe: bool,
               blockdiag_gates: bool = False,
               dense_2d: bool = False) -> tuple:
    """PartitionSpec entries for the *unstacked* rank of this parameter."""
    name = names[-1]
    if "mamba2" in names:
        # the Mamba-2 mixer replicates: its input projection packs z, x, B,
        # C and dt along one axis, which no head split divides evenly
        return (None,) * leaf_rank(leaf)
    in_moe = is_moe and "ffn" in names and "shared" not in names
    # --- Jacquard cluster: big tables / expert banks, sharded & stationary
    if name in ("embed", "lm_head"):
        return ("model", None)
    if in_moe and name in ("w_gate", "w_up"):
        # experts on `model` (EP) + d_ff on `data` (FSDP-style 2D sharding):
        # pure EP leaves the expert bank replicated across `data`, which
        # overflows HBM for the 42B/109B MoE archs (caught by the dry-run
        # memory analysis) — the second axis shards it 256-way.
        return ("model", None, "data")
    if in_moe and name == "w_down":
        return ("model", "data", None)
    # --- Pascal cluster: Megatron column->row pairs.  For >20B-param archs
    # the second mesh axis also shards the non-contracted weight dim
    # (FSDP-style 2D) so replicated dense weights never exceed HBM.
    if name in ("wq", "wk", "wv", "w_gate", "w_up", "w_in"):
        return ("data" if dense_2d else None, "model")
    if name in ("wo", "w_down", "w_out", "out_proj", "x_proj"):
        return ("model", "data" if dense_2d else None)
    if name in ("bq", "bk", "bv", "b_in"):
        return ("model",)
    if name in ("b_out",):
        return (None,)
    # --- Pavlov cluster: recurrence width on `model`
    if name in ("w_x", "w_y", "in_proj", "dt_proj"):
        return (None, "model")
    if name in ("w_a", "w_i"):
        # dense (rank 2): row-parallel (psum).  block-diagonal (rank 3,
        # flagged): blocks on `model` -> fully local gate matmuls
        if blockdiag_gates:
            return ("model", None, None)
        return ("model", None)
    if name == "conv_w":
        return (None, "model")
    if name in ("lambda", "dt_bias", "d_skip"):
        return ("model",)
    if name == "a_log":
        return ("model", None)
    if name == "b":                        # lstm bias (4H,)
        return ("model",)
    if name == "w_h":
        return (None, "model")
    # --- small/replicated
    return (None,) * leaf_rank(leaf)


def leaf_rank(leaf) -> int:
    return len(leaf.shape)


def _names_from_path(path) -> list[str]:
    names = []
    for p in path:
        if hasattr(p, "key"):
            names.append(str(p.key))
        elif hasattr(p, "name"):
            names.append(str(p.name))
        elif hasattr(p, "idx"):
            names.append(str(p.idx))
    return names


# parameter-name families, used to route each leaf to the block kinds whose
# ExecutionPolicy governs it under strategy="auto" (plan-aware sharding)
_ATTN_PARAMS = frozenset(
    {"wq", "wk", "wv", "wo", "bq", "bk", "bv"})
_REC_PARAMS = frozenset(
    {"w_x", "w_y", "in_proj", "dt_proj", "w_a", "w_i", "conv_w", "lambda",
     "dt_bias", "d_skip", "a_log", "w_h", "b", "out_proj", "x_proj"})
_FAMILY_KINDS = {
    "attn": ("attn", "local", "dec", "enc"),
    "rec": ("rec", "ssm"),
    "ffn": ("ffn",),
}


def _family_of(names: list[str]) -> str | None:
    name = names[-1]
    if name in _ATTN_PARAMS:
        return "attn"
    if name in _REC_PARAMS:
        return "rec"
    if "ffn" in names or name in ("w_gate", "w_up", "w_down", "w_in",
                                  "w_out", "b_in", "b_out"):
        return "ffn"
    return None


def _plan_family_axes(plan) -> dict:
    """family -> preferred mesh axis from the plan's per-cluster policies
    (``ExecutionPolicy.sharding_axis``).  "model" wins when a family spans
    clusters that disagree; families the plan says nothing about map to
    None (the TP templates decide)."""
    out = {}
    for family, kinds in _FAMILY_KINDS.items():
        axes = []
        for k in kinds:
            pol = plan.policy_for(k)
            if pol is not None and pol.sharding_axis:
                axes.append(pol.sharding_axis)
        out[family] = ("model" if "model" in axes
                       else (axes[0] if axes else None))
    return out


def param_specs(cfg: ArchConfig, params_shape: PyTree,
                strategy: str = "tp", plan=None) -> PyTree:
    """PartitionSpec tree matching `params_shape` (ShapeDtypeStructs or arrays).
    Stacked (scan) leading axes are padded with None on the left.

    strategy:
      "tp"   — the Mensa cluster templates (Pascal-TP / Jacquard / Pavlov).
      "dp"   — pascal_dp plan: every block parameter replicated (batch shards
               over all mesh axes); embeddings stay Jacquard vocab-sharded.
      "auto" — per-cluster, from ``plan`` (a ``serve.placement.PlacementPlan``):
               families whose policy prefers the "data" axis (memory-centric
               clusters — they scale by replication over slots) drop to
               replicated specs, families preferring "model" keep the TP
               templates.  Embeddings always stay Jacquard vocab-sharded
               (the table must never be replicated).  A plan with no
               policies (``fixed_plan``) degrades to plain "tp".
    """
    if strategy == "auto" and plan is None:
        raise ValueError('param_specs(strategy="auto") needs a PlacementPlan '
                         "(build the engine with a policy, or pass plan=...)")
    family_axes = _plan_family_axes(plan) if strategy == "auto" else {}
    is_moe = cfg.ffn_kind == "moe"
    blockdiag = getattr(cfg, "rglru_gate_blocks", 0) > 0
    dense_2d = cfg.param_count() > 20e9

    def spec(path, leaf):
        names = _names_from_path(path)
        if names[-1] not in ("embed", "lm_head"):
            if strategy == "dp":
                return P(*((None,) * len(leaf.shape)))
            if strategy == "auto":
                fam = _family_of(names)
                if fam is not None and family_axes.get(fam) == "data":
                    return P(*((None,) * len(leaf.shape)))
        base = _base_spec(names, leaf, is_moe, blockdiag, dense_2d)
        pad = len(leaf.shape) - len(base)
        if pad < 0:       # scalar-ish leaf with generic base
            base = base[-len(leaf.shape):] if len(leaf.shape) else ()
            pad = 0
        return P(*((None,) * pad + tuple(base)))

    return jax.tree_util.tree_map_with_path(spec, params_shape)


# ----------------------------------------------------------------- batch/state
def batch_specs(cfg: ArchConfig, mesh: Mesh, global_batch: int,
                strategy: str = "tp") -> dict:
    """Specs for the training batch dict."""
    d = data_axes(mesh)
    if strategy == "dp":
        d = d + ("model",)                  # batch over every mesh axis
    nd = int(np.prod([mesh.shape[a] for a in d]))
    bspec = d if global_batch % nd == 0 and global_batch >= nd else None
    out = {"tokens": P(bspec, None), "labels": P(bspec, None)}
    if cfg.modality_tokens:
        out["modality"] = P(bspec, None, None)
    if cfg.is_encdec:
        out["src_embeds"] = P(bspec, None, None)
    return out


def state_specs(model: Model, mesh: Mesh, batch: int, max_len: int) -> PyTree:
    """Specs mirroring Model.init_states structure.

    KV caches shard sequence on `model` (context parallelism) and batch on
    data; recurrent states shard their width on `model`.
    """
    cfg = model.cfg
    d = data_axes(mesh)
    nd = int(np.prod([mesh.shape[a] for a in d]))
    b = d if batch % nd == 0 and batch >= nd else None

    from ..models.transformer import BlockState
    from ..models.attention import KVCache

    def one(kind, stacked: bool):
        pad = (None,) if stacked else ()
        if kind in ("attn", "dec", "local"):
            kv = KVCache(
                k=P(*pad, b, "model", None, None),
                v=P(*pad, b, "model", None, None),
                length=P(*pad, b))
            return BlockState(kv=kv)
        if kind == "rec" or kind == "ssm":
            h = P(*pad, b, "model", None) if kind == "ssm" \
                else P(*pad, b, "model")
            return BlockState(rec={
                "conv": P(*pad, b, None, "model"),
                "h": h})
        if kind == "mamba2":
            return BlockState(rec={
                "conv": P(*pad, b, None, None),
                "h": P(*pad, b, "model", None, None)})
        raise ValueError(kind)

    groups = {}
    for j, kind in enumerate(model.pattern):
        if model.n_groups > 0:
            groups[str(j)] = one(kind, True)
    return {"groups": groups,
            "tail": [one(k, False) for k in model.tail_kinds]}


def serve_state_specs(model: Model, mesh: Mesh, slots: int, max_len: int, *,
                      kv_block_size: int | None = None,
                      kv_blocks: int | None = None) -> PyTree:
    """Specs mirroring ``Model.init_states`` for the SERVING path.

    Serving shards differently from training (:func:`state_specs`): the slot
    (batch) axis goes on the data axes — per-slot decode math then never
    crosses a shard, which keeps a pure-dp mesh bitwise identical to
    single-device — and per-head / recurrence-width axes go on ``model`` only
    when they divide the axis size.  A paged KV pool has no batch axis; its
    BLOCK axis is sharded over the data axes instead (each shard owns a
    contiguous stripe of physical blocks — the layout serve/kvpool.py's
    per-shard accounting mirrors), falling back to replicated when
    ``kv_blocks`` does not divide evenly.
    """
    cfg = model.cfg
    d = data_axes(mesh)
    nd = int(np.prod([mesh.shape[a] for a in d]))
    mp = int(mesh.shape.get("model", 1))
    b = d if slots % nd == 0 and slots >= nd else None
    if kv_block_size is not None and kv_blocks is None:
        kv_blocks = slots * (-(-max_len // kv_block_size))
    blk = d if kv_blocks is not None and kv_blocks % nd == 0 \
        and kv_blocks >= nd else None

    def wax(n: int):
        """`model` for a width/head axis only when it splits evenly."""
        return "model" if mp > 1 and n and n % mp == 0 else None

    from ..models.attention import KVCache, PagedKVCache
    from ..models.transformer import BlockState

    def one(kind, stacked: bool):
        pad = (None,) if stacked else ()
        if kind in ("attn", "dec") and kv_block_size is not None:
            kv = PagedKVCache(
                k=P(*pad, blk, None, wax(cfg.num_kv_heads), None),
                v=P(*pad, blk, None, wax(cfg.num_kv_heads), None),
                length=P(*pad, b))
            return BlockState(kv=kv)
        if kind in ("attn", "dec", "local"):
            kv = KVCache(
                k=P(*pad, b, None, wax(cfg.num_kv_heads), None),
                v=P(*pad, b, None, wax(cfg.num_kv_heads), None),
                length=P(*pad, b))
            return BlockState(kv=kv)
        if kind == "rec":
            return BlockState(rec={
                "conv": P(*pad, b, None, wax(cfg.d_rnn)),
                "h": P(*pad, b, wax(cfg.d_rnn))})
        if kind == "ssm":
            return BlockState(rec={
                "conv": P(*pad, b, None, wax(cfg.d_inner)),
                "h": P(*pad, b, wax(cfg.d_inner), None)})
        if kind == "mamba2":
            return BlockState(rec={
                "conv": P(*pad, b, None, None),
                "h": P(*pad, b, wax(cfg.ssm_heads), None, None)})
        raise ValueError(kind)

    groups = {}
    for j, kind in enumerate(model.pattern):
        if model.n_groups > 0:
            groups[str(j)] = one(kind, True)
    return {"groups": groups,
            "tail": [one(k, False) for k in model.tail_kinds]}


def to_named(tree: PyTree, mesh: Mesh) -> PyTree:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), tree,
        is_leaf=lambda x: isinstance(x, P))


def abstract_with_sharding(shapes: PyTree, specs: PyTree, mesh: Mesh) -> PyTree:
    """Attach shardings to ShapeDtypeStructs (for .lower without allocation)."""
    named = to_named(specs, mesh)
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, named)
