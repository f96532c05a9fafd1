"""Model assembly: decoder-only LMs, hybrid (RG-LRU + local attention), SSM,
MoE, encoder-decoder, and VLM backbones — all from one block vocabulary.

Layer stacking uses ``jax.lax.scan`` over repeating block groups (the config's
``block_pattern``) so HLO size and compile time stay bounded at 64 layers.  A
tail of ``num_layers % len(pattern)`` blocks continues the pattern cycle
outside the scan (e.g. RecurrentGemma's 26 = 8x(rec,rec,local) + rec,rec).

Three entry points per model:
  * ``forward``      — full-sequence logits (training / prefill-as-scoring).
  * ``loss``         — next-token cross-entropy (+ MoE aux losses).
  * ``prefill`` / ``decode_step`` — KV-cache/recurrent-state serving path.
"""
from __future__ import annotations

import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from . import attention as attn_lib
from . import ffn as ffn_lib
from . import moe as moe_lib
from . import recurrent as rec_lib
from .common import (cross_entropy_loss, embed, fan_in_init, init_embedding,
                     layer_norm, rms_norm, unembed)
from .layout import MeshLayout
from .model_config import ArchConfig

PyTree = Any


# ----------------------------------------------------------------------- norms
def _norm(cfg: ArchConfig, p: dict, x: jax.Array) -> jax.Array:
    if cfg.norm == "rms":
        return rms_norm(x, p["scale"], cfg.norm_eps)
    return layer_norm(x, p["scale"], p["bias"])


def _init_norm(cfg: ArchConfig, dim: int, dtype) -> dict:
    if cfg.norm == "rms":
        return {"scale": jnp.zeros((dim,), dtype)}
    return {"scale": jnp.ones((dim,), dtype), "bias": jnp.zeros((dim,), dtype)}


# ----------------------------------------------------------------- block init
def _init_block(cfg: ArchConfig, kind: str, key, dtype) -> dict:
    ks = jax.random.split(key, 4)
    p: dict = {"ln1": _init_norm(cfg, cfg.d_model, dtype)}
    if kind in ("attn", "local", "dec", "enc"):
        p["attn"] = attn_lib.init_attention(
            ks[0], cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            qkv_bias=cfg.qkv_bias, qk_norm=cfg.qk_norm, dtype=dtype)
        if kind == "dec":
            p["ln_x"] = _init_norm(cfg, cfg.d_model, dtype)
            p["xattn"] = attn_lib.init_attention(
                ks[3], cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
                cfg.head_dim, dtype=dtype)
    elif kind == "rec":
        p["rec"] = rec_lib.init_rglru_block(
            ks[0], cfg.d_model, cfg.d_rnn, conv_width=cfg.d_conv,
            gate_blocks=cfg.rglru_gate_blocks, dtype=dtype)
    elif kind == "ssm":
        p["ssm"] = rec_lib.init_mamba_block(
            ks[0], cfg.d_model, cfg.d_inner, cfg.d_state, cfg.d_conv,
            cfg.dt_rank or None, dtype=dtype)
        return p                                  # Mamba block has no FFN
    elif kind == "mamba2":
        p["mamba2"] = rec_lib.init_mamba2_block(
            ks[0], cfg.d_model, cfg.d_inner, cfg.ssm_heads, cfg.d_state,
            cfg.ssm_groups, cfg.d_conv, dtype=dtype)
    else:
        raise ValueError(kind)
    if cfg.ffn_kind == "glu":
        p["ln2"] = _init_norm(cfg, cfg.d_model, dtype)
        p["ffn"] = ffn_lib.init_glu_ffn(ks[1], cfg.d_model, cfg.d_ff, dtype)
    elif cfg.ffn_kind == "mlp":
        p["ln2"] = _init_norm(cfg, cfg.d_model, dtype)
        p["ffn"] = ffn_lib.init_mlp_ffn(ks[1], cfg.d_model, cfg.d_ff, dtype)
    elif cfg.ffn_kind == "moe":
        p["ln2"] = _init_norm(cfg, cfg.d_model, dtype)
        p["ffn"] = moe_lib.init_moe(ks[1], cfg.d_model, cfg.d_ff,
                                    cfg.num_experts,
                                    shared_expert=cfg.moe_shared_expert,
                                    dtype=dtype)
    return p


# ---------------------------------------------------------------- block apply
class BlockState(NamedTuple):
    """Per-block serving state (exactly one of the fields is populated)."""
    kv: attn_lib.KVCache | None = None
    rec: dict | None = None            # {"conv": ..., "h": ...}
    cross_kv: tuple | None = None      # (k, v) from encoder memory


def _residual(cfg: ArchConfig, x: jax.Array, y: jax.Array) -> jax.Array:
    """x + a residual branch y, scaled by ``residual_multiplier`` (in
    float32, so the multiplier is not rounded to the activations' dtype)."""
    if cfg.residual_multiplier == 1.0:
        return x + y
    return (x.astype(jnp.float32) + y.astype(jnp.float32)
            * cfg.residual_multiplier).astype(x.dtype)


def _attn_ffn_tail(cfg, p, x):
    """Returns (x, load_balance_aux) — aux flows through scan carries."""
    h = _norm(cfg, p["ln2"], x)
    if cfg.ffn_kind == "moe":
        y, moe_aux = moe_lib.moe_ffn(p["ffn"], h, top_k=cfg.top_k,
                                     capacity_factor=cfg.moe_capacity,
                                     activation=cfg.activation,
                                     return_aux=True, impl=cfg.moe_impl)
        return _residual(cfg, x, y), moe_aux["load_balance"]
    zero = jnp.zeros((), jnp.float32)
    if cfg.ffn_kind == "glu":
        return _residual(cfg, x, ffn_lib.glu_ffn(p["ffn"], h, cfg.activation)
                         ), zero
    if cfg.ffn_kind == "mlp":
        return _residual(cfg, x, ffn_lib.mlp_ffn(p["ffn"], h, cfg.activation)
                         ), zero
    return x, zero


def apply_block(cfg: ArchConfig, kind: str, p: dict, x: jax.Array,
                positions: jax.Array, *,
                mode: str = "train",
                state: BlockState | None = None,
                memory: jax.Array | None = None,
                length: jax.Array | None = None,
                offset: jax.Array | None = None,
                block_table: jax.Array | None = None,
                layout: MeshLayout | None = None,
                ) -> tuple[jax.Array, BlockState | None, jax.Array]:
    """One residual block. mode: train|prefill|decode.
    ``length``: (B,) valid prefix lengths for right-padded prefill — serving
    states then reflect position length-1, not S-1.  In decode mode a 0/1
    ``length`` acts as an activity mask: rows with length 0 leave all state
    (KV append, conv context, recurrent h) unchanged.
    ``offset``: (B,) tokens already consumed when this prefill call resumes a
    chunked prompt — attention continues against the cache, recurrences
    continue from the carried state (zeroed where offset == 0).
    ``block_table``: (B, max_len/bs) physical block ids when this block's KV
    cache is paged (state.kv is a PagedKVCache) — one table shared by every
    paged layer.
    ``layout``: a mesh engine's ``models.layout.MeshLayout`` — the paged
    ops' gathered K/V land in the slot layout once (see
    attention.gather_paged_kv), and Pallas kernels run under shard_map.
    Returns (x, new_state, load_balance_aux)."""
    new_state = state
    lb = jnp.zeros((), jnp.float32)
    paged = state is not None and isinstance(state.kv, attn_lib.PagedKVCache)
    if kind in ("attn", "local", "dec", "enc"):
        h = _norm(cfg, p["ln1"], x)
        q, k, v = attn_lib.qkv_project(
            p["attn"], h, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            positions, rope_theta=cfg.rope_theta,
            use_rope=cfg.use_rope and kind != "enc")
        if cfg.attention_multiplier:
            # every attention core scales scores by 1/sqrt(head_dim): fold
            # the model's own multiplier into q instead
            q = (q * (cfg.attention_multiplier * math.sqrt(cfg.head_dim))
                 ).astype(q.dtype)
        if mode == "decode":
            wm = None if length is None else length > 0
            if paged:
                out, kv = attn_lib.paged_decode_attention(
                    q, k, v, state.kv, block_table, write_mask=wm,
                    layout=layout, impl=cfg.attn_impl)
            else:
                out, kv = attn_lib.decode_attention(
                    q, k, v, state.kv,
                    window=cfg.window if kind == "local" else 0,
                    write_mask=wm)
            new_state = state._replace(kv=kv)
        elif mode == "prefill" and offset is not None:
            if kind not in ("attn", "local"):
                raise NotImplementedError(
                    "chunked prefill supports decoder-only self-attention")
            if paged:
                out, kv = attn_lib.paged_chunk_attention(
                    q, k, v, state.kv, block_table, offset=offset,
                    length=length, layout=layout)
            else:
                out, kv = attn_lib.chunk_attention(
                    q, k, v, state.kv, offset=offset, length=length,
                    window=cfg.window if kind == "local" else 0)
            new_state = state._replace(kv=kv)
        elif kind == "local":
            if q.shape[1] % cfg.window == 0:
                out = attn_lib.local_attention(q, k, v, window=cfg.window)
            else:  # short prompts: flash with a window mask (same math)
                out = attn_lib.flash_attention(q, k, v, causal=True,
                                               window=cfg.window,
                                               block_kv=cfg.attn_block_kv,
                                               unroll=cfg.unroll_scans,
                                               impl=cfg.attn_impl,
                                               layout=layout)
        elif kind == "enc":
            out = attn_lib.flash_attention(q, k, v, causal=False,
                                           block_kv=cfg.attn_block_kv,
                                           unroll=cfg.unroll_scans,
                                           f32_probs=cfg.attn_f32,
                                           impl=cfg.attn_impl, layout=layout)
        else:
            out = attn_lib.flash_attention(q, k, v, causal=True,
                                           block_kv=cfg.attn_block_kv,
                                           unroll=cfg.unroll_scans,
                                           f32_probs=cfg.attn_f32,
                                           impl=cfg.attn_impl, layout=layout)
        if mode == "prefill" and offset is None \
                and kind in ("attn", "local", "dec"):
            if paged:
                kv = attn_lib.paged_fill_cache(state.kv, k, v, block_table,
                                               length=length)
            else:
                kv = _fill_cache(state.kv, k, v, window=cfg.window
                                 if kind == "local" else 0, length=length)
            new_state = state._replace(kv=kv)
        b, s, _, _ = out.shape
        o = out.reshape(b, s, cfg.num_heads * cfg.head_dim)
        x = _residual(cfg, x, jnp.einsum("bsh,hd->bsd", o,
                                         p["attn"]["wo"].astype(x.dtype)))
        if kind == "dec":
            hx = _norm(cfg, p["ln_x"], x)
            qx, _, _ = attn_lib.qkv_project(
                p["xattn"], hx, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
                positions, rope_theta=cfg.rope_theta, use_rope=False)
            if state is not None and state.cross_kv is not None:
                ck, cv = state.cross_kv
            else:
                _, ck, cv = attn_lib.qkv_project(
                    p["xattn"], memory, cfg.num_heads, cfg.num_kv_heads,
                    cfg.head_dim, jnp.zeros(memory.shape[:2], jnp.int32),
                    rope_theta=cfg.rope_theta, use_rope=False)
            xo = attn_lib.flash_attention(qx, ck, cv, causal=False,
                                          block_kv=cfg.attn_block_kv)
            b, s, _, _ = xo.shape
            xo = xo.reshape(b, s, cfg.num_heads * cfg.head_dim)
            x = x + jnp.einsum("bsh,hd->bsd", xo,
                               p["xattn"]["wo"].astype(x.dtype))
        x, lb = _attn_ffn_tail(cfg, p, x)
    elif kind == "rec":
        h = _norm(cfg, p["ln1"], x)
        if mode == "train":
            x = x + rec_lib.rglru_block(p["rec"], h, chunk=cfg.scan_chunk,
                                        unroll=cfg.unroll_scans,
                                        impl=cfg.rglru_impl)
        else:
            y, rec_state = rec_lib.rglru_block(
                p["rec"], h, chunk=min(cfg.scan_chunk, h.shape[1]),
                state=_resume_rec(state.rec, offset), return_state=True,
                length=length, impl=cfg.rglru_impl, layout=layout)
            x = x + y
            new_state = state._replace(rec=rec_state)
        x, lb = _attn_ffn_tail(cfg, p, x)
    elif kind == "ssm":
        h = _norm(cfg, p["ln1"], x)
        if mode == "train":
            x = x + rec_lib.mamba_block(p["ssm"], h, d_state=cfg.d_state,
                                        dt_rank=cfg.dt_rank or None,
                                        chunk=cfg.scan_chunk,
                                        unroll=cfg.unroll_scans,
                                        impl=cfg.ssm_impl)
        else:
            y, rec_state = rec_lib.mamba_block(
                p["ssm"], h, d_state=cfg.d_state,
                dt_rank=cfg.dt_rank or None,
                chunk=min(cfg.scan_chunk, h.shape[1]),
                state=_resume_rec(state.rec, offset), return_state=True,
                length=length)
            x = x + y
            new_state = state._replace(rec=rec_state)
    elif kind == "mamba2":
        h = _norm(cfg, p["ln1"], x)
        kw = dict(n_heads=cfg.ssm_heads, d_state=cfg.d_state,
                  n_groups=cfg.ssm_groups, chunk=cfg.scan_chunk,
                  eps=cfg.norm_eps)
        if mode == "train":
            y = rec_lib.mamba2_block(p["mamba2"], h, **kw)
        else:
            y, rec_state = rec_lib.mamba2_block(
                p["mamba2"], h, state=_resume_rec(state.rec, offset),
                return_state=True, length=length, **kw)
            new_state = state._replace(rec=rec_state)
        x = _residual(cfg, x, y)
        x, lb = _attn_ffn_tail(cfg, p, x)
    else:
        raise ValueError(kind)
    return x, new_state, lb


def _resume_rec(rec: dict | None, offset: jax.Array | None) -> dict | None:
    """Carried conv/recurrent state for a (possibly resumed) prefill chunk.
    A slot being prefilled from scratch (offset == 0) may hold a previous
    request's residue — zero it per row; offset > 0 rows continue theirs."""
    if rec is None or offset is None:
        return rec
    live = offset > 0
    return {k: jnp.where(live.reshape((-1,) + (1,) * (a.ndim - 1)),
                         a, jnp.zeros_like(a))
            for k, a in rec.items()}


def _fill_cache(cache: attn_lib.KVCache, k, v, window: int = 0,
                length: jax.Array | None = None):
    """Write prefill K/V into the cache (left-aligned; ring for local).

    ``length``: (B,) valid prefix lengths for right-padded prefill.  Entries
    past ``length`` may hold padding garbage: they sit at cache positions that
    decode overwrites before its validity mask ever admits them, so they are
    never attended to."""
    b, s = k.shape[0], k.shape[1]
    smax = cache.k.shape[1]
    if length is not None and window:
        # ring layout: slot j must hold the last real position p < length with
        # p % smax == j (garbage slots are masked/overwritten downstream)
        j = jnp.arange(smax)[None, :]
        p = (length[:, None] - 1) - ((length[:, None] - 1 - j) % smax)
        p = jnp.clip(p, 0, s - 1)
        ck = jnp.take_along_axis(k, p[:, :, None, None], axis=1)
        cv = jnp.take_along_axis(v, p[:, :, None, None], axis=1)
        return attn_lib.KVCache(ck.astype(cache.k.dtype),
                                cv.astype(cache.v.dtype),
                                cache.length + length)
    if window and s > smax:
        k, v = k[:, -smax:], v[:, -smax:]
        s = smax
    ck = jax.lax.dynamic_update_slice(cache.k, k.astype(cache.k.dtype),
                                      (0, 0, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache.v, v.astype(cache.v.dtype),
                                      (0, 0, 0, 0))
    new_len = cache.length + (s if length is None else length)
    return attn_lib.KVCache(ck, cv, new_len)


def _logits(cfg: ArchConfig, x: jax.Array, table: jax.Array) -> jax.Array:
    """fp32 logits over the real vocabulary, divided by ``logits_scaling``."""
    logits = unembed(x, table)[..., :cfg.vocab_size]
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return logits


# ------------------------------------------------------------------- the model
class Model:
    """Bundles init/forward/loss/prefill/decode for one ArchConfig."""

    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg
        self.pattern = cfg.block_pattern
        self.n_groups = cfg.num_layers // len(self.pattern)
        self.tail_kinds = tuple(
            self.pattern[i % len(self.pattern)]
            for i in range(self.n_groups * len(self.pattern), cfg.num_layers))

    # ------------------------------------------------------------------- init
    def init(self, key) -> PyTree:
        cfg = self.cfg
        dtype = jnp.dtype(cfg.param_dtype)
        keys = jax.random.split(key, 8)
        params: dict = {
            "embed": init_embedding(keys[0], cfg.vocab_padded, cfg.d_model,
                                    dtype),
            "final_norm": _init_norm(cfg, cfg.d_model, dtype),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = init_embedding(keys[1], cfg.vocab_padded,
                                               cfg.d_model, dtype)
        # scanned groups: one stacked tree per pattern position
        group_params = {}
        for j, kind in enumerate(self.pattern):
            if self.n_groups > 0:
                ks = jax.random.split(jax.random.fold_in(keys[2], j),
                                      self.n_groups)
                group_params[str(j)] = jax.vmap(
                    lambda k: _init_block(cfg, kind, k, dtype))(ks)
        params["groups"] = group_params
        params["tail"] = [
            _init_block(cfg, kind, jax.random.fold_in(keys[3], i), dtype)
            for i, kind in enumerate(self.tail_kinds)]
        if cfg.is_encdec:
            ks = jax.random.split(keys[4], cfg.enc_layers)
            params["encoder"] = jax.vmap(
                lambda k: _init_block(cfg, "enc", k, dtype))(ks)
            params["enc_norm"] = _init_norm(cfg, cfg.d_model, dtype)
        if cfg.modality_tokens:
            k1, k2 = jax.random.split(keys[5])
            params["mm_proj"] = {
                "w1": fan_in_init(k1, (cfg.modality_dim, cfg.d_model), dtype),
                "w2": fan_in_init(k2, (cfg.d_model, cfg.d_model), dtype),
            }
        return params

    # ------------------------------------------------------------- embeddings
    def _embed_inputs(self, params, tokens, modality=None):
        cfg = self.cfg
        dt = jnp.dtype(cfg.compute_dtype)
        x = embed(params["embed"], tokens, dt) * (
            jnp.asarray(cfg.embedding_multiplier, dt)
            if cfg.embedding_multiplier
            else jnp.sqrt(jnp.asarray(cfg.d_model, dt)))
        if modality is not None and cfg.modality_tokens:
            m = modality.astype(dt)
            m = jnp.einsum("bmd,de->bme", m, params["mm_proj"]["w1"].astype(dt))
            m = jax.nn.gelu(m, approximate=True)
            m = jnp.einsum("bme,ef->bmf", m, params["mm_proj"]["w2"].astype(dt))
            x = jnp.concatenate([m, x], axis=1)
        return x

    # -------------------------------------------------------------- backbone
    def _run_stack(self, params, x, positions, memory=None):
        """Returns (x, total_load_balance_aux)."""
        cfg = self.cfg

        def group_fn(x, gp):
            lb_sum = jnp.zeros((), jnp.float32)
            for j, kind in enumerate(self.pattern):
                x, _, lb = apply_block(cfg, kind, gp[str(j)], x, positions,
                                       mode="train", memory=memory)
                lb_sum = lb_sum + lb
            return x, lb_sum

        if cfg.remat:
            group_fn = jax.checkpoint(group_fn,
                                      policy=jax.checkpoint_policies.nothing_saveable)
        lb_total = jnp.zeros((), jnp.float32)
        if self.n_groups > 0:
            if cfg.unroll_scans:
                for gi in range(self.n_groups):
                    gp = jax.tree.map(lambda a, gi=gi: a[gi], params["groups"])
                    x, lb = group_fn(x, gp)
                    lb_total = lb_total + lb
            else:
                def scan_step(carry, gp):
                    x, lb_acc = carry
                    x, lb = group_fn(x, gp)
                    return (x, lb_acc + lb), None
                (x, lb_total), _ = jax.lax.scan(scan_step, (x, lb_total),
                                                params["groups"])
        for p_t, kind in zip(params["tail"], self.tail_kinds):
            x, _, lb = apply_block(cfg, kind, p_t, x, positions,
                                   mode="train", memory=memory)
            lb_total = lb_total + lb
        return x, lb_total

    def _encode(self, params, src_embeds):
        cfg = self.cfg
        dt = jnp.dtype(cfg.compute_dtype)
        x = src_embeds.astype(dt)
        positions = jnp.broadcast_to(jnp.arange(x.shape[1])[None],
                                     x.shape[:2])

        def enc_fn(x, p):
            x, _, _ = apply_block(cfg, "enc", p, x, positions, mode="train")
            return x, None

        if cfg.unroll_scans:
            for li in range(cfg.enc_layers):
                x, _ = enc_fn(x, jax.tree.map(lambda a, li=li: a[li],
                                              params["encoder"]))
        else:
            x, _ = jax.lax.scan(enc_fn, x, params["encoder"])
        return _norm(cfg, params["enc_norm"], x)

    # ---------------------------------------------------------------- forward
    def forward(self, params, tokens, modality=None, src_embeds=None):
        """Full-sequence logits: (B,S) -> (B,S,V) fp32."""
        cfg = self.cfg
        memory = None
        if cfg.is_encdec:
            memory = self._encode(params, src_embeds)
        x = self._embed_inputs(params, tokens, modality)
        positions = jnp.broadcast_to(jnp.arange(x.shape[1])[None], x.shape[:2])
        x, lb = self._run_stack(params, x, positions, memory)
        x = _norm(cfg, params["final_norm"], x)
        table = params["embed"] if cfg.tie_embeddings else params["lm_head"]
        logits = _logits(cfg, x, table)
        if cfg.modality_tokens and modality is not None:
            logits = logits[:, modality.shape[1]:]
        aux = {"load_balance": lb} if cfg.ffn_kind == "moe" else {}
        return logits, aux

    # ------------------------------------------------------------------- loss
    def loss(self, params, batch):
        logits, aux = self.forward(
            params, batch["tokens"], batch.get("modality"),
            batch.get("src_embeds"))
        loss = cross_entropy_loss(logits, batch["labels"], batch.get("mask"))
        metrics = {"ce_loss": loss}
        if "load_balance" in aux:
            lb = aux["load_balance"] / max(self.cfg.num_layers, 1)
            loss = loss + 0.01 * lb
            metrics["load_balance"] = lb
        metrics["loss"] = loss
        return loss, metrics

    # ----------------------------------------------------------- serving path
    def init_states(self, batch: int, max_len: int, *,
                    kv_block_size: int | None = None,
                    kv_blocks: int | None = None,
                    shardings: PyTree | None = None) -> PyTree:
        """Stacked per-group states + tail states for the serving path.

        ``shardings``: optional pytree of ``NamedSharding`` mirroring the
        returned structure (``launch.shardings.serve_state_specs`` builds it)
        — the states are placed onto the mesh before returning, so a
        mesh-aware engine never round-trips the full dense pool through a
        single device.

        ``kv_block_size``/``kv_blocks``: when set, full-attention layers
        ("attn"/"dec" self-attention) store KV as a PAGED pool of
        ``kv_blocks`` blocks of ``kv_block_size`` tokens, addressed through a
        per-slot block table passed to prefill/decode_step.  Sliding-window
        layers keep their dense ring (already right-sized at ``window``
        tokens — the Mensa lesson of per-layer-class memory organization) and
        recurrent/SSM layers keep their fixed-size state."""
        cfg = self.cfg
        dt = jnp.dtype(cfg.compute_dtype)
        if kv_block_size is not None and kv_blocks is None:
            kv_blocks = batch * (-(-max_len // kv_block_size))

        def one(kind):
            if kind in ("attn", "dec"):
                if kv_block_size is not None:
                    kv = attn_lib.init_paged_kv_cache(
                        batch, kv_blocks, kv_block_size, cfg.num_kv_heads,
                        cfg.head_dim, dt)
                else:
                    kv = attn_lib.init_kv_cache(batch, max_len,
                                                cfg.num_kv_heads,
                                                cfg.head_dim, dt)
                return BlockState(kv=kv)
            if kind == "local":
                kv = attn_lib.init_kv_cache(batch, min(max_len, cfg.window),
                                            cfg.num_kv_heads, cfg.head_dim, dt)
                return BlockState(kv=kv)
            if kind == "rec":
                return BlockState(rec={
                    "conv": jnp.zeros((batch, cfg.d_conv - 1, cfg.d_rnn), dt),
                    "h": jnp.zeros((batch, cfg.d_rnn), jnp.float32)})
            if kind == "ssm":
                return BlockState(rec={
                    "conv": jnp.zeros((batch, cfg.d_conv - 1, cfg.d_inner), dt),
                    "h": jnp.zeros((batch, cfg.d_inner, cfg.d_state),
                                   jnp.float32)})
            if kind == "mamba2":
                conv_dim = cfg.d_inner + 2 * cfg.ssm_groups * cfg.d_state
                return BlockState(rec={
                    "conv": jnp.zeros((batch, cfg.d_conv - 1, conv_dim), dt),
                    "h": jnp.zeros((batch, cfg.ssm_heads,
                                    cfg.d_inner // cfg.ssm_heads,
                                    cfg.d_state), jnp.float32)})
            raise ValueError(kind)

        groups = {}
        for j, kind in enumerate(self.pattern):
            if self.n_groups > 0:
                groups[str(j)] = jax.tree.map(
                    lambda a: jnp.broadcast_to(
                        a[None], (self.n_groups,) + a.shape).copy(), one(kind))
        out = {"groups": groups,
               "tail": [one(k) for k in self.tail_kinds]}
        if shardings is not None:
            out = jax.device_put(out, shardings)
        return out

    def _run_stack_serving(self, params, states, x, positions, mode,
                           memory=None, length=None, offset=None,
                           block_table=None, layout=None):
        cfg = self.cfg

        def group_fn(x, gp_state):
            gp, gstate = gp_state
            new_states = {}
            for j, kind in enumerate(self.pattern):
                x, ns, _ = apply_block(cfg, kind, gp[str(j)], x, positions,
                                       mode=mode, state=gstate[str(j)],
                                       memory=memory, length=length,
                                       offset=offset, block_table=block_table,
                                       layout=layout)
                new_states[str(j)] = ns
            return x, new_states

        if self.n_groups > 0:
            if cfg.unroll_scans:
                outs = []
                for gi in range(self.n_groups):
                    gp_state = jax.tree.map(
                        lambda a, gi=gi: a[gi],
                        (params["groups"], states["groups"]))
                    x, ns = group_fn(x, gp_state)
                    outs.append(ns)
                new_group_states = jax.tree.map(
                    lambda *xs: jnp.stack(xs), *outs)
            else:
                def scan_step(x, gp_state):
                    x, ns = group_fn(x, gp_state)
                    return x, ns
                x, new_group_states = jax.lax.scan(
                    scan_step, x, (params["groups"], states["groups"]))
        else:
            new_group_states = states["groups"]
        new_tail = []
        for p_t, st, kind in zip(params["tail"], states["tail"],
                                 self.tail_kinds):
            x, ns, _ = apply_block(cfg, kind, p_t, x, positions,
                                   mode=mode, state=st, memory=memory,
                                   length=length, offset=offset,
                                   block_table=block_table, layout=layout)
            new_tail.append(ns)
        return x, {"groups": new_group_states, "tail": new_tail}

    def prefill(self, params, tokens, states, modality=None, src_embeds=None,
                length=None, offset=None, block_table=None, layout=None):
        """Process the prompt; fill caches; return last-position logits.

        ``length``: optional (B,) int32 valid prompt lengths for RIGHT-padded
        ``tokens`` (the bucketed serving path: pad every prompt to a shared
        bucket size so one compiled program serves all lengths in the bucket).
        Causal masking keeps real positions exact under right padding; the
        recurrent/SSM state updates freeze past ``length`` and caches record
        ``length`` (not S), so decode continues from the true prompt end.
        Logits are taken at position length-1 per row.

        ``offset``: optional (B,) int32 — ``tokens`` is one CHUNK of a longer
        prompt whose first ``offset`` tokens already live in ``states``
        (vLLM-style chunked prefill).  Attention resumes against the cache,
        recurrent/conv state continues from the carry (zeroed per row where
        offset == 0, so a recycled slot starts clean), and logits land at
        chunk position length-1.  Requires ``length``; decoder-only token
        models only.

        ``block_table``: (B, max_len/bs) int32, required when the states were
        built with ``init_states(kv_block_size=...)`` — paged layers write
        (and, for chunked continuation, read) their KV through it.

        ``layout``: optional ``models.layout.MeshLayout`` — a mesh-aware
        engine passes its layout here per call (paged K/V gathers, Pallas
        kernels under shard_map); the model itself stays stateless."""
        cfg = self.cfg
        memory = None
        if offset is not None:
            if length is None:
                raise ValueError("chunked prefill (offset=...) needs length")
            if cfg.is_encdec or cfg.modality_tokens:
                raise NotImplementedError(
                    "chunked prefill supports decoder-only token models")
        if cfg.is_encdec:
            memory = self._encode(params, src_embeds)
        x = self._embed_inputs(params, tokens, modality)
        base = jnp.arange(x.shape[1])[None]
        positions = jnp.broadcast_to(base, x.shape[:2]) if offset is None \
            else offset[:, None] + base
        x, states = self._run_stack_serving(params, states, x, positions,
                                            "prefill", memory, length, offset,
                                            block_table, layout)
        x = _norm(cfg, params["final_norm"], x)
        if length is None:
            x_last = x[:, -1:]
        else:
            idx = jnp.clip(length - 1, 0)[:, None, None]
            x_last = jnp.take_along_axis(
                x, jnp.broadcast_to(idx, (x.shape[0], 1, x.shape[2])), axis=1)
        table = params["embed"] if cfg.tie_embeddings else params["lm_head"]
        logits = _logits(cfg, x_last, table)
        return logits, states, memory

    def decode_step(self, params, token, states, position, memory=None,
                    active=None, block_table=None, layout=None):
        """token: (B,1) -> logits (B,1,V), updated states.

        ``active``: optional (B,) bool — False rows leave every piece of
        per-slot state (KV append + cache length, conv context, recurrent h)
        bit-for-bit unchanged and produce garbage logits, so an engine can
        tick a pool containing dead or mid-prefill slots without corrupting
        them.  Active rows are bitwise identical to active=None.

        ``block_table``: (B, max_len/bs) int32 for paged states — the new
        token's KV is scattered through it and attention gathers the slot's
        logical sequence from the block pool.

        ``layout``: optional ``models.layout.MeshLayout`` of a mesh-aware
        engine (see ``prefill``)."""
        cfg = self.cfg
        x = self._embed_inputs(params, token)
        positions = jnp.broadcast_to(position[:, None], token.shape)
        length = None if active is None else active.astype(jnp.int32)
        x, states = self._run_stack_serving(params, states, x, positions,
                                            "decode", memory, length,
                                            block_table=block_table,
                                            layout=layout)
        x = _norm(cfg, params["final_norm"], x)
        table = params["embed"] if cfg.tie_embeddings else params["lm_head"]
        logits = _logits(cfg, x, table)
        return logits, states


def build_model(cfg: ArchConfig) -> Model:
    return Model(cfg)
