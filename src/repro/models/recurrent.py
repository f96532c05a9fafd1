"""Recurrent blocks — the Mensa "Pavlov cluster" at pod scale.

* ``rglru_block`` — RecurrentGemma/Griffin recurrent block: temporal conv1d +
  RG-LRU gated linear recurrence, GeLU-gated output branch.
* ``mamba_block`` — Mamba-1 selective SSM (Falcon-Mamba).
* ``mamba2_block`` — Mamba-2 mixer (state-space duality, Granite 4.0-H):
  chunked SSD in matmul form for prefill, a one-token recurrence for decode.
* ``lstm_layer``  — classic LSTM (reference for the Pavlov kernels and the
  edge-model examples).

All recurrences are expressed as first-order linear recurrences
h_t = a_t * h_{t-1} + b_t and computed with ``jax.lax.associative_scan``
inside sequence chunks (lax.scan carries the state across chunks), which
bounds peak memory to O(chunk) per layer and keeps the HLO compact.

The Pavlov design maps here as: recurrence weights are fetched once and stay
resident across the whole time scan (VMEM-resident in the Pallas kernels);
input projections for *all* timesteps are hoisted out of the recurrence as one
large GEMM (the paper's decoupled input/hidden MVM schedule, §5.4).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .common import fan_in_init, normal_init
from .layout import MeshLayout


def _chunked_linear_scan(a, b, h0, chunk: int, unroll: bool = False):
    """h_t = a_t * h_{t-1} + b_t along axis 1.  a,b: (B,S,...), h0: (B,...).
    S need not divide chunk: the tail is identity-padded (a=1, b=0), which
    passes the state through unchanged, and the padded outputs are sliced
    off."""
    B, S = a.shape[0], a.shape[1]
    chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:
        widths = ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)
        a = jnp.pad(a, widths, constant_values=1.0)
        b = jnp.pad(b, widths, constant_values=0.0)
    nc = (S + pad) // chunk
    a_c = a.reshape((B, nc, chunk) + a.shape[2:])
    b_c = b.reshape((B, nc, chunk) + b.shape[2:])

    def combine(x, y):
        a1, b1 = x
        a2, b2 = y
        return a1 * a2, a2 * b1 + b2

    def step(h, ab):
        ac, bc = ab                          # (B, chunk, ...)
        aa, bb = jax.lax.associative_scan(combine, (ac, bc), axis=1)
        h_new = aa * h[:, None] + bb         # fold in carry
        return h_new[:, -1], h_new

    h_last, hs = jax.lax.scan(step, h0, (jnp.moveaxis(a_c, 1, 0),
                                         jnp.moveaxis(b_c, 1, 0)),
                              unroll=nc if unroll else 1)
    hs = jnp.moveaxis(hs, 0, 1).reshape((B, S + pad) + a.shape[2:])
    return hs[:, :S], h_last


def causal_conv1d(x: jax.Array, w: jax.Array, state: jax.Array | None = None,
                  length: jax.Array | None = None):
    """Depthwise causal temporal conv.  x: (B,S,C), w: (K,C).
    ``state``: (B,K-1,C) trailing context from the previous segment (decode).
    ``length``: (B,) valid prefix lengths for right-padded x — the returned
    state is then the context trailing position ``length-1``, not S-1.
    Returns (y, new_state)."""
    k = w.shape[0]
    if state is None:
        state = jnp.zeros((x.shape[0], k - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([state, x], axis=1)
    y = sum(xp[:, i:i + x.shape[1]] * w[i][None, None] for i in range(k))
    if k > 1:
        if length is None:
            new_state = xp[:, -(k - 1):]
        else:
            # xp index of real token t is (k-1)+t, so the k-1 inputs trailing
            # position length-1 live at xp[length .. length+k-2]
            idx = length[:, None] + jnp.arange(k - 1)[None, :]
            idx = jnp.clip(idx, 0, xp.shape[1] - 1)
            new_state = jnp.take_along_axis(xp, idx[:, :, None], axis=1)
    else:
        new_state = state
    return y.astype(x.dtype), new_state


# ---------------------------------------------------------------------- RG-LRU
def init_rglru_block(key, d_model: int, d_rnn: int, *, conv_width: int = 4,
                     gate_blocks: int = 0, dtype=jnp.float32) -> dict:
    """gate_blocks > 0: block-diagonal recurrence/input gates (Griffin's
    actual design) — with #blocks a multiple of the mesh `model` axis the
    gate matmuls are fully local under TP (no collectives)."""
    ks = jax.random.split(key, 7)
    # Lambda init so that a = sigmoid(L)^(c*r) sits in [0.9, 0.999]
    u = jax.random.uniform(ks[0], (d_rnn,), jnp.float32, 0.9, 0.999)
    c = 8.0
    lam = jnp.log(u ** (1.0 / c) / (1.0 - u ** (1.0 / c)))
    if gate_blocks:
        assert d_rnn % gate_blocks == 0
        bd = d_rnn // gate_blocks
        gk1 = jax.random.split(ks[4], gate_blocks)
        gk2 = jax.random.split(ks[5], gate_blocks)
        w_a = jnp.stack([fan_in_init(k, (bd, bd), dtype) for k in gk1])
        w_i = jnp.stack([fan_in_init(k, (bd, bd), dtype) for k in gk2])
    else:
        w_a = fan_in_init(ks[4], (d_rnn, d_rnn), dtype)
        w_i = fan_in_init(ks[5], (d_rnn, d_rnn), dtype)
    return {
        "w_x": fan_in_init(ks[1], (d_model, d_rnn), dtype),
        "w_y": fan_in_init(ks[2], (d_model, d_rnn), dtype),
        "conv_w": normal_init(ks[3], (conv_width, d_rnn),
                              1.0 / math.sqrt(conv_width), dtype),
        "w_a": w_a,   # recurrence gate
        "w_i": w_i,   # input gate
        "lambda": lam.astype(dtype),
        "w_out": fan_in_init(ks[6], (d_rnn, d_model), dtype),
    }


def _divisor_block(n: int, target: int, align: int) -> int:
    """Block size for one axis of a Pallas kernel: the largest multiple of
    ``align`` that is <= target and divides n, else n itself.  The kernels
    assert exact tiling, and the TPU tiling takes a block that is a
    multiple of the hardware tile (8 sublanes, 128 lanes) or the whole
    axis.  n is a static shape, so this runs at trace time."""
    for b in range(min(n, target) // align * align, 0, -align):
        if n % b == 0:
            return b
    return n


def rglru_core(params: dict, x: jax.Array, h0: jax.Array | None = None,
               chunk: int = 512, unroll: bool = False,
               seq_mask: jax.Array | None = None, impl: str = "xla",
               layout: MeshLayout | None = None):
    """The RG-LRU recurrence.  x: (B,S,d_rnn) (post-conv).  Returns (y, h_T).
    ``seq_mask``: (B,S) bool; False positions pass the state through
    unchanged (a=1, b=0), so h_T is the state at the last True position.
    ``impl="pallas"`` runs the scan through the fused pavlov_rglru kernel
    (h0 folded into b[:, 0]; identical math, same masking semantics); a
    mesh engine's ``layout`` runs it under ``shard_map`` (rows on the data
    axes, the recurrence width on ``model`` where it splits evenly)."""
    dt = x.dtype
    c = 8.0
    xf = x.astype(jnp.float32)
    if params["w_a"].ndim == 3:       # block-diagonal gates (local under TP)
        g = params["w_a"].shape[0]
        xg = xf.reshape(xf.shape[0], xf.shape[1], g, -1)
        r = jax.nn.sigmoid(jnp.einsum(
            "bsgd,gde->bsge", xg, params["w_a"].astype(jnp.float32)
        ).reshape(xf.shape))
        i = jax.nn.sigmoid(jnp.einsum(
            "bsgd,gde->bsge", xg, params["w_i"].astype(jnp.float32)
        ).reshape(xf.shape))
    else:
        # gate matmuls in compute dtype (bf16): the TP partial-sum all-reduce
        # moves half the bytes vs f32; sigmoid applied in f32 after
        r = jax.nn.sigmoid(jnp.einsum("bsd,de->bse", x,
                                      params["w_a"].astype(dt)
                                      ).astype(jnp.float32))
        i = jax.nn.sigmoid(jnp.einsum("bsd,de->bse", x,
                                      params["w_i"].astype(dt)
                                      ).astype(jnp.float32))
    log_a = -c * r * jax.nn.softplus(-params["lambda"].astype(jnp.float32))
    a = jnp.exp(log_a)
    gated = i * xf
    b = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-6)) * gated
    if seq_mask is not None:
        m = seq_mask[:, :, None]
        a = jnp.where(m, a, 1.0)
        b = jnp.where(m, b, 0.0)
    if h0 is None:
        h0 = jnp.zeros((x.shape[0], x.shape[2]), jnp.float32)
    if impl == "pallas":
        from ..kernels.pavlov_rglru.ops import pavlov_rglru
        # the kernel scans from h=0; folding a_0*h0 into b_0 reproduces the
        # h0-seeded recurrence exactly (h_1 = a_0*h0 + b_0 either way)
        b = b.at[:, 0].add(a[:, 0] * h0)

        def kernel(a, b):
            return pavlov_rglru(a, b,
                                block_t=_divisor_block(a.shape[1], 128, 8),
                                block_e=_divisor_block(a.shape[2], 512, 128))

        if layout is not None:
            spec = P(layout.batch(a.shape[0]), None, layout.width(a.shape[2]))
            kernel = layout.shard(kernel, (spec, spec), spec)
        h = kernel(a, b)
        # masked tail positions are identity steps (a=1, b=0), so the final
        # row already holds the state at the last valid position
        return h.astype(dt), h[:, -1].astype(jnp.float32)
    h, h_last = _chunked_linear_scan(a, b, h0, chunk, unroll)
    return h.astype(dt), h_last


def rglru_block(params: dict, x: jax.Array, *, chunk: int = 512,
                unroll: bool = False,
                state: dict | None = None, return_state: bool = False,
                length: jax.Array | None = None, impl: str = "xla",
                layout: MeshLayout | None = None):
    """Full Griffin recurrent block.  x: (B,S,D) -> (B,S,D).
    ``length``: (B,) valid prefix lengths when x is right-padded (bucketed
    prefill) — the returned state then reflects position length-1."""
    dt = x.dtype
    y = jax.nn.gelu(jnp.einsum("bsd,de->bse", x, params["w_y"].astype(dt)),
                    approximate=True)
    u = jnp.einsum("bsd,de->bse", x, params["w_x"].astype(dt))
    conv_state = state["conv"] if state else None
    h0 = state["h"] if state else None
    seq_mask = None if length is None else \
        jnp.arange(x.shape[1])[None, :] < length[:, None]
    u, new_conv = causal_conv1d(u, params["conv_w"].astype(dt), conv_state,
                                length=length)
    h, h_last = rglru_core(params, u, h0, chunk, unroll, seq_mask=seq_mask,
                           impl=impl, layout=layout)
    out = jnp.einsum("bse,ed->bsd", (h * y), params["w_out"].astype(dt))
    if return_state:
        return out, {"conv": new_conv, "h": h_last}
    return out


# ---------------------------------------------------------------------- Mamba-1
def init_mamba_block(key, d_model: int, d_inner: int, d_state: int = 16,
                     d_conv: int = 4, dt_rank: int | None = None,
                     dtype=jnp.float32) -> dict:
    dt_rank = dt_rank or max(1, d_model // 16)
    ks = jax.random.split(key, 7)
    a_init = jnp.tile(jnp.arange(1, d_state + 1, dtype=jnp.float32)[None],
                      (d_inner, 1))
    return {
        "in_proj": fan_in_init(ks[0], (d_model, 2 * d_inner), dtype),
        "conv_w": normal_init(ks[1], (d_conv, d_inner),
                              1.0 / math.sqrt(d_conv), dtype),
        "x_proj": fan_in_init(ks[2], (d_inner, dt_rank + 2 * d_state), dtype),
        "dt_proj": fan_in_init(ks[3], (dt_rank, d_inner), dtype),
        "dt_bias": jnp.log(jnp.expm1(
            jax.random.uniform(ks[4], (d_inner,), jnp.float32, 1e-3, 1e-1)
        )).astype(dtype),
        "a_log": jnp.log(a_init).astype(dtype),
        "d_skip": jnp.ones((d_inner,), dtype),
        "out_proj": fan_in_init(ks[5], (d_inner, d_model), dtype),
    }


def mamba_ssm(params: dict, x: jax.Array, dt_rank: int, d_state: int,
              h0: jax.Array | None = None, chunk: int = 256,
              unroll: bool = False, seq_mask: jax.Array | None = None,
              impl: str = "xla"):
    """Selective scan.  x: (B,S,d_inner) (post conv+silu).  Returns (y, h_T).
    ``seq_mask``: (B,S) bool; False positions leave the state unchanged.
    ``impl="pallas"`` runs the fused pavlov_ssm kernel; it scans from h=0 and
    yields outputs only, so it requires ``h0 is None`` and returns h_T=None —
    callers that carry state across calls (serving) must stay on "xla"."""
    B_, S, di = x.shape
    xf = x.astype(jnp.float32)
    proj = jnp.einsum("bsd,dr->bsr", xf, params["x_proj"].astype(jnp.float32))
    dt_in, b_in, c_in = jnp.split(proj, [dt_rank, dt_rank + d_state], axis=-1)
    delta = jax.nn.softplus(
        jnp.einsum("bsr,rd->bsd", dt_in, params["dt_proj"].astype(jnp.float32))
        + params["dt_bias"].astype(jnp.float32))                    # (B,S,di)
    a = -jnp.exp(params["a_log"].astype(jnp.float32))               # (di,Ns)
    if impl == "pallas" and h0 is None:
        from ..kernels.pavlov_ssm.ops import pavlov_ssm
        # tail padding only contributes to padded output rows (the state at
        # valid positions never sees a later timestep), so the unmasked
        # kernel matches the masked scan on every valid position
        y = pavlov_ssm(delta, xf, b_in, c_in, a,
                       params["d_skip"].astype(jnp.float32),
                       block_t=_divisor_block(S, 128, 8),
                       block_d=_divisor_block(di, 512, 128))
        return y.astype(x.dtype), None
    # first-order recurrence per (channel, state): h = exp(delta*a) h + delta*B*x
    alpha = jnp.exp(delta[..., None] * a[None, None])               # (B,S,di,Ns)
    beta = (delta * xf)[..., None] * b_in[:, :, None, :]            # (B,S,di,Ns)
    if seq_mask is not None:
        m = seq_mask[:, :, None, None]
        alpha = jnp.where(m, alpha, 1.0)
        beta = jnp.where(m, beta, 0.0)
    if h0 is None:
        h0 = jnp.zeros((B_, di, d_state), jnp.float32)
    h, h_last = _chunked_linear_scan(alpha, beta, h0, chunk, unroll)
    y = jnp.einsum("bsdn,bsn->bsd", h, c_in) \
        + xf * params["d_skip"].astype(jnp.float32)
    return y.astype(x.dtype), h_last


def mamba_block(params: dict, x: jax.Array, *, d_state: int = 16,
                dt_rank: int | None = None, chunk: int = 256,
                unroll: bool = False,
                state: dict | None = None, return_state: bool = False,
                length: jax.Array | None = None, impl: str = "xla"):
    """Full Mamba-1 block.  x: (B,S,D) -> (B,S,D).
    ``length``: (B,) valid prefix lengths when x is right-padded."""
    dt = x.dtype
    d_model = x.shape[-1]
    dt_rank = dt_rank or max(1, d_model // 16)
    xz = jnp.einsum("bsd,de->bse", x, params["in_proj"].astype(dt))
    xi, z = jnp.split(xz, 2, axis=-1)
    conv_state = state["conv"] if state else None
    h0 = state["h"] if state else None
    seq_mask = None if length is None else \
        jnp.arange(x.shape[1])[None, :] < length[:, None]
    xi, new_conv = causal_conv1d(xi, params["conv_w"].astype(dt), conv_state,
                                 length=length)
    xi = jax.nn.silu(xi)
    # the fused kernel yields no carry state — callers that thread state
    # (serving prefill/decode) must take the XLA scan
    ssm_impl = impl if (state is None and not return_state) else "xla"
    y, h_last = mamba_ssm(params, xi, dt_rank, d_state, h0, chunk, unroll,
                          seq_mask=seq_mask, impl=ssm_impl)
    out = jnp.einsum("bse,ed->bsd", y * jax.nn.silu(z),
                     params["out_proj"].astype(dt))
    if return_state:
        return out, {"conv": new_conv, "h": h_last}
    return out


# ---------------------------------------------------------------------- Mamba-2
def init_mamba2_block(key, d_model: int, d_inner: int, n_heads: int,
                      d_state: int, n_groups: int = 1, d_conv: int = 4,
                      dtype=jnp.float32) -> dict:
    """Mamba-2 mixer weights.  ``in_proj`` maps to [z, x, B, C, dt]
    (d_inner, d_inner, groups*d_state twice, n_heads); the depthwise conv
    (with bias) runs over [x, B, C]; ``norm`` is the gated RMSNorm's
    weight, stored as ``1 + norm`` like every RMSNorm here."""
    conv_dim = d_inner + 2 * n_groups * d_state
    ks = jax.random.split(key, 5)
    dt = jnp.exp(jax.random.uniform(ks[2], (n_heads,), jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    a = jax.random.uniform(ks[3], (n_heads,), jnp.float32, 1.0, 16.0)
    return {
        "in_proj": fan_in_init(ks[0], (d_model, 2 * d_inner
                                       + 2 * n_groups * d_state + n_heads),
                               dtype),
        "conv_w": normal_init(ks[1], (d_conv, conv_dim),
                              1.0 / math.sqrt(d_conv), dtype),
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "dt_bias": jnp.log(jnp.expm1(dt)).astype(dtype),
        "a_log": jnp.log(a).astype(dtype),
        "d_skip": jnp.ones((n_heads,), dtype),
        "norm": jnp.zeros((d_inner,), dtype),
        "out_proj": fan_in_init(ks[4], (d_inner, d_model), dtype),
    }


def ssd_chunked(x, dt, a, b, c, h0, chunk: int):
    """Chunked state-space duality (SSD) scan in matmul form.

    x: (B,S,G,E,P) inputs, dt: (B,S,G,E) step sizes (0 where masked),
    a: (G,E) negative decay rates, b/c: (B,S,G,N), h0: (B,G,E,P,N), all
    float32; G groups of E heads of P channels share B and C.  Returns
    (y (B,S,G,E,P), h_last).  With a_t = dt_t * a the recurrence is
    h_t = exp(a_t) h_{t-1} + dt_t x_t B_t^T, y_t = h_t C_t.  Within a
    chunk of Q steps, with A_i the cumulative sum of a_t up to step i,

      y_i = sum_{j<=i} (C_i . B_j) exp(A_i - A_j) dt_j x_j + exp(A_i) h C_i

    (the masked quadratic form, then the carried state ``h`` entering the
    chunk), and the state leaving it is
    exp(A_Q) h + sum_j exp(A_Q - A_j) dt_j x_j B_j^T.  A lax.scan carries h
    from chunk to chunk.  S need not divide ``chunk``: the tail is padded
    with dt = 0 steps, which leave the state unchanged."""
    B_, S = x.shape[:2]
    q = min(chunk, S)
    pad = (-S) % q
    if pad:
        def padt(t):
            return jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        x, dt, b, c = padt(x), padt(dt), padt(b), padt(c)
    nc = (S + pad) // q

    def chunks(t):                       # (B, S, ...) -> (B, nc, Q, ...)
        return t.reshape((B_, nc, q) + t.shape[2:])

    x, dt, b, c = chunks(x), chunks(dt), chunks(b), chunks(c)
    acs = jnp.cumsum(dt * a, axis=2)                         # (B,nc,Q,G,E)
    # intra-chunk decay exp(A_i - A_j), i >= j, as (B,nc,G,E,i,j)
    at = jnp.moveaxis(acs, 2, -1)                            # (B,nc,G,E,Q)
    seg = at[..., :, None] - at[..., None, :]
    causal = jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    cb = jnp.einsum("bcign,bcjgn->bcgij", c, b)              # (B,nc,G,Q,Q)
    w = cb[:, :, :, None] * decay * jnp.moveaxis(dt, 2, -1)[..., None, :]
    y = jnp.einsum("bcgeij,bcjgep->bcigep", w, x)
    # each chunk's own contribution to the state at its end
    to_end = jnp.exp(acs[:, :, -1:] - acs) * dt               # (B,nc,Q,G,E)
    states = jnp.einsum("bcjgn,bcjge,bcjgep->bcgepn", b, to_end, x)
    chunk_decay = jnp.exp(acs[:, :, -1])                      # (B,nc,G,E)

    def carry(h, inp):
        st, dec = inp
        return dec[..., None, None] * h + st, h

    h_last, h_in = jax.lax.scan(
        carry, h0, (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    h_in = jnp.moveaxis(h_in, 0, 1)                          # (B,nc,G,E,P,N)
    y = y + jnp.einsum("bcign,bcgepn->bcigep", c, h_in) \
        * jnp.exp(acs)[..., None]
    y = y.reshape((B_, nc * q) + y.shape[3:])[:, :S]
    return y, h_last


def ssd_step(x, dt, a, b, c, h0):
    """One token of the SSD recurrence.  x: (B,G,E,P), dt: (B,G,E),
    a: (G,E), b/c: (B,G,N), h0: (B,G,E,P,N).  Returns (y, h).  A row with
    dt = 0 keeps its state bit for bit."""
    h = jnp.exp(dt * a)[..., None, None] * h0 \
        + (dt[..., None] * x)[..., None] * b[:, :, None, None, :]
    y = jnp.sum(h * c[:, :, None, None, :], axis=-1)
    return y, h


def mamba2_block(params: dict, x: jax.Array, *, n_heads: int, d_state: int,
                 n_groups: int = 1, chunk: int = 256, eps: float = 1e-6,
                 state: dict | None = None, return_state: bool = False,
                 length: jax.Array | None = None):
    """Mamba-2 mixer.  x: (B,S,D) -> (B,S,D).

      [z, xBC, dt] = x W_in
      [x, B, C]    = silu(conv1d(xBC) + conv_b)         (causal, width K)
      dt           = softplus(dt + dt_bias),  a = -exp(a_log)   (per head)
      h_t          = exp(dt_t a) h_{t-1} + dt_t x_t B_t^T       (per head:
                                                             P x N state)
      y_t          = h_t C_t + D x_t
      out          = (rmsnorm(y * silu(z)) * (1 + norm)) W_out

    Prefill runs the recurrence as chunked SSD (:func:`ssd_chunked`),
    decode (S = 1) as one step (:func:`ssd_step`); both in float32.
    ``state``: {"conv": (B,K-1,conv_dim), "h": (B,H,P,N)} carried in;
    ``length``: (B,) valid prefix lengths of right-padded x — positions past
    it get dt = 0, so the returned state is the state at length-1 (and a
    decode row with length 0 keeps its state)."""
    with jax.named_scope("mamba2"):
        dt_ = x.dtype
        f32 = jnp.float32
        B_, S, _ = x.shape
        d_inner = params["out_proj"].shape[0]
        gn = n_groups * d_state
        p_dim = d_inner // n_heads
        e = n_heads // n_groups
        proj = jnp.einsum("bsd,de->bse", x, params["in_proj"].astype(dt_))
        z = proj[..., :d_inner]
        xbc = proj[..., d_inner:2 * d_inner + 2 * gn]
        dt = proj[..., 2 * d_inner + 2 * gn:]
        conv_state = state["conv"] if state else None
        xbc, new_conv = causal_conv1d(xbc, params["conv_w"].astype(dt_),
                                      conv_state, length=length)
        xbc = jax.nn.silu(xbc.astype(f32) + params["conv_b"].astype(f32))
        xs = xbc[..., :d_inner].reshape(B_, S, n_groups, e, p_dim)
        b = xbc[..., d_inner:d_inner + gn].reshape(B_, S, n_groups, d_state)
        c = xbc[..., d_inner + gn:].reshape(B_, S, n_groups, d_state)
        dt = jax.nn.softplus(dt.astype(f32) + params["dt_bias"].astype(f32))
        if length is not None:
            live = jnp.arange(S)[None, :] < length[:, None]
            dt = jnp.where(live[:, :, None], dt, 0.0)
        dt = dt.reshape(B_, S, n_groups, e)
        a = -jnp.exp(params["a_log"].astype(f32)).reshape(n_groups, e)
        h0 = state["h"].reshape(B_, n_groups, e, p_dim, d_state) if state \
            else jnp.zeros((B_, n_groups, e, p_dim, d_state), f32)
        if S == 1:
            y, h_last = ssd_step(xs[:, 0], dt[:, 0], a, b[:, 0], c[:, 0], h0)
            y = y[:, None]
        else:
            y, h_last = ssd_chunked(xs, dt, a, b, c, h0, chunk)
        y = y + xs * params["d_skip"].astype(f32).reshape(n_groups, e, 1)
        y = y.reshape(B_, S, d_inner) * jax.nn.silu(z.astype(f32))
        y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
        y = (y * (1.0 + params["norm"].astype(f32))).astype(dt_)
        out = jnp.einsum("bse,ed->bsd", y, params["out_proj"].astype(dt_))
        if return_state:
            return out, {"conv": new_conv,
                         "h": h_last.reshape(B_, n_heads, p_dim, d_state)}
        return out


# ------------------------------------------------------------------------ LSTM
def init_lstm_layer(key, d_in: int, d_hidden: int, dtype=jnp.float32) -> dict:
    k1, k2 = jax.random.split(key)
    return {
        "w_x": fan_in_init(k1, (d_in, 4 * d_hidden), dtype),
        "w_h": fan_in_init(k2, (d_hidden, 4 * d_hidden), dtype),
        "b": jnp.zeros((4 * d_hidden,), dtype),
    }


def lstm_layer(params: dict, x: jax.Array,
               state: tuple[jax.Array, jax.Array] | None = None):
    """x: (B,S,Din) -> (B,S,H).  The input MVMs for *all* timesteps are
    computed as one batched GEMM before the recurrence (the paper's Pavlov
    decoupled schedule) so W_x is read exactly once."""
    b, s, _ = x.shape
    h4 = params["w_x"].shape[1]
    hd = h4 // 4
    dt = x.dtype
    if state is None:
        state = (jnp.zeros((b, hd), jnp.float32), jnp.zeros((b, hd), jnp.float32))
    # decoupled input MVMs (one GEMM over the whole sequence)
    xg = jnp.einsum("bsd,dh->bsh", x, params["w_x"].astype(dt)) \
        + params["b"].astype(dt)

    wh = params["w_h"].astype(jnp.float32)

    def step(carry, xg_t):
        h, c = carry
        gates = xg_t.astype(jnp.float32) + h @ wh
        i, f, g, o = jnp.split(gates, 4, axis=-1)
        c = jax.nn.sigmoid(f + 1.0) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        h = jax.nn.sigmoid(o) * jnp.tanh(c)
        return (h, c), h

    (h, c), ys = jax.lax.scan(step, state, jnp.moveaxis(xg, 1, 0))
    return jnp.moveaxis(ys, 0, 1).astype(dt), (h, c)
