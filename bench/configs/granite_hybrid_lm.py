"""Plain float32 reference, weights and shape arithmetic of a Mamba-2 +
attention hybrid decoder LM (Granite 4.0-H), in the layout the serving
program takes.

Everything here is read from the configuration file's ``published`` keys
(the model's own ``config.json``); nothing is imported from the program.
Each layer is ``layer_types[i]``: a Mamba-2 mixer or a grouped-query
attention without positional encoding, each followed by a SwiGLU MLP of
width ``shared_intermediate_size``; every residual branch is scaled by
``residual_multiplier``, embeddings by ``embedding_multiplier``, scores by
``attention_multiplier`` and logits divided by ``logits_scaling``.  The
Mamba-2 mixer runs its recurrence one position at a time,

  h_t = exp(dt_t a) h_{t-1} + dt_t x_t B_t^T,   y_t = h_t C_t + D x_t,

the plainest form, and not the program's chunked matmul form.  Where the
program departs from the published architecture the reference follows the
program, and the configuration file lists the departure (``departures``):

* RMSNorm weights (layers, the final norm, the mixer's gated norm) are
  stored as ``1 + scale``.

``init`` makes the weights; ``hidden`` is the reference forward over one
sequence, with attention computed over blocks of queries so that 16k
positions fit; ``model_flops`` and ``decode_work`` count the operations
and bytes the benchmark's per-layer metrics divide by.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 256           # query rows per attention block of the reference
#: the residual branches' output projections (``out_proj``, ``wo``,
#: ``w_down``) are drawn at this multiple of the fan-in scale.  At the
#: fan-in scale, under ``residual_multiplier`` 0.22 and
#: ``embedding_multiplier`` 12, a token's own embedding dominates the tied
#: LM head, so greedy decoding of random weights repeats a few tokens
#: whatever the context, and a fault that loses context would not change a
#: served token.
BRANCH_GAIN = 8.0


def dims(pub: dict) -> dict:
    types = list(pub["layer_types"])
    n = len(types)
    period = next(p for p in range(1, n + 1) if n % p == 0
                  and all(types[i] == types[i % p] for i in range(n)))
    heads, p_dim = pub["mamba_n_heads"], pub["mamba_d_head"]
    groups, state = pub["mamba_n_groups"], pub["mamba_d_state"]
    di = heads * p_dim
    return dict(d=pub["hidden_size"], ff=pub["shared_intermediate_size"],
                layers=pub["num_hidden_layers"], types=types, period=period,
                h=pub["num_attention_heads"], kvh=pub["num_key_value_heads"],
                hd=pub["hidden_size"] // pub["num_attention_heads"],
                mh=heads, mp=p_dim, g=groups, n=state, di=di,
                conv=di + 2 * groups * state, k=pub["mamba_d_conv"],
                vocab=pub["vocab_size"],
                vocab_rows=-(-pub["vocab_size"] // 16) * 16,
                eps=float(pub["rms_norm_eps"]),
                emb=float(pub["embedding_multiplier"]),
                att=float(pub["attention_multiplier"]),
                res=float(pub["residual_multiplier"]),
                logit=float(pub["logits_scaling"]),
                tied=bool(pub["tie_word_embeddings"]))


# --------------------------------------------------------------- weights
def init(pub: dict, key, dtype=jnp.bfloat16) -> dict:
    """The weights, in the program's parameter layout (one stacked tree per
    position of the layer pattern), from one key.  Call under ``jax.jit``
    so that they are made on the device in one program."""
    m = dims(pub)
    d, ff, G = m["d"], m["ff"], m["layers"] // m["period"]
    hq, hk = m["h"] * m["hd"], m["kvh"] * m["hd"]
    ks = iter(jax.random.split(key, 16 * m["period"] + 4))

    def normal(shape, std):
        return (std * jax.random.normal(next(ks), (G,) + shape, jnp.float32)
                ).astype(dtype)

    def scale(shape):          # norm weights 1 + N(0, 0.1)
        return normal(shape, 0.1)

    def layer(kind):
        p = {"ln1": {"scale": scale((d,))},
             "ln2": {"scale": scale((d,))},
             "ffn": {"w_gate": normal((d, ff), d ** -0.5),
                     "w_up": normal((d, ff), d ** -0.5),
                     "w_down": normal((ff, d), BRANCH_GAIN * ff ** -0.5)}}
        if kind == "attention":
            p["attn"] = {"wq": normal((d, hq), d ** -0.5),
                         "wk": normal((d, hk), d ** -0.5),
                         "wv": normal((d, hk), d ** -0.5),
                         "wo": normal((hq, d), BRANCH_GAIN * hq ** -0.5)}
            return p
        di, H = m["di"], m["mh"]
        dt0 = jnp.exp(jax.random.uniform(next(ks), (G, H), jnp.float32,
                                         math.log(1e-3), math.log(1e-1)))
        a0 = jax.random.uniform(next(ks), (G, H), jnp.float32, 1.0, 16.0)
        p["mamba2"] = {
            "in_proj": normal((d, 2 * di + 2 * m["g"] * m["n"] + H),
                              d ** -0.5),
            "conv_w": normal((m["k"], m["conv"]), m["k"] ** -0.5),
            "conv_b": normal((m["conv"],), 0.1),
            "dt_bias": jnp.log(jnp.expm1(dt0)).astype(dtype),
            "a_log": jnp.log(a0).astype(dtype),
            "d_skip": (1.0 + normal((H,), 0.1).astype(jnp.float32)
                       ).astype(dtype),
            "norm": scale((di,)),
            "out_proj": normal((di, d), BRANCH_GAIN * di ** -0.5)}
        return p

    groups = {str(j): layer(m["types"][j]) for j in range(m["period"])}
    out = {"embed": (d ** -0.5 * jax.random.normal(
               next(ks), (m["vocab_rows"], d), jnp.float32)).astype(dtype),
           "final_norm": {"scale": (0.1 * jax.random.normal(
               next(ks), (d,), jnp.float32)).astype(dtype)},
           "groups": groups, "tail": []}
    if not m["tied"]:
        out["lm_head"] = (d ** -0.5 * jax.random.normal(
            next(ks), (m["vocab_rows"], d), jnp.float32)).astype(dtype)
    return out


def head_table(params: dict, pub: dict):
    return params["embed"] if pub["tie_word_embeddings"] else params["lm_head"]


# --------------------------------------------------------------- reference
def _rms(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + scale)


def _mlp(x, p, m, weight):
    f = p["ffn"]
    hn = _rms(x, p["ln2"]["scale"], m["eps"])
    gt = jnp.einsum("td,df->tf", hn, weight(f["w_gate"]), precision=HI)
    up = jnp.einsum("td,df->tf", hn, weight(f["w_up"]), precision=HI)
    y = jnp.einsum("tf,fd->td", jax.nn.silu(gt) * up, weight(f["w_down"]),
                   precision=HI)
    return x + m["res"] * y


def _attention(x, p, m, weight):
    """Causal GQA without positional encoding, scores x attention_multiplier,
    over blocks of ``Q_BLOCK`` query rows."""
    a = p["attn"]
    t = x.shape[0]
    h, kvh, hd = m["h"], m["kvh"], m["hd"]
    hn = _rms(x, p["ln1"]["scale"], m["eps"])
    q = jnp.einsum("td,dk->tk", hn, weight(a["wq"]), precision=HI)
    k = jnp.einsum("td,dk->tk", hn, weight(a["wk"]), precision=HI)
    v = jnp.einsum("td,dk->tk", hn, weight(a["wv"]), precision=HI)
    k, v = k.reshape(t, kvh, hd), v.reshape(t, kvh, hd)
    bq = min(Q_BLOCK, t)
    assert t % bq == 0, "pad the sequence to a multiple of the query block"
    qb = q.reshape(t // bq, bq, kvh, h // kvh, hd)

    def block(args):
        i, qi = args
        s = jnp.einsum("qngd,snd->ngqs", qi, k, precision=HI) * m["att"]
        causal = jnp.arange(t)[None, :] <= (i * bq + jnp.arange(bq))[:, None]
        s = jnp.where(causal[None, None], s, -jnp.inf)
        return jnp.einsum("ngqs,snd->qngd", jax.nn.softmax(s, axis=-1), v,
                          precision=HI)

    o = jax.lax.map(block, (jnp.arange(t // bq), qb)).reshape(t, h * hd)
    return x + m["res"] * jnp.einsum("tk,kd->td", o, weight(a["wo"]),
                                     precision=HI)


def _mamba2(x, p, m, weight):
    """The Mamba-2 mixer from an empty state, one position at a time."""
    s = p["mamba2"]
    t = x.shape[0]
    di, gn, H, P, N = m["di"], m["g"] * m["n"], m["mh"], m["mp"], m["n"]
    hn = _rms(x, p["ln1"]["scale"], m["eps"])
    proj = jnp.einsum("td,de->te", hn, weight(s["in_proj"]), precision=HI)
    z, xbc, dt = proj[:, :di], proj[:, di:di + m["conv"]], \
        proj[:, di + m["conv"]:]
    # causal depthwise conv over time, zero history, with bias
    xp = jnp.concatenate([jnp.zeros((m["k"] - 1, m["conv"]), x.dtype), xbc])
    u = jax.nn.silu(sum(xp[i:i + t] * s["conv_w"][i] for i in range(m["k"]))
                    + s["conv_b"])
    xs = u[:, :di].reshape(t, H, P)
    b = u[:, di:di + gn].reshape(t, m["g"], N)
    c = u[:, di + gn:].reshape(t, m["g"], N)
    dt = jax.nn.softplus(dt + s["dt_bias"])                       # (t, H)
    a = -jnp.exp(s["a_log"])                                      # (H,)
    group = jnp.arange(H) // (H // m["g"])                        # head -> group

    def tick(hs, inp):
        dtt, xt, bt, ct = inp
        hs = jnp.exp(dtt * a)[:, None, None] * hs \
            + (dtt[:, None] * xt)[:, :, None] * bt[group][:, None, :]
        return hs, jnp.sum(hs * ct[group][:, None, :], axis=-1)

    _, y = jax.lax.scan(tick, jnp.zeros((H, P, N), x.dtype), (dt, xs, b, c))
    y = (y + xs * s["d_skip"][:, None]).reshape(t, di) * jax.nn.silu(z)
    y = _rms(y, s["norm"], m["eps"])
    return x + m["res"] * jnp.einsum("te,ed->td", y, weight(s["out_proj"]),
                                     precision=HI)


def hidden(params: dict, tokens, pub: dict, weight=lambda w: w):
    """Final-norm hidden states (T, d) in float32 of one causal sequence,
    divided by ``logits_scaling`` (so that the head gives the model's
    logits).  ``weight`` maps each matrix (upcast to float32) before use:
    the control rounds it to a lower precision."""
    m = dims(pub)
    f32 = jnp.float32
    emb = weight(params["embed"].astype(f32).T).T
    x = jnp.take(emb, tokens, axis=0) * m["emb"]

    def one(kind):
        mixer = _attention if kind == "attention" else _mamba2

        def step(x, p):
            p = jax.tree.map(lambda a: a.astype(f32), p)
            return _mlp(mixer(x, p, m, weight), p, m, weight), None
        return step

    # consecutive layers of one kind within the period run as one scan
    runs: list[tuple[str, list[int]]] = []
    for j, kind in enumerate(m["types"][:m["period"]]):
        if runs and runs[-1][0] == kind:
            runs[-1][1].append(j)
        else:
            runs.append((kind, [j]))

    def group(x, gp):
        for kind, js in runs:
            stacked = jax.tree.map(lambda *a: jnp.stack(a),
                                   *[gp[str(j)] for j in js])
            x, _ = jax.lax.scan(one(kind), x, stacked)
        return x, None

    assert not params["tail"], "the reference covers whole layer periods"
    x, _ = jax.lax.scan(group, x, params["groups"])
    return _rms(x, params["final_norm"]["scale"].astype(f32), m["eps"]) \
        / m["logit"]


# --------------------------------------------------------------- work
def _layer_params(m: dict) -> tuple[int, int]:
    """Matrix parameters of one (Mamba-2, attention) layer, MLP included."""
    d, di = m["d"], m["di"]
    mlp = 3 * d * m["ff"]
    mamba = d * (2 * di + 2 * m["g"] * m["n"] + m["mh"]) + di * d
    attn = 2 * d * m["h"] * m["hd"] + 2 * d * m["kvh"] * m["hd"]
    return mamba + mlp, attn + mlp


def _counts(m: dict) -> tuple[int, int]:
    """(Mamba-2 layers, attention layers)."""
    na = sum(t == "attention" for t in m["types"])
    return m["layers"] - na, na


def model_flops(pub: dict, ctx: list[int], sampled: int) -> float:
    """Operations the model needs for tokens that attend over ``ctx[i]``
    positions each (the token's own included), with the LM head at
    ``sampled`` positions.  A Mamba-2 token's work does not depend on its
    context: the recurrence counts 6 operations per state element (the
    decay's multiply, the input's two, the update's add and the readout's
    two), the conv 2 per tap."""
    m = dims(pub)
    nm, na = _counts(m)
    pm, pa = _layer_params(m)
    per_tok = 2.0 * (nm * pm + na * pa) + nm * (
        6.0 * m["mh"] * m["mp"] * m["n"] + 2.0 * m["k"] * m["conv"])
    attn = 4.0 * na * m["h"] * m["hd"] * float(sum(ctx))
    return per_tok * len(ctx) + attn + 2.0 * m["d"] * m["vocab"] * sampled


def state_bytes(pub: dict) -> int:
    """One slot's recurrent state over all Mamba-2 layers: the float32
    (heads, head dim, state) matrix and the bfloat16 conv context."""
    m = dims(pub)
    nm, _ = _counts(m)
    return nm * (4 * m["mh"] * m["mp"] * m["n"] + 2 * (m["k"] - 1) * m["conv"])


def decode_work(pub: dict, ctx: list[int], block: int) -> tuple[float, float]:
    """(operations, bytes) of one decode step for the active slots whose
    tokens attend over ``ctx`` positions: every weight read once in
    bfloat16 (the tied table too, for the logits), each active slot's
    recurrent state read and written, and its live KV over the attention
    layers, read in whole blocks of ``block`` tokens."""
    m = dims(pub)
    nm, na = _counts(m)
    pm, pa = _layer_params(m)
    weights = 2.0 * (nm * pm + na * pa + m["vocab_rows"] * m["d"])
    state = 2.0 * state_bytes(pub) * len(ctx)
    kv = sum(-(-c // block) * block for c in ctx)
    live = na * 2.0 * m["kvh"] * m["hd"] * 2 * kv
    return model_flops(pub, ctx, len(ctx)), weights + state + live
