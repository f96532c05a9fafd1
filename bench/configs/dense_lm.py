"""Plain float32 reference, weights and shape arithmetic of a dense
decoder LM with grouped-query attention, q/k RMSNorm, RoPE and a SwiGLU
feed-forward (Qwen3), in the layout the serving program takes.

Everything here is read from the configuration file's ``published`` keys;
nothing is imported from the program.  Where the program departs from the
published architecture the reference follows the program, and the
configuration file lists the departure (``departures``):

* embeddings are scaled by sqrt(hidden_size) before the first layer;
* RMSNorm multiplies by ``1 + scale``.

``init`` makes the weights; ``hidden`` is the reference forward over one
sequence; ``model_flops`` and the ``*_work`` functions count the
operations and bytes the benchmark's per-layer metrics divide by.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
#: the residual branches' output projections (``wo``, ``w_down``) are drawn
#: at this multiple of the fan-in scale.  At the fan-in scale a token's own
#: embedding dominates the tied LM head, so greedy decoding of random
#: weights repeats the last prompt token whatever the context, and a fault
#: that loses context would not change a served token.
BRANCH_GAIN = 4.0


def dims(pub: dict) -> dict:
    return dict(d=pub["hidden_size"], ff=pub["intermediate_size"],
                layers=pub["num_hidden_layers"],
                h=pub["num_attention_heads"], kvh=pub["num_key_value_heads"],
                hd=pub["head_dim"], vocab=pub["vocab_size"],
                vocab_rows=-(-pub["vocab_size"] // 16) * 16,
                theta=float(pub["rope_theta"]), eps=float(pub["rms_norm_eps"]),
                tied=bool(pub["tie_word_embeddings"]))


# --------------------------------------------------------------- weights
def init(pub: dict, key, dtype=jnp.bfloat16) -> dict:
    """The weights, in the program's parameter layout, from one key.  Call
    under ``jax.jit`` so that they are made on the device in one program."""
    m = dims(pub)
    d, ff, L, hq, hk = m["d"], m["ff"], m["layers"], m["h"] * m["hd"], \
        m["kvh"] * m["hd"]
    ks = iter(jax.random.split(key, 16))

    def normal(shape, std):
        return (std * jax.random.normal(next(ks), shape, jnp.float32)
                ).astype(dtype)

    def scale(shape):          # norm weights 1 + N(0, 0.1)
        return normal(shape, 0.1)

    layer = {
        "ln1": {"scale": scale((L, d))},
        "attn": {"wq": normal((L, d, hq), d ** -0.5),
                 "wk": normal((L, d, hk), d ** -0.5),
                 "wv": normal((L, d, hk), d ** -0.5),
                 "wo": normal((L, hq, d), BRANCH_GAIN * hq ** -0.5),
                 "q_norm": scale((L, m["hd"])),
                 "k_norm": scale((L, m["hd"]))},
        "ln2": {"scale": scale((L, d))},
        "ffn": {"w_gate": normal((L, d, ff), d ** -0.5),
                "w_up": normal((L, d, ff), d ** -0.5),
                "w_down": normal((L, ff, d), BRANCH_GAIN * ff ** -0.5)},
    }
    out = {"embed": normal((m["vocab_rows"], d), d ** -0.5),
           "final_norm": {"scale": scale((d,))},
           "groups": {"0": layer}, "tail": []}
    if not m["tied"]:
        out["lm_head"] = normal((m["vocab_rows"], d), d ** -0.5)
    return out


def head_table(params: dict, pub: dict):
    return params["embed"] if pub["tie_word_embeddings"] else params["lm_head"]


# --------------------------------------------------------------- reference
def _rms(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + scale)


def _rope(x, theta):
    """x: (T, heads, hd); rotate-half RoPE at positions 0..T-1."""
    t, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    f = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(f)[:, None], jnp.sin(f)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def hidden(params: dict, tokens, pub: dict, weight=lambda w: w):
    """Final-norm hidden states (T, d) in float32 of one causal sequence.
    ``weight`` maps each matrix (upcast to float32) before use: the control
    rounds it to a lower precision."""
    m = dims(pub)
    f32 = jnp.float32
    d, h, kvh, hd, eps = m["d"], m["h"], m["kvh"], m["hd"], m["eps"]
    t = tokens.shape[0]
    emb = weight(params["embed"].astype(f32).T).T
    x = jnp.take(emb, tokens, axis=0) * math.sqrt(d)
    causal = jnp.tril(jnp.ones((t, t), bool))

    def layer(x, p):
        p = jax.tree.map(lambda a: a.astype(f32), p)
        a = p["attn"]
        hn = _rms(x, p["ln1"]["scale"], eps)
        q = jnp.einsum("td,dk->tk", hn, weight(a["wq"]), precision=HI)
        k = jnp.einsum("td,dk->tk", hn, weight(a["wk"]), precision=HI)
        v = jnp.einsum("td,dk->tk", hn, weight(a["wv"]), precision=HI)
        q = _rope(_rms(q.reshape(t, h, hd), a["q_norm"], eps), m["theta"])
        k = _rope(_rms(k.reshape(t, kvh, hd), a["k_norm"], eps), m["theta"])
        v = v.reshape(t, kvh, hd)
        g = h // kvh
        qg = q.reshape(t, kvh, g, hd)
        s = jnp.einsum("tngd,snd->ngts", qg, k, precision=HI) / math.sqrt(hd)
        s = jnp.where(causal[None, None], s, -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("ngts,snd->tngd", pr, v, precision=HI).reshape(t, h * hd)
        x = x + jnp.einsum("tk,kd->td", o, weight(a["wo"]), precision=HI)
        f = p["ffn"]
        hn = _rms(x, p["ln2"]["scale"], eps)
        gt = jnp.einsum("td,df->tf", hn, weight(f["w_gate"]), precision=HI)
        up = jnp.einsum("td,df->tf", hn, weight(f["w_up"]), precision=HI)
        x = x + jnp.einsum("tf,fd->td", jax.nn.silu(gt) * up,
                           weight(f["w_down"]), precision=HI)
        return x, None

    assert not params["tail"], "the reference covers scanned layers only"
    x, _ = jax.lax.scan(layer, x, params["groups"]["0"])
    return _rms(x, params["final_norm"]["scale"].astype(f32), eps)


# --------------------------------------------------------------- work
def _matmul_params(m: dict) -> int:
    d, hq, hk = m["d"], m["h"] * m["hd"], m["kvh"] * m["hd"]
    return m["layers"] * (d * hq + 2 * d * hk + hq * d + 3 * d * m["ff"])


def model_flops(pub: dict, ctx: list[int], sampled: int) -> float:
    """Operations the model needs for tokens that attend over ``ctx[i]``
    positions each (the token's own included), with the LM head at
    ``sampled`` positions."""
    m = dims(pub)
    dense = 2.0 * _matmul_params(m) * len(ctx)
    attn = 4.0 * m["layers"] * m["h"] * m["hd"] * float(sum(ctx))
    return dense + attn + 2.0 * m["d"] * m["vocab"] * sampled


def paged_decode_work(pub: dict, ctx: list[int], block: int
                      ) -> tuple[float, float]:
    """(operations, bytes) one paged decode-attention call needs over all
    layers: each active slot's query attends its ``ctx`` cached positions,
    read at block granularity in bfloat16."""
    m = dims(pub)
    L, h, kvh, hd = m["layers"], m["h"], m["kvh"], m["hd"]
    flops = 4.0 * L * h * hd * float(sum(ctx))
    kv = sum(-(-c // block) * block for c in ctx)
    byts = L * (2.0 * kvh * hd * 2 * kv + 2.0 * h * hd * 2 * len(ctx))
    return flops, byts


def flash_prefill_work(pub: dict, rows: int, seq: int) -> tuple[float, float]:
    """(operations, bytes) one causal flash-attention prefill call over all
    layers needs at its padded (rows, seq) shape, in bfloat16."""
    m = dims(pub)
    L, h, kvh, hd = m["layers"], m["h"], m["kvh"], m["hd"]
    flops = 2.0 * L * rows * h * hd * seq * (seq + 1)
    byts = L * rows * seq * hd * 2.0 * (2 * h + 2 * kvh)
    return flops, byts
