"""Plain float32 reference, weights and shape arithmetic of an
attention-free Mamba-1 LM (falcon-mamba), in the layout the serving program
takes.

Read from the configuration file's ``published`` keys; nothing is imported
from the program.  Where the program departs from the published
architecture the reference follows the program, and the configuration file
lists the departure (``departures``):

* the mixer has no RMSNorm over B, C and the time step, and no conv bias;
* embeddings are scaled by sqrt(hidden_size); RMSNorm uses eps 1e-6 and
  multiplies by ``1 + scale``.

The selective scan runs step by step over time, the plainest form of
h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t, y_t = C_t h_t + D x_t.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
EPS = 1e-6          # the program's RMSNorm eps (published: layer_norm_epsilon)


def dims(pub: dict) -> dict:
    return dict(d=pub["hidden_size"], di=pub["intermediate_size"],
                n=pub["state_size"], k=pub["conv_kernel"],
                r=pub["time_step_rank"], layers=pub["num_hidden_layers"],
                vocab=pub["vocab_size"],
                vocab_rows=-(-pub["vocab_size"] // 16) * 16,
                tied=bool(pub["tie_word_embeddings"]))


# --------------------------------------------------------------- weights
def init(pub: dict, key, dtype=jnp.bfloat16) -> dict:
    """The weights, in the program's parameter layout, from one key.  Call
    under ``jax.jit`` so that they are made on the device in one program."""
    m = dims(pub)
    d, di, n, r, L = m["d"], m["di"], m["n"], m["r"], m["layers"]
    ks = iter(jax.random.split(key, 16))

    def normal(shape, std):
        return (std * jax.random.normal(next(ks), shape, jnp.float32)
                ).astype(dtype)

    dt0 = jax.random.uniform(next(ks), (L, di), jnp.float32, 1e-3, 1e-1)
    a0 = jnp.broadcast_to(jnp.arange(1, n + 1, dtype=jnp.float32), (L, di, n))
    layer = {
        "ln1": {"scale": normal((L, d), 0.1)},
        "ssm": {"in_proj": normal((L, d, 2 * di), d ** -0.5),
                "conv_w": normal((L, m["k"], di), m["k"] ** -0.5),
                "x_proj": normal((L, di, r + 2 * n), di ** -0.5),
                "dt_proj": normal((L, r, di), r ** -0.5),
                "dt_bias": jnp.log(jnp.expm1(dt0)).astype(dtype),
                "a_log": jnp.log(a0).astype(dtype),
                "d_skip": (1.0 + normal((L, di), 0.1).astype(jnp.float32)
                           ).astype(dtype),
                "out_proj": normal((L, di, d), di ** -0.5)},
    }
    out = {"embed": normal((m["vocab_rows"], d), d ** -0.5),
           "final_norm": {"scale": normal((d,), 0.1)},
           "groups": {"0": layer}, "tail": []}
    if not m["tied"]:
        out["lm_head"] = normal((m["vocab_rows"], d), d ** -0.5)
    return out


def head_table(params: dict, pub: dict):
    return params["embed"] if pub["tie_word_embeddings"] else params["lm_head"]


# --------------------------------------------------------------- reference
def _rms(x, scale):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS)
    return x * (1.0 + scale)


def hidden(params: dict, tokens, pub: dict, weight=lambda w: w):
    """Final-norm hidden states (T, d) in float32 of one sequence from an
    empty state.  ``weight`` maps each matrix (upcast to float32) before
    use: the control rounds it to a lower precision."""
    m = dims(pub)
    f32 = jnp.float32
    d, di, n, r, kw = m["d"], m["di"], m["n"], m["r"], m["k"]
    t = tokens.shape[0]
    emb = weight(params["embed"].astype(f32).T).T
    x = jnp.take(emb, tokens, axis=0) * math.sqrt(d)

    def layer(x, p):
        p = jax.tree.map(lambda a: a.astype(f32), p)
        s = p["ssm"]
        hn = _rms(x, p["ln1"]["scale"])
        xz = jnp.einsum("td,de->te", hn, weight(s["in_proj"]), precision=HI)
        xi, z = xz[:, :di], xz[:, di:]
        # causal depthwise conv over time, zero history
        xp = jnp.concatenate([jnp.zeros((kw - 1, di), f32), xi], axis=0)
        xc = sum(xp[i:i + t] * s["conv_w"][i] for i in range(kw))
        u = jax.nn.silu(xc)
        proj = jnp.einsum("te,ek->tk", u, weight(s["x_proj"]), precision=HI)
        dt_in, b, c = proj[:, :r], proj[:, r:r + n], proj[:, r + n:]
        delta = jax.nn.softplus(
            jnp.einsum("tk,ke->te", dt_in, weight(s["dt_proj"]), precision=HI)
            + s["dt_bias"])
        a = -jnp.exp(s["a_log"])                                  # (di, n)

        def tick(hs, inp):
            dl, bt, ct, ut = inp
            hs = jnp.exp(dl[:, None] * a) * hs + (dl * ut)[:, None] * bt[None]
            return hs, jnp.dot(hs, ct, precision=HI)

        _, y = jax.lax.scan(tick, jnp.zeros((di, n), f32), (delta, b, c, u))
        y = y + u * s["d_skip"]
        x = x + jnp.einsum("te,ed->td", y * jax.nn.silu(z),
                           weight(s["out_proj"]), precision=HI)
        return x, None

    assert not params["tail"], "the reference covers scanned layers only"
    x, _ = jax.lax.scan(layer, x, params["groups"]["0"])
    return _rms(x, params["final_norm"]["scale"].astype(f32))


# --------------------------------------------------------------- work
def model_flops(pub: dict, ctx: list[int], sampled: int) -> float:
    """Operations the model needs for ``len(ctx)`` tokens (the context does
    not change an SSM token's work), with the LM head at ``sampled``
    positions.  The scan counts 6 operations per state element: the decay's
    multiply, the input's two, the update's add and the readout's two."""
    m = dims(pub)
    d, di, n, r = m["d"], m["di"], m["n"], m["r"]
    mm = 2 * d * di + di * (r + 2 * n) + r * di + di * d
    per_tok = m["layers"] * (2.0 * mm + 6.0 * di * n + 2.0 * m["k"] * di)
    return per_tok * len(ctx) + 2.0 * d * m["vocab"] * sampled
