"""The comparison that decides ``correct``.

Once the window has closed and the engine's state is freed, a sample of
finished requests, drawn from the seed, is run through the
configuration's float32 reference, teacher-forced on each prompt and its
served tokens.  For every served token the reference gives the gap by
which the token's logit lies below the reference's best logit at that
position; the number compared is the widest gap over the sample.  Greedy
decoding serves the top token, so a sound program's gaps come only from
rounding at near-ties.

The sample always holds the request with the most served tokens and the
longest one, and one request of each prefill path the run served: a
prefix-cache hit (resumed through the chunk program at its offset), a
prompt longer than the largest bucket (chunked), and one of the bucketed
batched prefill.  The rest are drawn at random until the sample holds
``sample_tokens`` served tokens or ``sample_requests_max`` requests.

The control puts the reference in the program's place at the precision
below the configuration's (float8 e4m3 weights, per output channel, for
bfloat16): at the same positions, the gap of the token it ranks first.
"""
from __future__ import annotations

import numpy as np

PAD = 512           # sequence lengths are padded to a multiple of this
MAX_SERVED = 512    # served positions compared per request (>= any mix's max)


def pick_sample(records: list, seed: int, conf: dict, max_bucket: int
                ) -> list:
    """Finished requests to compare, drawn from ``seed``."""
    done = [r for r in records if r.done is not None and r.stamps]
    if not done:
        return []
    rng = np.random.default_rng([int(seed) & (2**64 - 1), 7])
    pick: list = []

    def add(r):
        if r is not None and all(r is not p for p in pick):
            pick.append(r)

    add(max(done, key=lambda r: (len(r.req.generated), r.prompt_len)))
    add(max(done, key=lambda r: (r.prompt_len + len(r.req.generated))))
    for path in (lambda r: r.prefix_hit > 0,
                 lambda r: r.prefix_hit == 0 and r.prompt_len > max_bucket,
                 lambda r: r.prefix_hit == 0 and r.prompt_len <= max_bucket):
        group = [r for r in done if path(r)]
        if group:
            add(group[int(rng.integers(len(group)))])
    rest = [done[j] for j in rng.permutation(len(done))]
    for r in rest:
        if sum(len(p.req.generated) for p in pick) >= conf["sample_tokens"] \
                or len(pick) >= conf["sample_requests_max"]:
            break
        add(r)
    return pick


def fp8(w):
    """Round a float32 matrix (..., in, out) to float8 e4m3 with one scale
    per output column, and back."""
    import jax.numpy as jnp
    s = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


class Reference:
    """The configuration's reference over one sequence at a time, jitted
    per padded length."""

    def __init__(self, family, pub: dict, *, control: bool = False):
        import jax
        import jax.numpy as jnp
        vocab = pub["vocab_size"]
        hi = jax.lax.Precision.HIGHEST

        def head(params, h, pos, weight):
            table = weight(family.head_table(params, pub)
                           .astype(jnp.float32).T)[:, :vocab]
            return jnp.einsum("pd,dv->pv", h[pos], table, precision=hi)

        def run(params, tokens, pos, served):
            ref = head(params, family.hidden(params, tokens, pub), pos,
                       lambda w: w)
            best = ref.max(-1)
            gap = best - jnp.take_along_axis(ref, served[:, None], 1)[:, 0]
            if not control:
                return gap, gap
            low = head(params, family.hidden(params, tokens, pub, weight=fp8),
                       pos, fp8)
            top = jnp.argmax(low, -1)
            return gap, best - jnp.take_along_axis(ref, top[:, None], 1)[:, 0]

        self._run = jax.jit(run)

    def gaps(self, params, prompt: list, served: list
             ) -> tuple[np.ndarray, np.ndarray]:
        """(the served tokens' gaps, the control's gaps) at each served
        position; without the control both are the served tokens'."""
        import jax.numpy as jnp
        if len(served) > MAX_SERVED:
            raise ValueError(f"{len(served)} served tokens > {MAX_SERVED}")
        seq = list(prompt) + list(served[:-1])
        toks = np.zeros(-(-len(seq) // PAD) * PAD, np.int32)
        toks[:len(seq)] = seq
        n = len(served)
        pos = np.zeros(MAX_SERVED, np.int32)
        pos[:n] = np.arange(len(prompt) - 1, len(prompt) - 1 + n)
        tok = np.zeros(MAX_SERVED, np.int32)
        tok[:n] = served
        g, c = self._run(params, jnp.asarray(toks), jnp.asarray(pos),
                         jnp.asarray(tok))
        return np.asarray(g)[:n], np.asarray(c)[:n]


def compare(ref: Reference, params, sample: list) -> dict:
    """The widest gaps over the sample, and how many tokens were read."""
    worst, worst_ctrl, n = 0.0, 0.0, 0
    for r in sample:
        g, c = ref.gaps(params, r.req.prompt, r.req.generated)
        worst = max(worst, float(g.max()))
        worst_ctrl = max(worst_ctrl, float(c.max()))
        n += len(g)
    return {"logit_gap": worst, "control_gap": worst_ctrl, "tokens": n,
            "requests": len(sample)}
