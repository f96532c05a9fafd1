"""The Mamba-2 mixer (``models/recurrent.py``) and the engine's recurrent
slot state: chunked SSD against the per-position recurrence (carried
state, right padding, lengths that are not multiples of the chunk), the
mixer's state carried across calls, frozen rows, the ``mamba2`` scope in
every serving program, the ``state`` summary and ring track, and the
placement plan of granite-4.0-h-micro."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.models import build_model
from repro.models import recurrent as rec
from repro.serve.engine import Request, ServeEngine
from repro.serve.placement import ExecutionOracle

ARCH = "granite-4.0-h-micro"


def _recurrence(x, dt, a, b, c, h0):
    """h_t = exp(dt_t a) h_{t-1} + dt_t x_t B_t^T, y_t = h_t C_t, one
    position at a time, in float64."""
    x, dt, a, b, c, h = (np.asarray(v, np.float64)
                         for v in (x, dt, a, b, c, h0))
    ys = np.zeros_like(x)
    for t in range(x.shape[1]):
        h = np.exp(dt[:, t] * a)[..., None, None] * h \
            + (dt[:, t, ..., None] * x[:, t])[..., None] \
            * b[:, t, :, None, None, :]
        ys[:, t] = (h * c[:, t, :, None, None, :]).sum(-1)
    return ys, h


def _ssd_inputs(seed, B=2, S=37, G=2, E=3, P=4, N=5):
    r = np.random.default_rng(seed)
    x = r.normal(size=(B, S, G, E, P)).astype(np.float32)
    dt = r.uniform(0.01, 0.5, (B, S, G, E)).astype(np.float32)
    a = -r.uniform(0.5, 4.0, (G, E)).astype(np.float32)
    b = r.normal(size=(B, S, G, N)).astype(np.float32)
    c = r.normal(size=(B, S, G, N)).astype(np.float32)
    h0 = r.normal(size=(B, G, E, P, N)).astype(np.float32)
    return x, dt, a, b, c, h0


@pytest.mark.parametrize("S,chunk", [(37, 8), (32, 8), (5, 16), (1, 4),
                                     (40, 40)])
def test_ssd_chunked_matches_the_recurrence(S, chunk):
    """Chunked SSD from a carried state equals the per-position recurrence,
    outputs and final state, for lengths that are and are not multiples of
    the chunk.  Float32 against float64: 1e-4 covers the summation order of
    up to 40 steps."""
    x, dt, a, b, c, h0 = _ssd_inputs(S, S=S)
    y, h = rec.ssd_chunked(*map(jnp.asarray, (x, dt, a, b, c, h0)), chunk)
    y_ref, h_ref = _recurrence(x, dt, a, b, c, h0)
    np.testing.assert_allclose(np.asarray(y), y_ref, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h), h_ref, rtol=1e-4, atol=1e-4)


def test_ssd_right_padding_keeps_the_state_at_the_last_real_position():
    """Rows padded on the right with dt = 0 end in the state of their last
    real position, whatever the padding holds."""
    x, dt, a, b, c, h0 = _ssd_inputs(3, S=29)
    lengths = np.array([29, 11])
    live = np.arange(29)[None, :] < lengths[:, None]
    dt_m = np.where(live[..., None, None], dt, 0.0).astype(np.float32)
    x_g = np.where(live[..., None, None, None], x, 1e3).astype(np.float32)
    y, h = rec.ssd_chunked(*map(jnp.asarray, (x_g, dt_m, a, b, c, h0)), 8)
    for i, n in enumerate(lengths):
        y_ref, h_ref = _recurrence(x[i:i + 1, :n], dt[i:i + 1, :n], a,
                                   b[i:i + 1, :n], c[i:i + 1, :n],
                                   h0[i:i + 1])
        np.testing.assert_allclose(np.asarray(h[i:i + 1]), h_ref,
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(y[i:i + 1, :n]), y_ref,
                                   rtol=1e-4, atol=1e-4)


def test_ssd_step_is_the_recurrence_and_dt_zero_freezes_bitwise():
    x, dt, a, b, c, h0 = _ssd_inputs(4, S=1)
    y, h = rec.ssd_step(*map(jnp.asarray, (x[:, 0], dt[:, 0], a, b[:, 0],
                                           c[:, 0], h0)))
    y_ref, h_ref = _recurrence(x, dt, a, b, c, h0)
    np.testing.assert_allclose(np.asarray(y), y_ref[:, 0], rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(h), h_ref, rtol=1e-5, atol=1e-5)
    _, frozen = rec.ssd_step(*map(jnp.asarray, (x[:, 0], 0 * dt[:, 0], a,
                                                b[:, 0], c[:, 0], h0)))
    np.testing.assert_array_equal(np.asarray(frozen), h0)


def _mixer(seed=0):
    cfg = reduced_config(ARCH)
    p = rec.init_mamba2_block(jax.random.PRNGKey(seed), cfg.d_model,
                              cfg.d_inner, cfg.ssm_heads, cfg.d_state,
                              cfg.ssm_groups, cfg.d_conv)
    p = dict(p, conv_b=0.1 * jax.random.normal(jax.random.PRNGKey(9),
                                               p["conv_b"].shape))
    kw = dict(n_heads=cfg.ssm_heads, d_state=cfg.d_state,
              n_groups=cfg.ssm_groups, chunk=cfg.scan_chunk, eps=1e-5)
    return cfg, p, kw


def test_mixer_state_carried_across_calls_equals_one_call():
    """A sequence fed in three calls (19 + 16 + 1 tokens: a chunk that is
    not a multiple of the SSD chunk, then a decode step) through the
    returned conv and SSD state gives what one call gives."""
    cfg, p, kw = _mixer()
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 36, cfg.d_model))
    full, st_full = rec.mamba2_block(p, x, return_state=True, **kw)
    outs, st = [], None
    for lo, hi in ((0, 19), (19, 35), (35, 36)):
        y, st = rec.mamba2_block(p, x[:, lo:hi], state=st, return_state=True,
                                 **kw)
        outs.append(y)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(outs, 1)),
                               np.asarray(full), rtol=2e-4, atol=2e-4)
    for k in ("conv", "h"):
        np.testing.assert_allclose(np.asarray(st[k]), np.asarray(st_full[k]),
                                   rtol=2e-4, atol=2e-4)


def test_mixer_padded_rows_and_idle_decode_rows_keep_their_state():
    """Right-padded rows return the state of their last real token; a
    decode row with length 0 keeps its state bit for bit."""
    cfg, p, kw = _mixer(1)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 24, cfg.d_model))
    _, st = rec.mamba2_block(p, x, return_state=True,
                             length=jnp.asarray([24, 13]), **kw)
    _, st13 = rec.mamba2_block(p, x[1:, :13], return_state=True, **kw)
    for k in ("conv", "h"):
        np.testing.assert_allclose(np.asarray(st[k][1:]),
                                   np.asarray(st13[k]), rtol=1e-5, atol=1e-5)
    _, st2 = rec.mamba2_block(p, x[:, :1], state=st, return_state=True,
                              length=jnp.asarray([1, 0]), **kw)
    for k in ("conv", "h"):
        np.testing.assert_array_equal(np.asarray(st2[k][1]),
                                      np.asarray(st[k][1]))
        assert not np.array_equal(np.asarray(st2[k][0]),
                                  np.asarray(st[k][0]))


# ------------------------------------------------------------- the engine
def _engine(**kw):
    cfg = reduced_config(ARCH)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, ServeEngine(model, params, **{
        **dict(slots=3, max_len=96, buckets=(16,), prefill_chunk=24,
               kv_block_size=8, prefix_cache=True), **kw})


def test_mamba2_scope_in_every_serving_program():
    """The mixer's operations carry the ``mamba2`` name scope in the
    bucketed prefill, chunk and decode programs, as warmup compiles them."""
    _, eng = _engine()
    seen = {}
    register = eng.programs.register

    def spy(name, fn, args, **kw):
        seen[name] = fn.lower(*args).compile().as_text()
        return register(name, fn, args, **kw)

    eng.programs.register = spy
    eng.warmup()
    for name in ("prefill[1x16]", "chunk", "decode"):
        assert "/mamba2/" in seen[name], name
    assert "/mamba2/" not in seen["copy"]


def test_engine_state_summary_and_ring_track():
    cfg, eng = _engine()
    rng = np.random.RandomState(0)
    reqs = [Request(rid=i, prompt=rng.randint(1, cfg.vocab_size, n).tolist(),
                    max_new_tokens=3) for i, n in enumerate((10, 50, 30, 7))]
    eng.run(reqs)
    st = eng.stats.summary()["state"]
    conv_dim = cfg.d_inner + 2 * cfg.ssm_groups * cfg.d_state
    per_layer = (4 * cfg.d_inner * cfg.d_state
                 + 2 * (cfg.d_conv - 1) * conv_dim)
    layers = cfg.layer_kinds.count("mamba2")
    assert st["kinds"] == {"mamba2": {"layers": layers,
                                      "bytes_per_slot": layers * per_layer,
                                      "bytes": 3 * layers * per_layer}}
    assert st["bytes"] == 3 * st["bytes_per_slot"]
    # 50 tokens in chunks of 24: two resumed; 30 tokens: one
    assert st["chunks_resumed"] == 3
    assert st["prefix_cache"].startswith("off") and "mamba2" \
        in st["prefix_cache"] and not eng.kv.prefix_enabled
    samples = [dict(e[5]) for e in eng.tracer.events()
               if e[0] == "C" and e[1] == "rec_state"]
    assert len(samples) == eng.stats.ticks
    assert sum(s["chunks_resumed"] for s in samples) == 3
    assert max(s["live_slots"] for s in samples) == 3
    assert samples[-1]["live_slots"] == st["slots_live"] == 0
    eng.reset_stats()
    assert eng.stats.summary()["state"]["chunks_resumed"] == 0


def test_attention_only_engine_has_no_state_section():
    cfg = reduced_config("qwen3-0.6b")
    model = build_model(cfg)
    eng = ServeEngine(model, model.init(jax.random.PRNGKey(0)), slots=2,
                      max_len=32, kv_block_size=8)
    eng.run([Request(rid=0, prompt=[3, 4, 5], max_new_tokens=2)])
    assert "state" not in eng.stats.summary()
    assert not any(e[1] == "rec_state" for e in eng.tracer.events())


# ------------------------------------------------------------- placement
def test_granite_plan_keeps_decode_on_the_gather_for_head_dim_64():
    """On a TPU backend the plan runs the flash prefill kernel (it compiles
    at head dim 64) and keeps decode attention on the XLA gather, whose
    reason it records; the Mamba-2 layers run in XLA, also with a reason."""
    plan = ExecutionOracle(get_config(ARCH), slots=16, max_len=16384,
                           min_bucket=512, max_bucket=512,
                           backend="tpu").resolve()
    assert plan.prefill_cfg_overrides == {"attn_impl": "pallas"}
    assert plan.decode_cfg_overrides == {}
    text = plan.dumps()
    assert "cannot copy pages of 8 kv heads x 64" in text
    assert "mamba2 has no kernel" in text
    assert set(plan.layer_kinds) == {"mamba2", "attn"}
    # the recurrent layers bound the chunk at scan_chunk (256), floored at
    # the smallest bucket
    assert plan.prefill_chunk == 512
