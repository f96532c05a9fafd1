"""Per-architecture smoke tests: reduced config of the same family runs one
forward/train step on CPU, asserting output shapes + no NaNs (assignment
requirement), plus serving-path consistency."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config, reduced_config
from repro.models import build_model

ARCH_IDS = sorted(ARCHS)


def _batch(cfg, b=2, s=32):
    rng = np.random.RandomState(0)
    batch = {
        "tokens": jnp.asarray(rng.randint(0, cfg.vocab_size, (b, s)), jnp.int32),
        "labels": jnp.asarray(rng.randint(0, cfg.vocab_size, (b, s)), jnp.int32),
    }
    if cfg.modality_tokens:
        batch["modality"] = jnp.asarray(
            rng.randn(b, cfg.modality_tokens, cfg.modality_dim), jnp.float32)
    if cfg.is_encdec:
        batch["src_embeds"] = jnp.asarray(rng.randn(b, s, cfg.d_model),
                                          jnp.float32)
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_shapes_and_finite(arch):
    cfg = reduced_config(arch)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    batch = _batch(cfg)
    logits, aux = m.forward(params, batch["tokens"], batch.get("modality"),
                            batch.get("src_embeds"))
    assert logits.shape == (2, 32, cfg.vocab_size)
    assert logits.dtype == jnp.float32
    assert bool(jnp.all(jnp.isfinite(logits)))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_train_step_no_nans(arch):
    cfg = reduced_config(arch)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    batch = _batch(cfg)

    def loss_fn(p):
        return m.loss(p, batch)

    (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
    assert bool(jnp.isfinite(loss))
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                         for g in jax.tree.leaves(grads)))
    assert bool(jnp.isfinite(gnorm)) and float(gnorm) > 0.0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_decode_matches_forward(arch):
    """Token-by-token decode logits must match teacher-forced forward logits."""
    cfg = reduced_config(arch)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    b, s = 2, 16
    batch = _batch(cfg, b, s)
    full_logits, _ = m.forward(params, batch["tokens"], batch.get("modality"),
                               batch.get("src_embeds"))
    if cfg.modality_tokens:
        pytest.skip("decode parity with modality prefix covered via prefill")

    states = m.init_states(b, max(2 * s, cfg.window or 0))
    prefix = s // 2
    logits_p, states, memory = m.prefill(
        params, batch["tokens"][:, :prefix], states, None,
        batch.get("src_embeds"))
    np.testing.assert_allclose(
        np.asarray(logits_p[:, 0]), np.asarray(full_logits[:, prefix - 1]),
        atol=5e-2, rtol=5e-2)
    # decode the rest one token at a time
    for t in range(prefix, s):
        tok = batch["tokens"][:, t:t + 1]
        pos = jnp.full((b,), t, jnp.int32)
        logits_d, states = m.decode_step(params, tok, states, pos, memory)
        np.testing.assert_allclose(
            np.asarray(logits_d[:, 0]), np.asarray(full_logits[:, t]),
            atol=5e-2, rtol=5e-2,
            err_msg=f"{arch}: decode@{t} != forward@{t}")


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_full_config_structure(arch):
    """Full configs match the assignment table exactly."""
    cfg = get_config(arch)
    table = {
        "recurrentgemma-2b": (26, 2560, 10, 1, 7680, 256000),
        "qwen3-0.6b": (28, 1024, 16, 8, 3072, 151936),
        "starcoder2-7b": (32, 4608, 36, 4, 18432, 49152),
        "smollm-135m": (30, 576, 9, 3, 1536, 49152),
        "qwen2-0.5b": (24, 896, 14, 2, 4864, 151936),
        "internvl2-2b": (24, 2048, 16, 8, 8192, 92553),
        "phi3.5-moe-42b-a6.6b": (32, 4096, 32, 8, 6400, 32064),
        "llama4-scout-17b-a16e": (48, 5120, 40, 8, 8192, 202048),
        "seamless-m4t-medium": (12, 1024, 16, 16, 4096, 256206),
        "falcon-mamba-7b": (64, 4096, 0, 0, 0, 65024),
        "granite-4.0-h-micro": (40, 2048, 32, 8, 8192, 100352),
    }
    layers, d, h, kv, dff, vocab = table[arch]
    assert cfg.num_layers == layers
    assert cfg.d_model == d
    assert cfg.num_heads == h
    assert cfg.num_kv_heads == kv
    assert cfg.d_ff == dff
    assert cfg.vocab_size == vocab


def test_moe_active_vs_total_params():
    phi = get_config("phi3.5-moe-42b-a6.6b")
    assert 38e9 < phi.param_count() < 46e9
    assert 5.5e9 < phi.param_count(active_only=True) < 8e9
    scout = get_config("llama4-scout-17b-a16e")
    assert 95e9 < scout.param_count() < 115e9
    assert 15e9 < scout.param_count(active_only=True) < 19e9


def test_sub_quadratic_flags():
    assert get_config("falcon-mamba-7b").sub_quadratic
    assert get_config("recurrentgemma-2b").sub_quadratic
    for a in ("qwen3-0.6b", "starcoder2-7b", "phi3.5-moe-42b-a6.6b",
              "seamless-m4t-medium"):
        assert not get_config(a).sub_quadratic
