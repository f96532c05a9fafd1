"""Placement-oracle tests: characterize -> cluster -> cost -> policy.

Pins down (a) the expected cluster structure for the three served state
families, (b) pure/deterministic policy resolution, (c) backend gating —
Pallas variants only where they lower natively, so CPU CI's auto plan is
the fixed engine, (d) engine integration: ``--policy auto`` generates
tokens bitwise-identical to the fixed-knob engine with zero recompiles
after warmup, and the stats placement section survives resets, (e) the
kernel-variant switches themselves: every ``impl="pallas"`` route through
the model entry points matches its XLA reference numerically (interpret
mode on CPU), and (f) k-means determinism including the degenerate
all-points-coincident input a pure-attention stack produces.
"""
import json
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.core.executor import RUNTIME_SAFE_KEYS, phase_profiles
from repro.serve.placement import (
    ExecutionOracle,
    PlacementPlan,
    fixed_plan,
    verify_kmeans_agreement,
)

GEOM = dict(slots=4, max_len=256, max_bucket=64)


# ------------------------------------------------------------ cluster shape
def test_oracle_qwen3_clusters():
    plan = ExecutionOracle(get_config("qwen3-0.6b"), backend="cpu",
                           **GEOM).resolve()
    # pure full-attention stack: every layer lands in cluster 2 (pascal),
    # embeddings + FC in cluster 3 (pavlov)
    assert set(plan.layer_clusters) == {2}
    assert set(plan.layer_kinds) == {"attn"}
    assert plan.policy_for("attn").accelerator == "pascal"
    assert plan.policy_for("ffn").cluster == 3
    assert plan.policy_for("ffn").accelerator == "pavlov"
    assert plan.policy_for("embed").cluster == 3
    assert plan.rule_kmeans_agreement > 0.9
    assert plan.buckets == (16, 32, 64) and plan.prefill_chunk == 64


def test_oracle_recurrentgemma_clusters():
    plan = ExecutionOracle(get_config("recurrentgemma-2b"), backend="cpu",
                           **GEOM).resolve()
    # Griffin interleave: local-attention layers cluster 2, RG-LRU layers
    # with the big recurrent footprint land in cluster 3 alongside FC
    assert set(plan.layer_clusters) == {2, 3}
    assert set(plan.layer_kinds) == {"local", "rec"}
    assert plan.policy_for("local").cluster == 2
    assert plan.policy_for("rec").cluster == 3
    assert plan.policy_for("rec").accelerator == "pavlov"
    assert plan.rule_kmeans_agreement > 0.6


def test_oracle_falcon_mamba_clusters():
    plan = ExecutionOracle(get_config("falcon-mamba-7b"), backend="cpu",
                           **GEOM).resolve()
    # homogeneous SSM stack: one cluster, one policy covering ssm + embed
    assert set(plan.layer_clusters) == {3}
    assert plan.policy_for("ssm").accelerator == "pavlov"
    assert plan.rule_kmeans_agreement > 0.9


# ------------------------------------------------------- purity/determinism
@pytest.mark.parametrize("arch", ["qwen3-0.6b", "recurrentgemma-2b",
                                  "falcon-mamba-7b"])
def test_resolution_is_deterministic(arch):
    cfg = get_config(arch)
    a = ExecutionOracle(cfg, backend="cpu", **GEOM).resolve()
    b = ExecutionOracle(cfg, backend="cpu", **GEOM).resolve()
    assert a == b                      # frozen dataclasses, full deep equality
    assert a.dumps() == b.dumps()
    assert json.loads(a.dumps())["arch"] == cfg.name


def test_predictions_are_positive_and_phase_ordered():
    plan = ExecutionOracle(get_config("qwen3-0.6b"), backend="cpu",
                           **GEOM).resolve()
    assert plan.predicted_prefill_s > 0 and plan.predicted_decode_s > 0
    # a full 64-token chunk must cost more than one decode step
    assert plan.predicted_prefill_s > plan.predicted_decode_s


# ---------------------------------------------------------- backend gating
def test_cpu_backend_resolves_to_xla():
    for arch in ("qwen3-0.6b", "recurrentgemma-2b", "falcon-mamba-7b"):
        plan = ExecutionOracle(get_config(arch), backend="cpu",
                               **GEOM).resolve()
        assert plan.prefill_cfg_overrides == {}
        assert plan.decode_cfg_overrides == {}
        assert all(p.kernel == "xla" for p in plan.policies)


def test_tpu_backend_picks_pallas_variants():
    plan = ExecutionOracle(get_config("qwen3-0.6b"), backend="tpu",
                           **GEOM).resolve()
    assert plan.prefill_cfg_overrides == {"attn_impl": "pallas"}
    assert plan.decode_cfg_overrides == {"attn_impl": "pallas"}
    plan = ExecutionOracle(get_config("recurrentgemma-2b"), backend="tpu",
                           **GEOM).resolve()
    assert plan.decode_cfg_overrides == {"attn_impl": "pallas",
                                         "rglru_impl": "pallas"}
    # the serving SSM path needs h_last, which the fused kernel doesn't
    # return — the oracle must never pick it for serving
    plan = ExecutionOracle(get_config("falcon-mamba-7b"), backend="tpu",
                           **GEOM).resolve()
    assert plan.decode_cfg_overrides == {}
    assert "ssm_impl" not in plan.prefill_cfg_overrides


def test_tpu_backend_on_mesh_keeps_paged_decode_on_xla():
    """On a mesh every chosen kernel runs under shard_map except the paged
    decode kernel (a slot's blocks may sit on any data shard): the plan
    keeps decode attention on XLA and says why, rather than dropping the
    kernel silently at trace time."""
    mesh = ("data", "model")
    plan = ExecutionOracle(get_config("qwen3-0.6b"), backend="tpu",
                           mesh_axes=mesh, **GEOM).resolve()
    assert plan.prefill_cfg_overrides == {"attn_impl": "pallas"}
    assert plan.decode_cfg_overrides == {}
    attn = plan.policy_for("attn")
    assert attn.kernel == "pallas" and attn.variants == ("pallas_flash",)
    assert "paged gather on a mesh" in attn.note
    assert "paged gather on a mesh" in plan.dumps()
    plan = ExecutionOracle(get_config("recurrentgemma-2b"), backend="tpu",
                           mesh_axes=mesh, **GEOM).resolve()
    assert plan.prefill_cfg_overrides == {"attn_impl": "pallas",
                                          "rglru_impl": "pallas"}
    assert plan.decode_cfg_overrides == {"rglru_impl": "pallas"}


def test_tpu_backend_keeps_paged_decode_on_xla_for_heads_it_cannot_copy():
    """The paged kernel copies whole pages out of the pool by hand, which the
    TPU compiler refuses for hd 64 or an odd kv-head count in bf16: the plan
    keeps such decode attention on XLA and says why."""
    plan = ExecutionOracle(get_config("qwen2-0.5b"), backend="tpu",
                           **GEOM).resolve()
    assert plan.prefill_cfg_overrides == {"attn_impl": "pallas"}
    assert plan.decode_cfg_overrides == {}
    assert "cannot copy pages of 2 kv heads x 64" in plan.dumps()


def test_overrides_are_runtime_safe():
    for arch in ("qwen3-0.6b", "recurrentgemma-2b", "falcon-mamba-7b"):
        plan = ExecutionOracle(get_config(arch), backend="tpu",
                               **GEOM).resolve()
        assert set(plan.prefill_cfg_overrides) <= RUNTIME_SAFE_KEYS
        assert set(plan.decode_cfg_overrides) <= RUNTIME_SAFE_KEYS


# --------------------------------------------------- phase-profile merging
def test_phase_profiles_merge_policy_overrides():
    cfg = get_config("qwen3-0.6b")
    plan = PlacementPlan(arch=cfg.name, source="auto", backend="tpu",
                         prefill_overrides=(("attn_impl", "pallas"),),
                         decode_overrides=(("attn_impl", "pallas"),))
    pre, dec = phase_profiles(cfg, policy=plan)
    assert pre.cfg_overrides["attn_impl"] == "pallas"
    assert dec.cfg_overrides["attn_impl"] == "pallas"
    assert pre.apply(cfg, runtime_only=True).attn_impl == "pallas"


def test_phase_profiles_reject_unsafe_policy_keys():
    cfg = get_config("qwen3-0.6b")
    bad = PlacementPlan(arch=cfg.name, source="auto", backend="cpu",
                        decode_overrides=(("d_model", "128"),))
    with pytest.raises(ValueError, match="not runtime-safe"):
        phase_profiles(cfg, policy=bad)


def test_fixed_plan_records_knobs_and_decides_nothing():
    cfg = get_config("qwen3-0.6b")
    plan = fixed_plan(cfg, buckets=(16, 32), prefill_chunk=32)
    assert plan.source == "fixed" and plan.policies == ()
    assert plan.prefill_cfg_overrides == {}
    assert plan.summary()["buckets"] == [16, 32]
    assert plan.policy_for("attn") is None


# ------------------------------------------------------- k-means hardening
def _char(footprint, flop_per_byte, macs):
    return SimpleNamespace(sched_param_bytes=footprint,
                           sched_flop_per_byte=flop_per_byte,
                           sched_macs=macs)


def test_kmeans_is_seed_deterministic():
    from repro.core.clustering import kmeans_cluster
    chars = [_char(10e3 * (i + 1), 100.0 / (i + 1), 1e6 * (i + 1))
             for i in range(12)]
    la, _ = kmeans_cluster(chars, seed=0)
    lb, _ = kmeans_cluster(chars, seed=0)
    assert np.array_equal(la, lb)


def test_kmeans_survives_coincident_points():
    # a pure-attention stack characterizes every layer identically: all
    # pairwise distances are zero and the k-means++ weighted draw is
    # undefined — this used to crash np.random.choice
    from repro.core.clustering import kmeans_cluster
    chars = [_char(64e3, 120.0, 30e6)] * 8
    la, _ = kmeans_cluster(chars, seed=0)
    lb, _ = kmeans_cluster(chars, seed=0)
    assert np.array_equal(la, lb)
    assert len(set(la.tolist())) == 1   # coincident points, one cluster


@pytest.mark.parametrize("arch,floor", [("qwen3-0.6b", 0.9),
                                        ("recurrentgemma-2b", 0.6),
                                        ("falcon-mamba-7b", 0.9)])
def test_rule_vs_kmeans_agreement(arch, floor):
    score = verify_kmeans_agreement(get_config(arch), max_len=256,
                                    min_agreement=floor)
    assert score >= floor


# ------------------------------------------------------ engine integration
def _tiny(arch="qwen3-0.6b"):
    cfg = reduced_config(arch)
    return cfg.replace(num_layers=max(2, len(cfg.block_pattern)))


def test_engine_constructor_knobs_beat_policy():
    from repro.models import build_model
    from repro.serve.engine import ServeEngine
    cfg = _tiny()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    plan = ExecutionOracle(cfg, slots=2, max_len=64, max_bucket=32,
                           backend="cpu").resolve()
    # explicit constructor geometry wins over the plan's
    eng = ServeEngine(model, params, slots=2, max_len=64, buckets=(16,),
                      prefill_chunk=16, policy=plan)
    assert eng.buckets == (16,) and eng.prefill_chunk == 16
    # without explicit knobs the plan's geometry is adopted
    eng = ServeEngine(model, params, slots=2, max_len=64, policy=plan)
    assert eng.buckets == plan.buckets
    assert eng.prefill_chunk == plan.prefill_chunk


def test_policy_auto_token_identity_and_stats():
    from repro.launch.serve import build_engine
    from repro.models import build_model
    from repro.serve.engine import Request
    cfg = _tiny()
    params = build_model(cfg).init(jax.random.PRNGKey(0))

    def trace():
        rng = np.random.RandomState(5)
        return [Request(rid=i,
                        prompt=rng.randint(1, cfg.vocab_size,
                                           6 + 9 * i).tolist(),
                        max_new_tokens=6) for i in range(3)]

    def run(policy):
        eng = build_engine(cfg, params, slots=2, max_len=64, max_bucket=32,
                           policy=policy)
        eng.warmup()
        w = eng.stats.summary()
        eng.reset_stats()
        done = eng.run(trace())
        s = eng.stats.summary()
        rec = (s["prefill_compiles"] - w["prefill_compiles"]) \
            + (s["decode_compiles"] - w["decode_compiles"])
        return [r.generated for r in done], s, rec

    fixed_toks, fixed_s, _ = run("fixed")
    auto_toks, auto_s, auto_rec = run("auto")
    assert auto_toks == fixed_toks
    assert auto_rec == 0
    # the stats placement section: plan summary + measured phase times,
    # surviving the reset_stats() between warmup and the measured run
    p = auto_s["placement"]
    assert p["source"] == "auto" and p["policies"]
    assert p["measured"]["decode_step_s"] > 0
    assert p["predicted"]["decode_step_s"] > 0
    assert fixed_s["placement"]["source"] == "fixed"


def test_build_engine_rejects_unknown_policy():
    from repro.launch.serve import build_engine
    cfg = _tiny()
    with pytest.raises(ValueError, match="policy"):
        build_engine(cfg, slots=2, max_len=64, policy="oracle")


# ------------------------------------------- kernel-variant switch numerics
def test_flash_attention_impl_switch_matches_xla():
    from repro.models.attention import flash_attention
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(2, 32, 4, 16), jnp.float32)
    k = jnp.asarray(rng.randn(2, 32, 2, 16), jnp.float32)
    v = jnp.asarray(rng.randn(2, 32, 2, 16), jnp.float32)
    for window in (0, 16):
        ref = flash_attention(q, k, v, causal=True, window=window)
        out = flash_attention(q, k, v, causal=True, window=window,
                              impl="pallas")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)


def test_rglru_impl_switch_matches_xla():
    from repro.models.recurrent import rglru_core
    rng = np.random.RandomState(1)
    d = 32
    params = {
        "w_a": jnp.asarray(rng.randn(d, d) * 0.05, jnp.float32),
        "w_i": jnp.asarray(rng.randn(d, d) * 0.05, jnp.float32),
        "lambda": jnp.asarray(rng.randn(d), jnp.float32),
    }
    x = jnp.asarray(rng.randn(2, 24, d) * 0.1, jnp.float32)
    h0 = jnp.asarray(rng.randn(2, d) * 0.1, jnp.float32)
    mask = jnp.asarray(np.arange(24)[None, :] < np.array([[24], [17]]))
    ref_h, ref_last = rglru_core(params, x, h0=h0, seq_mask=mask)
    out_h, out_last = rglru_core(params, x, h0=h0, seq_mask=mask,
                                 impl="pallas")
    np.testing.assert_allclose(np.asarray(out_h), np.asarray(ref_h),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(out_last), np.asarray(ref_last),
                               atol=2e-5, rtol=2e-5)


def test_model_forward_with_pallas_overrides_matches_xla():
    """End-to-end: a reduced model lowered with the oracle's TPU override
    set must produce the same logits as the XLA reference (interpret mode
    executes the kernels on CPU)."""
    from repro.models import build_model
    for arch in ("qwen3-0.6b", "recurrentgemma-2b"):
        cfg = _tiny(arch)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        tokens = jnp.asarray(
            np.random.RandomState(2).randint(1, cfg.vocab_size, (2, 32)))
        ref, _ = model.forward(params, tokens)
        plan = ExecutionOracle(cfg, slots=2, max_len=64, max_bucket=32,
                               backend="tpu").resolve()
        fast_cfg = cfg.replace(**plan.prefill_cfg_overrides)
        out, _ = build_model(fast_cfg).forward(params, tokens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=5e-4, rtol=5e-4)
