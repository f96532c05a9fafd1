"""Unit tests for the multi-device serving pieces that don't need multiple
devices: serve_state_specs structure, mesh helpers / CLI parsing, and the
block pool's per-shard accounting (tests/test_distributed.py runs the real
sharded engines under 8 forced host devices)."""
import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import reduced_config
from repro.launch import shardings as sh
from repro.launch.mesh import make_serve_mesh, parse_mesh_arg
from repro.models import build_model
from repro.serve.engine import Request, ServeEngine
from repro.serve.kvpool import KVBlockPool, PagedKVManager, RadixPrefixCache

ARCHS = ("qwen3-0.6b", "recurrentgemma-2b", "falcon-mamba-7b",
         "granite-4.0-h-micro")


# ------------------------------------------------------------------ specs
@pytest.mark.parametrize("arch", ARCHS)
@pytest.mark.parametrize("paged", [False, True])
def test_serve_state_specs_mirror_init_states(arch, paged):
    """One full-rank PartitionSpec per state leaf, for every family and for
    both dense and paged KV layouts."""
    cfg = reduced_config(arch)
    cfg = cfg.replace(num_layers=max(2, len(cfg.block_pattern)))
    model = build_model(cfg)
    kw = {}
    if paged:
        if "attn" not in tuple(model.pattern) + tuple(model.tail_kinds):
            pytest.skip("paged KV covers full-attention layers")
        kw = dict(kv_block_size=16, kv_blocks=8)
    mesh = make_serve_mesh(1, 1)
    states = model.init_states(4, 64, **kw)
    specs = sh.serve_state_specs(model, mesh, 4, 64, **kw)
    is_p = lambda x: isinstance(x, P)
    state_leaves = jax.tree.leaves(states)
    spec_leaves = jax.tree.leaves(specs, is_leaf=is_p)
    assert len(state_leaves) == len(spec_leaves)
    # tree_map across both trees raises on any structural mismatch and lets
    # us pin specs to full rank (device_put requires len(spec) <= ndim; full
    # rank means every axis got an explicit decision)
    def check(leaf, spec):
        assert isinstance(spec, P), spec
        assert len(spec) == leaf.ndim, (leaf.shape, spec)
        return leaf
    jax.tree.map(check, states, specs, is_leaf=lambda x: is_p(x) or None)


def test_serve_state_specs_shard_fallbacks():
    """Axes that don't divide the mesh fall back to replicated instead of
    erroring: odd slot counts and odd pool sizes must still serve."""
    cfg = reduced_config("qwen3-0.6b")
    cfg = cfg.replace(num_layers=2)
    model = build_model(cfg)
    mesh = make_serve_mesh(1, 1)
    # slots=3 divides nd=1, so the batch axis keeps its data spec (qwen3's
    # two layers land in one scanned group: axis 0 is the stack, 1 the batch)
    specs = sh.serve_state_specs(model, mesh, 3, 64)
    kv = specs["groups"]["0"].kv
    # compare whole specs: PartitionSpec normalises ("data",) to "data"
    assert kv.k == P(None, ("data",), None, None, None)
    # a device_put through the specs round-trips the real states
    states = model.init_states(3, 64)
    placed = jax.device_put(states, sh.to_named(specs, mesh))
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), states, placed)


# ------------------------------------------------------------------ mesh CLI
def test_make_serve_mesh_shapes_and_validation():
    m = make_serve_mesh(1, 1)
    assert dict(m.shape) == {"data": 1, "model": 1}
    assert make_serve_mesh().shape["model"] == 1       # defaults to pure dp
    with pytest.raises(RuntimeError):
        make_serve_mesh(64, 64)                        # more than we have
    with pytest.raises(ValueError):
        make_serve_mesh(1, 0)


def test_parse_mesh_arg():
    assert parse_mesh_arg("off") is None
    assert parse_mesh_arg("none") is None
    assert parse_mesh_arg("") is None
    m = parse_mesh_arg("1x1")
    assert dict(m.shape) == {"data": 1, "model": 1}
    assert parse_mesh_arg("auto") is not None
    with pytest.raises(ValueError):
        parse_mesh_arg("banana")


# ------------------------------------------------------------- pool shards
def test_pool_per_shard_accounting():
    tree = RadixPrefixCache(block_size=4)
    pool = KVBlockPool(12, 4, shards=4)                # 3 blocks per stripe
    got = [pool.alloc(tree) for _ in range(7)]
    assert pool.in_use == 7 == sum(pool.in_use_by_shard)
    assert pool.in_use_by_shard == [3, 3, 1, 0]        # contiguous stripes
    assert pool.peak_by_shard == [3, 3, 1, 0]
    assert sum(pool.peak_by_shard) == pool.peak_in_use == 7
    for b in got[2:]:
        pool.release(b, tree)
    assert pool.in_use == 2 == sum(pool.in_use_by_shard)
    # the peak snapshot is frozen at the high-water mark
    assert pool.peak_by_shard == [3, 3, 1, 0]
    b = pool.alloc(tree)                               # below peak: no change
    assert pool.shard_of(b) == b // 3
    assert sum(pool.peak_by_shard) == pool.peak_in_use == 7


def test_pool_shards_must_tile_blocks():
    with pytest.raises(ValueError):
        KVBlockPool(10, 4, shards=4)


def test_manager_shards_survive_clear_and_reset():
    mgr = PagedKVManager(slots=2, max_len=16, block_size=4, num_blocks=8,
                         shards=2)
    assert mgr.shards == 2
    plan = mgr.admit(0, list(range(6)))
    assert plan is not None
    assert sum(mgr.in_use_by_shard) == mgr.in_use == 2
    mgr.release(0)
    mgr.clear()
    assert mgr.shards == 2
    assert mgr.in_use_by_shard == [0, 0]
    mgr.reset_stats()
    assert mgr.peak_by_shard == [0, 0]


# ------------------------------------------------------------ 1-device mesh
def test_engine_on_one_device_mesh_matches_meshless():
    """The mesh path (sharded params/states, pinned out-shardings, gather
    spec) on a 1-device mesh is plumbing-only: tokens must match the
    meshless engine exactly — paged + prefix cache included."""
    cfg = reduced_config("qwen3-0.6b")
    cfg = cfg.replace(num_layers=2)
    params = build_model(cfg).init(jax.random.PRNGKey(0))

    def trace():
        rng = np.random.RandomState(11)
        shared = rng.randint(1, cfg.vocab_size, 20).tolist()
        out = [Request(rid=i, prompt=shared + rng.randint(
                   1, cfg.vocab_size, 2 + i).tolist(), max_new_tokens=4)
               for i in range(3)]
        out.append(Request(rid=9, prompt=rng.randint(
            1, cfg.vocab_size, 7).tolist(), max_new_tokens=4))
        return out

    def build(mesh):
        return ServeEngine(build_model(cfg), params, slots=2, max_len=64,
                           buckets=(16, 32), kv_block_size=16, mesh=mesh)

    ref = build(None).run(trace())
    eng = build(make_serve_mesh(1, 1))
    assert eng.mesh is not None
    eng.warmup()
    w = eng.stats.summary()
    eng.reset_stats()
    done = eng.run(trace())
    s = eng.stats.summary()
    rec = (s["prefill_compiles"] - w["prefill_compiles"]) \
        + (s["decode_compiles"] - w["decode_compiles"])
    assert rec == 0, f"{rec} recompiles after warmup on the 1-device mesh"
    assert [r.generated for r in done] == [r.generated for r in ref]
    assert s["kv"]["prefix_hit_rate"] > 0


# --------------------------------------------------------- auto param specs
@pytest.mark.parametrize("dp,mp", [(1, 1), (1, 2)])
def test_param_specs_auto_follows_plan_sharding_axis(dp, mp):
    """The PR 7 leftover, closed: ``ExecutionPolicy.sharding_axis`` now
    drives the weight layout.  The oracle marks falcon-mamba's SSM cluster
    memory-centric (axis "data"), so ``param_specs(..., "auto")`` replicates
    the SSM family that the "tp" templates would slice over the model axis —
    while embeddings stay Jacquard vocab-sharded and, on a TP mesh, the
    engine still generates the exact tokens the "tp" layout does."""
    import jax.tree_util as jtu
    from jax.sharding import NamedSharding

    from repro.configs import get_config
    from repro.launch.serve import build_engine
    from repro.serve.placement import resolve_policy

    if int(np.prod([d for d in (dp, mp)])) > len(jax.devices()):
        pytest.skip(f"needs {dp * mp} devices")

    plan = resolve_policy(get_config("falcon-mamba-7b"), slots=4,
                          max_len=256, mesh_axes=("data", "model"))
    # the empirical anchor: the oracle really does rank the SSM cluster
    # memory-centric (data axis) where qwen3's attention ranks compute-
    # centric (model axis) — if the cost model changes its mind, this test
    # must be revisited along with the layout it pins
    ssm = next(p for p in plan.policies if "ssm" in p.kinds)
    assert ssm.sharding_axis == "data"
    qwen_plan = resolve_policy(get_config("qwen3-0.6b"), slots=4,
                               max_len=256, mesh_axes=("data", "model"))
    assert all(p.sharding_axis == "model" for p in qwen_plan.policies)

    cfg = reduced_config("falcon-mamba-7b")
    cfg = cfg.replace(num_layers=max(2, len(cfg.block_pattern)))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    shapes = jax.eval_shape(lambda: params)
    tp = sh.param_specs(cfg, shapes, "tp")
    auto = sh.param_specs(cfg, shapes, "auto", plan=plan)

    is_p = lambda x: isinstance(x, P)
    changed = {
        jtu.keystr(path): (a, b)
        for (path, a), (_, b) in zip(
            jtu.tree_leaves_with_path(tp, is_leaf=is_p),
            jtu.tree_leaves_with_path(auto, is_leaf=is_p))
        if a != b}
    assert changed, "auto layout identical to tp — the plan had no effect"
    for key, (a, b) in changed.items():
        assert "ssm" in key, f"auto changed a non-SSM leaf: {key}"
        assert b == P(*((None,) * len(b))), (key, b)   # fully replicated
        assert "model" in a, (key, a)   # tp really sliced it
    # embeddings never replicate, whatever the plan says
    assert auto["embed"] == tp["embed"] == P("model", None)

    # qwen3 (every cluster model-axis): auto degrades to exactly tp
    qcfg = reduced_config("qwen3-0.6b").replace(num_layers=2)
    qshapes = jax.eval_shape(
        lambda: build_model(qcfg).init(jax.random.PRNGKey(0)))
    assert sh.param_specs(qcfg, qshapes, "auto", plan=qwen_plan) \
        == sh.param_specs(qcfg, qshapes, "tp")

    # auto without a plan is a usage error, not a silent tp fallback
    with pytest.raises(ValueError):
        sh.param_specs(cfg, shapes, "auto")

    # placement end to end: the auto layout serves the same tokens as tp
    mesh = make_serve_mesh(dp, mp)
    placed = jax.device_put(params, jax.tree.map(
        lambda spec: NamedSharding(mesh, spec), auto, is_leaf=is_p))
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), params, placed)

    def run(strategy):
        eng = build_engine(cfg, params, slots=2, max_len=64, max_bucket=32,
                           mesh=make_serve_mesh(dp, mp),
                           param_strategy=strategy,
                           plan_cfg=get_config("falcon-mamba-7b"))
        rng = np.random.RandomState(11)
        return [r.generated for r in eng.run(
            [Request(rid=i, prompt=rng.randint(1, cfg.vocab_size,
                                               4 + 6 * i).tolist(),
                     max_new_tokens=4) for i in range(3)])]

    assert run("auto") == run("tp")
