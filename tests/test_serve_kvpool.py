"""Paged KV-cache subsystem tests.

Three layers:
  * host-side bookkeeping — block pool refcounts/free-list/LRU eviction and
    the radix prefix tree (full-block + partial-block/COW matching), no JAX;
  * model-level identity — the paged attention ops are bitwise-identical to
    the dense ones (decode gate for the kernels package, prefill, chunked
    continuation);
  * engine-level identity — a paged engine generates token-for-token what
    the dense engine generates across the causal-attention, sliding-window +
    RG-LRU, and Mamba state families, including chunked prefill, and the
    shared-prefix + divergent-tail copy-on-write path matches a cold run;
  * sampling — greedy stays exact argmax, non-greedy is reproducible and
    respects top-k/top-p.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.models import build_model
from repro.models.attention import (KVCache, PagedKVCache, decode_attention,
                                    init_kv_cache, init_paged_kv_cache,
                                    paged_decode_attention)
from repro.serve.engine import Request, ServeEngine
from repro.serve.kvpool import (KVBlockPool, PagedKVManager, RadixPrefixCache,
                                blocks_for)
from repro.serve.sampling import sample_tokens

ARCHS = ["qwen3-0.6b", "recurrentgemma-2b", "falcon-mamba-7b",
         "granite-4.0-h-micro"]


def _tiny_model(arch="qwen3-0.6b", layers=2):
    cfg = reduced_config(arch)
    cfg = cfg.replace(num_layers=max(layers, len(cfg.block_pattern)))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


# ---------------------------------------------------------------- block pool
def test_pool_alloc_free_refcount():
    tree = RadixPrefixCache(4)
    pool = KVBlockPool(3, 4)
    a = pool.alloc(tree)
    b = pool.alloc(tree)
    c = pool.alloc(tree)
    assert sorted([a, b, c]) == [0, 1, 2]
    assert pool.alloc(tree) is None          # exhausted, nothing evictable
    assert pool.in_use == 3
    pool.retain(a)                           # second reference (shared)
    pool.release(a, tree)
    assert pool.in_use == 3                  # still referenced once
    pool.release(a, tree)
    assert pool.in_use == 2
    assert pool.alloc(tree) == a             # recycled through the free list
    with pytest.raises(AssertionError):
        pool.release(b, tree)
        pool.release(b, tree)                # double release


def test_pool_lru_eviction_prefers_oldest_cached():
    """Cached (published, refcount-0) blocks are evicted LRU when the free
    list runs dry; referenced and recently-touched blocks survive."""
    bs = 2
    tree = RadixPrefixCache(bs)
    pool = KVBlockPool(3, bs)
    b0 = pool.alloc(tree)
    b1 = pool.alloc(tree)
    tree.insert([1, 2], [b0])                # two independent single-block
    tree.insert([3, 4], [b1])                # prefixes -> both are leaves
    pool.release(b0, tree)
    pool.release(b1, tree)                   # both cached now
    tree.match([1, 2])                       # touch b0: b1 becomes LRU
    b2 = pool.alloc(tree)
    b3 = pool.alloc(tree)                    # must evict exactly b1
    assert b3 == b1 and pool.blocks_evicted == 1
    assert tree.contains(b0) and not tree.contains(b1)
    assert pool.alloc(tree) == b0            # then the remaining cached block
    assert pool.blocks_evicted == 2
    del b2


def test_eviction_and_supply_walks_are_counted():
    """The KV manager's own work: each ``evict_lru`` call adds every cached
    node it looks at, each ``reclaimable`` walk every node of the tree;
    ``clear`` keeps the counts and ``reset_stats`` zeroes them."""
    bs = 2
    mgr = PagedKVManager(slots=2, max_len=8, block_size=bs, num_blocks=6)
    # fill the pool with a published, retired 4-block sequence and a
    # 2-block one: 6 cached nodes, no free block
    for slot, toks in ((0, [1, 2, 3, 4, 5, 6, 7]), (1, [9, 8, 7])):
        assert mgr.admit(slot, toks) is not None
        assert mgr.extend(slot, len(toks) + 1)
        mgr.finish(slot, toks + [0])
    assert len(mgr.tree) == 6 and not mgr.pool.free
    assert mgr.evict_scanned == 0
    walked = mgr.reclaim_walked                # the admissions' supply checks
    assert walked == 0 + 4                     # an empty tree, then 4 nodes
    # a cold 3-token prompt needs 2 blocks: one supply walk over 6 nodes,
    # then two evictions, scanning 6 and then 5 nodes
    assert mgr.admit(0, [5, 5, 5]) is not None
    assert mgr.reclaim_walked == walked + 6
    assert mgr.blocks_evicted == 2
    assert mgr.evict_scanned == 6 + 5
    mgr.release(0)
    mgr.clear()
    assert (mgr.evict_scanned, mgr.reclaim_walked) == (11, walked + 6)
    mgr.reset_stats()
    assert (mgr.evict_scanned, mgr.reclaim_walked) == (0, 0)


def test_radix_match_full_and_partial_blocks():
    bs = 4
    tree = RadixPrefixCache(bs)
    toks = [1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12]
    tree.insert(toks, [10, 11, 12])
    m = tree.match(toks)
    assert m.blocks == [10, 11, 12] and m.partial_tokens == 0
    m = tree.match([1, 2, 3, 4, 5, 6])       # 1 full block + half a block
    assert m.blocks == [10]
    assert m.partial_block == 11 and m.partial_tokens == 2
    m = tree.match([9, 9, 9, 9])             # cold
    assert m.blocks == [] and m.partial_block is None
    # divergence inside the first block -> partial only
    m = tree.match([1, 2, 9, 9, 9])
    assert m.blocks == [] and m.partial_block == 10 and m.partial_tokens == 2


def test_radix_eviction_is_leaf_only():
    """Evicting a mid-path node would orphan its children's prefix — only
    childless nodes may go, oldest first."""
    bs = 2
    tree = RadixPrefixCache(bs)
    tree.insert([1, 2, 3, 4], [0, 1])        # 0 is 1's parent
    evictable = lambda b: True
    assert tree.evict_lru(evictable) == 1    # leaf first
    assert tree.evict_lru(evictable) == 0    # now childless
    assert tree.evict_lru(evictable) is None


def test_manager_admit_shares_allocates_and_cows():
    mgr = PagedKVManager(slots=2, max_len=32, block_size=4, num_blocks=16)
    prompt = list(range(100, 110))           # 10 tokens -> 3 blocks
    plan = mgr.admit(0, prompt)
    assert plan.matched_tokens == 0 and plan.copy is None
    assert mgr.owned[0] == blocks_for(10, 4) == 3
    donor_blocks = list(mgr.table[0][:2])
    mgr.finish(0, prompt)                    # publishes 2 full blocks
    assert mgr.owned[0] == 0 and mgr.in_use == 0 and mgr.cached == 2
    # same first 6 tokens: 1 full shared block + COW of the second
    plan = mgr.admit(1, prompt[:6] + [7, 7, 7, 7])
    assert plan.matched_tokens == 6
    src, dst = plan.copy
    assert src == donor_blocks[1]                 # the straddled block
    assert mgr.table[1][0] == donor_blocks[0]     # shared, refcounted
    assert mgr.table[1][1] == dst != src
    assert mgr.stats.blocks_copied == 1
    assert mgr.pool.ref[donor_blocks[0]] == 1     # slot 1's reference
    mgr.release(1)
    assert mgr.in_use == 0


def test_manager_never_matches_full_prompt():
    """At least one prompt token must run through prefill so the first
    token's logits exist — a fully-cached prompt matches len-1 tokens."""
    mgr = PagedKVManager(slots=2, max_len=32, block_size=4, num_blocks=16)
    prompt = list(range(8))                  # exactly 2 blocks
    mgr.admit(0, prompt)
    mgr.finish(0, prompt)
    plan = mgr.admit(1, prompt)              # identical prompt
    assert plan.matched_tokens == 7          # 1 full block + 3-token COW
    assert plan.copy is not None


def test_manager_capacity_refusal_has_no_side_effects():
    mgr = PagedKVManager(slots=2, max_len=16, block_size=4, num_blocks=2)
    assert mgr.admit(0, list(range(9))) is None   # needs 3 of 2 blocks
    assert mgr.in_use == 0 and mgr.owned[0] == 0
    assert mgr.admit(0, list(range(5))) is not None
    assert mgr.admit(1, list(range(5))) is None   # pool now empty
    assert mgr.owned[1] == 0


def test_available_excludes_cached_ancestors_of_referenced_blocks():
    """Regression: leaf-only eviction can never reclaim a cached block whose
    subtree still holds another slot's referenced block — counting it as
    supply made admit pass its pre-check and then fail mid-allocation.  Two
    same-prefix prompts admitted cold (same tick, no sharing) set this up:
    the longer one's tail publishes under the shorter one's path."""
    mgr = PagedKVManager(slots=3, max_len=16, block_size=4, num_blocks=7)
    p8 = list(range(50, 58))
    tail = [1, 2, 3, 4]
    assert mgr.admit(0, p8).matched_tokens == 0          # cold, 2 blocks
    assert mgr.admit(1, p8 + tail).matched_tokens == 0   # cold, 3 blocks
    mgr.publish(0, p8)
    mgr.publish(1, p8 + tail)        # slot 1's 3rd block lands under slot 0's
    mgr.finish(0, p8)                # slot 0's chain cached but UNRECLAIMABLE
    assert mgr.cached == 2
    assert mgr.pool.available(mgr.tree) == 2             # free blocks only
    # a 3-block cold prompt must requeue (2 allocatable), not crash
    assert mgr.admit(2, list(range(900, 912))) is None
    assert mgr.owned[2] == 0 and mgr.in_use == 3


def test_manager_extend_and_max_len_cap():
    mgr = PagedKVManager(slots=1, max_len=16, block_size=4, num_blocks=4)
    mgr.admit(0, [1, 2, 3])
    assert mgr.owned[0] == 1
    assert mgr.extend(0, 5)                  # crosses into block 2
    assert mgr.owned[0] == 2
    assert mgr.extend(0, 16)
    assert not mgr.extend(0, 17)             # beyond max_len
    mgr2 = PagedKVManager(slots=2, max_len=16, block_size=4, num_blocks=2)
    mgr2.admit(0, [1, 2, 3, 4, 5])           # 2 blocks
    assert not mgr2.extend(0, 9)             # pool exhausted


def test_manager_rejects_misaligned_max_len():
    with pytest.raises(ValueError, match="multiple"):
        PagedKVManager(slots=1, max_len=30, block_size=4, num_blocks=8)


# ------------------------------------------------- model-level bitwise gates
def test_paged_decode_ref_bitwise_matches_dense_decode():
    """The kernels-package gate: the pure-JAX paged decode (the Pallas
    kernel's oracle) is bitwise-identical to the dense ``decode_attention``
    when the block table is the contiguous identity layout."""
    rng = jax.random.PRNGKey(3)
    B, H, KVH, hd, smax, bs = 2, 4, 2, 16, 32, 8
    ks = jax.random.split(rng, 5)
    q = jax.random.normal(ks[0], (B, 1, H, hd), jnp.float32)
    nk = jax.random.normal(ks[1], (B, 1, KVH, hd), jnp.float32)
    nv = jax.random.normal(ks[2], (B, 1, KVH, hd), jnp.float32)
    lengths = jnp.asarray([5, 19], jnp.int32)

    dense = init_kv_cache(B, smax, KVH, hd)
    prior_k = jax.random.normal(ks[3], (B, smax, KVH, hd), jnp.float32)
    prior_v = jax.random.normal(ks[4], (B, smax, KVH, hd), jnp.float32)
    dense = KVCache(prior_k.astype(dense.k.dtype),
                    prior_v.astype(dense.v.dtype), lengths)
    out_d, new_d = decode_attention(q, nk, nv, dense)

    nb = smax // bs
    table = jnp.asarray(np.arange(B * nb).reshape(B, nb), jnp.int32)
    paged = PagedKVCache(
        k=dense.k.reshape(B * nb, bs, KVH, hd),
        v=dense.v.reshape(B * nb, bs, KVH, hd), length=lengths)
    out_p, new_p = paged_decode_attention(q, nk, nv, paged, table)

    np.testing.assert_array_equal(np.asarray(out_d), np.asarray(out_p))
    np.testing.assert_array_equal(
        np.asarray(new_d.k), np.asarray(new_p.k.reshape(B, smax, KVH, hd)))
    np.testing.assert_array_equal(np.asarray(new_d.length),
                                  np.asarray(new_p.length))
    # write_mask freezes masked rows bit-for-bit, like the dense path
    wm = jnp.asarray([True, False])
    _, mp = paged_decode_attention(q, nk, nv, paged, table, write_mask=wm)
    _, md = decode_attention(q, nk, nv, dense, write_mask=wm)
    np.testing.assert_array_equal(
        np.asarray(md.k), np.asarray(mp.k.reshape(B, smax, KVH, hd)))
    np.testing.assert_array_equal(np.asarray(md.length),
                                  np.asarray(mp.length))


@pytest.mark.parametrize("arch", ARCHS)
def test_paged_model_prefill_decode_bitwise(arch):
    """Model-level: paged states produce bitwise-identical logits to dense
    states through prefill and several decode steps — KV, sliding-window
    ring (kept dense by design), RG-LRU, and SSM families."""
    _, model, params = _tiny_model(arch)
    max_len, bs = 32, 8
    B, nb = 2, 32 // 8
    toks = jnp.asarray([[5, 9, 2, 7, 0, 0], [4, 4, 3, 1, 8, 2]], jnp.int32)
    lens = jnp.asarray([4, 6], jnp.int32)
    table = jnp.asarray(np.arange(B * nb).reshape(B, nb), jnp.int32)

    sd = model.init_states(B, max_len)
    lgd, sd, _ = model.prefill(params, toks, sd, length=lens)
    sp = model.init_states(B, max_len, kv_block_size=bs, kv_blocks=B * nb)
    lgp, sp, _ = model.prefill(params, toks, sp, length=lens,
                               block_table=table)
    np.testing.assert_array_equal(np.asarray(lgd), np.asarray(lgp))
    pos = lens
    tok = jnp.argmax(lgd[:, :1, :], axis=-1).astype(jnp.int32)
    for _ in range(4):
        lgd, sd = model.decode_step(params, tok, sd, pos)
        lgp, sp = model.decode_step(params, tok, sp, pos,
                                    block_table=table)
        np.testing.assert_array_equal(np.asarray(lgd), np.asarray(lgp))
        tok = jnp.argmax(lgd[:, :1, :], axis=-1).astype(jnp.int32)
        pos = pos + 1


# --------------------------------------------------- engine-level identity
@pytest.mark.parametrize("arch", ARCHS)
def test_paged_engine_matches_dense_engine(arch):
    """A paged engine (paging on, prefix cache on where eligible) serves a
    mixed ragged trace token-for-token identically to the dense engine —
    causal, sliding-window + RG-LRU, and Mamba models."""
    _, model, params = _tiny_model(arch)
    rng = np.random.RandomState(3)
    prompts = [rng.randint(1, 400, 3 + 5 * i).tolist() for i in range(5)]

    def reqs():
        return [Request(rid=i, prompt=p, max_new_tokens=4)
                for i, p in enumerate(prompts)]

    dense = ServeEngine(model, params, slots=3, max_len=64)
    paged = ServeEngine(model, params, slots=3, max_len=64, kv_block_size=8)
    rd = dense.run(reqs())
    rp = paged.run(reqs())
    assert [r.generated for r in rd] == [r.generated for r in rp]
    # finished slots released their blocks the same tick they retired
    assert paged.stats.kv_blocks_in_use == 0
    assert paged.stats.kv_blocks_peak > 0


@pytest.mark.parametrize("arch", ARCHS)
def test_paged_chunked_prefill_matches_dense(arch):
    """Long prompts through the paged chunk-continuation program generate
    exactly what the dense chunked engine generates."""
    _, model, params = _tiny_model(arch)
    prompt = np.random.RandomState(5).randint(1, 400, 45).tolist()
    kw = dict(slots=2, max_len=128, buckets=(16,), prefill_chunk=16)
    dense = ServeEngine(model, params, **kw)
    paged = ServeEngine(model, params, kv_block_size=16, **kw)
    (rd,) = dense.run([Request(rid=0, prompt=prompt, max_new_tokens=5)])
    (rp,) = paged.run([Request(rid=0, prompt=prompt, max_new_tokens=5)])
    assert paged.stats.prefill_chunks == 3
    assert rd.generated == rp.generated


def test_shared_prefix_cow_matches_cold_run():
    """The acceptance path: request B shares a (non-block-aligned) prefix
    with finished request A — B skips prefill for the shared portion, clones
    the straddling block copy-on-write, and still generates exactly what a
    cold engine generates."""
    _, model, params = _tiny_model()
    rng = np.random.RandomState(7)
    shared = rng.randint(1, 400, 20).tolist()      # 2.5 blocks of 8
    tail_a = rng.randint(1, 400, 7).tolist()
    tail_b = rng.randint(1, 400, 9).tolist()

    engine = ServeEngine(model, params, slots=2, max_len=64, kv_block_size=8)
    (ra,) = engine.run([Request(rid=0, prompt=shared + tail_a,
                                max_new_tokens=4)])
    (rb,) = engine.run([Request(rid=1, prompt=shared + tail_b,
                                max_new_tokens=4)])
    s = engine.stats.summary()["kv"]
    assert s["prefix_hits"] == 1
    assert s["prefix_tokens_reused"] == 20         # 2 full blocks + 4 COW
    assert s["blocks_copied"] == 1
    # fewer prompt tokens computed than submitted
    assert engine.stats.prefill_tokens_computed \
        < engine.stats.prefill_prompt_tokens

    cold = ServeEngine(model, params, slots=2, max_len=64, kv_block_size=8)
    (rc,) = cold.run([Request(rid=1, prompt=shared + tail_b,
                              max_new_tokens=4)])
    assert rb.generated == rc.generated
    dense = ServeEngine(model, params, slots=2, max_len=64)
    (rd,) = dense.run([Request(rid=1, prompt=shared + tail_b,
                               max_new_tokens=4)])
    assert rb.generated == rd.generated


def test_finish_never_publishes_the_unwritten_last_token():
    """Regression: the last generated token is sampled but never fed back
    through decode, so its KV is never written.  A finished block-aligned
    sequence (prompt + generated divisible by the block size) must not
    publish its final block, or a prompt extending the full sequence would
    attend to a garbage position on the prefix hit."""
    _, model, params = _tiny_model()
    bs = 8
    prompt = np.random.RandomState(21).randint(1, 400, 12).tolist()
    engine = ServeEngine(model, params, slots=2, max_len=64,
                         kv_block_size=bs)
    (ra,) = engine.run([Request(rid=0, prompt=prompt, max_new_tokens=4)])
    total = len(prompt) + len(ra.generated)
    assert total % bs == 0                   # the dangerous alignment
    # only the WRITTEN prefix (total - 1 tokens) may be published: the final
    # block would expose one never-written KV position
    assert len(engine.kv.tree) == (total - 1) // bs
    follow = prompt + ra.generated + [7, 9, 11]
    (rb,) = engine.run([Request(rid=1, prompt=follow, max_new_tokens=4)])
    cold = ServeEngine(model, params, slots=2, max_len=64, kv_block_size=bs)
    (rc,) = cold.run([Request(rid=1, prompt=follow, max_new_tokens=4)])
    assert rb.generated == rc.generated


def test_prefix_cache_disabled_on_non_attention_models():
    """Hybrid/recurrent stacks have state the block pool can't share — the
    prefix cache must disable itself rather than corrupt outputs."""
    for arch in ["recurrentgemma-2b", "falcon-mamba-7b",
                 "granite-4.0-h-micro"]:
        _, model, params = _tiny_model(arch)
        eng = ServeEngine(model, params, slots=1, max_len=32, kv_block_size=8,
                          prefix_cache=True)
        assert not eng.kv.prefix_enabled
    _, model, params = _tiny_model("qwen3-0.6b")
    eng = ServeEngine(model, params, slots=1, max_len=32, kv_block_size=8)
    assert eng.kv.prefix_enabled


def test_paged_engine_rejects_misaligned_block_size():
    _, model, params = _tiny_model()
    with pytest.raises(ValueError, match="multiple"):
        ServeEngine(model, params, slots=1, max_len=30, kv_block_size=8)


def test_same_tick_block_release_while_neighbor_decodes():
    """Regression for the reclamation bug: a finished request's blocks free
    the same tick it retires, even while another slot keeps decoding —
    observable via submit()+step() as a drop in kv_blocks_in_use."""
    _, model, params = _tiny_model()
    engine = ServeEngine(model, params, slots=2, max_len=64, kv_block_size=8,
                         max_prefill_per_step=2)
    long_req = Request(rid=0, prompt=[3, 4, 5], max_new_tokens=12)
    short = Request(rid=1, prompt=list(range(1, 18)), max_new_tokens=4)
    engine.submit(long_req)
    engine.submit(short)
    engine.step()
    in_use_both = engine.stats.kv_blocks_in_use
    assert in_use_both >= 3 + 1            # 17 tokens = 3 blocks, + 1
    while not short.done:
        engine.step()
    # the tick that finished `short` already reflects the release: only the
    # long request's blocks remain referenced
    assert engine.stats.kv_blocks_in_use < in_use_both
    assert engine.stats.kv_blocks_in_use == engine.kv.in_use
    while not long_req.done:
        engine.step()
    assert engine.stats.kv_blocks_in_use == 0


def test_paged_pool_exhaustion_raises_not_spins():
    """Two slots sharing a one-slot-worst-case pool: when both grow past the
    supply and neither can retire, the engine must fail loudly, not spin."""
    _, model, params = _tiny_model()
    engine = ServeEngine(model, params, slots=2, max_len=32, kv_block_size=8,
                         kv_blocks=4, max_prefill_per_step=2)
    reqs = [Request(rid=i, prompt=list(range(1 + 9 * i, 14 + 9 * i)),
                    max_new_tokens=25) for i in range(2)]
    for r in reqs:
        engine.submit(r)
    with pytest.raises(RuntimeError, match="KV pool exhausted"):
        for _ in range(60):
            engine.step()


def test_paged_pool_floor_rejected_at_construction():
    """A pool smaller than one request's worst case would livelock admission
    of a long prompt — refuse it up front."""
    _, model, params = _tiny_model()
    with pytest.raises(ValueError, match="worst case"):
        ServeEngine(model, params, slots=1, max_len=32, kv_block_size=8,
                    kv_blocks=2)


def test_admit_does_not_count_pinned_cached_blocks_as_supply():
    """Regression: the shared blocks a plan pins (and the COW source) stop
    being evictable once retained — admit must requeue, not assert-crash,
    when the fresh allocations can't be covered without them."""
    mgr = PagedKVManager(slots=2, max_len=24, block_size=4, num_blocks=6)
    donor = list(range(100, 112))            # 3 blocks
    mgr.admit(0, donor)
    mgr.finish(0, donor)                     # 3 cached blocks, 3 free
    assert mgr.admit(1, list(range(200, 212))) is not None  # takes the 3 free
    # pool: 3 referenced (slot 1), 3 cached matching `donor`'s prefix.
    # a donor-prefixed prompt needing a fresh tail block must requeue —
    # the 3 cached blocks it would pin are not allocatable supply
    assert mgr.admit(0, donor + [7, 7, 7, 7]) is None
    assert mgr.owned[0] == 0 and mgr.in_use == 3


def test_paged_warmup_closes_program_inventory():
    """Paged engines: warmup compiles every (batch-bucket, bucket) prefill,
    the chunk continuation, the block-clone program, and decode; a trace
    with prefix hits, COW, chunked long prompts, and refills adds zero
    compile-cache entries."""
    _, model, params = _tiny_model()
    engine = ServeEngine(model, params, slots=2, max_len=128,
                         buckets=(16, 32), prefill_chunk=32,
                         max_prefill_per_step=2, max_prefill_batch=2,
                         kv_block_size=16)
    engine.warmup()
    warm_p = engine.stats.prefill_compiles
    warm_d = engine.stats.decode_compiles
    # 2 buckets x batch buckets (1, 2) + chunk + copy programs
    assert warm_p == 6
    assert warm_d == 1
    rng = np.random.RandomState(2)
    base = rng.randint(1, 400, 40).tolist()
    reqs = [Request(rid=i, prompt=rng.randint(1, 400, n).tolist(),
                    max_new_tokens=3)
            for i, n in enumerate([4, 9, 20, 30, 50, 100, 7, 25])]
    engine.run(reqs)
    # sequential runs so the second base-prefix request deterministically
    # sees the first one's published blocks (prefix hit + COW + chunk)
    engine.run([Request(rid=100, prompt=base + [7, 8], max_new_tokens=3)])
    engine.run([Request(rid=101, prompt=base + [9, 1, 2], max_new_tokens=3)])
    assert all(r.done for r in reqs)
    assert engine.stats.summary()["kv"]["prefix_hits"] >= 1
    assert engine.stats.prefill_compiles == warm_p    # zero recompiles
    assert engine.stats.decode_compiles == warm_d


def test_warmup_after_serving_drops_stale_prefix_cache():
    """warmup() re-zeroes the device pool, so every cached prefix describing
    the old contents must be forgotten — a post-warmup request must NOT hit
    blocks that no longer hold its KV."""
    _, model, params = _tiny_model()
    prompt = np.random.RandomState(1).randint(1, 400, 20).tolist()
    engine = ServeEngine(model, params, slots=2, max_len=64, kv_block_size=8)
    (r0,) = engine.run([Request(rid=0, prompt=prompt, max_new_tokens=4)])
    assert engine.kv.cached > 0              # published blocks are cached
    engine.warmup()
    assert engine.kv.cached == 0 and len(engine.kv.tree) == 0
    (r1,) = engine.run([Request(rid=1, prompt=prompt, max_new_tokens=4)])
    assert r1.generated == r0.generated      # cold-served, identical output


# ------------------------------------------------------------------ sampling
def test_greedy_requests_unchanged_by_sampling_support():
    """Default (temperature 0) requests on an engine that also serves
    stochastic ones generate exactly the greedy reference."""
    _, model, params = _tiny_model()
    prompts = [[5, 9, 2], [7, 1, 4, 2], [3, 3, 8]]
    greedy_ref = ServeEngine(model, params, slots=3, max_len=32)
    ref = greedy_ref.run([Request(rid=i, prompt=p, max_new_tokens=4)
                          for i, p in enumerate(prompts)])
    mixed = ServeEngine(model, params, slots=3, max_len=32)
    out = mixed.run([
        Request(rid=0, prompt=prompts[0], max_new_tokens=4),
        Request(rid=1, prompt=prompts[1], max_new_tokens=4,
                temperature=1.3, top_k=5, seed=11),
        Request(rid=2, prompt=prompts[2], max_new_tokens=4)])
    assert out[0].generated == ref[0].generated
    assert out[2].generated == ref[2].generated


def test_sampled_requests_reproducible_and_seed_sensitive():
    _, model, params = _tiny_model()

    def run_once(seed):
        eng = ServeEngine(model, params, slots=1, max_len=32)
        (r,) = eng.run([Request(rid=0, prompt=[5, 9, 2], max_new_tokens=8,
                                temperature=1.0, seed=seed)])
        return r.generated

    a, b = run_once(7), run_once(7)
    assert a == b                            # same seed -> same stream
    seqs = {tuple(run_once(s)) for s in range(6)}
    assert len(seqs) > 1                     # seeds actually matter


def test_sample_tokens_semantics():
    rng = np.random.RandomState(0)
    B, V = 4, 40
    logits = jnp.asarray(rng.randn(B, V).astype(np.float32))
    zf = jnp.zeros((B,))
    zi = jnp.zeros((B,), jnp.int32)
    ones = jnp.ones((B,))
    pos = jnp.arange(B, dtype=jnp.int32)
    argmax = np.asarray(jnp.argmax(logits, -1))
    # temperature 0 rows: exact argmax
    np.testing.assert_array_equal(
        np.asarray(sample_tokens(logits, zf, zi, ones, zi, pos)), argmax)
    # top_k=1 and tiny top_p degenerate to argmax under any temperature
    np.testing.assert_array_equal(
        np.asarray(sample_tokens(logits, 2 * ones,
                                 jnp.full((B,), 1, jnp.int32), ones, zi,
                                 pos)), argmax)
    np.testing.assert_array_equal(
        np.asarray(sample_tokens(logits, 2 * ones, zi,
                                 jnp.full((B,), 1e-6), zi, pos)), argmax)
    # top-k=5 sampling stays inside the top-5 set and is deterministic
    top5 = np.argsort(-np.asarray(logits), axis=-1)[:, :5]
    for p in range(10):
        o = sample_tokens(logits, 3 * ones, jnp.full((B,), 5, jnp.int32),
                          ones, zi, jnp.full((B,), p, jnp.int32))
        o2 = sample_tokens(logits, 3 * ones, jnp.full((B,), 5, jnp.int32),
                           ones, zi, jnp.full((B,), p, jnp.int32))
        np.testing.assert_array_equal(np.asarray(o), np.asarray(o2))
        for b in range(B):
            assert int(o[b]) in top5[b]


def test_sampling_in_chunked_and_prefix_paths_reproducible():
    """The first token of a chunked (and prefix-hit) prefill samples from
    the same (seed, position) stream as the bucketed path: same request,
    same stream, regardless of which program produced it."""
    _, model, params = _tiny_model()
    prompt = np.random.RandomState(5).randint(1, 400, 45).tolist()
    chunked = ServeEngine(model, params, slots=1, max_len=128,
                          buckets=(16,), prefill_chunk=16)
    (rc,) = chunked.run([Request(rid=0, prompt=prompt, max_new_tokens=5,
                                 temperature=0.8, seed=3)])
    one_shot = ServeEngine(model, params, slots=1, max_len=128)
    (ro,) = one_shot.run([Request(rid=0, prompt=prompt, max_new_tokens=5,
                                  temperature=0.8, seed=3)])
    assert rc.generated == ro.generated


# ------------------------------------------------------------ paged init API
def test_init_paged_kv_cache_shapes():
    c = init_paged_kv_cache(3, 10, 8, 2, 16)
    assert c.k.shape == (10, 8, 2, 16) and c.v.shape == (10, 8, 2, 16)
    assert c.length.shape == (3,)
