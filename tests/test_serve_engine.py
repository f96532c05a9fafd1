"""Bucketed continuous-batching engine tests: bucket selection, padded-prefill
state splicing vs the unpadded batch-1 reference, batched same-bucket
admission vs sequential batch-1, chunked prefill vs the unchunked reference,
slot eviction/refill, EOS, dead-slot isolation, queue/stats hygiene, and the
no-recompile-after-warmup guarantee (bounded compiled-program inventory)."""
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.models import build_model
from repro.serve.engine import (EngineStats, Request, ServeEngine,
                                bucket_for, prefill_buckets)


def _tiny_model(arch="qwen3-0.6b", layers=2):
    cfg = reduced_config(arch)
    cfg = cfg.replace(num_layers=max(layers, len(cfg.block_pattern)))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


# ------------------------------------------------------------------- buckets
def test_prefill_buckets_powers_of_two():
    assert prefill_buckets(64) == (16, 32, 64)
    # non-power-of-two max_len gets a final gap-covering bucket
    assert prefill_buckets(100) == (16, 32, 64, 100)
    assert prefill_buckets(16) == (16,)
    assert prefill_buckets(64, min_bucket=8) == (8, 16, 32, 64)
    with pytest.raises(ValueError):
        prefill_buckets(8, min_bucket=16)


def test_bucket_for_selects_smallest_fitting():
    buckets = (16, 32, 64)
    assert bucket_for(1, buckets) == 16
    assert bucket_for(16, buckets) == 16
    assert bucket_for(17, buckets) == 32
    assert bucket_for(64, buckets) == 64
    with pytest.raises(ValueError):
        bucket_for(65, buckets)


def test_submit_rejects_oversized_prompt():
    _, model, params = _tiny_model()
    engine = ServeEngine(model, params, slots=2, max_len=32)
    with pytest.raises(ValueError):
        engine.submit(Request(rid=0, prompt=list(range(40))))
    # a max_len prompt fills the cache with no room to decode one token
    with pytest.raises(ValueError):
        engine.submit(Request(rid=1, prompt=list(range(32))))
    with pytest.raises(ValueError):
        engine.submit(Request(rid=3, prompt=[]))
    with pytest.raises(ValueError):
        engine.submit(Request(rid=4, prompt=[1, 2], max_new_tokens=0))
    # invalid sampling knobs are rejected at submit
    with pytest.raises(ValueError):
        engine.submit(Request(rid=5, prompt=[1, 2], temperature=-0.5))
    with pytest.raises(ValueError):
        engine.submit(Request(rid=6, prompt=[1, 2], top_p=0.0))
    with pytest.raises(ValueError):
        engine.submit(Request(rid=7, prompt=[1, 2], top_k=-1))
    # greedy=False no longer raises: sampling is per-request now
    ServeEngine(model, params, slots=1, max_len=32, greedy=False)
    # max_len - 1 is the longest admissible prompt
    engine.submit(Request(rid=2, prompt=list(range(31))))


def test_non_power_of_two_max_len_accepts_prompts_near_cache_size():
    """Regression: max_len=48 must not silently reject a 40-token prompt
    (the bucket list gains a final 48-wide bucket)."""
    _, model, params = _tiny_model()
    engine = ServeEngine(model, params, slots=1, max_len=48)
    assert engine.buckets == (16, 32, 48)
    (req,) = engine.run([Request(rid=0, prompt=list(range(1, 41)),
                                 max_new_tokens=3)])
    assert req.done and len(req.generated) == 3


def test_gap_bucket_not_divisible_by_scan_chunk_on_recurrent_arch():
    """Regression: a 100-wide gap bucket is not a multiple of the reduced
    configs' scan_chunk=16 — the chunked linear scan must identity-pad the
    tail instead of crashing, and stay exact vs the unpadded reference."""
    _, model, params = _tiny_model("recurrentgemma-2b")
    engine = ServeEngine(model, params, slots=1, max_len=100)
    assert engine.buckets[-1] == 100
    prompt = list(range(1, 71))                   # selects the 100 bucket
    (req,) = engine.run([Request(rid=0, prompt=prompt, max_new_tokens=3)])
    assert req.done and len(req.generated) == 3

    states = model.init_states(1, 100)
    logits, states, _ = model.prefill(
        params, jnp.asarray([prompt], jnp.int32), states)
    toks = [int(jnp.argmax(logits[0, -1]))]
    pos = len(prompt)
    for _ in range(2):
        logits, states = model.decode_step(
            params, jnp.asarray([[toks[-1]]], jnp.int32), states,
            jnp.asarray([pos], jnp.int32), None)
        toks.append(int(jnp.argmax(logits[0, 0])))
        pos += 1
    assert req.generated == toks


# ----------------------------------------------- splice vs batch-1 reference
@pytest.mark.parametrize("arch", ["qwen3-0.6b", "recurrentgemma-2b",
                                  "falcon-mamba-7b", "granite-4.0-h-micro"])
def test_bucketed_prefill_matches_unpadded_reference(arch):
    """Engine output (padded/bucketed prefill spliced into the pool) must
    reproduce the manual unpadded batch-1 prefill + decode token-for-token —
    covers the KV, RG-LRU, and SSM state families."""
    _, model, params = _tiny_model(arch)
    prompt = [5, 9, 2, 7, 11]
    n_new = 4
    engine = ServeEngine(model, params, slots=2, max_len=64)
    (req,) = engine.run([Request(rid=0, prompt=prompt, max_new_tokens=n_new)])

    states = model.init_states(1, 64)
    logits, states, memory = model.prefill(
        params, jnp.asarray([prompt], jnp.int32), states)
    toks = [int(jnp.argmax(logits[0, -1]))]
    pos = len(prompt)
    for _ in range(n_new - 1):
        logits, states = model.decode_step(
            params, jnp.asarray([[toks[-1]]], jnp.int32), states,
            jnp.asarray([pos], jnp.int32), memory)
        toks.append(int(jnp.argmax(logits[0, 0])))
        pos += 1
    assert req.generated == toks


def test_padded_prefill_logits_and_states_exact():
    """Length-masked padded prefill is numerically identical to the unpadded
    one — logits at length-1 and the post-prefill decode logits match."""
    _, model, params = _tiny_model("recurrentgemma-2b")
    prompt = [5, 9, 2, 7, 11]
    L = len(prompt)
    s_ref = model.init_states(1, 64)
    lg_ref, s_ref, _ = model.prefill(
        params, jnp.asarray([prompt], jnp.int32), s_ref)
    toks = np.zeros((1, 16), np.int32)
    toks[0, :L] = prompt
    s_pad = model.init_states(1, 64)
    lg_pad, s_pad, _ = model.prefill(params, jnp.asarray(toks), s_pad,
                                     length=jnp.asarray([L], jnp.int32))
    np.testing.assert_allclose(np.asarray(lg_ref), np.asarray(lg_pad),
                               atol=1e-6, rtol=1e-6)
    lg1, _ = model.decode_step(params, jnp.asarray([[3]], jnp.int32), s_ref,
                               jnp.asarray([L], jnp.int32), None)
    lg2, _ = model.decode_step(params, jnp.asarray([[3]], jnp.int32), s_pad,
                               jnp.asarray([L], jnp.int32), None)
    np.testing.assert_allclose(np.asarray(lg1), np.asarray(lg2),
                               atol=1e-6, rtol=1e-6)


# ---------------------------------------------------------- batched prefill
@pytest.mark.parametrize("arch", ["qwen3-0.6b", "recurrentgemma-2b",
                                  "falcon-mamba-7b", "granite-4.0-h-micro"])
def test_batched_prefill_matches_sequential(arch):
    """Same-bucket admissions stacked into one (N, bucket) prefill call must
    generate exactly what N sequential batch-1 prefills generate, with fewer
    compiled calls than requests — covers all three state families."""
    _, model, params = _tiny_model(arch)
    rng = np.random.RandomState(3)
    prompts = [rng.randint(1, 400, 4 + i).tolist() for i in range(4)]

    def reqs():
        return [Request(rid=i, prompt=p, max_new_tokens=4)
                for i, p in enumerate(prompts)]

    batched = ServeEngine(model, params, slots=4, max_len=64,
                          max_prefill_per_step=4, max_prefill_batch=4)
    sequential = ServeEngine(model, params, slots=4, max_len=64,
                             max_prefill_per_step=1, max_prefill_batch=1)
    rb = batched.run(reqs())
    rs = sequential.run(reqs())
    assert [r.generated for r in rb] == [r.generated for r in rs]
    # all 4 prompts fit the 16-bucket: one compiled call admitted them all
    assert batched.stats.prefill_calls == 1
    assert batched.stats.prefills == 4
    assert sequential.stats.prefill_calls == 4


def test_batched_admission_splits_by_bucket_and_cap():
    """Mixed buckets admitted in one tick become one call per bucket group;
    a group larger than max_prefill_batch splits."""
    _, model, params = _tiny_model()
    engine = ServeEngine(model, params, slots=6, max_len=64,
                         max_prefill_per_step=6, max_prefill_batch=2)
    lens = [3, 5, 20, 25, 7, 9]                 # buckets 16,16,32,32,16,16
    reqs = [Request(rid=i, prompt=list(range(1, n + 1)), max_new_tokens=2)
            for i, n in enumerate(lens)]
    engine.run(reqs)
    assert all(r.done for r in reqs)
    assert engine.stats.prefills == 6
    # bucket16 group of 4 splits into 2 calls of 2; bucket32 group is 1 call
    assert engine.stats.prefill_calls == 3
    assert engine.stats.batch_counts == {2: 3}


def test_batch_bucket_padding_rows_are_inert():
    """A group of 3 into batch buckets (1,2,4) pads to 4 — the padding row
    targets a real slot but is spliced first and overwritten, so outputs
    match the sequential reference exactly."""
    _, model, params = _tiny_model()
    prompts = [[7, 8, 9], [4, 5], [11, 3, 2, 6]]

    def reqs():
        return [Request(rid=i, prompt=p, max_new_tokens=4)
                for i, p in enumerate(prompts)]

    batched = ServeEngine(model, params, slots=4, max_len=64,
                          max_prefill_per_step=4, max_prefill_batch=4)
    rb = batched.run(reqs())
    assert batched.stats.prefill_calls == 1     # one padded (4,16) call
    ref = ServeEngine(model, params, slots=4, max_len=64,
                      max_prefill_per_step=1, max_prefill_batch=1)
    rs = ref.run(reqs())
    assert [r.generated for r in rb] == [r.generated for r in rs]


# ---------------------------------------------------------- chunked prefill
@pytest.mark.parametrize("arch", ["qwen3-0.6b", "recurrentgemma-2b",
                                  "falcon-mamba-7b", "granite-4.0-h-micro"])
def test_chunked_prefill_matches_unchunked(arch):
    """A prompt longer than the largest bucket prefills in chunk-continuation
    calls (here 16+16+13) and must generate token-for-token what a one-shot
    unchunked engine generates — KV, ring-buffer sliding-window KV, RG-LRU,
    and SSM state families all resume correctly."""
    _, model, params = _tiny_model(arch)
    prompt = np.random.RandomState(5).randint(1, 400, 45).tolist()

    chunked = ServeEngine(model, params, slots=2, max_len=128,
                          buckets=(16,), prefill_chunk=16)
    (rc,) = chunked.run([Request(rid=0, prompt=prompt, max_new_tokens=5)])
    assert chunked.stats.prefill_chunks == 3
    assert chunked.stats.prefills == 1

    unchunked = ServeEngine(model, params, slots=2, max_len=128)
    (ru,) = unchunked.run([Request(rid=0, prompt=prompt, max_new_tokens=5)])
    assert rc.done and ru.done
    assert rc.generated == ru.generated


@pytest.mark.parametrize("arch", ["qwen3-0.6b", "recurrentgemma-2b",
                                  "falcon-mamba-7b", "granite-4.0-h-micro"])
def test_prefill_offset_continuation_matches_full(arch):
    """Model-level: prefill resumed via ``offset`` (ragged final chunk,
    right-padded) reproduces the one-shot prefill — last-position logits and
    the decode continuation match to float tolerance."""
    _, model, params = _tiny_model(arch)
    prompt = np.random.RandomState(11).randint(1, 400, 40).tolist()
    L = len(prompt)

    s_ref = model.init_states(1, 64)
    lg_ref, s_ref, _ = model.prefill(
        params, jnp.asarray([prompt], jnp.int32), s_ref)

    s = model.init_states(1, 64)
    off = 0
    for piece in (prompt[0:16], prompt[16:32], prompt[32:40]):
        n = len(piece)
        toks = np.zeros((1, 16), np.int32)
        toks[0, :n] = piece
        lg, s, _ = model.prefill(params, jnp.asarray(toks), s,
                                 length=jnp.asarray([n], jnp.int32),
                                 offset=jnp.asarray([off], jnp.int32))
        off += n
    np.testing.assert_allclose(np.asarray(lg_ref), np.asarray(lg),
                               atol=1e-6, rtol=1e-6)
    tok = int(jnp.argmax(lg_ref[0, -1]))
    lg1, _ = model.decode_step(params, jnp.asarray([[tok]], jnp.int32), s_ref,
                               jnp.asarray([L], jnp.int32), None)
    lg2, _ = model.decode_step(params, jnp.asarray([[tok]], jnp.int32), s,
                               jnp.asarray([L], jnp.int32), None)
    np.testing.assert_allclose(np.asarray(lg1), np.asarray(lg2),
                               atol=1e-6, rtol=1e-6)


def test_chunked_prefill_interleaves_with_decode():
    """While a long prompt prefills chunk-by-chunk, an already-running short
    request keeps decoding: the chunks and decode steps share ticks, and the
    short request's output is unaffected by the concurrent chunking."""
    _, model, params = _tiny_model()
    engine = ServeEngine(model, params, slots=2, max_len=128,
                         buckets=(16,), prefill_chunk=16,
                         max_prefill_per_step=1)
    short = Request(rid=0, prompt=[5, 6, 7], max_new_tokens=8)
    long_ = Request(rid=1,
                    prompt=np.random.RandomState(9).randint(
                        1, 400, 60).tolist(),
                    max_new_tokens=3)
    engine.run([short, long_])
    assert short.done and long_.done
    st = engine.stats
    assert st.prefill_chunks == 4               # ceil(60 / 16)
    # chunks ran on the same ticks as decode steps — a serializing engine
    # would need at least chunks + decode_steps ticks
    assert st.ticks < st.prefill_chunks + st.decode_steps
    # the short request decoded during the chunked prefill, unaffected by it
    solo = ServeEngine(model, params, slots=2, max_len=128,
                       buckets=(16,), prefill_chunk=16)
    (ref,) = solo.run([Request(rid=0, prompt=[5, 6, 7], max_new_tokens=8)])
    assert short.generated == ref.generated
    # and the long prompt's first token arrived after its chunks, not before
    assert long_.t_first_token > short.t_first_token


# ------------------------------------------------------- dead-slot isolation
def test_dead_slots_do_not_corrupt_state():
    """Regression for the dead-slot decode-write bug: while a slot sits empty
    (its neighbor still decoding), masked decode must leave it untouched so a
    request later admitted into it generates exactly what a fresh engine
    would.  Run A (long) + B (short) so B's slot is dead for several ticks,
    then admit C into the recycled slot."""
    _, model, params = _tiny_model()
    engine = ServeEngine(model, params, slots=2, max_len=64)
    a = Request(rid=0, prompt=[3, 4, 5], max_new_tokens=9)
    b = Request(rid=1, prompt=[6, 7], max_new_tokens=2)
    engine.run([a, b])
    assert a.done and b.done
    c = Request(rid=2, prompt=[8, 9, 10], max_new_tokens=5)
    engine.run([c])

    fresh = ServeEngine(model, params, slots=2, max_len=64)
    (ref,) = fresh.run([Request(rid=2, prompt=[8, 9, 10], max_new_tokens=5)])
    assert c.generated == ref.generated


def test_decode_active_mask_freezes_state_bitwise():
    """Model-level: a decode step with active=False must leave every state
    leaf (KV contents + length, conv context, recurrent h) bit-for-bit
    unchanged, and active=True rows must match active=None bitwise."""
    for arch in ["qwen3-0.6b", "recurrentgemma-2b", "falcon-mamba-7b",
                 "granite-4.0-h-micro"]:
        _, model, params = _tiny_model(arch)
        states = model.init_states(2, 32)
        toks = jnp.asarray([[5, 9, 2], [7, 1, 4]], jnp.int32)
        _, states, _ = model.prefill(params, toks, states)
        pos = jnp.asarray([3, 3], jnp.int32)
        step = jnp.asarray([[8], [8]], jnp.int32)
        # both rows frozen: states unchanged
        _, frozen = model.decode_step(params, step, states, pos,
                                      active=jnp.asarray([False, False]))
        for a, b in zip(jax.tree.leaves(states), jax.tree.leaves(frozen)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # both rows active: bitwise identical to no mask at all
        lg_ref, s_ref = model.decode_step(params, step, states, pos)
        lg_act, s_act = model.decode_step(params, step, states, pos,
                                          active=jnp.asarray([True, True]))
        np.testing.assert_array_equal(np.asarray(lg_ref), np.asarray(lg_act))
        for a, b in zip(jax.tree.leaves(s_ref), jax.tree.leaves(s_act)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------- eviction and refill
def test_slot_eviction_on_max_tokens_and_refill_order():
    """More requests than slots: every request completes with exactly its
    max_new_tokens, and slots are refilled in submission order."""
    _, model, params = _tiny_model()
    engine = ServeEngine(model, params, slots=2, max_len=64)
    reqs = [Request(rid=i, prompt=[1 + i, 2, 3], max_new_tokens=3 + i % 2)
            for i in range(5)]
    done = engine.run(reqs)
    assert all(r.done for r in done)
    for r in done:
        assert len(r.generated) == r.max_new_tokens
    # admission (first-token) order == submission order
    first_times = [r.t_first_token for r in done]
    assert first_times == sorted(first_times)
    assert engine.stats.requests_completed == 5


def test_slot_eviction_on_eos():
    """When the model emits eos_id the slot is evicted immediately."""
    _, model, params = _tiny_model()
    # learn what the (untrained) model generates first for this prompt
    probe = ServeEngine(model, params, slots=1, max_len=64)
    (r0,) = probe.run([Request(rid=0, prompt=[5, 6, 7], max_new_tokens=2)])
    eos = r0.generated[0]
    engine = ServeEngine(model, params, slots=1, max_len=64)
    (r1,) = engine.run([Request(rid=1, prompt=[5, 6, 7], max_new_tokens=8,
                                eos_id=eos)])
    assert r1.done
    assert r1.generated[0] == eos and len(r1.generated) == 1


def test_interleaved_admission_budget():
    """With max_prefill_per_step=1, a 4-request burst into 4 slots admits one
    request per tick — decode work proceeds between admissions."""
    _, model, params = _tiny_model()
    engine = ServeEngine(model, params, slots=4, max_len=64,
                         max_prefill_per_step=1)
    reqs = [Request(rid=i, prompt=[1 + i, 2], max_new_tokens=6)
            for i in range(4)]
    done = engine.run(reqs)
    assert all(r.done for r in done)
    # each of the 4 prefills happened on a distinct tick
    assert engine.stats.prefills == 4
    assert engine.stats.ticks >= 4
    # later arrivals decoded fewer steps before earlier ones finished, but
    # everyone still produced exactly max_new_tokens
    assert all(len(r.generated) == 6 for r in done)


# ------------------------------------------------------------ compile counts
def test_no_recompiles_after_warmup():
    """A mixed-length trace spanning 3 buckets compiles each bucket once;
    repeating the trace (same buckets, different lengths/slots) adds zero
    compile-cache entries."""
    _, model, params = _tiny_model()
    engine = ServeEngine(model, params, slots=2, max_len=64)

    def trace(seed):
        rng = np.random.RandomState(seed)
        lens = [3, 20, 40, 9, 27, 55]           # buckets 16, 32, 64
        return [Request(rid=i, prompt=rng.randint(1, 500, n).tolist(),
                        max_new_tokens=3)
                for i, n in enumerate(lens)]

    engine.run(trace(0))
    warm_prefill = engine.stats.prefill_compiles
    warm_decode = engine.stats.decode_compiles
    assert warm_prefill == 3                     # one program per bucket
    assert warm_decode == 1                      # one decode program
    assert engine.stats.bucket_counts == {16: 2, 32: 2, 64: 2}

    engine.reset_stats()
    engine.run(trace(1))
    assert engine.stats.prefill_compiles == warm_prefill
    assert engine.stats.decode_compiles == warm_decode


# -------------------------------------------------------------------- stats
def test_engine_stats_summary():
    _, model, params = _tiny_model()
    engine = ServeEngine(model, params, slots=2, max_len=32)
    reqs = [Request(rid=i, prompt=[1 + i, 2, 3], max_new_tokens=4)
            for i in range(3)]
    engine.run(reqs)
    s = engine.stats.summary()
    assert s["requests_completed"] == 3
    assert s["tokens_generated"] == 12
    assert s["tokens_per_s"] > 0
    assert engine.stats.ttft_count == 3
    assert s["ttft_ms"]["mean"] > 0
    assert s["obs"]["histograms"]["ttft_s"]["count"] == 3
    assert s["decode_step_ms"] > 0
    assert 0 < s["slot_occupancy"] <= 1
    assert s["prefills"] == 3
    # prompts of 3 tokens pad to the 16-bucket
    assert s["prefill_padding_overhead"] == pytest.approx(16 / 3 - 1)
    # ttft measured per request from submit to first token
    for r in reqs:
        assert r.t_first_token >= r.t_submit
        assert r.t_done >= r.t_first_token


def test_stats_meaningful_when_driven_via_step_api():
    """Callers embedding the engine in their own event loop (submit + step,
    never run) still get nonzero wall time and tokens_per_s."""
    _, model, params = _tiny_model()
    engine = ServeEngine(model, params, slots=1, max_len=32)
    engine.submit(Request(rid=0, prompt=[4, 5, 6], max_new_tokens=3))
    for _ in range(10):
        engine.step()
    s = engine.stats.summary()
    assert s["requests_completed"] == 1
    assert s["wall_time_s"] > 0
    assert s["tokens_per_s"] > 0


def test_stats_reset_keeps_compile_counts():
    _, model, params = _tiny_model()
    engine = ServeEngine(model, params, slots=1, max_len=32)
    engine.run([Request(rid=0, prompt=[4, 5], max_new_tokens=2)])
    n = engine.stats.prefill_compiles
    engine.reset_stats()
    assert engine.stats.prefill_compiles == n
    assert engine.stats.prefills == 0 and engine.stats.ticks == 0


def test_ttft_stats_exact_mean_max_and_bounded_memory():
    """The histogram-backed TTFT stats: mean and max stay exact no matter
    how many samples arrive (streaming aggregates next to the log2 buckets),
    memory stays fixed-size forever, and the p50 lands within one log2
    bucket of the true median."""
    st = EngineStats()
    st.record_ttft(1.0)
    st.record_ttft(3.0)
    p50 = st.summary()["ttft_ms"]["p50"]
    assert 1000.0 <= p50 <= 3000.0          # clamped to the exact envelope
    # stream a lot of samples: no growth, no aggregate drift
    st = EngineStats()
    n = 10_000
    vals = [float(i % 97) / 97.0 + (1000.0 if i == 3 else 0.0)
            for i in range(n)]
    for v in vals:
        st.record_ttft(v)
    assert st.ttft_count == n
    hist = st.metrics.histogram("ttft_s")
    assert len(hist.counts) == hist.nbuckets            # fixed-size forever
    s = st.summary()["ttft_ms"]
    assert s["mean"] == pytest.approx(1e3 * sum(vals) / n)      # exact
    assert s["max"] == pytest.approx(1e3 * max(vals))           # exact
    # p50 within one log2 bucket (factor of 2) of the true median
    true_p50 = 1e3 * float(np.median(vals))
    assert true_p50 / 2 <= s["p50"] <= true_p50 * 2


def test_queue_is_deque_and_deep_queue_admits_fifo():
    """Regression for the O(n) list.pop(0) admission queue: the queue is a
    deque, a deep backlog submits in O(1) each, and admission order is
    strictly FIFO."""
    _, model, params = _tiny_model()
    engine = ServeEngine(model, params, slots=1, max_len=32)
    assert isinstance(engine._queue, deque)
    reqs = [Request(rid=i, prompt=[1 + i % 30, 2], max_new_tokens=1)
            for i in range(5000)]
    for r in reqs:
        engine.submit(r)
    assert len(engine._queue) == 5000
    # drain a few ticks: admissions come off the head in submission order
    for _ in range(3):
        engine.step()
    first_done = [r.rid for r in reqs if r.done]
    assert first_done == sorted(first_done)
    assert engine._queue[0].rid == 5000 - len(engine._queue)


def test_run_truncation_marks_aborted_and_warns_or_raises():
    """run() hitting max_steps must not silently hand back unfinished
    requests: survivors are marked, counted, and reported."""
    _, model, params = _tiny_model()
    engine = ServeEngine(model, params, slots=1, max_len=64)
    reqs = [Request(rid=i, prompt=[2 + i, 3], max_new_tokens=30)
            for i in range(3)]
    with pytest.warns(RuntimeWarning, match="max_steps"):
        engine.run(reqs, max_steps=2)
    unfinished = [r for r in reqs if not r.done]
    assert unfinished and all(r.aborted for r in unfinished)
    assert engine.stats.requests_aborted == len(unfinished)
    assert engine.stats.summary()["requests_aborted"] == len(unfinished)
    # a second truncated run over the same survivors must not double-count
    with pytest.warns(RuntimeWarning, match="max_steps"):
        engine.run([], max_steps=1)
    assert engine.stats.requests_aborted == len(unfinished)
    # finishing them later clears the flag
    engine.run([], max_steps=10_000)
    assert all(r.done and not r.aborted for r in reqs)

    engine2 = ServeEngine(model, params, slots=1, max_len=64)
    with pytest.raises(RuntimeError, match="max_steps"):
        engine2.run([Request(rid=9, prompt=[5, 6], max_new_tokens=30)],
                    max_steps=1, on_truncate="raise")
    with pytest.raises(ValueError):
        engine2.run([], on_truncate="explode")


# ---------------------------------------------------------------- warmup
def test_warmup_precompiles_closed_program_inventory():
    """warmup() compiles every (batch-bucket, bucket) prefill shape plus the
    chunk and decode programs; any trace afterwards — batched admissions,
    long chunked prompts, refills — adds zero compile-cache entries."""
    _, model, params = _tiny_model()
    engine = ServeEngine(model, params, slots=2, max_len=128,
                         buckets=(16, 32), prefill_chunk=32,
                         max_prefill_per_step=2, max_prefill_batch=2)
    engine.warmup()
    # 2 buckets x batch buckets (1, 2) + 1 chunk program
    assert engine.stats.prefill_compiles == 5
    assert engine.stats.decode_compiles == 1
    rng = np.random.RandomState(2)
    reqs = [Request(rid=i, prompt=rng.randint(1, 400, n).tolist(),
                    max_new_tokens=3)
            for i, n in enumerate([4, 9, 20, 30, 50, 100, 7, 25])]
    engine.run(reqs)
    assert all(r.done for r in reqs)
    assert engine.stats.prefill_compiles == 5    # zero recompiles
    assert engine.stats.decode_compiles == 1
    with pytest.raises(RuntimeError):            # mid-flight warmup refused
        engine.submit(Request(rid=99, prompt=[1, 2], max_new_tokens=1))
        engine.warmup()
