"""The granite-4.0-h-micro cell on the CPU at a reduced size: its weights
in the program's layout, the engine's logits against the float32
reference through every prefill path and decode, whole runs of the cell
(plain, traced, with faults), the float8 control against the limit, and
the cell's five readers on a made-up traced run."""
import json
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import compare, harness, manifest, trace_reduce
from bench.tests.test_bench_harness import PEAKS, run

CELL = "granite-4.0-h-micro.offline-long-doc"
READERS = ("prefill_ms_per_ktok.longdoc", "decode_step_ms.longdoc",
           "step_mfu.longdoc", "device_idle_share.longdoc",
           "decode_roofline.longdoc")
#: one period of the layer pattern (5 Mamba-2, attention, 4 Mamba-2) at
#: test widths; the heads, groups and state layout keep their structure
SMALL = dict(hidden_size=64, shared_intermediate_size=128,
             intermediate_size=128, num_attention_heads=4,
             num_key_value_heads=2, mamba_n_heads=8, mamba_d_head=16,
             mamba_d_state=8, vocab_size=512)
SMALL_ARCH = dict(d_model=64, d_ff=128, num_heads=4, num_kv_heads=2,
                  head_dim=16, vocab_size=512, d_inner=128, ssm_heads=8,
                  d_state=8, scan_chunk=16)


def granite_cell(root, periods: int = 1):
    """The cell with its configuration's widths and depth cut (``periods``
    whole layer periods) and its mix shortened: prompts of 8-150 tokens,
    buckets of 32 (so most prompts are chunked, in chunks of 48: not a
    multiple of the SSD chunk of 16)."""
    for d in ("configs", "traffic", "metrics"):
        shutil.copytree(harness.BENCH / d, root / "bench" / d)
    man = harness.load_manifest()
    w = {x["name"]: x for x in man["workloads"]}[CELL]
    p = root / "bench" / "configs" / f"{w['config']}.json"
    cfg = json.loads(p.read_text())
    types = cfg["published"]["layer_types"][:10] * periods
    cfg["published"].update(SMALL, num_hidden_layers=len(types),
                            layer_types=types)
    cfg["overrides"].update(SMALL_ARCH, num_layers=len(types))
    cfg["engine"].update(slots=3, max_len=192, kv_blocks=36, min_bucket=32,
                         max_bucket=32, max_prefill_batch=2,
                         prefill_chunk=48)
    # the comparison reads at least 100 served tokens, and answers here are
    # short: sample up to 16 requests so that a slow (loaded) host, which
    # finishes fewer of them in the window, still gives enough
    cfg["correct"].update(sample_tokens=240, sample_requests_max=16)
    p.write_text(json.dumps(cfg))
    mp = root / "bench" / "traffic" / f"{w['traffic']}.json"
    mix = json.loads(mp.read_text())
    mix.update(prompt=dict(mix["prompt"], median=60, min=8, max=150),
               output=dict(mix["output"], median=12, min=4, max=40),
               ramp_s=1, pool=200)
    mp.write_text(json.dumps(mix))
    return harness.cell(man, CELL, root=root)


def limit():
    conf = json.loads((harness.BENCH / "configs" / "granite-4.0-h-micro.json")
                      .read_text())["correct"]
    return conf["max_logit_gap"]


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    return granite_cell(tmp_path_factory.mktemp("granite"))


def test_the_cell_keeps_the_manifest_rules_and_resolves():
    man = harness.load_manifest()
    assert manifest.check(man, harness.REPO) == []
    c = harness.cell(man, CELL)
    assert c.config["reduced"] == [] and c.workload["chips"] == 1
    assert {m["name"] for m in c.end_to_end} == {"tokens_per_s", "setup_s"}
    assert [m["name"] for m in c.per_layer] == list(READERS)
    assert all(m["moves"] == "tokens_per_s" for m in c.per_layer)


def test_the_file_holds_the_published_config_at_full_size():
    body = json.loads((harness.BENCH / "configs" / "granite-4.0-h-micro.json")
                      .read_text())
    pub = body["published"]
    assert {k: body[k] for k in pub} == pub
    m = harness.load_module(harness.BENCH / "configs"
                            / "granite_hybrid_lm.py").dims(pub)
    assert (m["layers"], m["period"], m["d"], m["hd"], m["di"], m["conv"]) \
        == (40, 10, 2048, 64, 4096, 4352)
    assert [i for i, t in enumerate(m["types"]) if t == "attention"] \
        == [5, 15, 25, 35]
    arch = harness.arch_config(body)
    assert arch.layer_kinds.count("attn") == 4 and arch.head_dim == 64
    assert (arch.d_inner, arch.ssm_heads, arch.d_state, arch.d_ff) \
        == (4096, 64, 128, 8192)
    assert 3.18e9 < arch.param_count() < 3.20e9


def test_weights_have_the_program_layout(cell, tmp_path):
    params = harness.make_weights(cell, 2**31 + 3)
    assert params["groups"]["5"]["attn"]["wq"].shape == (1, 64, 64)
    assert params["groups"]["0"]["mamba2"]["in_proj"].shape \
        == (1, 64, 2 * 128 + 2 * 8 + 8)
    # a reference whose layout differs from the program's is refused
    bad = harness.Cell(**{**cell.__dict__, "config": {
        **cell.config, "published": {**cell.config["published"],
                                     "mamba_d_state": 4}}})
    with pytest.raises(ValueError, match="layout"):
        harness.make_weights(bad, 1)


def _spy_logits(monkeypatch):
    """Every logits row the engine's programs sample from, with the
    position it is sampled for, in call order."""
    from repro.serve import engine as engine_mod
    got = []
    real = engine_mod.sample_tokens

    def spy(logits, temp, topk, topp, seed, pos):
        jax.debug.callback(lambda lg, ps: got.append(
            (np.asarray(lg), np.asarray(ps))), logits, pos)
        return real(logits, temp, topk, topp, seed, pos)

    monkeypatch.setattr(engine_mod, "sample_tokens", spy)
    return got


def test_engine_logits_match_the_reference_on_every_path(cell, monkeypatch):
    """The engine in float32 against the reference's whole forward pass:
    a bucketed prefill of three right-padded prompts in a batch bucket of
    four (one padding row), prompts chunked across chunk boundaries with
    the Mamba-2 state carried (61 and 130 tokens in chunks of 48), and
    every decode step through the paged pool and the carried state.  Each
    served token's logits row lies within 2e-4 times the spread of the
    reference's logits of the reference's row: float32 throughout, so only
    the order of summation differs (chunked SSD against the reference's
    step-by-step recurrence)."""
    from repro.launch.serve import build_engine
    from repro.serve.engine import Request
    pub = cell.config["published"]
    arch = harness.arch_config(cell.config).replace(param_dtype="float32",
                                                    compute_dtype="float32")
    params = cell.family.init(pub, jax.random.PRNGKey(7), jnp.float32)
    got = _spy_logits(monkeypatch)
    eng = build_engine(arch, params, **{**cell.config["engine"], "slots": 4,
                                        "kv_blocks": 48,
                                        "max_prefill_batch": 4})
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 512, n).tolist() for n in (20, 31, 9, 61, 130)]
    reqs = [Request(rid=i, prompt=p, max_new_tokens=6)
            for i, p in enumerate(prompts)]
    eng.run(reqs)
    assert all(r.done for r in reqs)
    assert eng.stats.prefills_chunked == 2 and eng.stats.batch_counts == {3: 1}
    assert eng.stats.summary()["state"]["chunks_resumed"] == 1 + 2
    table = np.asarray(params["embed"], np.float32)[:512]
    rows = [(lg[i], int(pos[i])) for lg, pos in got for i in range(len(lg))]
    with jax.default_matmul_precision("highest"):
        for r in reqs:
            seq = r.prompt + r.generated[:-1]
            toks = np.zeros(-(-len(seq) // 256) * 256, np.int32)
            toks[:len(seq)] = seq
            h = np.asarray(cell.family.hidden(params, jnp.asarray(toks), pub))
            ref = h[:len(seq)] @ table.T
            tol = 2e-4 * float(ref.std())
            for k, tok in enumerate(r.generated):
                at = len(r.prompt) + k          # the sampled token's index
                match = [lg for lg, pos in rows if pos == at
                         and np.abs(lg - ref[at - 1]).max() <= tol]
                assert match, (r.rid, k)
                assert int(np.argmax(match[0])) == tok


def test_a_plain_run_is_correct(cell):
    res, checks = run(cell)
    assert res["correct"], checks
    assert res["failed"] == 0
    assert set(res["metrics"]) == {"tokens_per_s", "setup_s"}


def test_a_traced_run_reads_the_host_side_metrics(cell, tmp_path):
    res, checks = run(cell, seed=2**31 + 17, trace=True, runs_dir=tmp_path)
    assert res["correct"], checks
    m = res["metrics"]
    for k in ("prefill_ms_per_ktok.longdoc", "decode_step_ms.longdoc",
              "step_mfu.longdoc"):
        assert k in m and m[k]["value"] > 0, k


def _state_unchanged(engine):
    """A decode step that returns the state it was given."""
    good = engine._decode_and_sample
    engine._decode = jax.jit(
        lambda p, t, s, *rest: (good(p, t, s, *rest)[0], s))
    return engine


def _chunk_state_dropped(engine):
    """A chunk call that hands the next chunk a zeroed Mamba-2 state: the
    carried recurrence is lost at every chunk boundary."""
    good = engine._chunk_and_splice

    def bad(p, tok, off, n, slot, states, *rest):
        zero = jax.tree.map(
            lambda a: jnp.zeros_like(a) if a.dtype == jnp.float32
            and a.ndim == 5 else a, states)
        return good(p, tok, off, n, slot, zero, *rest)
    engine._chunk = jax.jit(bad)
    return engine


@pytest.mark.parametrize("fault", [_state_unchanged, _chunk_state_dropped],
                         ids=["decode_state_unchanged", "chunk_state_lost"])
def test_a_broken_timed_path_is_not_correct(cell, monkeypatch, fault):
    make = harness.make_engine
    monkeypatch.setattr(harness, "make_engine",
                        lambda *a, **k: fault(make(*a, **k)))
    res, checks = run(cell)
    assert not res["correct"], checks
    assert res["checks"]["logit_gap"]["value"] > limit()


def test_the_control_fails_the_limit(tmp_path):
    """The reference with float8 weights in the program's place reads a
    wider gap than the limit on the same prompts and served tokens, at
    four layer periods (the control's rounding grows with depth)."""
    c = granite_cell(tmp_path, periods=4)
    params = harness.make_weights(c, 11)
    rng = np.random.default_rng(0)
    ref = compare.Reference(c.family, c.config["published"], control=True)
    worst = 0.0
    for _ in range(6):
        prompt = rng.integers(0, 512, 80).tolist()
        served = rng.integers(0, 512, 40).tolist()
        _, ctrl = ref.gaps(params, prompt, served)
        worst = max(worst, float(ctrl.max()))
    assert worst > limit()


# ------------------------------------------------- readers on a made-up run
def _made_up_run(c):
    """A traced run of two ticks: 10 ms of decode program operations (two
    overlapping), 5 ms of a chunk, in a 40-ms traced span."""
    ms = 1e6
    ops = [(0, 4, "_decode_and_sample"), (2, 6, "_decode_and_sample"),
           (8, 12, "_decode_and_sample"), (14, 19, "_chunk_and_splice")]
    tr = trace_reduce.Trace(
        start=np.asarray([a * ms for a, _, _ in ops]),
        end=np.asarray([b * ms for _, b, _ in ops]),
        op=["fusion.1"] * len(ops), module=[m for _, _, m in ops])
    ticks = [harness.Tick(0.0, 0.02, decode_ctx=[3000, 7000], traced=True,
                          chunks=[(0, 1024, False)]),
             harness.Tick(0.02, 0.04, decode_ctx=[3001, 7001], traced=True)]
    r = harness.Run(c, 0.0, 0.04, [], ticks,
                    {"prefill_tokens_computed": 1024, "prefill_time_s": 0.005,
                     "decode_time_s": 0.01, "decode_steps": 2},
                    {"kv_block_size": 16}, PEAKS, trace=tr, trace_lo=0.0,
                    trace_hi=40 * ms)
    return r


def test_the_five_readers_on_a_made_up_run():
    c = harness.cell(harness.load_manifest(), CELL)
    r = _made_up_run(c)
    got = {n: c.readers[n].read(r) for n in READERS}
    assert got["prefill_ms_per_ktok.longdoc"] == pytest.approx(5.0 / 1.024)
    assert got["decode_step_ms.longdoc"] == pytest.approx(5.0)
    assert got["device_idle_share.longdoc"] == pytest.approx(100 * 25 / 40)
    pub = c.config["published"]
    flops = bytes_ = 0.0
    for t in r.ticks:
        f, b = c.family.decode_work(pub, t.decode_ctx, 16)
        flops, bytes_ = flops + f, bytes_ + b
    least = max(flops / PEAKS["bf16_flops"], bytes_ / PEAKS["hbm_bytes_per_s"])
    # the decode program's device time is the union of its operations: 10 ms
    assert got["decode_roofline.longdoc"] == pytest.approx(
        100 * least / 0.010)
    wall = sum(t.t1 - t.t0 for t in r.ticks)
    fl = sum(c.family.model_flops(pub, t.decode_ctx, 2) for t in r.ticks) \
        + c.family.model_flops(pub, range(1, 1025), 0)
    assert got["step_mfu.longdoc"] == pytest.approx(
        100 * fl / (wall * PEAKS["bf16_flops"]))
    # nothing to read: no trace, no decode program in it
    r.trace = None
    assert c.readers["decode_roofline.longdoc"].read(r) is None
    assert c.readers["device_idle_share.longdoc"].read(r) is None


def test_decode_work_counts_weights_state_and_live_kv():
    fam = harness.load_module(harness.BENCH / "configs"
                              / "granite_hybrid_lm.py")
    pub = json.loads((harness.BENCH / "configs" / "granite-4.0-h-micro.json")
                     .read_text())["published"]
    f1, b1 = fam.decode_work(pub, [100], 16)
    f2, b2 = fam.decode_work(pub, [100, 100], 16)
    state = fam.state_bytes(pub)
    assert state == 36 * (4 * 64 * 64 * 128 + 2 * 3 * 4352)   # 75.5 MB
    kv_100 = 4 * 2 * 8 * 64 * 2 * 112          # 7 blocks of 16 tokens
    assert b2 - b1 == pytest.approx(2 * state + kv_100)
    assert 6.3e9 < b1 - 2 * state - kv_100 < 6.5e9            # the weights
    assert f2 > f1 > 2 * 3.0e9
