"""The harness's whole run (set-up, window, drain, comparison) through
reduced configurations on the CPU, called as a function; and the
comparison seeing faults of the timed path and the control."""
import json
import shutil
import time

import jax
import numpy as np
import pytest

from bench import compare, harness

PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}
SMALL_DENSE = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=4,
                   num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                   vocab_size=512)
SMALL_SSM = dict(hidden_size=64, intermediate_size=128, state_size=4,
                 time_step_rank=8, num_hidden_layers=2, vocab_size=512)


#: cells the tests run that BENCHMARK.json does not hold: a configuration
#: kept under bench/configs, with a mix written here (test sizes are set
#: by ``small_cell``)
EXTRA = {"falcon-mamba-7b-32l.chat-bursty": {
    "config": "falcon-mamba-7b-32l", "traffic": "chat-bursty",
    "mix": {"kind": "open_loop", "pool_seed": 1202, "ramp_s": 1,
            "arrivals": {"process": "gamma", "cv": 2.0, "rate_per_s": 4.0},
            "prompt": {"dist": "lognormal", "median": 40, "sigma": 0.8,
                       "min": 4, "max": 120},
            "output": {"dist": "lognormal", "median": 12, "sigma": 0.9,
                       "min": 4, "max": 40}}}}


def limit_of(config: str) -> float:
    conf = json.loads((harness.BENCH / "configs" / f"{config}.json")
                      .read_text())["correct"]
    return conf["max_logit_gap"]


def small_cell(root, workload: str, layers: int | None = None):
    """The cell at a size a test can hold: its configuration's widths and
    depth cut, its mix shortened; the limit is the configuration's own.
    ``layers`` sets the depth (the control's rounding grows with it)."""
    for d in ("configs", "traffic", "metrics"):
        shutil.copytree(harness.BENCH / d, root / "bench" / d)
    man = harness.load_manifest()
    if workload in EXTRA:
        x = EXTRA[workload]
        (root / "bench" / "traffic" / f"{x['traffic']}.json").write_text(
            json.dumps(x["mix"]))
        body = json.loads((harness.BENCH / "configs" / f"{x['config']}.json")
                          .read_text())
        man["configs"].append({"name": x["config"], "source": body["source"],
                               "file": f"bench/configs/{x['config']}.json",
                               "reduced": body["reduced"], "why": "a test"})
        man["workloads"].append({"name": workload, "config": x["config"],
                                 "traffic": x["traffic"], "chips": 1,
                                 "why": "a test cell"})
        for m in man["end_to_end"]:
            if m["name"] in ("ttft_p95_ms", "tbt_p99_ms"):
                m["workloads"].append(workload)
    w = {x["name"]: x for x in man["workloads"]}[workload]
    p = root / "bench" / "configs" / f"{w['config']}.json"
    cfg = json.loads(p.read_text())
    if cfg["family"] == "dense_lm":
        cfg["published"].update(SMALL_DENSE)
        cfg["overrides"].update(num_layers=4, d_model=64, d_ff=128,
                                num_heads=4, num_kv_heads=2, head_dim=16,
                                vocab_size=512)
        cfg["engine"].update(kv_blocks=48)
    else:
        cfg["published"].update(SMALL_SSM)
        cfg["overrides"].update(num_layers=2, d_model=64, d_inner=128,
                                d_state=4, dt_rank=8, vocab_size=512,
                                scan_chunk=16)
    if layers:
        cfg["published"]["num_hidden_layers"] = layers
        cfg["overrides"]["num_layers"] = layers
    cfg["engine"].update(slots=3, max_len=192, min_bucket=32, max_bucket=64,
                         max_prefill_batch=2)
    cfg["correct"].update(sample_tokens=120)
    p.write_text(json.dumps(cfg))
    mp = root / "bench" / "traffic" / f"{w['traffic']}.json"
    mix = json.loads(mp.read_text())
    mix.update(prompt=dict(mix["prompt"], median=40, min=4, max=120),
               output=dict(mix["output"], median=12, min=4, max=40), ramp_s=1)
    if "shared_prefix" in mix:
        mix["shared_prefix"] = {"count": 2, "tokens": 32}
    if mix["kind"] == "open_loop":
        if workload not in EXTRA:
            mix["arrivals"]["rate_per_s"] = 3.0
        mix["pool"] = 200
    else:
        mix["pool"] = 200
    mp.write_text(json.dumps(mix))
    return harness.cell(man, workload, root=root)


def run(c, seed=2**31 + 5, trace=False, runs_dir=None):
    return harness.run_cell(c, seed, 3.0, trace=trace, peaks=PEAKS,
                            t_proc=time.perf_counter(),
                            compile_cache=False, log=lambda *_: None,
                            runs_dir=runs_dir)


@pytest.fixture(scope="module")
def chat(tmp_path_factory):
    return small_cell(tmp_path_factory.mktemp("chat"),
                      "qwen3-0.6b.chat-shared-prefix")


def test_chat_run_is_correct_and_reports_its_metrics(chat):
    res, checks = run(chat)
    assert res["correct"], checks
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"ttft_p95_ms", "setup_s"}
    assert list(res)[-1] == "checks"
    assert res["device"]["platform"] == "cpu"


def test_traced_run_reports_per_layer_metrics(chat, tmp_path):
    res, _ = run(chat, seed=3, trace=True, runs_dir=tmp_path)
    assert res["correct"]
    m = res["metrics"]
    for k in ("submit_lag_p99_ms", "queue_wait_p95_ms", "prefix_reuse_share",
              "decode_step_ms", "prefill_ms_per_ktok.chat", "step_mfu.chat"):
        assert k in m, k
    assert m["prefix_reuse_share"]["value"] > 0
    assert res["device"]["window_s"] > 0 and "breakdown" in res


@pytest.mark.parametrize("workload", ["falcon-mamba-7b-32l.chat-bursty",
                                      "qwen3-0.6b.offline-long-prompt"])
def test_other_cells_run_correct(tmp_path, workload):
    res, checks = run(small_cell(tmp_path, workload))
    assert res["correct"], checks
    assert res["failed"] == 0


def test_traced_span_starts_where_the_mix_says(chat, tmp_path, monkeypatch):
    """A mix's ``trace_at_s`` places the profiled span inside the window;
    the traced ticks lie in it."""
    c = harness.Cell(**{**chat.__dict__,
                        "mix": dict(chat.mix, trace_at_s=0.25)})
    runs = []
    serve = harness.serve
    monkeypatch.setattr(harness, "serve",
                        lambda *a, **k: runs.append(serve(*a, **k)) or runs[-1])
    res, _ = run(c, seed=9, trace=True, runs_dir=tmp_path)
    assert res["correct"] and res["device"]["window_s"] > 0
    r = runs[0]
    tt = r.traced_ticks()
    assert tt and r.open + 0.25 <= tt[0].t0 < r.open + 1.0
    assert tt[-1].t1 < r.close - 0.5


def _dropped(engine, every=4):
    """A request dropped where it is taken in: every few submissions one
    is never queued, so it never gets a token."""
    submit, n = engine.submit, [0]

    def bad_submit(req):
        n[0] += 1
        if n[0] % every:
            submit(req)
    engine.submit = bad_submit
    return engine


def test_a_request_never_served_is_not_correct(chat, monkeypatch):
    make = harness.make_engine
    monkeypatch.setattr(harness, "make_engine",
                        lambda *a, **k: _dropped(make(*a, **k)))
    monkeypatch.setattr(harness, "DRAIN_S", 2.0)
    res, checks = run(chat)
    assert res["failed"] > 0 and not res["correct"], checks
    assert res["checks"]["unserved"]["value"] == res["failed"]


def _altered_token(engine, every=5):
    """A token altered where it is produced: each few ticks one running
    request's newest token is replaced before it is fed back."""
    step, n = engine.step, [0]

    def bad_step():
        step()
        n[0] += 1
        if n[0] % every == 0:
            for r in engine.requests:
                if r is not None and len(r.generated) > 1:
                    r.generated[-1] = (r.generated[-1] + 1) % 512
                    break
    engine.step = bad_step
    return engine


def _state_unchanged(engine):
    """A decode step that returns the state it was given: the new token's
    cache write and recurrent update are lost."""
    good = engine._decode_and_sample
    engine._decode = jax.jit(
        lambda p, t, s, *rest: (good(p, t, s, *rest)[0], s))
    return engine


@pytest.mark.parametrize("fault", [_altered_token, _state_unchanged],
                         ids=["token_altered", "state_unchanged"])
@pytest.mark.parametrize("workload", ["qwen3-0.6b.chat-shared-prefix",
                                      "falcon-mamba-7b-32l.chat-bursty"])
def test_a_broken_timed_path_is_not_correct(tmp_path, monkeypatch, fault,
                                            workload):
    c = small_cell(tmp_path, workload)
    make = harness.make_engine
    monkeypatch.setattr(harness, "make_engine",
                        lambda *a, **k: fault(make(*a, **k)))
    res, checks = run(c)
    assert not res["correct"], checks
    assert res["checks"]["logit_gap"]["value"] > limit_of(c.config["name"])


@pytest.mark.parametrize("workload", ["qwen3-0.6b.chat-shared-prefix",
                                      "falcon-mamba-7b-32l.chat-bursty"])
def test_the_control_fails_the_limit(tmp_path, workload):
    """The reference at the precision below the configuration's (float8
    weights), in the program's place, reads a wider gap than the limit on
    the same prompts and served tokens.  At 16 layers: the limit was set
    at the cells' depth, and the control's rounding grows with depth."""
    c = small_cell(tmp_path, workload, layers=16)
    pub = c.config["published"]
    params = harness.make_weights(c, 11)
    rng = np.random.default_rng(0)
    ref = compare.Reference(c.family, pub, control=True)
    worst = 0.0
    for _ in range(12):
        prompt = rng.integers(0, 512, 80).tolist()
        served = rng.integers(0, 512, 40).tolist()
        _, ctrl = ref.gaps(params, prompt, served)
        worst = max(worst, float(ctrl.max()))
    assert worst > limit_of(c.config["name"])


def test_sweep_sets_limits_and_finds_a_knee(chat):
    from bench import sweep
    params = harness.make_weights(chat, 4)
    engine = harness.make_engine(chat, params)
    engine.warmup()
    lines = []
    knee, limits = sweep.sweep(chat, engine, 4, 3.0, [1.0, 40.0],
                               unloaded=1.5, out=lines.append)
    rows = [json.loads(x) for x in lines]
    assert rows[0]["unloaded"] and limits["ttft_ms"] > 0 < limits["tbt_ms"]
    assert [r["rate"] for r in rows[1:3]] == [1.0, 40.0]
    assert rows[2]["due"] > rows[1]["due"]
    assert rows[-1] == {"knee": knee, "limits": limits}


def test_control_readings_on_a_small_cell(tmp_path):
    """bench/control.py's loop: the program's gap and the control's, per
    seed, on the sample a run would compare."""
    from bench import control
    c = small_cell(tmp_path, "falcon-mamba-7b-32l.chat-bursty", layers=16)
    lines = control.readings(c, [5, 6], 3.0, out=lambda *_: None)
    assert [x["seed"] for x in lines] == [5, 6]
    for x in lines:
        assert x["tokens"] >= 100 and x["requests"] >= 1
        assert x["logit_gap"] <= limit_of(c.config["name"])
        assert x["control_gap"] > x["logit_gap"]
