"""Observability-layer tests: tracer ring/export semantics, histogram math,
Timed sync discipline, drift arithmetic, and the engine's trace schema —
valid Chrome trace-event JSON with per-track monotonic timestamps, nested
request spans, stable request ids across the lifecycle, and deterministic
event sequences under a fixed seed, across all three state families."""
import json
import math

import jax
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.models import build_model
from repro.obs import (LEDGER_SCHEMA_VERSION, OBS_SCHEMA_VERSION,
                       PROGRAMS_SCHEMA_VERSION, Counter, Gauge, Histogram,
                       MetricsRegistry, ProgramRegistry, Timed, Tracer,
                       append_record, read_ledger, trend_check)
from repro.obs import ledger as ledger_mod
from repro.obs.drift import (PHASES, drift_report, geomean, plan_predictions,
                             residual_factor)
from repro.obs.trace import Section
from repro.serve.engine import Request, ServeEngine

ARCHS = ("qwen3-0.6b", "recurrentgemma-2b", "falcon-mamba-7b")


def _tiny_model(arch="qwen3-0.6b", layers=2):
    cfg = reduced_config(arch)
    cfg = cfg.replace(num_layers=max(layers, len(cfg.block_pattern)))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _trace(cfg, n=4, max_new=3, seed=5):
    rng = np.random.RandomState(seed)
    return [Request(rid=i,
                    prompt=rng.randint(1, cfg.vocab_size, 3 + 5 * i).tolist(),
                    max_new_tokens=max_new)
            for i in range(n)]


# ------------------------------------------------------------------- tracer
def test_tracer_ring_overflow_drops_oldest():
    tr = Tracer(capacity=4)
    for i in range(7):
        tr.instant(f"e{i}", 0, float(i))
    assert len(tr) == 4
    assert tr.dropped == 3
    names = [e[1] for e in tr.events()]
    assert names == ["e3", "e4", "e5", "e6"]     # oldest three fell out
    doc = tr.to_chrome()
    assert doc["otherData"]["dropped_events"] == 3
    tr.clear()
    assert len(tr) == 0 and tr.dropped == 0


def test_tracer_disabled_emits_nothing():
    tr = Tracer(enabled=False)
    tr.span("s", 0, 0.0, 1.0)
    tr.counter("c", 0.0, (("a", 1),))
    assert len(tr) == 0
    doc = tr.to_chrome()
    # still a valid (empty) document: process metadata only
    assert all(e["ph"] == "M" for e in doc["traceEvents"])


def test_tracer_chrome_export_shape():
    tr = Tracer()
    tr.set_track(1, "slot 0")
    t0 = tr.now()
    tr.begin("req 7", 1, t0, (("rid", 7),))
    tr.span("prefill", 1, t0 + 0.001, t0 + 0.002, (("rid", 7),))
    tr.counter("queue_depth", t0 + 0.001, (("queued", 3),))
    tr.end("req 7", 1, t0 + 0.003, (("rid", 7), ("tokens", 4)))
    doc = json.loads(tr.dumps(other_data={"extra": 1}))
    assert doc["displayTimeUnit"] == "ms"
    assert doc["otherData"]["extra"] == 1
    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    assert {m["name"] for m in meta} == {
        "process_name", "thread_name", "thread_sort_index"}
    x = next(e for e in evs if e["ph"] == "X")
    assert x["dur"] == pytest.approx(1000.0)     # 1 ms in microseconds
    assert x["args"]["rid"] == 7
    inst = [e for e in evs if e["ph"] == "i"]
    assert all(e["s"] == "t" for e in inst) or not inst
    c = next(e for e in evs if e["ph"] == "C")
    assert c["args"] == {"queued": 3}
    # B/E pair well ordered
    b = next(e for e in evs if e["ph"] == "B")
    e_ = next(e for e in evs if e["ph"] == "E")
    assert b["ts"] <= e_["ts"] and e_["args"]["tokens"] == 4


def test_tracer_export_sorted_even_with_late_spans():
    """X spans are emitted at t1 but stamped at t0 — export must re-sort so
    every track reads monotonically."""
    tr = Tracer()
    tr.instant("late", 0, 10.0)
    tr.span("early", 0, 1.0, 2.0)      # emitted after, starts before
    ts = [e[3] for e in tr.events()]
    assert ts == sorted(ts)


# ------------------------------------------------------------------ metrics
def test_histogram_bucket_edges_and_quantiles():
    h = Histogram("lat", base=1.0, nbuckets=8, unit="s")
    # bucket 0: below base; bucket i: [2**(i-1), 2**i)
    assert h.bucket_of(0.5) == 0
    assert h.bucket_of(1.0) == 1
    assert h.bucket_of(1.99) == 1
    assert h.bucket_of(2.0) == 2
    assert h.bucket_of(2.0 ** 30) == 7          # clamped to last bucket
    for v in (1.0, 1.5, 3.0, 3.5):
        h.record(v)
    assert h.count == 4
    assert h.mean == pytest.approx(9.0 / 4)
    assert h.min == 1.0 and h.max == 3.5
    # quantiles clamp to the exact envelope
    assert h.quantile(0.0) == 1.0
    assert h.quantile(1.0) == 3.5
    assert 1.0 <= h.quantile(0.5) <= 3.5
    d = h.to_dict()
    assert d["count"] == 4 and d["buckets"] == {"1": 2, "2": 2}
    with pytest.raises(ValueError):
        h.quantile(1.5)
    with pytest.raises(ValueError):
        Histogram("bad", base=0.0)


def test_registry_get_or_create_and_versioned_dict():
    reg = MetricsRegistry()
    reg.counter("waste", unit="tokens").inc(3)
    reg.counter("waste").inc(2)
    reg.histogram("ttft_s").record(0.5)
    assert reg.counter("waste").value == 5
    d = reg.to_dict()
    assert d["version"] == OBS_SCHEMA_VERSION
    assert d["counters"]["waste"] == {"unit": "tokens", "value": 5}
    assert d["histograms"]["ttft_s"]["count"] == 1


def test_counter_basics():
    c = Counter("n", unit="x")
    c.inc()
    c.inc(4)
    assert c.to_dict() == {"unit": "x", "value": 5}


# ------------------------------------------------------------------- timing
def _double(v):
    return v * 2


def _incr(v):
    return v + 1


_jit_double = jax.jit(_double)
_jit_incr = jax.jit(_incr)


def test_timed_syncs_device_work_before_stamping():
    x = _jit_double(np.arange(8.0))
    with Timed("section") as tm:
        out = tm.sync(_jit_incr(x))
    assert tm.synced
    assert tm.dur >= 0.0 and tm.t1 >= tm.t0
    np.testing.assert_array_equal(np.asarray(out), np.arange(8.0) * 2 + 1)


def test_timed_unsynced_section_is_visible():
    with Timed("section") as tm:
        pass
    assert not tm.synced


# -------------------------------------------------------------------- drift
def test_drift_arithmetic():
    assert geomean([2.0, 8.0]) == pytest.approx(4.0)
    with pytest.raises(ValueError):
        geomean([])
    assert residual_factor(4.0, 4.0) == pytest.approx(1.0)
    assert residual_factor(8.0, 4.0) == pytest.approx(2.0)
    assert residual_factor(2.0, 4.0) == pytest.approx(2.0)   # symmetric
    rep = drift_report({"decode_step_s": 1e-3}, {"decode_step_s": 2e-3})
    ph = rep["phases"]["decode_step_s"]
    assert ph["ratio"] == pytest.approx(2.0)
    assert rep["max_residual_factor"] >= 1.0
    assert drift_report({}, {}) == {}


def test_engine_drift_section_uses_shared_arithmetic():
    from repro.launch.serve import build_engine
    cfg, _, params = _tiny_model()
    engine = build_engine(cfg, params, slots=2, max_len=64, max_bucket=32,
                          policy="auto")
    engine.run(_trace(cfg))
    p = engine.stats.summary()["placement"]
    drift = p["drift"]
    assert set(drift["phases"]) == set(PHASES)
    for ph, rec in drift["phases"].items():
        assert rec["predicted"] == plan_predictions(p)[ph]
        assert rec["residual_factor"] == pytest.approx(
            residual_factor(rec["ratio"], 1.0))


# ----------------------------------------------------------- engine schema
def _run_traced(arch, seed=5, enabled=True, max_new=3):
    cfg, model, params = _tiny_model(arch)
    tracer = Tracer(enabled=enabled)
    engine = ServeEngine(model, params, slots=2, max_len=64, buckets=(8, 16),
                         prefill_chunk=8, tracer=tracer)
    engine.run(_trace(cfg, seed=seed, max_new=max_new))
    return engine


def _by_track(events):
    tracks: dict = {}
    for e in events:
        if e["ph"] != "M":
            tracks.setdefault(e["tid"], []).append(e)
    return tracks


@pytest.mark.parametrize("arch", ARCHS)
def test_engine_trace_schema(arch):
    engine = _run_traced(arch)
    doc = json.loads(engine.tracer.dumps())
    evs = doc["traceEvents"]

    # track metadata covers every tid in use
    named = {e["tid"] for e in evs if e["ph"] == "M"
             and e["name"] == "thread_name"}
    assert {e["tid"] for e in evs} <= named
    names = {e["args"]["name"] for e in evs
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert {"requests", "slot 0", "slot 1", "engine"} <= names

    # per-track monotonic timestamps
    for tid, track in _by_track(evs).items():
        ts = [e["ts"] for e in track]
        assert ts == sorted(ts), f"track {tid} not monotonic"

    # request spans nest: balanced B/E per slot track, E follows its B
    for tid, track in _by_track(evs).items():
        depth = 0
        for e in track:
            if e["ph"] == "B":
                depth += 1
                assert depth == 1       # one request resident per slot
            elif e["ph"] == "E":
                depth -= 1
                assert depth >= 0
        assert depth == 0

    # stable rids: every request's submit instant, B span, and E span agree,
    # and every per-request event carries the rid
    rids = {e["args"]["rid"] for e in evs
            if e["ph"] == "i" and e["name"] == "submit"}
    assert rids == {0, 1, 2, 3}
    for rid in rids:
        b = [e for e in evs if e["ph"] == "B" and e["args"]["rid"] == rid]
        e_ = [e for e in evs if e["ph"] == "E" and e["args"]["rid"] == rid]
        assert len(b) == 1 and len(e_) == 1
        assert b[0]["name"] == e_[0]["name"] == f"req {rid}"
        assert b[0]["tid"] == e_[0]["tid"]       # resident on one slot track
        assert b[0]["ts"] <= e_[0]["ts"]

    # counters sampled every tick, with the engine's full series vocabulary
    counters = {e["name"]: e for e in evs if e["ph"] == "C"}
    assert {"queue_depth", "slots"} <= set(counters)
    assert set(counters["slots"]["args"]) == {"busy", "free"}

    # decode spans live on the engine track
    decode = [e for e in evs if e["ph"] == "X" and e["name"] == "decode"]
    assert decode and len({e["tid"] for e in decode}) == 1


@pytest.mark.parametrize("arch", ARCHS)
def test_engine_trace_deterministic_under_seed(arch):
    """Same seed -> same event sequence (names, tracks, args) modulo
    timestamps and durations."""
    def shape(engine):
        # args keyed *_s are wall-clock durations — timing, not structure
        return [(ph, name, tid,
                 tuple((k, v) for k, v in args if not k.endswith("_s")))
                for ph, name, tid, ts, dur, args in engine.tracer.events()]
    a = shape(_run_traced(arch, seed=9))
    b = shape(_run_traced(arch, seed=9))
    assert a == b


def test_engine_trace_disabled_and_empty_paths():
    engine = _run_traced("qwen3-0.6b", enabled=False)
    assert len(engine.tracer) == 0
    doc = json.loads(engine.tracer.dumps())      # still valid JSON
    assert all(e["ph"] == "M" for e in doc["traceEvents"])
    # stats stay fully populated with the tracer off
    s = engine.stats.summary()
    assert s["requests_completed"] == 4
    assert s["obs"]["histograms"]["ttft_s"]["count"] == 4

    # engine with no work: empty but well-formed trace, zero-valued obs
    cfg, model, params = _tiny_model()
    idle = ServeEngine(model, params, slots=1, max_len=32)
    json.loads(idle.tracer.dumps())
    assert idle.stats.summary()["obs"]["version"] == OBS_SCHEMA_VERSION


def test_engine_trace_stall_and_save(tmp_path):
    """A pool-starved engine emits stall instants; save_trace round-trips
    through disk with the obs summary attached."""
    cfg, model, params = _tiny_model()
    # pool of 5 blocks: two 14-token prompts hold 2 blocks each, the first
    # boundary crossing takes the last free block for slot 0 and stalls
    # slot 1 (its neighbours' blocks are referenced, so nothing is evictable)
    # until the short request retires
    engine = ServeEngine(model, params, slots=2, max_len=40, buckets=(16,),
                         kv_block_size=8, kv_blocks=5)
    rng = np.random.RandomState(2)
    prompts = [rng.randint(1, cfg.vocab_size, 14).tolist() for _ in range(2)]
    engine.run([Request(rid=0, prompt=prompts[0], max_new_tokens=6),
                Request(rid=1, prompt=prompts[1], max_new_tokens=18)])
    assert engine.stats.summary()["kv"]["decode_stalls"] > 0
    stalls = [e for e in engine.tracer.events() if e[0] == "i"
              and e[1] == "stall"]
    assert stalls and all(dict(e[5])["rid"] == 1 for e in stalls)
    out = tmp_path / "trace.json"
    engine.save_trace(out)
    doc = json.loads(out.read_text())
    assert doc["otherData"]["obs"]["version"] == OBS_SCHEMA_VERSION
    assert any(e["ph"] == "i" and e["name"] == "stall"
               for e in doc["traceEvents"])


def test_engine_prefill_waste_counter():
    cfg, model, params = _tiny_model()
    engine = ServeEngine(model, params, slots=2, max_len=32, buckets=(16,))
    engine.run([Request(rid=0, prompt=[1, 2, 3], max_new_tokens=2)])
    obs = engine.stats.summary()["obs"]
    # 3-token prompt padded to the 16 bucket: 13 wasted positions
    assert obs["counters"]["prefill_waste_tokens"]["value"] == 13
    assert obs["histograms"]["decode_tick_s"]["count"] == \
        engine.stats.decode_steps
    assert obs["histograms"]["tokens_per_tick"]["count"] == \
        engine.stats.decode_steps


# ------------------------------------------------------------------- gauges
def test_gauge_last_write_wins_and_registry_section():
    g = Gauge("pool", unit="bytes")
    g.set(100)
    g.set(42.5)
    assert g.to_dict() == {"unit": "bytes", "value": 42.5}
    reg = MetricsRegistry()
    reg.gauge("kv_pool_bytes", "bytes").set(4096)
    assert reg.gauge("kv_pool_bytes").value == 4096  # get-or-create
    d = reg.to_dict()
    assert d["version"] == OBS_SCHEMA_VERSION >= 2  # v2 added gauges
    assert d["gauges"]["kv_pool_bytes"] == {"unit": "bytes", "value": 4096.0}


# --------------------------------------------------------------- prometheus
def test_prometheus_exposition_counters_and_gauges():
    reg = MetricsRegistry()
    reg.counter("prefill_waste_tokens", "tokens").inc(13)
    reg.gauge("kv_pool_bytes", "bytes").set(6144)
    text = reg.to_prometheus()
    lines = text.splitlines()
    assert text.endswith("\n")
    assert "# TYPE repro_serve_prefill_waste_tokens_total counter" in lines
    assert "repro_serve_prefill_waste_tokens_total 13" in lines
    assert "# TYPE repro_serve_kv_pool_bytes gauge" in lines
    assert "repro_serve_kv_pool_bytes 6144" in lines
    # HELP lines carry the unit
    assert "# HELP repro_serve_kv_pool_bytes (bytes)" in lines


def test_prometheus_exposition_histogram_cumulative_buckets():
    reg = MetricsRegistry()
    h = reg.histogram("tick", base=1.0, nbuckets=8, unit="s")
    for v in (0.5, 1.5, 1.7, 3.0):
        h.record(v)
    lines = reg.to_prometheus(prefix="x").splitlines()
    buckets = [ln for ln in lines if ln.startswith("x_tick_bucket")]
    # bucket 0 (le=1): 1 sample; bucket 1 (le=2): +2; bucket 2 (le=4): +1
    assert buckets == ['x_tick_bucket{le="1"} 1',
                       'x_tick_bucket{le="2"} 3',
                       'x_tick_bucket{le="4"} 4',
                       'x_tick_bucket{le="+Inf"} 4']
    assert "x_tick_sum 6.7" in lines
    assert "x_tick_count 4" in lines
    assert "# TYPE x_tick histogram" in lines


def test_prometheus_name_sanitization():
    reg = MetricsRegistry()
    reg.counter("kv.blocks-copied", "blocks").inc(1)
    text = reg.to_prometheus()
    assert "repro_serve_kv_blocks_copied_total 1" in text
    # empty prefix + leading digit gets a guard underscore
    reg2 = MetricsRegistry()
    reg2.gauge("2fast").set(1)
    assert "_2fast 1" in reg2.to_prometheus(prefix="")


# ----------------------------------------------------- memory normalization
def test_normalize_memory_analysis_shapes():
    from repro.utils.hlo import normalize_memory_analysis

    class Stats:                       # the CompiledMemoryStats shape
        temp_size_in_bytes = 100
        argument_size_in_bytes = 30
        output_size_in_bytes = 8
        generated_code_size_in_bytes = 7

    assert normalize_memory_analysis(None) == {}
    one = normalize_memory_analysis(Stats())
    assert one["temp_size_in_bytes"] == 100
    assert one["argument_size_in_bytes"] == 30
    # per-program lists sum; dict entries read the same keys; None entries
    # are skipped
    many = normalize_memory_analysis(
        [Stats(), {"temp_size_in_bytes": 11, "peak_memory_in_bytes": 5},
         None])
    assert many["temp_size_in_bytes"] == 111
    assert many["peak_memory_in_bytes"] == 5
    assert many["output_size_in_bytes"] == 8


# ----------------------------------------------------------- program registry
def jnp_ones(shape):
    return jax.numpy.ones(shape, jax.numpy.float32)


def _mm(a, b):
    return a @ b


def _sq_sum(a):
    return (a @ a.T).sum()


def test_program_registry_static_cost_and_observe():
    from repro.core.accelerators import TPU_V5E
    reg = ProgramRegistry(chip=TPU_V5E)
    fn = jax.jit(_mm)
    args = (jnp_ones((8, 16)), jnp_ones((16, 4)))
    e = reg.register("matmul", fn, args, phase="prefill", program="_prefill")
    assert e.analyzed and e.flops > 0 and e.bytes_accessed > 0
    assert e.arithmetic_intensity == pytest.approx(
        e.flops / e.bytes_accessed)
    assert e.invocations == 0 and e.measured_s == 0.0
    reg.observe("matmul", 0.25)
    reg.observe("matmul", 0.25)
    s = reg.summary()
    assert s["version"] == PROGRAMS_SCHEMA_VERSION
    p = s["programs"]["matmul"]
    assert p["invocations"] == 2 and p["measured_s"] == pytest.approx(0.5)
    assert p["flops_per_s"] == pytest.approx(2 * e.flops / 0.5)
    assert p["utilization"] == pytest.approx(
        p["flops_per_s"] / s["chip"]["peak_flops"])
    assert p["bandwidth_utilization"] == pytest.approx(
        p["bytes_per_s"] / s["chip"]["hbm_bw"])
    assert s["chip"]["peak_flops"] == TPU_V5E.peak_flops
    # a device whose peaks are unknown gets no utilization at all
    unknown = ProgramRegistry(chip=None)
    unknown.register("matmul", fn, args, phase="prefill")
    unknown.observe("matmul", 0.25)
    u = unknown.summary()
    assert u["chip"] is None
    assert u["programs"]["matmul"]["flops_per_s"] == pytest.approx(
        e.flops / 0.25)
    assert u["programs"]["matmul"]["utilization"] is None
    assert u["programs"]["matmul"]["bandwidth_utilization"] is None
    # reset_observed zeroes the dynamic side, keeps the static cost
    reg.reset_observed()
    p2 = reg.summary()["programs"]["matmul"]
    assert p2["invocations"] == 0 and p2["measured_s"] == 0.0
    assert p2["flops"] == p["flops"] and p2["analyzed"]


def test_host_chip_table_resolves_by_device_kind():
    """Peaks come from the device JAX reports: the v5e's from the table, and
    none for a device the table does not know (this CPU run's engine)."""
    from repro.core.accelerators import HOST_CHIPS, TPU_V5E, host_chip
    assert host_chip("TPU v5 lite") is TPU_V5E
    assert host_chip(jax.devices()[0].device_kind) is None
    assert all(c.peak_flops > 0 and c.hbm_bw > 0 for c in HOST_CHIPS.values())
    cfg, model, params = _tiny_model()
    engine = ServeEngine(model, params, slots=2, max_len=32, buckets=(16,))
    engine.warmup()
    engine.run([Request(rid=0, prompt=[1, 2, 3], max_new_tokens=2)])
    s = engine.stats.summary()["programs"]
    assert s["chip"] is None
    assert s["programs"]["decode"]["invocations"] > 0
    assert s["programs"]["decode"]["utilization"] is None


def test_program_registry_memory_watermarks():
    reg = ProgramRegistry()
    fn = jax.jit(_sq_sum)
    args = (jnp_ones((16, 16)),)
    e = reg.register("m", fn, args, phase="decode", memory=True)
    assert e.memory, "memory=True should AOT-compile for memory_analysis"
    assert e.memory.get("argument_size_in_bytes", 0) > 0
    assert reg.temp_bytes_peak() == e.memory.get("temp_size_in_bytes", 0)
    assert reg.summary()["programs"]["m"]["memory"] == e.memory


def test_program_registry_never_raises_into_serving():
    """A backend without cost analysis leaves the entry un-analyzed and
    serving goes on; a program that fails to lower is a fault and raises."""
    class _NoCost:
        def cost_analysis(self):
            return None

    class _Fn:
        def lower(self, *args):
            return _NoCost()

    reg = ProgramRegistry()
    e = reg.register("no_cost", _Fn(), (), phase="decode")
    assert not e.analyzed and e.flops == 0.0
    with pytest.raises(AttributeError):
        reg.register("broken", object(), (), phase="decode")
    # un-analyzed entries still accumulate observations (graceful path for
    # engines that never warmed up)
    reg.observe("never_registered", 0.1, phase="decode", program="_decode")
    s = reg.summary()
    assert s["programs"]["never_registered"]["invocations"] == 1
    assert not s["programs"]["never_registered"]["analyzed"]


def test_program_registry_cluster_rollup_attribution():
    from repro.core.accelerators import by_name
    plan = {"policies": [
        {"cluster": 2, "kinds": ["attention"], "accelerator": "pascal",
         "predicted_prefill_s": 0.03, "predicted_decode_s": 0.001},
        {"cluster": 3, "kinds": ["ffn"], "accelerator": "pavlov",
         "predicted_prefill_s": 0.01, "predicted_decode_s": 0.003},
    ]}
    reg = ProgramRegistry(plan_summary=plan)
    fn = jax.jit(_mm)
    reg.register("prefill[1x16]", fn, (jnp_ones((16, 32)), jnp_ones((32, 8))),
                 phase="prefill", program="_prefill")
    reg.register("decode", fn, (jnp_ones((4, 32)), jnp_ones((32, 8))),
                 phase="decode", program="_decode")
    reg.observe("prefill[1x16]", 0.08, phase="prefill")
    reg.observe("decode", 0.02, phase="decode")
    roll = reg.cluster_rollup()
    assert set(roll) == {"2", "3"}
    # predicted shares: prefill 3:1, decode 1:3 — measured time splits along
    # them and sums back to the phase totals
    assert roll["2"]["prefill"]["share"] == pytest.approx(0.75)
    assert roll["3"]["prefill"]["share"] == pytest.approx(0.25)
    assert roll["2"]["prefill"]["measured_s"] \
        + roll["3"]["prefill"]["measured_s"] == pytest.approx(0.08)
    assert roll["2"]["decode"]["share"] == pytest.approx(0.25)
    # ratio is measured/predicted per cluster; uniform within a phase by
    # construction (documented attribution limit)
    assert roll["2"]["prefill"]["ratio"] == pytest.approx(
        roll["3"]["prefill"]["ratio"])
    # utilization divides by the policy's own Mensa accelerator peak
    c2 = roll["2"]["prefill"]
    assert c2["utilization"] == pytest.approx(
        c2["flops_per_s"] / by_name("pascal").peak_flops)
    assert roll["2"]["accelerator"] == "pascal"
    # no plan -> no rollup -> no clusters key in the summary
    assert ProgramRegistry().cluster_rollup() == {}
    assert "clusters" not in ProgramRegistry().summary()


def test_engine_programs_cover_warmed_inventory_vs_jl006():
    """The acceptance cross-check: the cost observatory's coverage equals
    the static JL006 compile inventory — every ``self.X = jax.jit(...)`` in
    ``ServeEngine.__init__`` (the rule's definition of a program) appears as
    the ``program`` owner of at least one registered entry, with full static
    cost, and the runtime-expected name set matches exactly."""
    import ast
    import inspect

    from repro.analysis.rules.compile_inventory import _jit_value
    from repro.serve import engine as engine_mod

    tree = ast.parse(inspect.getsource(engine_mod))
    cls = next(n for n in ast.walk(tree) if isinstance(n, ast.ClassDef)
               and n.name == "ServeEngine")
    init = next(n for n in cls.body if isinstance(n, ast.FunctionDef)
                and n.name == "__init__")
    jl006 = set()
    for stmt in ast.walk(init):
        if isinstance(stmt, ast.Assign) and _jit_value(stmt.value) \
                and isinstance(stmt.targets[0], ast.Attribute):
            jl006.add(stmt.targets[0].attr)
    assert jl006 == {"_prefill", "_chunk", "_copy", "_decode",
                     "_export", "_import"}

    cfg, model, params = _tiny_model()
    engine = ServeEngine(model, params, slots=2, max_len=64, buckets=(16,),
                         kv_block_size=8, program_memory=True)
    engine.warmup()
    progs = engine.stats.summary()["programs"]["programs"]
    expected = {f"prefill[{nb}x{b}]" for b in engine.buckets
                for nb in engine.batch_buckets}
    expected |= {"chunk", "copy", "decode"}   # paged + beyond-bucket prompts
    assert set(progs) == expected
    # the handoff pair is role-gated to None on an interleaved engine; every
    # other JL006 inventory entry owns at least one registered program
    assert {p["program"] for p in progs.values()} \
        == jl006 - {"_export", "_import"}
    for name, p in progs.items():
        assert p["analyzed"], name
        assert p["flops"] > 0 and p["bytes_accessed"] > 0, name
        assert p["memory"].get("argument_size_in_bytes", 0) > 0, name
    assert engine.stats.summary()["programs"].get("temp_bytes_peak", 0) > 0

    # a role-split pair warms (and registers) each side of the handoff,
    # completing 100% coverage of the JL006 inventory
    role_progs = {}
    for role in ("prefill", "decode"):
        e = ServeEngine(model, params, slots=2, max_len=64, buckets=(16,),
                        kv_block_size=8, program_memory=True, role=role)
        e.warmup()
        role_progs[role] = e.stats.summary()["programs"]["programs"]
    exp = role_progs["prefill"]["export"]
    imp = role_progs["decode"]["import"]
    assert exp["program"] == "_export" and exp["analyzed"]
    assert imp["program"] == "_import" and imp["analyzed"]
    assert exp["bytes_accessed"] > 0 and imp["bytes_accessed"] > 0
    covered = {p["program"] for ps in role_progs.values()
               for p in ps.values()} | {p["program"] for p in progs.values()}
    assert covered == jl006


def test_engine_memory_gauges_and_device_memory_track(tmp_path):
    cfg, model, params = _tiny_model()
    engine = ServeEngine(model, params, slots=2, max_len=32, buckets=(16,),
                         kv_block_size=8)
    engine.run([Request(rid=0, prompt=[1, 2, 3], max_new_tokens=3)])
    g = engine.stats.summary()["obs"]["gauges"]
    assert g["kv_pool_capacity_bytes"]["value"] > 0
    assert g["kv_pool_bytes_peak"]["value"] > 0
    assert g["kv_pool_bytes_peak"]["value"] \
        <= g["kv_pool_capacity_bytes"]["value"]
    # block-granular accounting: peak bytes = peak blocks x block bytes
    assert g["kv_pool_bytes_peak"]["value"] == \
        engine.stats.kv_blocks_peak * engine.kv.block_bytes
    out = tmp_path / "t.json"
    engine.save_trace(out)
    doc = json.loads(out.read_text())
    mem = [e for e in doc["traceEvents"]
           if e["ph"] == "C" and e["name"] == "device_memory_bytes"]
    assert mem, "no device_memory_bytes counter track in the trace"
    assert {"slot_state", "kv_pool"} <= set(mem[0]["args"])
    assert "programs" in doc["otherData"]


# ------------------------------------------------------- one span call
def test_tracer_section_is_one_span_in_both_sinks():
    """``Tracer.section`` stamps on the tracer's clock, syncs like Timed,
    appends one X event at exit (args may be set inside the block), and
    emits nothing to the ring without a track or with the tracer off."""
    ticks = iter([1.0, 1.5, 2.0, 2.25, 3.0, 3.5])
    tr = Tracer(clock=lambda: next(ticks))      # epoch reads 1.0
    with tr.section("tick", 4, (("a", 1),)) as sec:
        out = sec.sync(_jit_incr(np.arange(3.0)))
        sec.args = (("a", 2),)
    assert isinstance(sec, Timed) and sec.synced
    assert (sec.t0, sec.t1, sec.dur) == (1.5, 2.0, 0.5)
    np.testing.assert_array_equal(np.asarray(out), np.arange(3.0) + 1)
    assert tr.events() == [("X", "tick", 4, 1.5, 0.5, (("a", 2),))]
    with tr.section("prefill", None) as sec:    # caller emits its own
        pass
    assert sec.dur == 0.75 and len(tr) == 1
    off = Tracer(enabled=False)
    with off.section("tick", 0):
        pass
    assert len(off) == 0


def test_timed_opens_no_profiler_annotation():
    """The annotation path lives in the one span call only."""
    assert not hasattr(Timed("x"), "profile")
    with pytest.raises(TypeError):
        Timed("x", profile=True)


#: the engine's host phases of a tick, and where each KV-manager entry
#: point and program call must run
SCHEDULER_PHASES = ("schedule", "decode_prep", "retire", "tick_stats")
KV_SPANS = {"admit": "kv_admit", "extend": "kv_extend",
            "publish": "kv_publish", "finish": "kv_release"}
PROGRAM_SPANS = {"_decode": "decode", "_prefill": "prefill",
                 "_chunk": "prefill_chunk", "_copy": "kv_copy"}


class _StackTracer(Tracer):
    """A tracer that keeps the names of the sections open right now."""

    def __init__(self):
        super().__init__()
        self.open: list[str] = []

    def section(self, name, tid, args=(), *, profile=False):
        return _OpenSection(self, name, tid, args, profile)


class _OpenSection(Section):
    __slots__ = ()

    def __enter__(self):
        self.tracer.open.append(self.name)
        return super().__enter__()

    def __exit__(self, *exc):
        out = super().__exit__(*exc)
        assert self.tracer.open.pop() == self.name
        return out


def _shared_prefix_requests(cfg, n=4):
    """Prompts that share 12 tokens: after the first publishes its two full
    8-token blocks, the others hit one block and copy the next (COW)."""
    rng = np.random.RandomState(3)
    head = rng.randint(1, cfg.vocab_size, 12).tolist()
    return [Request(rid=i, prompt=head + rng.randint(
                1, cfg.vocab_size, 5 + 7 * i).tolist(), max_new_tokens=4)
            for i in range(n)]


def _paged_engine(tracer=None, slots=2):
    cfg, model, params = _tiny_model()
    engine = ServeEngine(model, params, slots=slots, max_len=64,
                         buckets=(8, 16, 32), prefill_chunk=16,
                         kv_block_size=8, kv_blocks=16, tracer=tracer)
    return cfg, engine


def test_engine_calls_run_inside_their_spans():
    """Every KV-manager entry point and program call on the tick path runs
    inside its own span, nested in ``tick`` and a scheduler phase."""
    tracer = _StackTracer()
    cfg, engine = _paged_engine(tracer)
    engine.warmup()
    seen: dict[str, list] = {}

    def spy(obj, attr, key):
        fn = getattr(obj, attr)

        def wrapped(*a, **k):
            seen.setdefault(key, []).append(list(tracer.open))
            return fn(*a, **k)
        if hasattr(fn, "_cache_size"):   # the compile-count gate reads it
            wrapped._cache_size = fn._cache_size
        setattr(obj, attr, wrapped)

    for attr in KV_SPANS:
        spy(engine.kv, attr, attr)
    for attr in PROGRAM_SPANS:
        spy(engine, attr, attr)
    engine.run(_shared_prefix_requests(cfg))
    assert set(seen) == set(KV_SPANS) | set(PROGRAM_SPANS)
    want = {**KV_SPANS, **PROGRAM_SPANS}
    for key, stacks in seen.items():
        for stack in stacks:
            # ``finish`` publishes the finished sequence itself
            assert stack[-1] == want[key] or (
                key == "publish" and stack[-1] == "kv_release"), (key, stack)
            # the decode program is a phase of the tick itself
            assert stack[0] == "tick" and (stack[1] in SCHEDULER_PHASES or (
                key == "_decode" and len(stack) == 2)), (key, stack)
    assert any(st[-1] == "kv_publish" for st in seen["publish"])
    # the whole tick is covered: every span is tick or inside one
    ticks = [e for e in tracer.events() if e[1] == "tick"]
    assert len(ticks) == engine.stats.ticks
    assert sum(dict(e[5])["admitted"] for e in ticks) == 4
    assert sum(dict(e[5])["chunks"] for e in ticks) \
        == engine.stats.prefill_chunks
    for e in tracer.events():
        if e[0] == "X" and e[1] in SCHEDULER_PHASES + tuple(KV_SPANS.values()):
            assert any(t[3] <= e[3] and e[3] + e[4] <= t[3] + t[4]
                       for t in ticks), e


def test_profiled_ticks_carry_host_phases_as_annotations(tmp_path):
    """With ``profile`` set after construction, a CPU profile holds the
    engine's phases as host annotations nested under ``tick``, and each
    tick's annotation carries the ring's stamp of it (``t_us``)."""
    from jax.profiler import ProfileData
    cfg, engine = _paged_engine()
    engine.warmup()
    engine.submit(_shared_prefix_requests(cfg, n=1)[0])
    engine.step()                      # unprofiled: no annotation objects
    engine.profile = True
    jax.profiler.start_trace(str(tmp_path))
    for r in _shared_prefix_requests(cfg)[1:]:
        engine.submit(r)
    for _ in range(4):
        engine.step()
    jax.profiler.stop_trace()
    pb = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    host: dict[str, list] = {}
    for plane in ProfileData.from_file(str(pb)).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for ev in line.events:
                host.setdefault(ev.name, []).append(
                    (ev.start_ns, ev.start_ns + ev.duration_ns,
                     dict(ev.stats) if ev.name == "tick" else {}))
    assert len(host["tick"]) == 4
    for name in SCHEDULER_PHASES + ("kv_admit", "kv_extend", "kv_publish",
                                    "decode", "prefill_chunk"):
        assert name in host, name
        for a, b, _ in host[name]:
            assert any(ta <= a and b <= tb for ta, tb, _ in host["tick"]), \
                name
    ring = [e[3] for e in engine.tracer.events() if e[1] == "tick"][-4:]
    stamps = sorted(st["t_us"] for _, _, st in host["tick"])
    assert stamps == [round(engine.tracer._us(t)) for t in ring]


def test_ring_events_per_tick_stay_bounded():
    """The ring takes a few events per tick plus a few per request and per
    slot, however long the engine runs: far below the 2^19-event ring a
    benchmark run keeps whole."""
    cfg, engine = _paged_engine(slots=4)
    engine.warmup()
    engine.tracer.clear()
    rng = np.random.RandomState(0)
    reqs = [Request(rid=i, prompt=rng.randint(1, cfg.vocab_size,
                                               int(rng.randint(3, 40))
                                               ).tolist(),
                    max_new_tokens=int(rng.randint(2, 9)))
            for i in range(24)]
    engine.run(reqs)
    evs = engine.tracer.events()
    ticks = [(e[3], e[3] + e[4]) for e in evs if e[1] == "tick"]
    assert len(ticks) == engine.stats.ticks and engine.tracer.dropped == 0
    per_tick = [sum(1 for e in evs if a <= e[3] <= b) for a, b in ticks]
    # 7 spans + 5 counters a tick; per admitted slot at most 9 events
    # (B, E, kv_admit, cow_copy, kv_copy, prefill or a chunk, kv_publish,
    # kv_release, stall)
    assert max(per_tick) <= 12 + 9 * engine.slots, max(per_tick)
    # over the run: the same, with submit, and one event per chunk call
    chunks = sum(1 for e in evs if e[1] == "prefill_chunk")
    stalls = sum(1 for e in evs if e[1] == "stall")
    assert len(evs) <= 12 * len(ticks) + 9 * len(reqs) + chunks + stalls


def test_engine_kv_work_counters_in_summary_and_counter_track():
    cfg, engine = _paged_engine()
    engine.run(_shared_prefix_requests(cfg))
    kv = engine.stats.summary()["kv"]
    assert kv["reclaim_walked"] == engine.kv.reclaim_walked > 0
    assert kv["evict_scanned"] == engine.kv.evict_scanned
    samples = [dict(e[5]) for e in engine.tracer.events()
               if e[0] == "C" and e[1] == "kv_blocks"]
    assert set(samples[0]) == {"in_use", "cached", "evict_scanned",
                               "reclaim_walked"}
    # per-tick work: the samples add up to the totals
    assert sum(s["reclaim_walked"] for s in samples) == kv["reclaim_walked"]
    assert sum(s["evict_scanned"] for s in samples) == kv["evict_scanned"]
    engine.reset_stats()
    assert engine.stats.summary()["kv"]["reclaim_walked"] == 0


# -------------------------------------------------------------------- ledger
def _rec(tps, ttft, **kw):
    return ledger_mod.make_record(arch="qwen3-0.6b", tokens_per_s=tps,
                                  ttft_p50_ms=ttft, sha="abc123", **kw)


def test_ledger_append_read_roundtrip(tmp_path):
    p = tmp_path / "ledger.jsonl"
    assert read_ledger(p) == []            # missing file is an empty history
    r = _rec(1000.0, 20.0, prefix_hit_rate=0.5,
             program_utilization={"decode": 1e-5})
    assert r["version"] == LEDGER_SCHEMA_VERSION
    append_record(p, r)
    append_record(p, _rec(1100.0, 19.0))
    got = read_ledger(p)
    assert [x["tokens_per_s"] for x in got] == [1000.0, 1100.0]
    assert got[0]["program_utilization"] == {"decode": 1e-5}
    assert got[0]["git_sha"] == "abc123"
    p.write_text(p.read_text() + "{not json\n")
    with pytest.raises(ValueError, match="malformed ledger line"):
        read_ledger(p)


def test_ledger_trend_vacuous_then_binding(tmp_path):
    # fewer than MIN_HISTORY prior records: vacuously ok
    assert trend_check([]) == {"ok": True, "band": ledger_mod.DEFAULT_BAND,
                               "runs": 0, "checks": []}
    one = trend_check([_rec(1000, 20)])
    assert one["ok"] and all(c["median"] is None for c in one["checks"])
    # healthy history, healthy newcomer
    hist = [_rec(1000 + 10 * i, 20.0) for i in range(5)]
    ok = trend_check(hist + [_rec(1010, 21.0)])
    assert ok["ok"] and ok["runs"] == 6
    # the acceptance case: a synthetic regressed record fails the check
    bad_tps = trend_check(hist + [_rec(400, 20.0)])       # < half the median
    assert not bad_tps["ok"]
    failed = [c for c in bad_tps["checks"] if not c["ok"]]
    assert [c["metric"] for c in failed] == ["tokens_per_s"]
    assert failed[0]["bound"] == pytest.approx(0.5 * 1020)
    bad_ttft = trend_check(hist + [_rec(1010, 70.0)])     # latency tripled
    assert not bad_ttft["ok"]
    assert [c["metric"] for c in bad_ttft["checks"] if not c["ok"]] \
        == ["ttft_p50_ms"]
    # the window slides: 400-tps history long past stops dragging the median
    assert trend_check([_rec(400, 20)] * 3
                       + [_rec(1000, 20)] * ledger_mod.DEFAULT_WINDOW
                       + [_rec(950, 20)])["ok"]
    with pytest.raises(ValueError):
        trend_check(hist, band=0.0)


def test_ledger_cli_blocking_step(tmp_path, capsys):
    p = tmp_path / "ledger.jsonl"
    for r in [_rec(1000, 20), _rec(1010, 20), _rec(990, 21)]:
        append_record(p, r)
    assert ledger_mod.main([str(p)]) == 0
    # a near-zero band flags even ordinary run-to-run jitter
    assert ledger_mod.main([str(p), "--band", "0.001"]) == 1
    capsys.readouterr()
    append_record(p, _rec(100, 20))        # collapse: an order of magnitude
    assert ledger_mod.main([str(p)]) == 1
    out = capsys.readouterr().out
    assert '"ok": false' in out
