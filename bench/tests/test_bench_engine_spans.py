"""The engine's host phases read from a traced run (``bench/engine_spans``):
the four per-layer readers on a small cell on the CPU, and None, never a
number, where the profile on disk is not the run's or holds no engine
span."""
import dataclasses

import numpy as np
import pytest

from bench import engine_spans, harness
from bench.tests.test_bench_harness import run, small_cell

CHAT = "qwen3-0.6b.chat-shared-prefix"
READERS = ("sched_host_ms_per_tick.chat", "sched_host_ms_per_tick.offline",
           "kv_host_ms_per_tick.chat", "kv_host_ms_per_tick.offline")


def _traced(c, runs_dir, monkeypatch):
    """A traced run of ``c`` with its profile under ``runs_dir``, where
    the readers look for it: (result line, the harness's Run)."""
    monkeypatch.setattr(harness, "RUNS", runs_dir)
    runs = []
    serve = harness.serve
    monkeypatch.setattr(harness, "serve",
                        lambda *a, **k: runs.append(serve(*a, **k)) or runs[-1])
    res, _ = run(c, seed=2**31 + 11, trace=True, runs_dir=runs_dir)
    return res, runs[0]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    c = small_cell(tmp_path_factory.mktemp("cell"), CHAT)
    with pytest.MonkeyPatch.context() as mp:
        res, r = _traced(c, tmp_path_factory.mktemp("runs"), mp)
        yield c, res, r, harness.RUNS


def _read(r, name):
    return harness.load_module(harness.BENCH / "metrics" / f"{name}.py").read(r)


def test_traced_run_reports_the_engine_host_phases(traced):
    c, res, r, _ = traced
    assert res["correct"]
    m = res["metrics"]
    for k in ("sched_host_ms_per_tick.chat", "kv_host_ms_per_tick.chat"):
        assert k in m and m[k]["unit"] == "ms" and m[k]["value"] > 0, k
    assert "sched_host_ms_per_tick.offline" not in m


def test_all_four_readers_read_the_run(traced, monkeypatch):
    c, res, r, runs = traced
    monkeypatch.setattr(harness, "RUNS", runs)
    got = {n: _read(r, n) for n in READERS}
    assert all(v is not None and v > 0 for v in got.values()), got
    assert got["sched_host_ms_per_tick.chat"] \
        == got["sched_host_ms_per_tick.offline"] \
        == res["metrics"]["sched_host_ms_per_tick.chat"]["value"]
    # the per-tick idle of every phase and of the whole tick adds up to no
    # more than the idle the harness's own spans put under ``step``
    idle = engine_spans.idle_by_span(r)
    step_before = dict(map(tuple, res["breakdown"]["idle_gaps"]))["step"]
    under_engine = sum(idle.get(n, 0.0) for n in engine_spans.NAMES)
    assert under_engine + idle.get("step", 0.0) \
        == pytest.approx(step_before, rel=1e-6)
    assert set(engine_spans.SCHEDULER) <= set(idle)


def test_a_stale_or_missing_profile_reads_none(traced, monkeypatch,
                                               tmp_path):
    c, res, r, runs = traced
    monkeypatch.setattr(harness, "RUNS", runs)
    t = r.trace
    for other in (dataclasses.replace(t, skew_ns=t.skew_ns + 1),
                  dataclasses.replace(t, start=np.append(t.start, 0.0),
                                      end=np.append(t.end, 1.0),
                                      op=t.op + ["%fusion.9 = f32[8]"],
                                      module=t.module + ["_decode"])):
        stale = dataclasses.replace(r, trace=other, info={})
        assert [_read(stale, n) for n in READERS] == [None] * 4
    monkeypatch.setattr(harness, "RUNS", tmp_path)      # nothing written
    assert [_read(dataclasses.replace(r, info={}), n)
            for n in READERS] == [None] * 4
    assert _read(dataclasses.replace(r, trace=None, info={}),
                 READERS[0]) is None


def test_a_program_without_engine_spans_reports_none(traced, monkeypatch,
                                                     tmp_path):
    """A program whose tick opens no spans (the harness's own ``step``
    alone in the profile) leaves the metrics out of the line, and the run
    goes on."""
    c = traced[0]
    make = harness.make_engine

    def unspanned(*a, **k):
        e = make(*a, **k)
        e.profile = False
        return e
    monkeypatch.setattr(harness, "make_engine", unspanned)
    res, r = _traced(c, tmp_path, monkeypatch)
    assert res["correct"] and r.trace.spans("step")
    assert not {"sched_host_ms_per_tick.chat",
                "kv_host_ms_per_tick.chat"} & set(res["metrics"])
    assert "device_idle_share.chat" in res["metrics"]
