"""The trace reduction: busy union, kernel time by name, idle gaps by the
host span that covers them; on made-up traces and on a small trace
recorded on a TPU v5e (``bench/tests/record_trace.py``)."""
from pathlib import Path

import numpy as np
import pytest

from bench import trace_reduce as tr

TESTDATA = Path(__file__).resolve().parent.parent / "testdata"
SMALL = TESTDATA / "v5e_small.xplane.pb"


def made_up():
    # ops (ns): two overlapping, one alone, one starting before the window
    ops = [("%fusion.1 = f32[8] fusion()", 0, 40, "_prefill"),
           ("%fusion.2 = f32[8] fusion()", 100, 250, "_decode"),
           ('%closed_call.7 = f32[8] custom-call(), '
            'custom_call_target="tpu_custom_call"', 200, 300, "_decode"),
           ("%while.3 = (f32[8]) while()", 500, 600, "_decode"),
           ("%fusion.3 = f32[8] fusion()", 510, 590, "_decode")]
    host = [("step", 90, 320), ("decode", 95, 310), ("step", 480, 610),
            ("wait_arrival", 320, 470)]
    return tr.Trace(start=np.array([float(o[1]) for o in ops]),
                    end=np.array([float(o[2]) for o in ops]),
                    op=[o[0] for o in ops], module=[o[3] for o in ops],
                    host=host)


def test_busy_is_the_union_clipped_to_the_window():
    t = made_up()
    assert tr.busy_ns(t, 0, 1000) == 40 + 200 + 100
    assert tr.busy_ns(t, 20, 550) == 20 + 200 + 50
    assert tr.union(np.array([0., 5., 20.]), np.array([10., 8., 30.])) == \
        [(0.0, 10.0), (20.0, 30.0)]


def test_kernel_time_by_program_and_target():
    t = made_up()
    assert tr.kernel_ns(t, r"_decode", "tpu_custom_call", 0, 1000) == \
        (100.0, 1)
    assert tr.kernel_ns(t, r"_prefill", "tpu_custom_call", 0, 1000) == \
        (0.0, 0)
    # only operations that start inside the window count
    assert tr.kernel_ns(t, r"", "tpu_custom_call", 250, 1000) == (0.0, 0)


def test_top_ops_count_loop_bodies_not_loops():
    top = dict(tr.top_ops(made_up(), 0, 1000))
    assert top == {"_decode/fusion": pytest.approx(230e-9),
                   "_decode/closed_call:tpu_custom_call":
                       pytest.approx(100e-9),
                   "_prefill/fusion": pytest.approx(40e-9)}
    assert tr.label("%while.3 = (f32[8]) while()") == "while"
    assert tr.module_name("jit__decode_and_sample(6393501149)") == \
        "_decode_and_sample"


def test_idle_gaps_go_to_the_innermost_covering_span():
    t = made_up()
    # idle: 40-100 (none to 90, step to 95, decode), 300-500 (decode to
    # 310, step to 320, wait_arrival to 470, none to 480, step), 600-700
    # (step to 610, then none)
    gaps = dict(tr.idle_gaps(t, 0, 700))
    assert gaps == {"(none)": pytest.approx((50 + 10 + 90) * 1e-9),
                    "step": pytest.approx((5 + 10 + 20 + 10) * 1e-9),
                    "decode": pytest.approx((5 + 10) * 1e-9),
                    "wait_arrival": pytest.approx(150e-9)}


def test_recorded_v5e_trace():
    t = tr.load(SMALL, host_names={"step", "decode", "wait_arrival"})
    # the host's clock runs about a millisecond ahead of the device's
    assert -5e6 < t.skew_ns < 0
    steps = sorted(t.spans("step"))
    assert len(steps) == 5 and len(t.spans("wait_arrival")) == 5
    lo, hi = steps[0][0], steps[-1][1]
    busy = tr.busy_ns(t, lo, hi)
    assert 0 < busy < hi - lo
    # each tick runs the Pallas kernel once, on the device inside its step
    ns, calls = tr.kernel_ns(t, r"lambda", "tpu_custom_call", lo, hi)
    assert calls == 5 and 0 < ns < busy
    for a, b in steps:
        assert tr.kernel_ns(t, r"", "tpu_custom_call", a, b)[1] == 1
    # the sleeps between ticks are idle, and go to wait_arrival
    waits = sum(b - a for a, b in t.spans("wait_arrival") if b <= hi)
    gaps = dict(tr.idle_gaps(t, lo, hi))
    assert gaps["wait_arrival"] == pytest.approx(waits * 1e-9, rel=0.01)
    assert sum(gaps.values()) == pytest.approx((hi - lo - busy) * 1e-9)
