"""The engine's host phases in a traced run: the device's idle time per
traced tick, by the engine span innermost over it.

The harness reduces the trace with its own host spans only
(``harness.HOST_SPANS``), so idle time inside a tick that no program call
covers lands under ``step``.  The engine opens every stretch of a tick as
a span (``serve/engine.py``, ``ServeEngine.step``); this module re-reads
the traced run's profile with those names too and gives the same idle
gaps (``trace_reduce.idle_gaps``: the innermost span wins) to the
scheduler's and the KV manager's phases.  It is idle time, not span
length: host work that overlaps a running program does not count.

A program without these spans (no ``tick`` in the profile) reads None.
"""
from __future__ import annotations

from . import harness, trace_reduce

#: the scheduler's host phases of a tick
SCHEDULER = ("schedule", "decode_prep", "retire", "tick_stats")
#: the KV manager's entry points on the tick path, nested in the above
KV_MANAGER = ("kv_admit", "kv_extend", "kv_publish", "kv_release")
#: the whole tick: idle with it (or the harness's ``step``) innermost is
#: time no phase accounts for
TICK = "tick"
NAMES = (TICK,) + SCHEDULER + KV_MANAGER


def idle_by_span(run) -> dict | None:
    """Idle seconds of the traced window by innermost span, the engine's
    spans loaded beside the harness's.  None without a trace, without the
    engine's spans in it, or when the profile on disk is not the one the
    run reduced (another skew or another number of operations)."""
    if run.trace is None:
        return None
    if "engine_idle" in run.info:
        return run.info["engine_idle"]
    out = None
    try:
        path = trace_reduce.find_xplane(harness.RUNS
                                        / f"trace-{run.cell.name}")
    except FileNotFoundError:
        path = None
    if path is not None:
        names = set(harness.HOST_SPANS) | set(NAMES)
        tr = trace_reduce.load(path, host_names=names)
        same = tr.skew_ns == run.trace.skew_ns \
            and len(tr.op) == len(run.trace.op)
        if same and tr.spans(TICK):
            out = dict(trace_reduce.idle_gaps(tr, run.trace_lo, run.trace_hi,
                                              k=len(names) + 1))
    run.info["engine_idle"] = out
    return out


def host_ms_per_tick(run, names) -> float | None:
    """Idle ms per traced tick with one of ``names`` innermost."""
    idle = idle_by_span(run)
    ticks = len(run.traced_ticks())
    if idle is None or not ticks:
        return None
    return 1e3 * sum(idle.get(n, 0.0) for n in names) / ticks


def sched_host_ms_per_tick(run):
    return host_ms_per_tick(run, SCHEDULER)


def kv_host_ms_per_tick(run):
    return host_ms_per_tick(run, KV_MANAGER)
