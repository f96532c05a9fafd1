"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device
numbers: busy time, kernel time, the operations that took most time, and
the device's idle gaps attributed to what the host was doing.

The trace is read with ``jax.profiler.ProfileData``.  Device operations
are the events of the ``XLA Ops`` line of a ``/device:TPU:<n>`` plane,
each inside one event of the ``XLA Modules`` line (its program); host
spans are the ``TraceAnnotation`` events on the host plane.

The two planes' clocks differ by about a millisecond on a v5e.  The k-th
program the host launched (``PJRT_LoadedExecutable_Execute``) is the k-th
program on the device, and none starts before its launch, so the host
spans are moved by the least difference between the two.

Busy time is the union of the operation intervals, clipped to the window.
An idle gap is a stretch of the window that no operation covers; each
part of it goes to the innermost host span over that part, or to
``(none)``.

A Pallas kernel shows in the trace as a ``custom-call`` with
``custom_call_target="tpu_custom_call"`` named after the function that
called it, not after the kernel, so a kernel is found by its program and
that target (``kernel_ns``).
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
LAUNCH = "PJRT_LoadedExecutable_Execute"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OP_NAME = re.compile(r"^%?([A-Za-z_][\w-]*?)(?:\.\d+)*(?:\s|=|$)")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_MODULE = re.compile(r"^(?:jit_)?(.*?)(?:\(\d+\))?$")
CONTAINERS = ("while", "conditional", "call")


@dataclass
class Trace:
    """Device operations of one chip and the host spans, in ns on the
    device's clock."""
    start: np.ndarray                     # op start times, sorted
    end: np.ndarray
    op: list[str]                         # op names (the HLO text)
    module: list[str]                     # each op's program
    host: list[tuple[str, float, float]] = field(default_factory=list)
    skew_ns: float = 0.0                  # added to the host clock

    def spans(self, name: str) -> list[tuple[float, float]]:
        return [(a, b) for n, a, b in self.host if n == name]


def _host_name(name: str) -> str:
    """A TraceAnnotation's name without the ``#key=value#`` metadata."""
    return name.split("#", 1)[0]


def module_name(event_name: str) -> str:
    """``jit__decode_and_sample(6393...)`` -> ``_decode_and_sample``."""
    return _MODULE.match(event_name).group(1)


def find_xplane(directory) -> Path:
    found = sorted(Path(directory).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def load(path, *, chip: int = 0, host_names=None) -> Trace:
    """Read one chip's operations and the host spans named in
    ``host_names`` (every host span when None), on the device's clock."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    starts, ends, ops = [], [], []
    mods: list[tuple[float, float, str]] = []
    host, launches = [], []
    for plane in pd.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) == chip:
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    mods = [(ev.start_ns, ev.start_ns + ev.duration_ns,
                             module_name(ev.name)) for ev in line.events]
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    starts.append(ev.start_ns)
                    ends.append(ev.start_ns + ev.duration_ns)
                    ops.append(ev.name)
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == LAUNCH:
                        launches.append(ev.start_ns)
                    n = _host_name(ev.name)
                    if host_names is None or n in host_names:
                        host.append((n, ev.start_ns,
                                     ev.start_ns + ev.duration_ns))
    mods.sort()
    launches.sort()
    skew = 0.0
    if mods and len(mods) == len(launches):
        skew = min(m[0] - h for m, h in zip(mods, launches))
    host = [(n, a + skew, b + skew) for n, a, b in host]
    s = np.asarray(starts, dtype=np.float64)
    order = np.argsort(s, kind="stable")
    mstart = [m[0] for m in mods]
    module = []
    for i in order:
        k = bisect.bisect_right(mstart, s[i]) - 1
        module.append(mods[k][2] if k >= 0 and s[i] <= mods[k][1] else "")
    return Trace(start=s[order], end=np.asarray(ends, np.float64)[order],
                 op=[ops[i] for i in order], module=module, host=host,
                 skew_ns=skew)


def _clip(tr: Trace, lo: float, hi: float):
    keep = (tr.end > lo) & (tr.start < hi)
    idx = np.nonzero(keep)[0]
    return idx, np.maximum(tr.start[idx], lo), np.minimum(tr.end[idx], hi)


def union(starts: np.ndarray, ends: np.ndarray) -> list[tuple[float, float]]:
    """Merge intervals (sorted by start) into disjoint ones."""
    out: list[list[float]] = []
    for a, b in zip(starts, ends):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_ns(tr: Trace, lo: float, hi: float) -> float:
    """Time in [lo, hi) in which some operation ran."""
    _, s, e = _clip(tr, lo, hi)
    order = np.argsort(s, kind="stable")
    return float(sum(b - a for a, b in union(s[order], e[order])))


def kernel_ns(tr: Trace, module: str, target: str, lo: float, hi: float
              ) -> tuple[float, int]:
    """(device time, calls) of the custom calls to ``target`` inside the
    programs whose name matches the regular expression ``module``, that
    start inside [lo, hi)."""
    mx = re.compile(module)
    tot, n = 0.0, 0
    for i in np.nonzero((tr.start >= lo) & (tr.start < hi))[0]:
        t = _TARGET.search(tr.op[i])
        if t and t.group(1) == target and mx.search(tr.module[i]):
            tot += tr.end[i] - tr.start[i]
            n += 1
    return tot, n


def label(op: str, module: str = "") -> str:
    """A short name for an operation: its program, its HLO name without
    the instance number, and a custom call's target
    (``_decode_and_sample/closed_call:tpu_custom_call``)."""
    m = _OP_NAME.match(op)
    name = m.group(1) if m else op.split(" ", 1)[0]
    t = _TARGET.search(op)
    if t:
        name += ":" + t.group(1)
    return f"{module}/{name}" if module else name


def _leaves(tr: Trace, idx: np.ndarray) -> list[int]:
    """The operations that hold no other: a loop or call op spans its
    body's operations, which the breakdown counts instead."""
    out = []
    for i in idx:
        name = _OP_NAME.match(tr.op[i])
        if name and name.group(1) in CONTAINERS:
            continue
        out.append(i)
    return out


def top_ops(tr: Trace, lo: float, hi: float, k: int = 10
            ) -> list[tuple[str, float]]:
    """The ``k`` operation labels with the most device time in [lo, hi),
    in seconds."""
    idx, s, e = _clip(tr, lo, hi)
    dur = dict(zip(idx, e - s))
    tot: dict[str, float] = defaultdict(float)
    for i in _leaves(tr, idx):
        tot[label(tr.op[i], tr.module[i])] += dur[i] * 1e-9
    return [(n, float(v)) for n, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:k]]


def idle_gaps(tr: Trace, lo: float, hi: float, k: int = 10
              ) -> list[tuple[str, float]]:
    """Idle seconds in [lo, hi) by what the host was doing: each stretch
    of an idle gap goes to the innermost host span over it, or to
    ``(none)``; the ``k`` largest sums."""
    _, s, e = _clip(tr, lo, hi)
    order = np.argsort(s, kind="stable")
    busy = union(s[order], e[order])
    gaps, cur = [], lo
    for a, b in busy:
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if cur < hi:
        gaps.append((cur, hi))
    spans = sorted(tr.host, key=lambda x: x[1])
    tot: dict[str, float] = defaultdict(float)
    active: list[tuple[str, float, float]] = []
    j = 0
    for a, b in gaps:                  # in time order, so a span that ended
        while j < len(spans) and spans[j][1] < b:     # before this gap
            active.append(spans[j])                   # covers none later
            j += 1
        active = [sp for sp in active if sp[2] > a]
        cuts = sorted({a, b} | {x for sp in active for x in sp[1:]
                                if a < x < b})
        for c0, c1 in zip(cuts, cuts[1:]):
            mid = 0.5 * (c0 + c1)
            over = [sp for sp in active if sp[1] <= mid <= sp[2]]
            best = min(over, key=lambda sp: sp[2] - sp[1])[0] if over \
                else "(none)"
            tot[best] += (c1 - c0) * 1e-9
    return [(n, float(v)) for n, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:k]]
