"""Window arithmetic and exact percentiles over the client-side record.

A request's record holds its due time, its submit time, the time of its
admission into a slot, and one stamp per output token, all on the host's
``time.perf_counter`` clock (the engine's tracer clock).  The window is
``[open, close)``.
"""
from __future__ import annotations

import numpy as np


def percentile(values, q: float) -> float | None:
    """The ``q``-th percentile (0-100), linear between order statistics;
    None for no values."""
    v = np.asarray(list(values), dtype=np.float64)
    if v.size == 0:
        return None
    return float(np.percentile(v, q))


def due_in(records, open_: float, close: float) -> list:
    """Requests due inside the window."""
    return [r for r in records if open_ <= r.due < close]


def ttfts(records, open_: float, close: float) -> tuple[list[float], int]:
    """(first-token stamp minus due time, for each request due in the
    window that has a first token; how many due in it have none)."""
    due = due_in(records, open_, close)
    got = [r.stamps[0] - r.due for r in due if r.stamps]
    return got, len(due) - len(got)


def ttft_tail(records, open_: float, close: float, until: float
              ) -> list[float]:
    """The times to first token that the tail is taken over: one for each
    request due in the window, and for a request that never got its first
    token the time it waited, until ``until`` (the drain's end).  A request
    dropped cannot make the tail shorter."""
    return [(r.stamps[0] if r.stamps else until) - r.due
            for r in due_in(records, open_, close)]


def token_gaps(records, open_: float, close: float) -> list[float]:
    """Gaps between successive token stamps of a request, for every gap
    that ends inside the window."""
    out = []
    for r in records:
        s = r.stamps
        for a, b in zip(s, s[1:]):
            if open_ <= b < close:
                out.append(b - a)
    return out


def tokens_in(records, open_: float, close: float) -> int:
    """Output tokens stamped inside the window."""
    return sum(1 for r in records for t in r.stamps if open_ <= t < close)


def waiting_at(records, t: float) -> int:
    """Requests due by ``t`` and not yet admitted into a slot at ``t``."""
    return sum(1 for r in records
               if r.due <= t and (r.admit is None or r.admit > t))
