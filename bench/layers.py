"""Per-layer arithmetic over a run record (``harness.Run``), shared by the
metric readers in ``bench/metrics``.  Each function returns None where the
run holds nothing to read, never 0 for a share of a peak."""
from __future__ import annotations

import numpy as np

from . import stats
from . import trace_reduce


def _due(run):
    return stats.due_in(run.records, run.open, run.close)


def submit_lag_p99_ms(run):
    v = stats.percentile([r.submit - r.due for r in _due(run)
                          if r.submit is not None], 99)
    return None if v is None else 1e3 * v


def queue_wait_p95_ms(run):
    v = stats.percentile([r.admit - r.due for r in _due(run)
                          if r.admit is not None], 95)
    return None if v is None else 1e3 * v


def decode_batch_mean(run):
    rows = [len(t.decode_ctx) for t in run.window_ticks() if t.decode_ctx]
    return float(np.mean(rows)) if rows else None


def prefix_reuse_share(run):
    """Prompt tokens served from the prefix cache, as a share of the prompt
    tokens of the requests admitted in the window (%)."""
    adm = [r for r in run.records
           if r.admit is not None and run.open <= r.admit < run.close]
    tot = sum(r.prompt_len for r in adm)
    return 100.0 * sum(r.prefix_hit for r in adm) / tot if tot else None


def decode_step_ms(run):
    c = run.counters
    return 1e3 * c["decode_time_s"] / c["decode_steps"] \
        if c["decode_steps"] else None


def prefill_ms_per_ktok(run):
    c = run.counters
    return 1e6 * c["prefill_time_s"] / c["prefill_tokens_computed"] \
        if c["prefill_tokens_computed"] else None


def tick_flops(run, tk) -> float:
    """Model operations of one tick: decoded tokens over their contexts,
    prefilled tokens over theirs, the LM head at each sampled position."""
    fam, pub = run.cell.family, run.cell.config["published"]
    f = fam.model_flops(pub, tk.decode_ctx, len(tk.decode_ctx)) \
        if tk.decode_ctx else 0.0
    for _rows, _bucket, lens in tk.prefills:
        for n in lens:
            f += fam.model_flops(pub, range(1, n + 1), 1)
    for off, n, final in tk.chunks:
        f += fam.model_flops(pub, range(off + 1, off + n + 1), int(final))
    return f


def step_mfu(run):
    """Model operations of the window's ticks over the ticks' wall time at
    the chip's bfloat16 peak (%)."""
    ticks = run.window_ticks()
    wall = sum(t.t1 - t.t0 for t in ticks)
    if not ticks or not wall or not run.peaks:
        return None
    flops = sum(tick_flops(run, t) for t in ticks)
    return 100.0 * flops / (wall * run.peaks["bf16_flops"])


def roofline(run, module: str, target: str, work) -> float | None:
    """Least time the chip could take for a kernel's calls in the traced
    window (the larger of operations over peak and bytes over bandwidth,
    ``work`` giving them per traced tick) over the device time of the
    kernel: the custom calls to ``target`` in the programs matching
    ``module`` (%)."""
    if run.trace is None or not run.peaks:
        return None
    ns, calls = trace_reduce.kernel_ns(run.trace, module, target,
                                       run.trace_lo, run.trace_hi)
    flops = byts = 0.0
    for t in run.traced_ticks():
        f, b = work(t)
        flops += f
        byts += b
    if not calls or not ns or not (flops or byts):
        return None
    least = max(flops / run.peaks["bf16_flops"],
                byts / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (ns * 1e-9)


def device_idle_share(run):
    if run.trace is None:
        return None
    span = run.trace_hi - run.trace_lo
    if span <= 0:
        return None
    busy = trace_reduce.busy_ns(run.trace, run.trace_lo, run.trace_hi)
    return 100.0 * (1.0 - busy / span)
