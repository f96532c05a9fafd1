#!/usr/bin/env python3
"""Find the knee of an open-loop cell on the chip: the highest offered rate
at which the queue does not grow and enough requests meet the limits.

  python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \\
      --rates 1,2,3,4 [--unloaded 0.3]

One process builds the cell's engine once and serves the cell's mix at
each rate in turn, rising (the same draws, the clock scaled), each with
its ramp, window and drain, and stops at the first rate past the knee.  A
rate holds when both are true:

* requests waiting for a slot at the window's end are no more than at the
  end of its first quarter;
* at least ``--share`` (default 0.9) of the requests due in the window
  meet the limits: a first token within ``ttft_ms`` of being due, and a
  mean gap between its tokens within ``tbt_ms``.

The limits are the mix's ``limits``.  Where the mix has none yet,
``--unloaded <rate>`` serves that low rate first and sets them by the rule
this tool states: each limit is ``LIMIT_FACTOR`` times the unloaded run's
95th percentile of the same per-request number.  Each rate prints one JSON
line; the last line names the knee and the limits.  This tool is not part
of a cell's run.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

LIMIT_FACTOR = 5.0
DRAIN_S = 30.0      # past this a rate has failed anyway


def mean_gap(r) -> float | None:
    s = r.stamps
    return (s[-1] - s[0]) / (len(s) - 1) if len(s) > 1 else None


def meets(r, limits: dict) -> bool:
    """A request meets the limits when its first token came in time and
    the mean gap between its tokens (as far as it got) is within the
    limit; one with no first token does not."""
    if not r.stamps or 1e3 * (r.stamps[0] - r.due) > limits["ttft_ms"]:
        return False
    g = mean_gap(r)
    return g is None or 1e3 * g <= limits["tbt_ms"]


def serve_at(c, engine, seed: int, seconds: float, rate: float) -> dict:
    """Serve the cell's mix at ``rate`` on a warm engine, through the
    harness's own window; the window's numbers and its requests."""
    from bench import harness, stats, traffic
    specs = traffic.generate(c.mix, seed, seconds=seconds,
                             vocab=harness.arch_config(c.config).vocab_size,
                             rate=rate)
    run = harness.serve(c, engine, specs, seconds, drain_s=DRAIN_S)
    recs, lo, hi = run.records, run.open, run.close
    end = time.perf_counter() + DRAIN_S      # let the engine go idle
    while any(r.req.done is False for r in recs) \
            and time.perf_counter() < end:
        engine.step()
    due = stats.due_in(recs, lo, hi)
    ttft, missing = stats.ttfts(recs, lo, hi)
    gaps = stats.token_gaps(recs, lo, hi)
    ms = lambda v: None if v is None else 1e3 * v  # noqa: E731
    return {"rate": rate, "due": len(due), "unserved": missing,
            "ttft_p50_ms": ms(stats.percentile(ttft, 50)),
            "ttft_p95_ms": ms(stats.percentile(
                stats.ttft_tail(recs, lo, hi, run.drained), 95)),
            "tbt_p50_ms": ms(stats.percentile(gaps, 50)),
            "tbt_p99_ms": ms(stats.percentile(gaps, 99)),
            "tokens_per_s": stats.tokens_in(recs, lo, hi) / seconds,
            "waiting_q1": stats.waiting_at(recs, lo + seconds / 4),
            "waiting_end": stats.waiting_at(recs, hi),
            "_due": due}


def limits_from(unloaded: dict) -> dict:
    """The rule: ``LIMIT_FACTOR`` x the unloaded p95 of each number."""
    from bench import stats
    due = unloaded["_due"]
    if not due:
        raise ValueError("no request fell due in the unloaded window")
    ttft = [r.stamps[0] - r.due for r in due if r.stamps]
    gaps = [g for g in map(mean_gap, due) if g is not None]
    return {"ttft_ms": LIMIT_FACTOR * 1e3 * stats.percentile(ttft, 95),
            "tbt_ms": LIMIT_FACTOR * 1e3 * stats.percentile(gaps, 95)}


def sweep(c, engine, seed: int, seconds: float, rates, *, limits=None,
          unloaded: float | None = None, share: float = 0.9, out=print):
    """Serve each rate in turn; returns (knee, limits)."""
    if not (limits and limits.get("ttft_ms")):
        if unloaded is None:
            raise ValueError("the mix has no limits: give an unloaded rate")
        u = serve_at(c, engine, seed, seconds, unloaded)
        limits = limits_from(u)
        out(json.dumps({k: v for k, v in u.items() if k != "_due"}
                       | {"unloaded": True, "limits": limits}))
    knee = None
    for rate in rates:
        line = serve_at(c, engine, seed, seconds, rate)
        due = line.pop("_due")
        line["share_meeting_limits"] = \
            sum(meets(r, limits) for r in due) / max(len(due), 1)
        line["holds"] = line["waiting_end"] <= line["waiting_q1"] \
            and line["share_meeting_limits"] >= share
        if line["holds"]:
            knee = rate
        out(json.dumps(line))
        if knee is not None and not line["holds"]:
            break                       # rates rise: past the knee
    out(json.dumps({"knee": knee, "limits": limits}))
    return knee, limits


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--share", type=float, default=0.9)
    ap.add_argument("--unloaded", type=float, default=None)
    args = ap.parse_args(argv)

    from bench import harness
    harness.src_on_path()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("refused: needs a TPU", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import setup_compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    setup_compile_cache()
    c = harness.cell(harness.load_manifest(), args.workload)
    _, engine, _ = harness.setup(c, args.seed)
    sweep(c, engine, args.seed, args.seconds,
          [float(x) for x in args.rates.split(",")],
          limits=c.mix.get("limits"), unloaded=args.unloaded,
          share=args.share, out=lambda s: print(s, flush=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
