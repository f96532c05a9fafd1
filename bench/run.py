#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is a ``workloads`` entry of ``BENCHMARK.json``.  ``--trace 0``
prints the cell's end-to-end metrics, ``--trace 1`` its per-layer metrics
from a run with the profiler on over the window's last seconds.  The last
line of standard output is the result (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``, and
last ``checks``: each number compared with its limit); the last lines of
standard error repeat the checks.  The run refuses any platform but a TPU
and fewer chips than the cell asks for: it then exits 2 and prints no
result.
"""
from __future__ import annotations

import time

T_PROC = time.perf_counter()            # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

REPO = Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))


def load_peaks(kind: str) -> dict:
    table = json.loads((REPO / "bench" / "peaks.json").read_text())
    if kind not in table["chips"]:
        raise KeyError(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return table["chips"][kind]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness
    harness.src_on_path()
    try:
        import repro  # noqa: F401
    except ImportError as e:
        print(f"the system under test is missing: {e}", file=sys.stderr)
        return 2
    c = harness.cell(harness.load_manifest(), args.workload)
    import jax
    devs = jax.devices()
    chips = c.workload["chips"]
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"refused: the cell needs {chips} TPU chip(s); JAX has "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return 2
    peaks = load_peaks(devs[0].device_kind)
    result, checks = harness.run_cell(
        c, args.seed, args.seconds, trace=bool(args.trace), peaks=peaks,
        t_proc=T_PROC, device=devs[0])
    sys.stdout.flush()
    for name, v, rule, lim in checks:
        print(f"check {name}: {v!r} {rule} {lim!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
