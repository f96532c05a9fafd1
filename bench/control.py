#!/usr/bin/env python3
"""Readings for the limit of ``correct``: on each seed, the program's
widest gap and the control's, at the cell's own size and load.

  python3 bench/control.py --workload <cell> --seconds <s> --seeds 1,2,3

For every seed, in one process: the weights from the seed, the cell's
engine, a window of ``--seconds`` of the cell's traffic and its drain,
then the comparison of ``compare`` on the sample a run would take, with
the control (float8 weights, the precision below the configuration's
bfloat16) put in the program's place at the same positions.  One JSON line
per seed.  The benchmark's own runs never run the control.
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


def readings(c, seeds, seconds: float, out=print) -> list[dict]:
    """One line per seed: the program's widest gap and the control's on
    the sample a run would compare, at the cell's size and load, through
    the harness's own set-up, window and sample."""
    from bench import compare, harness, traffic
    vocab = harness.arch_config(c.config).vocab_size
    ref = compare.Reference(c.family, c.config["published"], control=True)
    lines = []
    for seed in seeds:
        t = time.perf_counter()
        specs = traffic.generate(c.mix, seed, seconds=seconds, vocab=vocab)
        params, engine, _ = harness.setup(c, seed, log=lambda *_: None)
        run = harness.serve(c, engine, specs, seconds)
        harness.release(engine)
        del engine
        got, sample = harness.judge(ref, run, params, seed)
        top = run.engine["max_bucket"]
        served = [tok for r in sample for tok in r.req.generated]
        got.update(seed=seed, unique_served=len(set(served)),
                   unserved=harness.attempted_failed(run)[1],
                   paths={"prefix_hit": sum(r.prefix_hit > 0 for r in sample),
                          "chunked": sum(r.prefix_hit == 0
                                         and r.prompt_len > top
                                         for r in sample)},
                   seconds=time.perf_counter() - t)
        out(json.dumps(got))
        lines.append(got)
        del params
    return lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
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
    readings(c, [int(s) for s in args.seeds.split(",")], args.seconds,
             out=lambda s: print(s, flush=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
