"""Record the small TPU trace that the trace-reduction tests read
(``bench/testdata/v5e_small.xplane.pb``).  Run on one TPU chip:

  python3 bench/tests/record_trace.py bench/testdata/v5e_small.xplane.pb

Five ticks, each a host ``step`` span holding a ``decode`` span (a jitted
matrix product, then a small Pallas kernel named ``tiny_add_kernel``) and
then a ``wait_arrival`` span of 3 ms in which the device is idle.
"""
from __future__ import annotations

import shutil
import sys
import tempfile
import time
from pathlib import Path

TICKS = 5
SLEEP_S = 0.003


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 2

    def tiny_add_kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] + 1.0

    add = jax.jit(lambda x: pl.pallas_call(
        tiny_add_kernel, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(x))
    mm = jax.jit(lambda a: jnp.tanh(a @ a))
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    add(mm(x)).block_until_ready()
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    for _ in range(TICKS):
        with jax.profiler.TraceAnnotation("step"):
            with jax.profiler.TraceAnnotation("decode"):
                add(mm(x)).block_until_ready()
        with jax.profiler.TraceAnnotation("wait_arrival"):
            time.sleep(SLEEP_S)
    jax.profiler.stop_trace()
    src = sorted(Path(tmp).rglob("*.xplane.pb"))[-1]
    Path(out).parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(src, out)
    shutil.rmtree(tmp, ignore_errors=True)
    print(f"wrote {out}: {Path(out).stat().st_size} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
