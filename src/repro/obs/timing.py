"""Device-synchronized timing sections.

JAX dispatches asynchronously: ``t1 - t0`` around a jitted call measures how
long *enqueueing* took, not the computation — on an accelerator the gap is
orders of magnitude (the dispatch returns in microseconds while the program
runs for milliseconds).  Every duration the serving stack reports must
therefore block on the program's outputs before the closing stamp.  ``Timed``
packages that discipline:

    with Timed("decode") as tm:
        out, states = program(...)
        out = tm.sync(out)          # block_until_ready BEFORE the stamp
    stats.decode_time_s += tm.dur

jitlint rule JL008 (timing-discipline) statically rejects raw
``time.perf_counter()`` pairs around device work; routing through ``Timed``
(whose ``sync`` is the one sanctioned blocking point) is the fix it suggests.

``Timed`` itself records nothing.  The engine opens every section,
program calls and host phases alike, through one span call,
:meth:`repro.obs.Tracer.section`: a ``Timed`` on the tracer's clock that
also lands in the tracer's ring and, when the engine is profiling, in a
``jax.profiler.TraceAnnotation`` of the same name (:func:`profile_trace`),
so the two timelines carry the same spans.
"""
from __future__ import annotations

import time
from contextlib import contextmanager

import jax


class Timed:
    """Context manager timing one device-synchronized section.

    Attributes after the block: ``t0`` / ``t1`` (clock stamps), ``dur``
    (seconds), ``synced`` (whether :meth:`sync` ran — callers timing device
    work must call it on the program outputs, or the duration only covers
    dispatch).
    """

    __slots__ = ("name", "t0", "t1", "dur", "synced", "_clock")

    def __init__(self, name: str = "", *, clock=time.perf_counter):
        self.name = name
        self._clock = clock
        self.t0 = self.t1 = self.dur = 0.0
        self.synced = False

    def __enter__(self) -> "Timed":
        self.t0 = self._clock()
        return self

    def sync(self, out):
        """Block until ``out`` (any pytree of arrays) is computed; returns it.
        Call on the program outputs before the block closes."""
        out = jax.block_until_ready(out)
        self.synced = True
        return out

    def __exit__(self, *exc) -> bool:
        self.t1 = self._clock()
        self.dur = self.t1 - self.t0
        return False


@contextmanager
def profile_trace(profile_dir):
    """Run the body under ``jax.profiler`` trace collection when
    ``profile_dir`` is truthy (no-op otherwise): the XLA-level companion to
    the engine's own Chrome trace, viewable in TensorBoard/Perfetto."""
    if not profile_dir:
        yield
        return
    jax.profiler.start_trace(str(profile_dir))
    try:
        yield
    finally:
        jax.profiler.stop_trace()
