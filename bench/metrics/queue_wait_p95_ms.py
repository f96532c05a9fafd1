"""Scheduler: due time to admission into a slot (the engine tracer's
``req`` begin), 95th percentile over requests due in the window (ms)."""
from bench.layers import queue_wait_p95_ms as read  # noqa: F401
