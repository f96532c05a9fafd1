"""Scheduler: device-idle ms per traced tick with one of the engine's
scheduler spans innermost (``schedule``, ``decode_prep``, ``retire``,
``tick_stats``), from the profile re-read with the engine's spans."""
from bench.engine_spans import sched_host_ms_per_tick as read  # noqa: F401
