"""Programs: the decode program's synced time per decode step over the
window (ms), from the engine's counters."""
from bench.layers import decode_step_ms as read  # noqa: F401
