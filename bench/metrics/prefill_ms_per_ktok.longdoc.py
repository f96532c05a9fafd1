"""Programs: prefill and chunk program time per 1000 prompt tokens
computed in the window (ms), from the engine's counters."""
from bench.layers import prefill_ms_per_ktok as read  # noqa: F401
