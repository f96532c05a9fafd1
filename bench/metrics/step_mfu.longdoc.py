"""Model step: model operations of the window's ticks over the ticks'
wall time at the chip's bfloat16 peak (%)."""
from bench.layers import step_mfu as read  # noqa: F401
