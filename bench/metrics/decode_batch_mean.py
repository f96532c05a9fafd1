"""Scheduler: active slots per decode tick, mean over the window."""
from bench.layers import decode_batch_mean as read  # noqa: F401
