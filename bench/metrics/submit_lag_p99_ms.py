"""Load generator: how late requests were submitted after they were due,
99th percentile over the window (ms)."""
from bench.layers import submit_lag_p99_ms as read  # noqa: F401
