"""Device: share of the traced window in which no operation ran (%)."""
from bench.layers import device_idle_share as read  # noqa: F401
