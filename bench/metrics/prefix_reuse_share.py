"""KV cache manager: prompt tokens served from the prefix cache, as a
share of the prompt tokens admitted in the window (%)."""
from bench.layers import prefix_reuse_share as read  # noqa: F401
