"""KV cache manager: device-idle ms per traced tick with one of the
engine's KV-manager spans innermost (``kv_admit``, ``kv_extend``,
``kv_publish``, ``kv_release``), from the profile re-read with the
engine's spans."""
from bench.engine_spans import kv_host_ms_per_tick as read  # noqa: F401
