"""Host ms per step the device waited for: slicing, parameters, dispatch."""
from bench.lib.spans import dispatch_ms as read  # noqa: F401
