"""Host ms per step the device waited for in the slots' stop rules."""
from bench.lib.spans import stop_rule_ms as read  # noqa: F401
