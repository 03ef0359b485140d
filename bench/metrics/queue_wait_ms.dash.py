"""Mean ms from a submit to its attach (see bench/lib/spans)."""
from bench.lib.spans import queue_wait_ms as read  # noqa: F401
