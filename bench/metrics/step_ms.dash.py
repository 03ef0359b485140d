"""Median ms between consecutive scan steps (see bench/lib/spans)."""
from bench.lib.spans import step_ms as read  # noqa: F401
