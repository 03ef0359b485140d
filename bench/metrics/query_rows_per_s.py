"""Rows the panels witnessed inside the window, per second of window."""
from bench.lib.layers import rows_witnessed_per_s as read  # noqa: F401
