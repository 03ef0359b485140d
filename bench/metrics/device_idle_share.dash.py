"""Percent of the traced window with no operation on the device."""
from bench.lib.layers import device_idle_share as read  # noqa: F401
