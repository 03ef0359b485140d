"""Percent of the serving steps' device time spent in all-reduce."""
from bench.lib.layers import psum_share as read  # noqa: F401
