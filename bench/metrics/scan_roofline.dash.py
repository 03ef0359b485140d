"""HBM roofline share of the serving step programs (see bench/lib/layers)."""
from bench.lib.layers import scan_roofline as read  # noqa: F401
