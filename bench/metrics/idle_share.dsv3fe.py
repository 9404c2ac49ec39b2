"""Device idle share of the window: 1 - union of op intervals / window."""
from bench.layers import idle_pct as read  # noqa: F401
