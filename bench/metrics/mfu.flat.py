"""Whole serving step's share of the bf16 peak: rows answered per second
in the traced window times the operations of one row, over the peak."""
from bench.layers import serve_mfu_pct as read  # noqa: F401
