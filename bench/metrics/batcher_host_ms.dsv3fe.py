"""launch/serve_memhd batcher host time per batch: ``host_prep`` +
``dispatch`` spans of ``serve_batches``."""
from bench.layers import host_ms_per_batch as read  # noqa: F401
