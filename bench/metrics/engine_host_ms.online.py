"""serve/engine host time per batch: ``host_prep`` + ``dispatch`` spans."""
from bench.layers import host_ms_per_batch as read  # noqa: F401
