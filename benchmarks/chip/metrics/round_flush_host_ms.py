"""`flush_host_ms`, read the same way in the synchronous round cells, whose
batches are a different end-to-end metric's (`round_reselect_ms`)."""
from chip.metrics.flush_host_ms import read  # noqa: F401
