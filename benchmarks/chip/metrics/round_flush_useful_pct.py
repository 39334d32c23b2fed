"""`flush_useful_pct`, read the same way in the synchronous round cells, whose
batches are a different end-to-end metric's (`round_reselect_ms`)."""
from chip.metrics.flush_useful_pct import read  # noqa: F401
