"""Host milliseconds per batch in the store flush: the program's
`store.flush` span (drain, assembly, upload and the scatter's dispatch;
it does not wait for the device)."""
from chip.metrics import _spans


def read(ctx):
    recs, n = _spans.spans(ctx), _spans.batches(ctx)
    if recs is None or not n:
        return None
    return _spans.seconds(recs, "store.flush") / n * 1e3
