"""Host milliseconds per batch in `engine.select` besides waiting for the
results (its `fetch` child) and any flush inside it: key lookup,
gather and GA dispatch, and unpacking the answers."""
from chip.metrics import _spans


def read(ctx):
    recs, n = _spans.spans(ctx), _spans.batches(ctx)
    if recs is None or not n or not any(r.name == "engine.select"
                                        for r in recs):
        return None
    s = (_spans.seconds(recs, "engine.select")
         - _spans.seconds(recs, "engine.select.fetch")
         - _spans.seconds(recs, "store.flush", parent="engine.select"))
    return s / n * 1e3
