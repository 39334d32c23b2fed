"""Useful operations of the window's re-selection batches (chip/flops.py)
over their time, as a share of the bf16 peak of all the chips the batch
is sharded over."""


def read(ctx):
    recs = [r for r in ctx.records if "shards" in r]
    if not recs or not ctx.peaks:
        return None
    done = sum(r["flops"] for r in recs)
    secs = sum(r["seconds"] for r in recs)
    return done / secs / (recs[0]["shards"] * ctx.peaks["bf16_flops"]) * 100.0
