"""Padded GA lanes over all lanes of the window's sharded selects, in
percent: every shard runs as many lanes as the busiest shard's clients
(to a power of two), and the rest repeat a client (the engine's
`n_pad_lanes` over `n_lanes`, per batch in the records)."""


def read(ctx):
    lanes = sum(r.get("lanes", 0) for r in ctx.records)
    if not lanes:
        return None
    return sum(r["pad_lanes"] for r in ctx.records) / lanes * 100.0
