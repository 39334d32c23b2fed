"""What the span metrics share: the traced segment's spans, byte
counters and device scopes, each None where the run has none (a run
without the program's spans, the CPU's trace, an older program)."""


def spans(ctx):
    return getattr(ctx, "spans", None) or None


def batches(ctx) -> int:
    return getattr(ctx, "n_traced", 0) or 0


def seconds(records, name, parent=...):
    """Total seconds of the spans called `name` (under `parent`)."""
    return sum(r.end - r.start for r in records if r.name == name
               and (parent is ... or r.parent == parent))


def counters(ctx):
    c = getattr(ctx, "counters", None)
    return c if c and c.get("bytes_uploaded") else None


def scope_ms(ctx, scope):
    """Device milliseconds of one scope per traced batch."""
    tr = getattr(ctx, "trace", None) or {}
    found = dict(tr.get("device_scopes") or ())
    n = batches(ctx)
    if scope not in found or not n:
        return None
    return found[scope] / n * 1e3
