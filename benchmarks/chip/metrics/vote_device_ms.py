"""Device milliseconds per batch in the winner vote (device scope
`vote`: every front member's mean-probability vote), from the trace."""
from chip.metrics import _spans


def read(ctx):
    return _spans.scope_ms(ctx, "vote")
