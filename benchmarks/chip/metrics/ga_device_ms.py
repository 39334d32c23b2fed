"""Device milliseconds per batch in the GA (device scope `ga`: NSGA-II's
init, evaluations, ranking, breeding and survival), from the trace."""
from chip.metrics import _spans


def read(ctx):
    return _spans.scope_ms(ctx, "ga")
