"""Share of the uploaded flush bytes that are real validation samples
of distinct dirty rows (`n_bytes_useful` over `n_bytes_uploaded`): the
rest is the power-of-two row and client repeats and the V pad."""
from chip.metrics import _spans


def read(ctx):
    c = _spans.counters(ctx)
    if c is None:
        return None
    return c["bytes_useful"] / c["bytes_uploaded"] * 100.0
