"""Megabytes (1e6 bytes) a batch's flushes copy from host to device: the
padded row block and its index arrays (`DeviceStoreBatch.n_bytes_uploaded`)."""
from chip.metrics import _spans


def read(ctx):
    c, n = _spans.counters(ctx), _spans.batches(ctx)
    if c is None or not n:
        return None
    return c["bytes_uploaded"] / n / 1e6
