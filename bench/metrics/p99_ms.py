"""99th percentile of request latency over all requests of the window."""
from bench.metrics import _read


def read(ctx):
    return _read.latency_ms(ctx, 99)
