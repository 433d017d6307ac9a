"""Median request latency, due to scores on the host."""
from bench.metrics import _read


def read(ctx):
    return _read.latency_ms(ctx, 50)
