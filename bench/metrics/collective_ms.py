"""Device time of collective operations per served step, slowest chip."""
from bench.metrics import _read


def read(ctx):
    return _read.collective_ms(ctx)
