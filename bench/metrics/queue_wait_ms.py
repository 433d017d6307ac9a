"""Median due-to-flush time in the admission queue and batcher."""
from bench.metrics import _read


def read(ctx):
    return _read.queue_wait_ms(ctx, 50)
