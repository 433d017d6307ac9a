"""The wait for the step's result per batch (the program's serve.block span)."""
from bench.metrics import _spans


def read(ctx):
    return _spans.mean_ms(ctx, "serve.block")
