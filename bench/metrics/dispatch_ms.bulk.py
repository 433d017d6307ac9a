"""The jitted step's call per batch, until it returns (the program's
serve.dispatch span)."""
from bench.metrics import _spans


def read(ctx):
    return _spans.mean_ms(ctx, "serve.dispatch")
