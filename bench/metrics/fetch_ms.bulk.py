"""The copy of the scores to the host per batch: the harness's execute span
less the program's serve.execute inside it."""
from bench.metrics import _spans


def read(ctx):
    return _spans.fetch_ms(ctx)
