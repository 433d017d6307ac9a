"""Real requests over padded batch rows."""
from bench.metrics import _read


def read(ctx):
    return _read.batch_fill_pct(ctx)
