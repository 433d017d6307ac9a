"""Host padding per batch (the program's padder)."""
from bench.metrics import _read


def read(ctx):
    return _read.mean_span_ms(ctx, "pad")
