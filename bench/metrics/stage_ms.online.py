"""Host to device of the padded batch per batch (the program's serve.stage
span)."""
from bench.metrics import _spans


def read(ctx):
    return _spans.mean_ms(ctx, "serve.stage")
