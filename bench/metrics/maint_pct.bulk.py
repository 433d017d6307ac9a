"""Share of the window spent in observe and replan on the serving thread."""
from bench.metrics import _read


def read(ctx):
    return _read.span_share_pct(ctx, ("observe", "replan"))
