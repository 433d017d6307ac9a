"""Share of the window in the program's dedup probe after each observed
batch (its observe.probe span)."""
from bench.metrics import _spans


def read(ctx):
    return _spans.share_pct(ctx, "observe.probe")
