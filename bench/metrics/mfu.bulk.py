"""The served steps' share of the chips' roofline: least time at peak over device time."""
from bench.metrics import _read


def read(ctx):
    return _read.mfu_pct(ctx)
