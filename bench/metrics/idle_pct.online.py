"""Share of the traced window in which no operation ran on the device."""
from bench.metrics import _read


def read(ctx):
    return _read.idle_pct(ctx)
