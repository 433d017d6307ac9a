"""Device time per served step, slowest chip, from the profiler trace."""
from bench.metrics import _read


def read(ctx):
    return _read.step_device_ms(ctx)
