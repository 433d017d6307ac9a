"""Requests whose scores reached the host inside the window, per second of window."""
from bench.metrics import _read


def read(ctx):
    return _read.scored_per_s(ctx)
