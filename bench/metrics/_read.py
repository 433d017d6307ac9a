"""Shared arithmetic of the metric readers (one reader per metric file)."""
from __future__ import annotations

import numpy as np

from bench.stats import percentile


def mean_span_ms(ctx, name: str):
    spans = ctx.spans(name)
    if not spans:
        return None
    return 1e3 * float(np.mean([b - a for a, b in spans]))


def span_share_pct(ctx, names) -> float:
    """Share of the host-measured window spent in spans ``names``."""
    s = ctx.served
    total = sum(b - a for n in names for a, b in ctx.spans(n))
    return 100.0 * total / (s.t_end - s.t0)


def latency_ms(ctx, q: float):
    lat = ctx.latencies_s()
    return None if lat.size == 0 else 1e3 * percentile(lat, q)


def queue_wait_ms(ctx, q: float):
    s = ctx.served
    ok = ~np.isnan(s.flushed) & (s.due < s.t_end)
    if not ok.any():
        return None
    return 1e3 * percentile((s.flushed - s.due)[ok], q)


def batch_fill_pct(ctx):
    batches = ctx.batches()
    if not batches:
        return None
    real = sum(len(b[3]) for b in batches)
    padded = sum(b[2] for b in batches)
    return 100.0 * real / padded


def scored_per_s(ctx):
    s = ctx.served
    n = int(np.count_nonzero(s.done <= s.t_end))
    return n / (s.t_end - s.t0)


def step_device_ms(ctx):
    t = ctx.trace
    if t is None or not t.steps:
        return None
    return 1e3 * t.step_seconds() / len(t.steps)


def mfu_pct(ctx):
    """The traced steps' least time at the chips' peaks over their device
    time (the slowest chip's, per step)."""
    t = ctx.trace
    if t is None or not t.steps or ctx.peaks is None:
        return None
    least = sum(ctx.least_time_s(b) for b in ctx.traced_batches())
    return 100.0 * least / t.step_seconds()


def idle_pct(ctx):
    t = ctx.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)


def collective_ms(ctx):
    t = ctx.trace
    if t is None or not t.steps:
        return None
    return 1e3 * t.collective_s / len(t.steps)
