"""Shared arithmetic of the readers of the program's own spans and counters
(``repro.core.spans``).  A reader takes the records whose start falls in
the window ``[served.t0, served.t_end)``, and returns ``None`` where the
program has no recorder, recorded nothing there, or dropped records inside
the window."""
from __future__ import annotations

import bisect

import numpy as np

BATCHER_COUNTERS = ("queue.view", "batcher.decide")


def window(ctx):
    try:
        from repro.core.spans import SPANS
    except ImportError:
        return None
    s = ctx.served
    if SPANS.dropped and SPANS.dropped_since < s.t_end:
        return None
    return [r for r in SPANS.records if s.t0 <= r.t0 < s.t_end] or None


def _named(ctx, name: str):
    recs = window(ctx)
    return [r for r in recs if r.name == name] if recs else []


def mean_ms(ctx, name: str):
    """Mean duration of the window's ``name`` spans."""
    recs = _named(ctx, name)
    if not recs:
        return None
    return 1e3 * float(np.mean([r.t1 - r.t0 for r in recs]))


def share_pct(ctx, name: str):
    """The window's ``name`` spans as a share of the window."""
    recs = _named(ctx, name)
    if not recs:
        return None
    s = ctx.served
    return 100.0 * sum(r.t1 - r.t0 for r in recs) / (s.t_end - s.t0)


def fetch_ms(ctx):
    """Mean of the harness's ``execute`` span less the ``serve.execute``
    span inside it: the copy of the scores to the host."""
    execs = sorted((r.t0, r.t1) for r in _named(ctx, "serve.execute"))
    if not execs:
        return None
    starts = [a for a, _ in execs]
    gaps = []
    for a, b in ctx.spans("execute"):
        i = bisect.bisect_left(starts, a)
        if a >= ctx.served.t0 and i < len(execs) and execs[i][1] <= b:
            gaps.append((b - a) - (execs[i][1] - execs[i][0]))
    return 1e3 * float(np.mean(gaps)) if gaps else None


def batcher_ms(ctx):
    """Queue views, batcher decisions and queue pops per batch, between
    the window's first and last ``serve.execute``."""
    recs = window(ctx)
    execs = sorted((r for r in recs or () if r.name == "serve.execute"),
                   key=lambda r: r.t0)
    if len(execs) < 2:
        return None
    first, last = execs[0], execs[-1]

    def seconds(snap):
        return sum(snap[k][1] for k in BATCHER_COUNTERS if k in snap)

    pops = sum(r.t1 - r.t0 for r in recs
               if r.name == "queue.pop" and first.t1 <= r.t0 < last.t0)
    total = seconds(last.counters) - seconds(first.counters) + pops
    return 1e3 * total / (len(execs) - 1)
