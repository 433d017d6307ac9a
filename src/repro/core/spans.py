"""Host spans and counters of the serving path, on the profiler's clock.

One recorder for the whole process, ``SPANS``.  It records while a profile
is being taken (``jax.profiler.TraceAnnotation.is_enabled()``) or after
:meth:`Recorder.enable`.  That state is read at the start of each batch
(``AdmissionQueue.pop_n``) and of each ``ServeBinding.execute``
(:meth:`Recorder.refresh`); calls between batches (``queue.view``,
``batcher.decide``) follow the state the last batch read.

* ``with SPANS.span(name):`` records a :class:`Span` on
  ``time.perf_counter`` and opens ``jax.profiler.TraceAnnotation`` named
  ``repro.<name>``, so the span sits on the profiler's host plane, on the
  device events' time base.
* ``with SPANS.tally(name):`` adds one call and its seconds to a cumulative
  counter, for calls too frequent for a span each.  Every ``serve.execute``
  span carries a snapshot of all counters, so a reader takes the difference
  over a window.

While off, both return one shared context that does nothing.  Records go to
a buffer of ``capacity`` spans; past it they are dropped, counted in
``dropped``, and ``dropped_since`` keeps the start of the first one dropped.
"""
from __future__ import annotations

import time
from typing import Dict, List, NamedTuple, Optional, Tuple

from jax.profiler import TraceAnnotation

PREFIX = "repro."
SNAPSHOT = "serve.execute"       # the span that carries the counters


class Span(NamedTuple):
    name: str
    t0: float                  # perf_counter seconds
    t1: float
    counters: Optional[Dict[str, Tuple[int, float]]]   # SNAPSHOT only


class _Null:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL = _Null()


class _Open:
    __slots__ = ("rec", "name", "t0", "mark")

    def __init__(self, rec: "Recorder", name: str):
        self.rec, self.name = rec, name
        self.mark = TraceAnnotation(PREFIX + name)

    def __enter__(self):
        self.mark.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self.mark.__exit__(*exc)
        self.rec._close(self.name, self.t0, t1)
        return False


class _Tally:
    __slots__ = ("counter", "t0")

    def __init__(self, counter: list):
        self.counter = counter

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.counter[1] += time.perf_counter() - self.t0
        self.counter[0] += 1
        return False


class Recorder:
    def __init__(self, capacity: int = 1 << 18):
        self.capacity = capacity
        self.on = False
        self.forced = False
        self.clear()

    def clear(self) -> None:
        """Forget every record and counter."""
        self.records: List[Span] = []
        self.counters: Dict[str, list] = {}
        self.dropped = 0
        self.dropped_since: Optional[float] = None

    def enable(self, flag: bool = True) -> None:
        """Record without a profile running (tests, operators)."""
        self.forced = flag
        self.refresh()

    def refresh(self) -> bool:
        self.on = self.forced or TraceAnnotation.is_enabled()
        return self.on

    def span(self, name: str):
        return _Open(self, name) if self.on else NULL

    def tally(self, name: str):
        if not self.on:
            return NULL
        c = self.counters.get(name)
        if c is None:
            c = self.counters[name] = [0, 0.0]
        return _Tally(c)

    def _close(self, name: str, t0: float, t1: float) -> None:
        if len(self.records) >= self.capacity:
            self.dropped += 1
            if self.dropped_since is None:
                self.dropped_since = t0
            return
        snap = ({k: (c[0], c[1]) for k, c in self.counters.items()}
                if name == SNAPSHOT else None)
        self.records.append(Span(name, t0, t1, snap))


SPANS = Recorder()
