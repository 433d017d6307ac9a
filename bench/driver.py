"""Wall-clock load driver around the program's serving pieces.

The program's ``ServingRuntime.run`` keeps arrivals on a virtual clock and
charges only the measured execute, so host padding, ``observe`` and
``replan`` never reach a latency.  This driver runs the same pieces on the
wall clock, on one thread, as a server would: it admits due requests into the
program's ``AdmissionQueue``, asks the program's batcher to ``decide`` with
the wall clock as ``now``, pads with the program's padder, calls
``ServeBinding.execute`` and copies the scores to the host, and runs
``observe``/``replan`` between batches at the program's cadence.  A request's
latency runs from when it was due to when its scores are on the host.

Open loop: requests fall due on the mix's schedule whatever the server does.
Closed loop: a fixed number of requests are outstanding; each one that
completes is replaced at once.  When the window closes no more are admitted,
and those already queued are served (their latency counts).
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Callable, List, Optional

import numpy as np

import jax


@dataclasses.dataclass
class Served:
    """What one window did.  Times are ``perf_counter`` seconds."""
    t0: float
    t_end: float
    due: np.ndarray          # per issued request
    admitted: np.ndarray     # when the driver offered it to the queue
    flushed: np.ndarray      # nan where dropped
    done: np.ndarray         # nan where dropped
    scores: np.ndarray       # nan where dropped
    pool_index: np.ndarray   # which generated request each issue carried
    dropped: int
    batches: List[tuple]     # (t_flush, t_done, bucket_batch, rids)
    spans: List[tuple]       # (name, t0, t1)
    replans: int
    trace_t: Optional[tuple] = None   # (t_start, t_stop) of the traced part
    oversleep: float = 0.0            # latest wake-up past its time


class Driver:
    def __init__(self, program, requests, mix: dict,
                 clock: Callable[[], float] = time.perf_counter):
        self.p = program
        self.reqs = requests
        self.mix = mix
        self.clock = clock
        self.slo_s = float(mix["slo_ms"]) * 1e-3
        self.spans: list = []
        self.annotate = False
        self.oversleep = 0.0

    @contextlib.contextmanager
    def span(self, name: str, keep: bool = True):
        """Time a host span; while traced, also mark it in the trace (the
        reduction labels device idle gaps by these marks)."""
        t = self.clock()
        if self.annotate:
            with jax.profiler.TraceAnnotation(f"bench.{name}"):
                yield
        else:
            yield
        if keep:
            self.spans.append((name, t, self.clock()))

    def _sleep_until(self, until: float, now: float) -> None:
        """Wait for arrivals; keeps the latest wake-up past ``until``."""
        with self.span("wait", keep=False):
            time.sleep(max(0.0, until - now))
        self.oversleep = max(self.oversleep, self.clock() - max(until, now))

    def run(self, seconds: float, trace=None) -> Served:
        """Serve for ``seconds``.  ``trace``, if given, has ``start`` and
        ``stop``, called around the window (see run.py)."""
        from repro.serving.request import AdmissionQueue, Request
        from repro.serving.batcher import Flush, Wait

        p, mix, clock = self.p, self.mix, self.clock
        load = mix["load"]
        closed = load["loop"] == "closed"
        batcher, service = p.batcher, p.service
        queue = AdmissionQueue(p.queue_capacity)
        feats_dense, feats_ids = p.features(self.reqs)
        n_pool = len(self.reqs)
        due: list = []
        admitted: list = []
        pool_index: list = []
        batches: list = []
        spans = self.spans
        n_batches = replans = 0
        dropped = 0
        slo = self.slo_s
        pooling = feats_ids.shape[-1]

        def issue(t_due: float) -> None:
            nonlocal dropped
            j = len(due)
            k = j % n_pool
            due.append(t_due)
            admitted.append(clock())
            pool_index.append(k)
            rel = t_due - t0
            r = Request(rid=j, arrival_s=rel, deadline_s=rel + slo,
                        features={"dense": feats_dense[k],
                                  "indices": feats_ids[k]},
                        pooling=pooling)
            if not queue.offer(r):
                dropped += 1

        if trace is not None:
            # started before the window: starting the profiler stalls
            self.annotate = True
            trace.start()
        t0 = clock()
        t_end = t0 + seconds
        if closed:
            offsets = None
            for _ in range(int(load["outstanding"])):
                issue(t0)
        else:
            offsets = t0 + self.reqs.offset_s
        nxt = 0                       # next scheduled request (open loop)
        trace_t = None
        while True:
            now = clock()
            closing = now >= t_end
            if not closed and not closing:
                while nxt < len(offsets) and offsets[nxt] <= now:
                    issue(offsets[nxt])
                    nxt += 1
            if closing and self.annotate:
                trace.stop()
                trace_t = (t0, now)
                self.annotate = False
            view = queue.view()
            nxt_due = None
            if not closing and not closed and nxt < len(offsets):
                nxt_due = offsets[nxt]
            if not view:
                if nxt_due is None:
                    if closing or closed:
                        break
                    nxt_due = t_end
                self._sleep_until(min(nxt_due, t_end), now)
                continue
            decision = batcher.decide(
                now - t0, view, None if nxt_due is None else nxt_due - t0,
                service)
            if isinstance(decision, Wait):
                wake = t0 + decision.until
                if nxt_due is not None:
                    wake = min(wake, nxt_due)
                self._sleep_until(min(wake, t_end), now)
                continue
            assert isinstance(decision, Flush)
            t_flush = clock()
            batch_reqs = queue.pop_n(decision.count)
            with self.span("pad"):
                batch = p.pad(batch_reqs, decision.bucket)
            with self.span("execute"):
                t1 = clock()
                scores = p.execute(batch)
                t_done = clock()
            service.update(decision.bucket, t_done - t1)
            rids = np.fromiter((r.rid for r in batch_reqs), np.int64,
                               len(batch_reqs))
            batches.append((t_flush, t_done, decision.bucket.batch, rids,
                            scores[:len(batch_reqs)]))
            n_batches += 1
            if p.observe_every and n_batches % p.observe_every == 0:
                with self.span("observe"):
                    p.observe(batch)
            if p.replan_every and n_batches % p.replan_every == 0:
                with self.span("replan"):
                    p.replan()
                replans += 1
            if closed and not closing:
                t_now = clock()
                if t_now < t_end:
                    for _ in batch_reqs:
                        issue(t_now)
        n = len(due)
        flushed = np.full(n, np.nan)
        done = np.full(n, np.nan)
        sc = np.full(n, np.nan, np.float32)
        for t_flush, t_done, _, rids, s in batches:
            flushed[rids] = t_flush
            done[rids] = t_done
            sc[rids] = s
        return Served(t0=t0, t_end=t_end, due=np.asarray(due),
                      admitted=np.asarray(admitted),
                      flushed=flushed, done=done, scores=sc,
                      pool_index=np.asarray(pool_index, np.int64),
                      dropped=dropped,
                      batches=[(a, b, c, d) for a, b, c, d, _ in batches],
                      spans=list(spans), replans=replans, trace_t=trace_t,
                      oversleep=self.oversleep)
