"""The one traffic generator: every mix is a data file ``traffic/<mix>.json``.

A mix file holds only parameters:

* ``ids``: ``{"distribution": "zipf", "alpha", "drift_every", "drift_share",
  "drift_window"}`` or ``{"distribution": "random"}``.  Zipf ranks map to
  rows through a per-table permutation; every ``drift_every`` requests
  ``drift_share`` of the ``drift_window`` hottest ranks of each table swap
  with as many distinct ranks outside that window (production popularity
  drift, after the program's ``data/traces.py``).  ``random`` draws every id
  independently and uniformly over its table (the paper's "Random (Rm)").
* ``pooling``: lookups per bag, every table.
* ``load``: ``{"loop": "open", "arrivals": "poisson", "rate_per_s"}``,
  ``{"loop": "open", "arrivals": "bursty", "rate_per_s", "burst_factor",
  "burst_share", "mean_burst_s"}`` (a two-state Markov-modulated Poisson
  process whose time-weighted mean rate is ``rate_per_s``), or
  ``{"loop": "closed", "outstanding", "pool"}`` (a fixed number of requests
  in flight, each replaced when it completes, drawn in turn from a pool).
* ``slo_ms``: each request's latency limit; ``buckets``: the batch sizes the
  server warms and pads to.

Everything is drawn from the seed with numpy, vectorised: the same seed gives
the same requests, in the same order, at the same offsets.
"""
from __future__ import annotations

import concurrent.futures
import dataclasses
import json
import os
from pathlib import Path
from typing import Optional

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"

_IDS, _DENSE, _ARRIVALS = 1, 2, 3


def load_mix(name: str) -> dict:
    return json.loads((TRAFFIC_DIR / f"{name}.json").read_text())


@dataclasses.dataclass
class Requests:
    """Request ``i``: ``dense[i]`` (n_dense,), ``ids[i]`` (T, L) table-local
    row ids; ``offset_s[i]`` is when it is due, from the window's start
    (open loop only)."""
    dense: np.ndarray
    ids: np.ndarray
    offset_s: Optional[np.ndarray] = None

    def __len__(self) -> int:
        return len(self.dense)


def arrival_offsets(load: dict, seconds: float,
                    rng: np.random.Generator) -> np.ndarray:
    """Due offsets in [0, seconds) of an open-loop schedule."""
    rate = float(load["rate_per_s"])
    kind = load["arrivals"]
    if kind == "poisson":
        n = int(rate * seconds * 1.1) + 64
        t = np.cumsum(rng.exponential(1.0 / rate, n))
        while t[-1] < seconds:
            t = np.concatenate([t, t[-1] + np.cumsum(
                rng.exponential(1.0 / rate, n))])
        return t[t < seconds]
    if kind != "bursty":
        raise ValueError(f"unknown arrivals {kind!r}")
    # MMPP-2 by time rescaling: draw the state path, then unit-rate Poisson
    # points in the integrated rate, mapped back through its inverse
    f, k = float(load["burst_share"]), float(load["burst_factor"])
    if not 0 < f * k < 1:
        raise ValueError("bursty arrivals need burst_share * burst_factor "
                         "in (0, 1)")
    r_burst, r_base = k * rate, rate * (1 - f * k) / (1 - f)
    dwell = {True: float(load["mean_burst_s"]),
             False: float(load["mean_burst_s"]) * (1 - f) / f}
    edges, rates, t, burst = [0.0], [], 0.0, False
    while t < seconds:
        t += rng.exponential(dwell[burst])
        edges.append(t)
        rates.append(r_burst if burst else r_base)
        burst = not burst
    edges = np.asarray(edges)
    cum = np.concatenate([[0.0], np.cumsum(np.diff(edges) * rates)])
    total = np.interp(seconds, edges, cum)
    n = int(total * 1.1) + 64
    u = np.cumsum(rng.exponential(1.0, n))
    while u[-1] < total:
        u = np.concatenate([u, u[-1] + np.cumsum(rng.exponential(1.0, n))])
    u = u[u < total]
    return np.interp(u, cum, edges)


def _zipf_cdf(n: int, alpha: float) -> np.ndarray:
    w = np.arange(1, n + 1, dtype=np.float64) ** -alpha
    cdf = np.cumsum(w) / w.sum()
    cdf[-1] = 1.0
    return cdf


def guide_table(cdf: np.ndarray) -> np.ndarray:
    m = len(cdf)
    return np.searchsorted(cdf, np.arange(m, dtype=np.float64) / m
                           ).astype(np.int32)


def zipf_ranks(cdf: np.ndarray, u: np.ndarray, guide=None) -> np.ndarray:
    """``searchsorted(cdf, u)`` (0-based ranks), through a guide table: a
    rank is found from its bucket's first rank in a few vectorised steps."""
    m = len(cdf)
    if guide is None:
        guide = guide_table(cdf)
    flat = u.ravel()
    k = guide[np.minimum((flat * m).astype(np.int64), m - 1)]
    todo = np.nonzero(cdf[k] < flat)[0]
    while todo.size:
        k[todo] += 1
        todo = todo[cdf[k[todo]] < flat[todo]]
    return k.reshape(u.shape)


def _zipf_table(ids: dict, n_req: int, pooling: int, n_rows: int,
                cdf: np.ndarray, guide: np.ndarray,
                rng: np.random.Generator) -> np.ndarray:
    """(n_req, pooling) ids of one table: zipf ranks through a permutation
    that drifts every ``drift_every`` requests."""
    ranks = zipf_ranks(cdf, rng.random((n_req, pooling)), guide)
    window = min(int(ids["drift_window"]), n_rows // 2)
    m = int(window * float(ids["drift_share"]))
    every = int(ids["drift_every"])
    perm = rng.permutation(n_rows).astype(np.int32)
    hot_order = rng.permutation(window)
    offsets = rng.integers(0, [window, n_rows - window - m + 1],
                           (-(-n_req // every), 2))
    out = np.empty((n_req, pooling), np.int32)
    arange_m = np.arange(m)
    for k, a in enumerate(range(0, n_req, every)):
        b = min(n_req, a + every)
        out[a:b] = perm[ranks[a:b]]
        if b - a < every or m == 0:
            continue
        # a random share of the hot window swaps with a run of as many
        # colder ranks (distinct rows at random depth, as perm is random)
        hot = hot_order[(offsets[k, 0] + arange_m) % window]
        cold = slice(window + offsets[k, 1], window + offsets[k, 1] + m)
        perm[hot], perm[cold] = perm[cold], perm[hot].copy()
    return out


def _zipf_ids(ids: dict, n_req: int, n_tables: int, pooling: int,
              n_rows: int, seed: int) -> np.ndarray:
    cdf = _zipf_cdf(n_rows, float(ids["alpha"]))
    guide = guide_table(cdf)
    out = np.empty((n_req, n_tables, pooling), np.int32)

    def table(t: int) -> None:
        out[:, t] = _zipf_table(ids, n_req, pooling, n_rows, cdf, guide,
                                np.random.default_rng([seed, _IDS, t]))

    # tables are independent streams: numpy releases the GIL in the
    # searches and gathers, so set-up spreads them over a few threads
    workers = min(16, n_tables, os.cpu_count() or 1)
    with concurrent.futures.ThreadPoolExecutor(workers) as pool:
        list(pool.map(table, range(n_tables)))
    return out


def make_ids(ids: dict, n_req: int, n_tables: int, pooling: int,
             n_rows: int, seed: int) -> np.ndarray:
    dist = ids["distribution"]
    if dist == "random":
        return np.random.default_rng([seed, _IDS]).integers(
            0, n_rows, (n_req, n_tables, pooling), dtype=np.int32)
    if dist == "zipf":
        return _zipf_ids(ids, n_req, n_tables, pooling, n_rows, seed)
    raise ValueError(f"unknown id distribution {dist!r}")


def generate(mix: dict, model: dict, seed: int, seconds: float) -> Requests:
    """Every request a run can need: the whole schedule of an open loop, or
    the pool a closed loop draws from in turn."""
    load = mix["load"]
    offsets = None
    if load["loop"] == "open":
        offsets = arrival_offsets(
            load, seconds, np.random.default_rng([seed, _ARRIVALS]))
        n = len(offsets)
    elif load["loop"] == "closed":
        n = int(load["pool"])
    else:
        raise ValueError(f"unknown loop {load['loop']!r}")
    ids = make_ids(mix["ids"], n, model["n_tables"], int(mix["pooling"]),
                   model["emb_num"], seed)
    dense = np.random.default_rng([seed, _DENSE]).standard_normal(
        (n, model["n_dense"]), dtype=np.float32)
    return Requests(dense=dense, ids=ids, offset_s=offsets)
