"""Placement planner: global hotness detection + embedding spreading
(paper sections IV-B2, IV-B3).

Host-side control-plane logic (numpy), mirroring the paper's host daemon:
  1. *Global hotness detection*: rank pages by (decayed) access frequency;
     promote the top `hot_pages` into the replicated hot tier, but only evict
     a resident hot page when a challenger exceeds it by more than
     `cold_age_threshold` (hysteresis, paper default 20%, best 16%).
  2. *Embedding spreading*: distribute cold pages across shards so per-shard
     access load is balanced.  A shard whose load exceeds the mean by
     `1 - migrate_threshold` (default 35%) triggers redistribution; we realize
     the paper's iterative pairwise rebalance with a weighted LPT bin-pack of
     the pages that need (re)placement, which converges to the same balanced
     fixed point without the O(rounds) loop.

The planner only produces a new PageTable; executing the move is
`repro.core.pifs.PIFSEmbeddingEngine.migrate` (a pure gather — the cache-line
granular migration of section IV-B4).
"""
from __future__ import annotations

import dataclasses
import heapq
from typing import Optional, Tuple

import numpy as np

from repro.core.paging import HOT_SHARD, PageTable, PagingConfig


@dataclasses.dataclass(frozen=True)
class PlannerConfig:
    migrate_threshold: float = 0.35   # paper section IV-B3 (best value, Fig 13a)
    cold_age_threshold: float = 0.16  # paper section VI-C6 (best value, Fig 13d)
    sticky: bool = True               # keep resident placements when possible


def shard_loads(cfg: PagingConfig, table: PageTable, counts: np.ndarray
                ) -> np.ndarray:
    """Access load per cold shard."""
    shard = np.asarray(table.page_to_shard)
    loads = np.zeros(cfg.n_shards)
    cold = shard != HOT_SHARD
    np.add.at(loads, shard[cold], counts[cold])
    return loads


def needs_migration(cfg: PagingConfig, table: PageTable, counts: np.ndarray,
                    pcfg: PlannerConfig) -> bool:
    """Paper trigger: a node is 'warm' when its access count exceeds the mean
    of the others by more than (1 - migrate_threshold)."""
    loads = shard_loads(cfg, table, counts)
    mean = loads.mean()
    if mean <= 0:
        return False
    return bool(loads.max() > mean * (2.0 - pcfg.migrate_threshold))


def plan(cfg: PagingConfig, table: PageTable, counts: np.ndarray,
         pcfg: Optional[PlannerConfig] = None) -> Tuple[PageTable, dict]:
    """Compute a new placement from page access counts.

    Returns (new_table, stats) where stats records what the paper reports:
    moved-page count, load std-dev before/after (Fig. 13b), hot promotions.
    """
    pcfg = pcfg or PlannerConfig()
    counts = np.asarray(counts, dtype=np.float64)
    old_shard = np.asarray(table.page_to_shard)
    old_slot = np.asarray(table.page_to_slot)
    P = cfg.num_pages

    # ---- 1. hot set selection with hysteresis --------------------------------
    order = np.argsort(-counts, kind="stable")
    want_hot = set(order[: cfg.hot_pages].tolist())
    resident_hot = set(np.nonzero(old_shard == HOT_SHARD)[0].tolist())
    if pcfg.sticky and resident_hot:
        # evict a resident page only if some challenger beats it by
        # margin.  The coldest member is the victim (a heap: the hot set
        # holds ~5% of the pages, so a linear scan per challenger is
        # quadratic at table scale); challengers come hottest first and
        # the coldest member only warms, so the first challenger that
        # loses ends the scan.
        new_hot = set(resident_hot)
        heap = [(counts[p], p) for p in new_hot]
        heapq.heapify(heap)
        challengers = [int(p) for p in order[: 4 * cfg.hot_pages]
                       if p not in resident_hot]
        for c in challengers:
            if len(new_hot) < cfg.hot_pages:
                new_hot.add(c)
                heapq.heappush(heap, (counts[c], c))
                continue
            floor, victim = heap[0]
            if not counts[c] > floor * (1.0 + pcfg.cold_age_threshold):
                break
            heapq.heapreplace(heap, (counts[c], c))
            new_hot.discard(victim)
            new_hot.add(c)
        hot_set = new_hot
    else:
        hot_set = want_hot
    hot_list = sorted(hot_set, key=lambda p: -counts[p])[: cfg.hot_pages]
    hot_mask = np.zeros(P, dtype=bool)
    hot_mask[hot_list] = True

    # ---- 2. embedding spreading over cold shards -----------------------------
    new_shard = np.full(P, HOT_SHARD, dtype=np.int32)
    new_slot = np.zeros(P, dtype=np.int32)
    new_slot[hot_list] = np.arange(len(hot_list), dtype=np.int32)

    cold_pages = np.nonzero(~hot_mask)[0]
    loads = np.zeros(cfg.n_shards)
    fill = np.zeros(cfg.n_shards, dtype=np.int64)

    sticky_kept = 0
    if pcfg.sticky and not needs_migration(cfg, table, counts, pcfg):
        # no node is warm: keep every already-cold page in place (slots
        # stay unique because assignment within a shard is unchanged)
        keep = cold_pages[old_shard[cold_pages] != HOT_SHARD]
        new_shard[keep] = old_shard[keep]
        new_slot[keep] = old_slot[keep]
        np.add.at(loads, old_shard[keep], counts[keep])
        np.maximum.at(fill, old_shard[keep], old_slot[keep] + 1)
        sticky_kept = int(keep.size)
        unplaced = cold_pages[new_shard[cold_pages] == HOT_SHARD]
    else:
        unplaced = cold_pages

    # weighted LPT: heaviest page -> least-loaded shard with capacity
    order_c = unplaced[np.argsort(-counts[unplaced], kind="stable")]
    cap = cfg.pages_per_shard
    for p in order_c:
        cands = np.nonzero(fill < cap)[0]
        s = cands[np.argmin(loads[cands])]
        new_shard[p] = s
        new_slot[p] = fill[s]
        fill[s] += 1
        loads[s] += counts[p]

    moved = int(np.sum((new_shard != old_shard) | (new_slot != old_slot)))
    stats = {
        "moved_pages": moved,
        "moved_fraction": moved / max(1, P),
        "sticky_kept": sticky_kept,
        "hot_pages": len(hot_list),
        "load_std_before": float(shard_loads(cfg, table, counts).std()),
        "load_std_after": float(loads.std()),
        "load_max_over_mean": float(loads.max() / max(loads.mean(), 1e-9)),
    }
    return PageTable(
        page_to_shard=np.asarray(new_shard),
        page_to_slot=np.asarray(new_slot),
    ), stats
