"""Page-granular placement for sharded embedding tables (paper section IV-B1).

The logical embedding address space (all tables stacked) is divided into
fixed-size pages (default 4 KB worth of rows, like the OS pages the paper
manages).  Every page lives in exactly one location:

  * HOT tier  — replicated on every device ("Private Hot Region" / local DRAM
                in the paper; local-HBM replica in the TPU mapping), or
  * COLD tier — one shard of the row-sharded cold storage ("Public Cold
                Region" spread over CXL memory devices; `model`-axis shards
                in the TPU mapping).

The indirection (`page_to_shard`, `page_to_slot`) is the FM-endpoint memory
indexing unit of the paper: lookups go through it, so the planner can migrate
pages without callers noticing (lookup results are placement-invariant — this
is tested as a property).
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.sls import LANES

HOT_SHARD = -1  # sentinel in page_to_shard

STORAGE_FORMATS = ("fp32", "int8")  # cold-tier storage format knob


@dataclasses.dataclass(frozen=True)
class PagingConfig:
    total_rows: int            # stacked rows across all tables
    dim: int
    n_shards: int              # size of the `model` axis
    page_bytes: int = 4096
    itemsize: int = 4          # logical (hot-tier / fp32) bytes per element
    hot_fraction: float = 0.05  # fraction of pages the hot tier can hold
    headroom: float = 1.3      # cold-shard slot over-provisioning for imbalance
    storage: str = "fp32"      # cold-tier storage: fp32 passthrough or int8

    def __post_init__(self):
        if self.storage not in STORAGE_FORMATS:
            raise ValueError(f"unknown storage {self.storage!r}; "
                             f"expected one of {STORAGE_FORMATS}")

    @property
    def cold_itemsize(self) -> int:
        """*Stored* bytes per element in the cold tier — the bytes that
        actually cross the memory interface (the paper's CXL traffic)."""
        return 1 if self.storage == "int8" else self.itemsize

    @property
    def store_dim(self) -> int:
        """Stored row width: ``dim`` rounded up to the TPU's 128-lane tile.
        The chip lays out and DMAs rows only in whole lane tiles, so the
        store is padded once, when it is built, instead of being copied to
        a padded layout inside every program that reads it.  The pad lanes
        hold zeros and never leave the engine."""
        return -(-self.dim // LANES) * LANES

    @property
    def page_size(self) -> int:
        """Rows per page (>=1).  ``page_bytes`` counts the bytes of the
        logical ``dim`` lanes in the cold tier's storage format, so an int8
        cold tier packs ``itemsize/cold_itemsize``x the rows per page; hot
        pages hold the same rows at fp32 width (they are larger in DRAM —
        the hot tier is small by construction).  A page occupies
        ``store_dim/dim``x ``page_bytes`` of device memory when ``dim`` is
        not lane-aligned."""
        return max(1, self.page_bytes // (self.dim * self.cold_itemsize))

    @property
    def num_pages(self) -> int:
        return -(-self.total_rows // self.page_size)

    @property
    def hot_pages(self) -> int:
        return max(1, int(self.num_pages * self.hot_fraction))

    @property
    def pages_per_shard(self) -> int:
        base = -(-self.num_pages // self.n_shards)
        return max(1, int(np.ceil(base * self.headroom)))

    @property
    def rows_per_shard(self) -> int:
        return self.pages_per_shard * self.page_size

    @property
    def padded_rows(self) -> int:
        return self.num_pages * self.page_size

    @property
    def cold_rows_total(self) -> int:
        return self.n_shards * self.rows_per_shard

    @property
    def hot_rows(self) -> int:
        return self.hot_pages * self.page_size


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PageTable:
    """Placement state: for each page, its tier/shard and slot."""
    page_to_shard: jax.Array   # (num_pages,) int32; HOT_SHARD => hot tier
    page_to_slot: jax.Array    # (num_pages,) int32; slot within shard or hot tier

    def tree_flatten(self):
        return (self.page_to_shard, self.page_to_slot), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def initial_page_table(cfg: PagingConfig) -> PageTable:
    """Paper's initial policy: interleave cold pages round-robin across shards
    (section IV-B3 "initially spread them ... through the interleave policy").
    Hot tier starts empty; the planner promotes pages after observing traffic.
    """
    pages = np.arange(cfg.num_pages)
    shard = (pages % cfg.n_shards).astype(np.int32)
    slot = (pages // cfg.n_shards).astype(np.int32)
    assert slot.max(initial=0) < cfg.pages_per_shard, "headroom too small"
    return PageTable(jnp.asarray(shard), jnp.asarray(slot))


def locate(cfg: PagingConfig, table: PageTable, row_idx: jax.Array
           ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """row id -> (shard, local_row, is_hot). Pure, vectorized, static-shape."""
    ps = cfg.page_size
    page = row_idx // ps
    offset = row_idx % ps
    shard = table.page_to_shard[page]
    local_row = table.page_to_slot[page] * ps + offset
    is_hot = shard == HOT_SHARD
    return shard, local_row, is_hot


def placement_gather_indices(cfg: PagingConfig, old: PageTable, new: PageTable
                             ) -> Tuple[np.ndarray, np.ndarray]:
    """Row-level gather maps realizing a migration (host-side, numpy).

    Returns (cold_src, hot_src): for each destination row in the new cold
    storage (resp. new hot tier), the source position in the *concatenated*
    old storage [cold_flat | hot_flat].  Unmapped destination rows point at
    source 0 (their content is unused — no page maps to them).

    This is the cache-line-granular migration of section IV-B4: the copy is a
    pure gather, no page is ever "blocked"; in the latency simulator the
    page-block vs line-granular costs are modeled explicitly.
    """
    ps = cfg.page_size
    o_shard = np.asarray(old.page_to_shard)
    o_slot = np.asarray(old.page_to_slot)
    n_shard = np.asarray(new.page_to_shard)
    n_slot = np.asarray(new.page_to_slot)

    def src_base(shard, slot):
        # position of a page's first row in [cold_flat | hot_flat]
        cold = shard * cfg.rows_per_shard + slot * ps
        hot = cfg.cold_rows_total + slot * ps
        return np.where(shard == HOT_SHARD, hot, cold)

    src = src_base(o_shard, o_slot)                      # (P,)
    cold_src = np.zeros(cfg.cold_rows_total, dtype=np.int64)
    hot_src = np.zeros(cfg.hot_rows, dtype=np.int64)

    row_offsets = np.arange(ps)
    cold_mask = n_shard != HOT_SHARD
    cold_pages = np.nonzero(cold_mask)[0]
    dst = (n_shard[cold_pages] * cfg.rows_per_shard + n_slot[cold_pages] * ps)
    cold_src[(dst[:, None] + row_offsets).ravel()] = (
        src[cold_pages][:, None] + row_offsets).ravel()

    hot_pages = np.nonzero(~cold_mask)[0]
    dsth = n_slot[hot_pages] * ps
    hot_src[(dsth[:, None] + row_offsets).ravel()] = (
        src[hot_pages][:, None] + row_offsets).ravel()
    return cold_src, hot_src
