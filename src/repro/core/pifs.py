"""PIFSEmbeddingEngine: the paper's contribution as a composable JAX module.

Distributed embedding lookup with three execution modes (paper baselines):

  * ``pifs``   — reduce-then-communicate: each `model`-axis shard runs a
                 partial SLS over the rows it owns (the fabric-switch Process
                 Core), and only pooled ``(bags, D)`` partials cross the ICI
                 (psum / psum_scatter).  Hot-tier hits are served from a
                 replicated local copy with zero communication.
  * ``pond``   — communicate-then-reduce: shards ship the *raw rows*
                 (``bags*L*D`` bytes) and the bag owner reduces — the
                 host-centric CXL baseline (Pond).  With a planner-populated
                 hot tier this is the paper's "Pond + PM".
  * ``beacon`` — reduce-then-communicate but with tiering disabled
                 (all-"CXL" placement): construct the engine with
                 ``hot_fraction=0`` and never run the planner.  Mode string
                 maps to the pifs code path; the placement is what differs.

State is a pure pytree; every method is functional.  Lookup results are
placement-invariant (property-tested): the planner may migrate pages at any
time without perturbing numerics.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import quant
from repro.core import sls as sls_ops
from repro.core.paging import (HOT_SHARD, PageTable, PagingConfig,
                               initial_page_table, locate,
                               placement_gather_indices)
from repro.core.planner import PlannerConfig, plan
from repro.core.spans import SPANS
from repro.distributed.sharding import MeshAxes, axes_for, shard_map


@jax.tree_util.register_pytree_with_keys_class
@dataclasses.dataclass
class EngineState:
    cold: jax.Array           # (n_shards * rows_per_shard, D) sharded over tp;
    #                           fp32, or int8 codes for storage='int8'
    hot: jax.Array            # (hot_rows, D) fp32 replicated (never quantized)
    page_scales: jax.Array    # (num_pages,) float32 replicated per-page dequant
    #                           scales (all-ones for fp32 storage).  Indexed by
    #                           *global* page id, so a scale travels with its
    #                           page across any migration untouched — that is
    #                           what makes cold->hot->cold round trips exact
    #                           (demotion re-quantizes with the carried scale
    #                           and recovers the codes bit-for-bit).
    page_to_shard: jax.Array  # (num_pages,) int32 replicated
    page_to_slot: jax.Array   # (num_pages,) int32 replicated
    counts: jax.Array         # (num_pages,) float32 replicated access histogram

    _FIELDS = ("cold", "hot", "page_scales", "page_to_shard", "page_to_slot",
               "counts")

    def tree_flatten_with_keys(self):
        # named keys (not positional indices) so checkpoint manifests keep
        # stable leaf names across state-layout changes
        return (tuple((jax.tree_util.GetAttrKey(f), getattr(self, f))
                      for f in self._FIELDS), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def page_table(self) -> PageTable:
        return PageTable(self.page_to_shard, self.page_to_slot)


def _take_two_tier(cold: jax.Array, hot: jax.Array, src: jax.Array
                   ) -> jax.Array:
    """Rows of the concatenation ``[cold | hot]`` at positions ``src``,
    without a second store-sized buffer: one gather from ``cold``, then the
    few rows sourced from ``hot`` (at most one per hot row — each page has
    one destination) are patched in place.  At the paper's table sizes a
    full-store concat, or a select between two full-size gathers, does not
    fit one chip's HBM beside the old and new store."""
    C = cold.shape[0]
    from_hot = src >= C
    out = jnp.take(cold, jnp.where(from_hot, 0, src), axis=0)
    n = min(hot.shape[0], src.shape[0])
    pos = jnp.nonzero(from_hot, size=n, fill_value=src.shape[0])[0]
    hsrc = jnp.take(src, jnp.minimum(pos, src.shape[0] - 1)) - C
    rows = jnp.take(hot, jnp.clip(hsrc, 0, hot.shape[0] - 1), axis=0)
    return out.at[pos].set(rows.astype(out.dtype), mode="drop")


class PIFSEmbeddingEngine:
    """Sharded multi-table embedding with paged placement + hot tier."""

    DEDUP_MODES = ("off", "auto", "on")
    FRONT_END_MODES = ("split", "fused")
    TIER_MODES = ("all", "hot_only")

    def __init__(self, paging: PagingConfig, mesh: Mesh,
                 axes: Optional[MeshAxes] = None,
                 planner: Optional[PlannerConfig] = None,
                 dtype=jnp.float32, dedup: str = "off",
                 dedup_auto_threshold: float = 1.5,
                 dedup_staging_bytes: int = 4 << 20,
                 validate_ids: bool = False):
        """``dedup`` is the engine-wide default for :meth:`lookup`'s
        gather-once duplicate-coalescing knob (off / auto / on);
        ``dedup_auto_threshold`` is the expected batch-level duplicate
        factor above which ``auto`` turns coalescing on for a plan, and
        ``dedup_staging_bytes`` bounds the per-device staging buffer — a
        signature whose worst-case staging exceeds it falls back to the
        non-dedup datapath (exact, just without the bytes win).
        ``validate_ids`` is the strict-mode debug knob: lookups check their
        (concrete, host-visible) indices against the padded address space
        and raise on out-of-range ids instead of letting the device gather
        clamp them silently — OOB traffic otherwise serves row 0 /
        last-row embeddings with no error at all."""
        self.cfg = paging
        self.mesh = mesh
        self.axes = axes or axes_for(mesh)
        self.planner = planner or PlannerConfig()
        self.dtype = dtype
        if dedup not in self.DEDUP_MODES:
            raise ValueError(f"unknown dedup {dedup!r}; "
                             f"expected one of {self.DEDUP_MODES}")
        self.default_dedup = dedup
        self.validate_ids = validate_ids
        self.dedup_auto_threshold = dedup_auto_threshold
        self.dedup_staging_bytes = dedup_staging_bytes
        # optional measured-duplicate-factor hint for 'auto' resolutions
        # that happen under an outer trace (serving warmup): the page
        # histogram cannot see row-level skew when hot rows are scattered
        # across pages (production id hashing does exactly that), so
        # serving primes this from a measured replay of the live stream's
        # prefix (repro.serving.prime_dedup_auto)
        self.dedup_auto_hint: Optional[float] = None
        # compiled-lookup plan registry: signature -> shard_map+jit closure,
        # built once per (mode, combine, dp_shard, impl, dedup, shapes) and
        # reused so steady-state serving never retraces (lru_cache-style, but
        # explicit so plan_stats() can report hits/traces).
        self._plans: dict = {}
        self._dedup_plans: dict = {}   # key -> resolution record (plan_stats)
        self._fe_plans: dict = {}      # key -> front-end resolution record
        self._migrate_plan = None
        self._trace_count = 0
        self._plan_calls = 0
        # host-side copy of the page-access histogram, refreshed by
        # observe()/plan_and_migrate(): dedup='auto' resolution may run
        # under an outer jit trace where state.counts is a tracer
        self._host_counts: Optional[np.ndarray] = None
        if self.axes.tp_size(mesh) != paging.n_shards:
            raise ValueError(
                f"paging.n_shards={paging.n_shards} != tp axis size "
                f"{self.axes.tp_size(mesh)}")

    @property
    def quantized(self) -> bool:
        return self.cfg.storage == "int8"

    @property
    def cold_dtype(self):
        """Cold-tier storage dtype (int8 codes for storage='int8')."""
        return jnp.int8 if self.quantized else self.dtype

    # ------------------------------------------------------------------ specs
    def state_pspecs(self) -> EngineState:
        tp = self.axes.tp
        return EngineState(
            cold=P(tp), hot=P(), page_scales=P(), page_to_shard=P(),
            page_to_slot=P(), counts=P())

    def state_shapes(self) -> EngineState:
        c = self.cfg
        return EngineState(
            cold=jax.ShapeDtypeStruct((c.cold_rows_total, c.store_dim),
                                      self.cold_dtype),
            hot=jax.ShapeDtypeStruct((c.hot_rows, c.store_dim), self.dtype),
            page_scales=jax.ShapeDtypeStruct((c.num_pages,), jnp.float32),
            page_to_shard=jax.ShapeDtypeStruct((c.num_pages,), jnp.int32),
            page_to_slot=jax.ShapeDtypeStruct((c.num_pages,), jnp.int32),
            counts=jax.ShapeDtypeStruct((c.num_pages,), jnp.float32),
        )

    def state_shardings(self) -> EngineState:
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s),
                            self.state_pspecs(),
                            is_leaf=lambda x: isinstance(x, P))

    def commit(self, state: EngineState) -> EngineState:
        """Place every leaf on :meth:`state_shardings`.  jit keys its
        cache on input shardings, so every method that produces an
        EngineState returns it through here: a leaf left uncommitted or on
        another sharding would retrace every compiled plan after each
        maintenance step.  A leaf already placed there is not copied."""
        return jax.device_put(state, self.state_shardings())

    def _pad_lanes(self, rows: jax.Array) -> jax.Array:
        """(..., dim) -> (..., store_dim), zero pad lanes."""
        pad = self.cfg.store_dim - rows.shape[-1]
        if pad == 0:
            return rows
        return jnp.pad(rows, [(0, 0)] * (rows.ndim - 1) + [(0, pad)])

    def _logical(self, rows: jax.Array) -> jax.Array:
        """(..., store_dim) -> (..., dim): drop the pad lanes."""
        return rows[..., :self.cfg.dim]

    def optimizer_step(self, emb_optimizer, state: EngineState,
                       grad_cold: jax.Array, grad_hot: jax.Array, opt_state
                       ) -> Tuple[EngineState, object]:
        """Apply an embedding optimizer to both tiers over the logical
        ``dim`` lanes only, so a row-wise statistic never averages in the
        store's zero pad lanes (which stay zero)."""
        params = {"cold": self._logical(state.cold),
                  "hot": self._logical(state.hot)}
        grads = {"cold": self._logical(grad_cold),
                 "hot": self._logical(grad_hot)}
        new, new_opt = emb_optimizer.update(grads, opt_state, params)
        return dataclasses.replace(
            state, cold=self._pad_lanes(new["cold"]),
            hot=self._pad_lanes(new["hot"])), new_opt

    # ------------------------------------------------------------------- init
    def init_state(self, key: jax.Array, scale: float = 0.01) -> EngineState:
        """Random-init tables, initial round-robin interleave placement.

        Equal to ``from_dense(normal(key, (padded_rows, D)) * scale)``, but
        built by one program with the state's output shardings: the
        interleave puts page ``k * n_shards + s`` in slot ``k`` of shard
        ``s``, so drawing the pages as ``(slots, n_shards, page, D)`` and
        moving the shard axis first is the cold layout, and the counter-
        based generator gives every row the same value in any draw shape.
        Each shard draws only its own pages, so the store never exists as
        one dense array on one device.  Rows are stored ``store_dim`` wide
        (see ``PagingConfig.store_dim``)."""
        c = self.cfg
        n, ps, D = c.n_shards, c.page_size, c.dim
        S = -(-c.num_pages // n)             # interleave slots in use
        table = initial_page_table(c)

        def build(key):
            # the barrier keeps XLA from fusing the scale into the
            # generator's arithmetic (which changes the rounding)
            pages = jax.lax.optimization_barrier(
                jax.random.normal(key, (S, n, ps, D), self.dtype)) * scale
            page_id = jnp.arange(S)[:, None] * n + jnp.arange(n)[None, :]
            pages = jnp.where((page_id < c.num_pages)[:, :, None, None],
                              pages, 0).transpose(1, 0, 2, 3)
            if self.quantized:
                codes, sc = quant.quantize_pages(pages.reshape(n * S, ps, D))
                pages = codes.reshape(n, S, ps, D)
                scales = sc.reshape(n, S).T.reshape(-1)[:c.num_pages]
            else:
                scales = jnp.ones((c.num_pages,), jnp.float32)
            cold = jnp.pad(pages, ((0, 0), (0, c.pages_per_shard - S),
                                   (0, 0), (0, c.store_dim - D)))
            return EngineState(
                cold=cold.reshape(c.cold_rows_total, c.store_dim),
                hot=jnp.zeros((c.hot_rows, c.store_dim), self.dtype),
                page_scales=scales,
                page_to_shard=jnp.asarray(table.page_to_shard, jnp.int32),
                page_to_slot=jnp.asarray(table.page_to_slot, jnp.int32),
                counts=jnp.zeros((c.num_pages,), jnp.float32))

        return jax.jit(build, out_shardings=self.state_shardings())(key)

    def from_dense(self, dense: jax.Array, table: Optional[PageTable] = None
                   ) -> EngineState:
        """Pack a dense (rows, D) table into paged/sharded storage.

        With ``storage='int8'`` every page gets a symmetric per-page scale
        and cold pages are stored as int8 codes; hot pages keep their raw
        fp32 values (hot-hit numerics are untouched), but still carry a
        scale so a later demotion quantizes deterministically.  Note the
        default placement starts with an *empty* hot tier, so in the
        canonical lifecycle every hot page was once cold — its values sit
        on the quantized grid and all later migrations are bit-exact.
        """
        c = self.cfg
        if table is None:
            table = initial_page_table(c)
        if dense.shape[0] < c.padded_rows:
            pad = c.padded_rows - dense.shape[0]
            dense = jnp.concatenate(
                [dense, jnp.zeros((pad, c.dim), dense.dtype)], axis=0)
        dense = self._pad_lanes(dense)
        ps = c.page_size
        shard = np.asarray(table.page_to_shard)
        slot = np.asarray(table.page_to_slot)
        # destination row for each source page
        cold_dst = shard.astype(np.int64) * c.rows_per_shard + slot.astype(np.int64) * ps
        hot_dst = slot.astype(np.int64) * ps
        row_off = np.arange(ps)
        cold_pages = np.nonzero(shard != HOT_SHARD)[0]
        hot_pages = np.nonzero(shard == HOT_SHARD)[0]

        if self.quantized:
            q_pages, scales = quant.quantize_pages(
                dense.reshape(c.num_pages, ps, c.store_dim))
            cold_vals = q_pages.reshape(c.num_pages * ps, c.store_dim)
        else:
            scales = jnp.ones((c.num_pages,), jnp.float32)
            cold_vals = dense
        cold = jnp.zeros((c.cold_rows_total, c.store_dim), self.cold_dtype)
        hot = jnp.zeros((c.hot_rows, c.store_dim), dense.dtype)
        if cold_pages.size:
            dst = (cold_dst[cold_pages, None] + row_off).ravel()
            src = (cold_pages[:, None] * ps + row_off).ravel()
            cold = cold.at[dst].set(cold_vals[src])
        if hot_pages.size:
            dst = (hot_dst[hot_pages, None] + row_off).ravel()
            src = (hot_pages[:, None] * ps + row_off).ravel()
            hot = hot.at[dst].set(dense[src])
        return self.commit(EngineState(
            cold=cold, hot=hot, page_scales=scales,
            page_to_shard=jnp.asarray(shard, jnp.int32),
            page_to_slot=jnp.asarray(slot, jnp.int32),
            counts=jnp.zeros((c.num_pages,), jnp.float32)))

    def to_dense(self, state: EngineState) -> jax.Array:
        """Inverse of from_dense (tests / checkpoints / planner-free export).

        For ``storage='int8'`` the cold tier is dequantized, so the result
        is the *effective* table every lookup path computes against.
        """
        c = self.cfg
        ps = c.page_size
        row = jnp.arange(c.padded_rows)
        shard, local_row, is_hot = locate(c, state.page_table, row)
        cold_pos = shard * c.rows_per_shard + local_row
        cold_rows = jnp.take(state.cold, jnp.where(is_hot, 0, cold_pos), axis=0)
        if self.quantized:
            cold_rows = quant.dequantize_rows(
                cold_rows, state.page_scales[row // ps][:, None])
        hot_rows = jnp.take(state.hot, jnp.where(is_hot, local_row, 0), axis=0)
        return self._logical(jnp.where(is_hot[:, None], hot_rows, cold_rows))

    def export_state(self, state: EngineState
                     ) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """Placement-invariant logical export in each tier's *native* domain.

        Returns ``(codes, values, scales)``: ``codes`` is (padded_rows, D)
        in the cold-tier storage dtype — cold-resident rows are their stored
        representation verbatim (int8 codes for ``storage='int8'``), hot-
        resident rows are their demoted form (re-quantized on the page's
        carried scale, exactly what :meth:`migrate` would write on a
        demotion); ``values`` is (padded_rows, D) fp32 — hot rows verbatim,
        cold rows dequantized with the carried scale (exactly what a
        promotion would write); ``scales`` is ``state.page_scales``
        untouched.  For fp32 storage ``codes`` and ``values`` are the same
        dense table.

        Together with :meth:`pack_state` this is the cross-engine analog of
        the typed migration gather: page geometry (``page_size`` /
        ``num_pages``) depends only on dim/page_bytes/storage — never on
        ``n_shards`` — so the triple round-trips bit-exactly through any
        placement on any tp size (the elastic re-mesh path,
        ``repro.runtime.elastic.remesh_engine``, is built on it)."""
        c = self.cfg
        ps = c.page_size
        row = jnp.arange(c.padded_rows)
        shard, local_row, is_hot = locate(c, state.page_table, row)
        cold_pos = shard * c.rows_per_shard + local_row
        cold_rows = self._logical(
            jnp.take(state.cold, jnp.where(is_hot, 0, cold_pos), axis=0))
        hot_rows = self._logical(
            jnp.take(state.hot, jnp.where(is_hot, local_row, 0), axis=0))
        if self.quantized:
            s = state.page_scales[row // ps][:, None]
            codes = jnp.where(is_hot[:, None],
                              quant.quantize_rows(hot_rows, s), cold_rows)
            values = jnp.where(is_hot[:, None], hot_rows,
                               quant.dequantize_rows(cold_rows, s))
        else:
            codes = values = jnp.where(is_hot[:, None], hot_rows, cold_rows)
        return codes, values, state.page_scales

    def pack_state(self, codes: jax.Array, values: jax.Array,
                   page_scales: jax.Array, table: Optional[PageTable] = None,
                   counts=None) -> EngineState:
        """Inverse of :meth:`export_state` under any placement on *this*
        engine's mesh: cold slots take their rows from ``codes`` (storage-
        native, moved verbatim — never re-quantized), hot slots from
        ``values`` (fp32, moved verbatim), and ``page_scales`` is carried
        untouched.  Packing therefore preserves the quantized domain
        exactly: a page that was cold there and lands cold here keeps its
        codes bit-for-bit, a hot->cold transition is the standard carried-
        scale demotion, and cold->hot the standard dequantize promotion —
        the same tier-boundary semantics as :meth:`migrate`."""
        c = self.cfg
        if table is None:
            table = initial_page_table(c)
        ps = c.page_size
        shard = np.asarray(table.page_to_shard)
        slot = np.asarray(table.page_to_slot)
        cold_dst = (shard.astype(np.int64) * c.rows_per_shard
                    + slot.astype(np.int64) * ps)
        hot_dst = slot.astype(np.int64) * ps
        row_off = np.arange(ps)
        cold_pages = np.nonzero(shard != HOT_SHARD)[0]
        hot_pages = np.nonzero(shard == HOT_SHARD)[0]
        cold = jnp.zeros((c.cold_rows_total, c.store_dim), self.cold_dtype)
        hot = jnp.zeros((c.hot_rows, c.store_dim), self.dtype)
        codes = self._pad_lanes(jnp.asarray(codes))
        values = self._pad_lanes(jnp.asarray(values))
        if cold_pages.size:
            dst = (cold_dst[cold_pages, None] + row_off).ravel()
            src = (cold_pages[:, None] * ps + row_off).ravel()
            cold = cold.at[dst].set(codes[src].astype(self.cold_dtype))
        if hot_pages.size:
            dst = (hot_dst[hot_pages, None] + row_off).ravel()
            src = (hot_pages[:, None] * ps + row_off).ravel()
            hot = hot.at[dst].set(values[src].astype(self.dtype))
        if counts is None:
            counts = jnp.zeros((c.num_pages,), jnp.float32)
        state = EngineState(
            cold=cold, hot=hot,
            page_scales=jnp.asarray(page_scales, jnp.float32),
            page_to_shard=jnp.asarray(shard, jnp.int32),
            page_to_slot=jnp.asarray(slot, jnp.int32),
            counts=jnp.asarray(counts, jnp.float32))
        # the inputs may live on a different (larger/smaller) mesh — the
        # elastic re-mesh path hands us arrays computed under the pre-loss
        # mesh's sharding
        return self.commit(state)

    # ----------------------------------------------------------------- lookup
    def _check_ids(self, indices) -> None:
        """Strict-mode OOB guard (``validate_ids=True``): raise host-side on
        ids outside the padded address space instead of letting the device
        gather clamp them to valid rows silently.  Only concrete arrays can
        be checked — under an outer jit trace the caller (e.g.
        ``ServeBinding.execute``) must validate the host batch *before*
        entering the trace, which is where serving wires this in."""
        if isinstance(indices, jax.core.Tracer):
            return
        idx = np.asarray(indices)
        bad = (idx < 0) | (idx >= self.cfg.padded_rows)
        if bad.any():
            n = int(bad.sum())
            example = int(idx[np.unravel_index(np.argmax(bad), idx.shape)])
            raise ValueError(
                f"validate_ids: {n} out-of-range id(s) in lookup batch "
                f"(e.g. {example}; valid range is [0, "
                f"{self.cfg.padded_rows})) — the device gather would have "
                "clamped these to real rows and served wrong embeddings "
                "silently")

    def lookup(self, state: EngineState, indices: jax.Array,
               weights: Optional[jax.Array] = None, mode: str = "pifs",
               combine: str = "psum", dp_shard: bool = True,
               impl: str = "jnp", block_l: int = 8,
               dedup: Optional[str] = None,
               tiers: str = "all") -> jax.Array:
        """Pooled lookup.

        indices: (B, G, L) int32 — B batch (sharded over dp), G bags per
        example (e.g. tables), L lookups per bag.  Returns (B, G, D) for
        combine='psum', or (B, G, D) sharded additionally over tp on the batch
        dim for combine='psum_scatter' (caller's consumer must accept that
        layout; it halves collective bytes).
        weights: optional (B, G, L).
        impl: 'jnp' (gather + segment-sum; differentiable) or 'pallas'
        (the bag-tiled masked-partial SLS kernel; serving fast path).
        dedup: 'off' | 'auto' | 'on' (None = the engine default) —
        gather-once duplicate coalescing: each shard sort-uniques its owned
        (nbags*L) rows and gathers/dequantizes every unique row exactly
        once; the accumulate order is unchanged, so results are bit-for-bit
        equal to 'off'.  'auto' decides per plan from the observe-phase
        access histogram (expected duplicate factor >= the engine
        threshold); 'on' still falls back for signatures whose staging
        exceeds the VMEM budget.  The decision is frozen into the cached
        plan (the key carries the *requested* knob), so 'auto' never
        retraces across observe/replan cycles.
        tiers: 'all' (normal) or 'hot_only' — the serving brown-out rung:
        only the replicated hot tier is read, cold rows contribute exact
        zeros, and **no cross-shard collective runs at all** (the degraded
        mode for a congested/faulted fabric link).  Scores change (cold
        contributions are zero-filled), so this is never resolved
        implicitly — callers opt in per plan.

        The shard_map+jit closure for each distinct
        (mode, combine, dp_shard, impl, dedup, tiers, idx/weights
        shape+dtype) signature is built once and cached — steady-state
        serving does zero retraces (see ``plan_stats``).
        """
        if mode not in ("pifs", "pond", "beacon"):
            raise ValueError(f"unknown mode {mode!r}")
        if combine not in ("psum", "psum_scatter"):
            raise ValueError(f"unknown combine {combine!r}")
        if impl not in ("jnp", "pallas"):
            raise ValueError(f"unknown impl {impl!r}")
        if dedup is None:
            dedup = self.default_dedup
        if dedup not in self.DEDUP_MODES:
            raise ValueError(f"unknown dedup {dedup!r}; "
                             f"expected one of {self.DEDUP_MODES}")
        if tiers not in self.TIER_MODES:
            raise ValueError(f"unknown tiers {tiers!r}; "
                             f"expected one of {self.TIER_MODES}")
        if self.validate_ids:
            self._check_ids(indices)
        key = ("lookup", mode, combine, dp_shard, impl,
               int(block_l) if impl == "pallas" else None,  # jnp ignores it
               self.cfg.storage, dedup, tiers,
               tuple(indices.shape), jnp.dtype(indices.dtype).name,
               None if weights is None
               else (tuple(weights.shape), jnp.dtype(weights.dtype).name))
        plan = self._plans.get(key)
        if plan is None:
            dedup_on = self._resolve_dedup(key, dedup, state, indices,
                                           dp_shard=dp_shard)
            plan = self._build_lookup_plan(
                mode=mode, combine=combine, dp_shard=dp_shard, impl=impl,
                block_l=block_l, has_weights=weights is not None,
                dedup=dedup_on, tiers=tiers)
            self._plans[key] = plan
        self._plan_calls += 1
        args = (state.cold, state.hot, state.page_scales,
                state.page_to_shard, state.page_to_slot, indices)
        if weights is not None:
            args = args + (weights,)
        return plan(*args)

    # --------------------------------------------------- fused front end
    def lookup_interact(self, state: EngineState, indices: jax.Array,
                        dense_feature: jax.Array,
                        weights: Optional[jax.Array] = None,
                        mode: str = "pifs", combine: str = "psum",
                        dp_shard: bool = True, impl: str = "jnp",
                        block_l: int = 8, block_b: int = 32,
                        dedup: Optional[str] = None,
                        front_end: str = "split") -> jax.Array:
        """Pooled lookup fused with the DLRM dot-interaction.

        indices: (B, G, L) as in :meth:`lookup`; dense_feature: (B, D) the
        bottom-MLP output, stacked as feature row 0.  Returns the (B, P)
        packed lower triangle of the (B, F, D) = (B, G+1, D) features'
        pairwise dots — the input of the DLRM top MLP (after concatenating
        the dense feature back on).

        front_end: 'split' materializes the pooled features and runs the
        interaction as a separate op (the seed pipeline); 'fused' keeps
        them in VMEM from the SLS accumulate through the interaction
        matmul (impl='pallas'; see ``kernels/sls.py``).  On the
        replicated/dp-sharded config (tp == 1, pifs/beacon) the knob
        resolves ``'fused'`` — the single three-phase kernel.  With
        tp-sharded cold partials (tp > 1), or in ``mode='pond'``, it
        resolves ``'fused_tp'``: each shard runs phases 1-2 on its owned
        rows (dedup staging stays per-shard), the small partial-pooled
        (B, F, D) cold tile is psum'd across shards instead of raw rows,
        and phase 3 resumes on the reduced tile — features stay
        VMEM-resident on both sides of the collective.  For pond this
        means the cold partials are pooled *before* the hot/cold add (the
        reduce-near-data datapath), so pond-fused matches the fixed
        l-order split composition bitwise, not pond-split's segment-sum
        order.  The resolution is recorded in
        ``plan_stats()['front_end']`` (the dedup resolution pattern).
        Bit-for-bit equal across {front_end, impl, storage, dedup} in
        fp32 for pifs/beacon on any mesh.

        ``combine`` only names the pooled-lookup collective for plan-cache
        symmetry with :meth:`lookup`: the interaction consumes every bag of
        a sample, so the split path always materializes the full psum
        (psum_scatter's bag-sharded layout cannot feed the interaction).
        """
        if mode not in ("pifs", "pond", "beacon"):
            raise ValueError(f"unknown mode {mode!r}")
        if combine not in ("psum", "psum_scatter"):
            raise ValueError(f"unknown combine {combine!r}")
        if impl not in ("jnp", "pallas"):
            raise ValueError(f"unknown impl {impl!r}")
        if front_end not in self.FRONT_END_MODES:
            raise ValueError(f"unknown front_end {front_end!r}; "
                             f"expected one of {self.FRONT_END_MODES}")
        if dedup is None:
            dedup = self.default_dedup
        if dedup not in self.DEDUP_MODES:
            raise ValueError(f"unknown dedup {dedup!r}; "
                             f"expected one of {self.DEDUP_MODES}")
        if self.validate_ids:
            self._check_ids(indices)
        if dense_feature.ndim != 2 or dense_feature.shape[-1] != self.cfg.dim:
            raise ValueError(
                f"dense_feature must be (B, {self.cfg.dim}); got "
                f"{dense_feature.shape}")
        key = ("interact", mode, combine, dp_shard, impl,
               (int(block_l), int(block_b)) if impl == "pallas" else None,
               self.cfg.storage, dedup, front_end,
               tuple(indices.shape), jnp.dtype(indices.dtype).name,
               None if weights is None
               else (tuple(weights.shape), jnp.dtype(weights.dtype).name))
        plan = self._plans.get(key)
        if plan is None:
            fe = self._resolve_front_end(key, front_end, mode)
            dedup_on = self._resolve_dedup(
                key, dedup, state, indices, dp_shard=dp_shard,
                fused_blocks=int(block_b) if fe != "split" else None)
            plan = self._build_interact_plan(
                mode=mode, dp_shard=dp_shard, impl=impl, block_l=block_l,
                block_b=block_b, has_weights=weights is not None,
                dedup=dedup_on, front_end_resolved=fe)
            self._plans[key] = plan
        self._plan_calls += 1
        args = (state.cold, state.hot, state.page_scales,
                state.page_to_shard, state.page_to_slot, indices,
                dense_feature)
        if weights is not None:
            args = args + (weights,)
        return plan(*args)

    def _resolve_front_end(self, key, front_end: str, mode: str) -> str:
        """Freeze the front-end fusion decision for one interact plan.

        Host-side, once per signature at plan build (the dedup pattern).
        Returns the resolved datapath, one of

          * ``'split'`` — requested split: pooled features materialize and
            the interaction runs as a separate op,
          * ``'fused'`` — the replicated/dp-sharded config (tp == 1,
            pifs/beacon): the single three-phase kernel,
          * ``'fused_tp'`` — tp-sharded cold partials (tp > 1) or pond:
            the partial-pool kernel emits per-tier (B, F, D) feature
            tiles, the cold tile is psum'd across tp shards (the pooled
            tile crosses the fabric, never raw rows), and the resume
            kernel runs phase 3 on the reduced tile.  Pond requesting
            fusion opts into pooling its cold partials before the
            hot/cold add — the reduce-near-data datapath.

        The resolution (requested/resolved/reason/tp) is recorded for
        ``plan_stats()['front_end']`` so benches can assert the datapath
        they are timing."""
        tp = self.axes.tp_size(self.mesh)
        if front_end == "split":
            resolved, reason = "split", "requested"
        elif tp > 1:
            resolved, reason = "fused_tp", (
                f"tp-sharded masked partials (tp={tp}): each shard pools "
                "its partial (B, F, D) cold tile; the cross-shard psum "
                "lands between the partial-pool and resume kernels")
        elif mode == "pond":
            resolved, reason = "fused_tp", (
                "pond requesting fusion pools cold partials before the "
                "hot/cold add (partial-pool -> psum -> resume) instead of "
                "shipping raw rows")
        else:
            resolved, reason = "fused", "replicated/dp-sharded config"
        self._fe_plans[key] = {
            "requested": front_end,
            "resolved": resolved,
            "reason": reason,
            "tp": tp,
        }
        return resolved

    def _build_interact_plan(self, *, mode: str, dp_shard: bool, impl: str,
                             block_l: int, block_b: int, has_weights: bool,
                             dedup: bool, front_end_resolved: str):
        """Build the shard_map + jit closure for one interact signature."""
        axes, mesh = self.axes, self.mesh
        dp, tp = axes.dp, axes.tp
        if not dp_shard:
            dp = ()
        idx_spec = P(dp or None, None, None)
        x_spec = P(dp or None, None)
        out_spec = P(dp or None, None)
        w_specs = (idx_spec,) if has_weights else ()

        def block(cold, hot, scales, p2s, p2slot, idx, x, *w):
            wloc = w[0] if w else None
            if front_end_resolved == "fused":
                return self._interact_block_fused(
                    cold, hot, scales, p2s, p2slot, idx, x, wloc,
                    impl=impl, block_l=block_l, block_b=block_b,
                    dedup=dedup)
            if front_end_resolved == "fused_tp":
                return self._interact_block_fused_tp(
                    cold, hot, scales, p2s, p2slot, idx, x, wloc,
                    impl=impl, block_l=block_l, block_b=block_b,
                    dedup=dedup)
            pooled = self._lookup_block(cold, hot, scales, p2s, p2slot,
                                        idx, wloc, mode=mode,
                                        combine="psum", impl=impl,
                                        block_l=block_l, dedup=dedup)
            from repro.kernels import ops as kernel_ops
            with jax.named_scope("interaction"):
                feats = jnp.concatenate([x[:, None, :], pooled], axis=1)
                return kernel_ops.dot_interaction(feats, impl=impl,
                                                  block_b=block_b)

        f = shard_map(
            block, mesh=mesh,
            in_specs=(P(tp), P(), P(), P(), P(), idx_spec, x_spec) + w_specs,
            out_specs=out_spec, check_vma=False)

        def traced(*args):
            self._trace_count += 1
            return f(*args)

        return jax.jit(traced)

    def _interact_block_fused(self, cold, hot, scales, p2s, p2slot, idx, x,
                              weights, *, impl: str, block_l: int,
                              block_b: int, dedup: bool):
        """Per-device fused front-end block (tp == 1 by resolution): locate
        each entry's tier + local row, then run the single-kernel SLS ->
        interaction datapath.  Mirrors :meth:`_lookup_block`'s address math
        exactly, so the masks/rows/scales the fused kernel sees are the
        ones the split accumulates would have seen."""
        c, axes = self.cfg, self.axes
        ps = c.page_size
        page = idx // ps
        offset = idx % ps
        shard = p2s[page]
        local_row = p2slot[page] * ps + offset                 # (b, G, L)
        owned = shard == jax.lax.axis_index(axes.tp)
        is_hot = shard == HOT_SHARD
        scale = scales[page] if self.quantized else None
        # one kernel pools and interacts: its time is all under "embed"
        with jax.named_scope("embed"):
            return sls_ops.fused_front_end_dense(
                cold, hot, self._pad_lanes(x), local_row, owned, is_hot,
                weights=weights,
                scales=scale, impl=impl, block_l=block_l, block_b=block_b,
                dedup=dedup, out_dtype=jnp.float32)

    def _interact_block_fused_tp(self, cold, hot, scales, p2s, p2slot, idx,
                                 x, weights, *, impl: str, block_l: int,
                                 block_b: int, dedup: bool):
        """Per-device tp-aware fused front-end block: phases 1-2 pool this
        shard's owned rows into the per-tier (b, F, D) partial feature
        tiles, the small *cold* tile is psum'd across tp shards (hot is
        replicated and x must be counted once, so only cold crosses the
        fabric — the reduce-then-communicate datapath the paper argues
        for), and phase 3 resumes on the reduced tile.  Each shard
        accumulates in the same fixed l-order as the split partials and
        the psum's per-element operand order is deterministic per mesh,
        so the composition equals ``psum(cold_part) + hot_out`` -> concat
        -> interaction bit-for-bit in fp32."""
        c, axes = self.cfg, self.axes
        ps = c.page_size
        page = idx // ps
        offset = idx % ps
        shard = p2s[page]
        local_row = p2slot[page] * ps + offset                 # (b, G, L)
        owned = shard == jax.lax.axis_index(axes.tp)
        is_hot = shard == HOT_SHARD
        scale = scales[page] if self.quantized else None
        with jax.named_scope("embed"):
            part_c, part_h = sls_ops.fused_partial_pool_dense(
                cold, hot, self._pad_lanes(x), local_row, owned, is_hot,
                weights=weights, scales=scale, impl=impl, block_l=block_l,
                block_b=block_b, dedup=dedup, out_dtype=jnp.float32)
            # only the logical lanes cross the fabric
            part_c, part_h = self._logical(part_c), self._logical(part_h)
        with jax.named_scope("combine"):
            reduced = jax.lax.psum(part_c, axes.tp)
        with jax.named_scope("interaction"):
            return sls_ops.fused_resume_dense(reduced, part_h, impl=impl,
                                              block_b=block_b)

    # ------------------------------------------------- compiled-lookup plans
    def _resolve_dedup(self, key, dedup: str, state: EngineState,
                       indices: jax.Array, dp_shard: bool = True,
                       fused_blocks: Optional[int] = None) -> bool:
        """Freeze the gather-once coalescing decision for one plan.

        Host-side, runs once per signature at plan build.  'on' only falls
        back when the worst-case *per-device* staging buffer — the dedup
        runs inside shard_map, so with ``dp_shard`` each device stages its
        ``(B/dp)*G*L`` local entries, not the full batch — exceeds the
        VMEM budget; 'auto' additionally requires the expected per-device
        duplicate factor — computed from the observe-phase page histogram
        (paper's profiler), or the engine's host copy of it when called
        under an outer trace — to clear ``dedup_auto_threshold``.  A plan
        built before the profiler has ever run (all-zero histogram) sees a
        uniform prior and resolves 'auto' off; serving primes the
        histogram before its post-warmup rebuild for exactly this reason
        (``repro.serving.prime_dedup_auto``).  The resolution record
        (requested/resolved/expected/measured factor) is reported by
        ``plan_stats()``.
        """
        if dedup == "off":
            return False
        B, G, L = indices.shape
        dp = self.axes.dp_size(self.mesh) if dp_shard else 1
        n_entries = max(B // max(dp, 1), 1) * G * L    # per-device entries
        W = self.cfg.store_dim          # kernels stage rows at stored width
        if fused_blocks is None:
            # split-path dedup: the hot and cold accumulates are separate
            # kernel invocations, so one (n_entries, store_dim) fp32 row
            # staging is live at a time
            staging_bytes = n_entries * W * 4
        else:
            # fused-front-end dedup: one kernel holds BOTH tiers' row
            # stagings plus the two (BB*F, D) per-tier feature accumulators
            # in VMEM simultaneously (kernels/sls.py
            # fused_front_end_dedup_pallas scratch list)
            b_local = max(B // max(dp, 1), 1)
            BB = max(1, min(fused_blocks, b_local))
            while b_local % BB:
                BB //= 2
            staging_bytes = (2 * n_entries * W * 4
                             + 2 * BB * (G + 1) * W * 4)
        capacity_ok = staging_bytes <= self.dedup_staging_bytes
        counts = state.counts
        if isinstance(counts, jax.core.Tracer):
            counts = self._host_counts
        expected = (None if counts is None
                    else self._expected_dup_factor(np.asarray(counts),
                                                   n_entries))
        measured = None
        if not any(isinstance(x, jax.core.Tracer)
                   for x in (indices, state.page_to_shard, state.page_to_slot)):
            measured = self.dedup_factor(state, indices)["factor"]
        if dedup == "on":
            resolved = capacity_ok
        else:   # auto: best available duplicate-factor evidence vs threshold.
            # The analytic page-histogram expectation is blind to row-level
            # skew scattered across pages, so a measured replay (the plan-
            # building batch when concrete, or the serving prime hint when
            # building under a trace) can overrule it upward.
            signals = [x for x in (expected, measured, self.dedup_auto_hint)
                       if x is not None]
            resolved = (capacity_ok and bool(signals)
                        and max(signals) >= self.dedup_auto_threshold)
        self._dedup_plans[key] = {
            "requested": dedup, "resolved": bool(resolved),
            "capacity_ok": bool(capacity_ok),
            "expected_factor": None if expected is None else float(expected),
            "measured_factor": measured,
            "hint_factor": self.dedup_auto_hint,
        }
        return bool(resolved)

    def _expected_dup_factor(self, counts: np.ndarray, n_entries: int
                             ) -> float:
        """Analytic expected duplicate factor for ``n_entries`` draws from
        the row distribution implied by the page-access histogram (uniform
        within a page): ``n / E[unique]`` with
        ``E[unique] = sum_r 1 - (1 - p_r)^n``.  Callers pass the
        *per-device* entry count (the dedup scope) — the per-shard factor
        the kernel realizes tracks it (EXPERIMENTS.md §Duplicate-access
        coalescing compares the two).  An all-zero histogram (profiler
        never ran) means a uniform prior over all rows — essentially
        duplicate-free at realistic vocab sizes."""
        c = np.asarray(counts, np.float64)
        ps = self.cfg.page_size
        tot = c.sum()
        if tot <= 0:
            p = np.full(1, 1.0 / max(self.cfg.padded_rows, 1))
            rows_per_p = np.full(1, float(self.cfg.padded_rows))
        else:
            p = c / (tot * ps)
            rows_per_p = np.full_like(c, float(ps))
        e_unique = float((rows_per_p * -np.expm1(
            n_entries * np.log1p(-np.minimum(p, 1 - 1e-12)))).sum())
        return n_entries / max(e_unique, 1.0)

    def dedup_factor(self, state: EngineState, indices,
                     weights=None) -> dict:
        """Measured (realized) duplicate-access factor of one batch.

        Host-side replay of exactly what the dedup'd datapath gathers:
        per (dp-group, shard) unique owned local rows in the cold tier,
        plus per dp-group unique hot-tier rows.  Returns entries (counting
        weight!=0 only, so serving pad entries don't skew it), unique_cold /
        unique_hot / unique_rows, and ``factor = entries / unique_rows`` —
        the bytes-moved reduction the coalescing buys on this batch.
        """
        c = self.cfg
        idx = np.asarray(indices)
        B = idx.shape[0]
        dp = min(max(1, self.axes.dp_size(self.mesh)), max(B, 1))
        mask = np.ones(idx.shape, bool)
        if weights is not None:
            mask = np.asarray(weights) != 0
        p2s = np.asarray(state.page_to_shard)
        p2slot = np.asarray(state.page_to_slot)
        ps = c.page_size
        entries = 0
        unique_cold = 0
        unique_hot = 0
        # array_split folds a non-divisible remainder into the groups
        # instead of silently dropping trailing rows from the ledger
        splits = np.array_split(np.arange(B), dp)
        for rows in splits:
            gi = idx[rows].reshape(-1)
            gm = mask[rows].reshape(-1)
            gi = gi[gm]
            entries += gi.size
            # mirror the device datapath's clamp semantics: XLA gathers
            # clip out-of-range ids, so the host replay must too (the probe
            # must never crash on traffic the engine itself would serve)
            page = np.clip(gi // ps, 0, c.num_pages - 1)
            shard = p2s[page]
            local = p2slot[page] * ps + gi % ps
            for s in range(c.n_shards):
                unique_cold += int(np.unique(local[shard == s]).size)
            unique_hot += int(np.unique(local[shard == HOT_SHARD]).size)
        unique_rows = unique_cold + unique_hot
        return {"entries": int(entries), "unique_cold": unique_cold,
                "unique_hot": unique_hot, "unique_rows": unique_rows,
                "factor": entries / max(unique_rows, 1)}

    def _build_lookup_plan(self, *, mode: str, combine: str, dp_shard: bool,
                           impl: str, block_l: int, has_weights: bool,
                           dedup: bool = False, tiers: str = "all"):
        """Build the shard_map + jit closure for one lookup signature."""
        axes, mesh = self.axes, self.mesh
        dp, tp = axes.dp, axes.tp
        if not dp_shard:
            dp = ()
        idx_spec = P(dp and dp or None, None, None) if dp else P(None, None, None)
        w_specs = (idx_spec,) if has_weights else ()
        if combine == "psum":
            out_spec = idx_spec
        else:
            out_spec = P((dp + (tp,)) if dp else tp, None, None)

        def block(cold, hot, scales, p2s, p2slot, idx, *w):
            wloc = w[0] if w else None
            return self._lookup_block(cold, hot, scales, p2s, p2slot, idx,
                                      wloc, mode=mode, combine=combine,
                                      impl=impl, block_l=block_l,
                                      dedup=dedup, tiers=tiers)

        f = shard_map(
            block, mesh=mesh,
            in_specs=(P(tp), P(), P(), P(), P(), idx_spec) + w_specs,
            out_specs=out_spec, check_vma=False)

        def traced(*args):
            # python side effect fires once per jit trace — the probe behind
            # plan_stats()['traces'] and the retrace tests/bench counters
            self._trace_count += 1
            return f(*args)

        return jax.jit(traced)

    def plan_stats(self) -> dict:
        """Compiled-plan cache stats: plans built, jit traces, lookup calls.

        When any plan was built with the gather-once coalescing knob
        requested (``dedup`` in {'auto', 'on'}), the dict additionally
        carries a ``"dedup"`` entry: one record per such plan with the
        requested knob, the frozen resolution (on/off after the capacity
        and — for 'auto' — histogram-threshold checks), the analytic
        ``expected_factor`` at decision time, and the ``measured_factor``
        realized on the plan-building batch (None when the plan was built
        under an outer trace).  The key is omitted entirely while no
        dedup-requesting plan exists, so ``dedup='off'`` callers see the
        exact legacy shape."""
        out = {"plans": len(self._plans), "traces": self._trace_count,
               "calls": self._plan_calls}
        if self._dedup_plans:
            out["dedup"] = {self._dedup_key_label(k): dict(v)
                            for k, v in self._dedup_plans.items()}
        if self._fe_plans:
            out["front_end"] = {self._dedup_key_label(k): dict(v)
                                for k, v in self._fe_plans.items()}
        return out

    @staticmethod
    def _dedup_key_label(key) -> str:
        """Compact human-readable label for a lookup- or interact-plan
        cache key — includes every key field that can distinguish two
        plans, so no two records ever collide in the
        ``plan_stats()['dedup']`` / ``['front_end']`` dicts."""
        if key[0] == "interact":
            (_, mode, combine, dp_shard, impl, blocks, storage, dedup,
             front_end, shape, _idx_dtype, weights_info) = key
            blk = ("" if blocks is None
                   else f"/bl{blocks[0]}bb{blocks[1]}")
            head, fe, tiers = "interact:", f"/fe={front_end}", "all"
        else:
            (_, mode, combine, dp_shard, impl, block_l, storage, dedup,
             tiers, shape, _idx_dtype, weights_info) = key
            blk = f"/bl{block_l}" if block_l is not None else ""
            head, fe = "", ""
        return (f"{head}{mode}/{combine}/{impl}" + blk
                + ("" if dp_shard else "/nodp")
                + f"/{storage}/dedup={dedup}" + fe
                + ("" if tiers == "all" else f"/{tiers}")
                + f"/idx={'x'.join(map(str, shape))}"
                + ("+w" if weights_info is not None else ""))

    def reset_plan_stats(self, clear_plans: bool = False) -> None:
        """Zero the trace/call counters; keeps compiled plans warm unless
        ``clear_plans`` (clearing forces a retrace of every signature —
        and also drops the per-plan dedup resolution records, which are
        re-frozen when the signatures rebuild)."""
        if clear_plans:
            self._plans.clear()
            self._dedup_plans.clear()
            self._fe_plans.clear()
        self._trace_count = 0
        self._plan_calls = 0

    def _lookup_block(self, cold, hot, scales, p2s, p2slot, idx, weights, *,
                      mode: str, combine: str, impl: str = "jnp",
                      block_l: int = 8, dedup: bool = False,
                      tiers: str = "all"):
        """Per-device block: the fabric-switch Process Core."""
        c, axes = self.cfg, self.axes
        tp = axes.tp
        b, G, L = idx.shape
        nbags = b * G
        bags = idx.reshape(nbags, L)
        wbags = None if weights is None else weights.reshape(nbags, L)

        my = jax.lax.axis_index(tp)
        with jax.named_scope("embed"):
            ps = c.page_size
            page = bags // ps
            offset = bags % ps
            shard = p2s[page]
            local_row = p2slot[page] * ps + offset              # (nbags, L)
            owned = shard == my
            is_hot = shard == HOT_SHARD
            # per-entry dequant scales (page-aligned addressing: the scale
            # of an entry is its *global page's* scale) — an O(bags*L)
            # scalar gather; the (rows, D)-sized fp32 cold table is never
            # materialized
            scale_be = scales[page] if self.quantized else None  # (nbags, L)

            # ---- hot tier: replicated, zero-communication ----
            # dedup applies here too: hot hits are local-HBM reads, and
            # under zipfian traffic the hot tier is where duplicates
            # concentrate; pooled rows leave without pad lanes
            hot_out = self._logical(sls_ops.masked_partial_sls_dense(
                hot, local_row, is_hot, wbags, impl=impl,
                block_l=block_l, dedup=dedup))                  # (nbags, D)

        if tiers == "hot_only":
            # brown-out rung: serve the replicated hot tier only — cold
            # entries are masked to exact zeros by ``is_hot`` above and the
            # faulted/congested cross-shard path is never touched (zero
            # collectives).  Scores change (cold contributions zero-fill),
            # which is why this datapath is an explicit opt-in per plan.
            if combine == "psum":
                return hot_out.reshape(b, G, -1)
            tp_size = axes.tp_size(self.mesh)
            if nbags % tp_size:
                raise ValueError(f"bags ({nbags}) must divide tp ({tp_size}) "
                                 "for psum_scatter combine")
            out = jax.lax.dynamic_slice_in_dim(
                hot_out, my * (nbags // tp_size), nbags // tp_size, 0)
            return out.reshape(b // tp_size, G, -1)

        # ---- cold tier ----
        if mode == "pond":
            # raw rows cross the interconnect (communicate-then-reduce):
            # there is no pooling near the data in this baseline, so the
            # kernel only serves the hot tier here.  Coalescing does not
            # apply either — the baseline's semantics ship one row per
            # pooling entry, so only the hot tier above dedups in pond mode.
            seg = jnp.repeat(jnp.arange(nbags, dtype=jnp.int32), L)
            rows = self._logical(sls_ops.masked_gather_rows(
                cold, local_row.reshape(-1), owned.reshape(-1)))
            if self.quantized:
                # dequant after the (int8) gather, before rows hit the wire:
                # pond still ships fp32 rows (the baseline's semantics), the
                # *memory* interface moved 1-byte elements
                rows = quant.dequantize_rows(
                    rows, scale_be.reshape(-1)[:, None])
            if wbags is not None:
                rows = rows * wbags.reshape(-1)[:, None].astype(rows.dtype)
            with jax.named_scope("combine"):
                rows = jax.lax.psum(rows, tp)                    # (b*G*L, D)!
            cold_out = jax.ops.segment_sum(rows, seg, num_segments=nbags)
            out = cold_out + hot_out
            if combine == "psum_scatter":
                tp_size = axes.tp_size(self.mesh)
                if b % tp_size:
                    raise ValueError(
                        f"per-device batch ({b}) must divide tp ({tp_size}) "
                        "for psum_scatter combine in pond mode")
                out = jax.lax.dynamic_slice_in_dim(
                    out.reshape(b, G, -1), my * (b // tp_size), b // tp_size, 0)
                return out
            return out.reshape(b, G, -1)

        # pifs / beacon: partial SLS near the data, pooled partials only
        with jax.named_scope("embed"):
            cold_part = self._logical(sls_ops.masked_partial_sls_dense(
                cold, local_row, owned, wbags, impl=impl,
                block_l=block_l, scales=scale_be,
                out_dtype=jnp.float32 if self.quantized else None,
                dedup=dedup))                                    # (nbags, D)
        if combine == "psum":
            with jax.named_scope("combine"):
                cold_sum = jax.lax.psum(cold_part, tp)
            return (cold_sum + hot_out).reshape(b, G, -1)
        # psum_scatter over the bag axis: each tp shard keeps its bag slice
        tp_size = axes.tp_size(self.mesh)
        if nbags % tp_size:
            raise ValueError(f"bags ({nbags}) must divide tp ({tp_size}) "
                             "for psum_scatter combine")
        with jax.named_scope("combine"):
            cold_sc = jax.lax.psum_scatter(cold_part, tp, scatter_dimension=0,
                                           tiled=True)           # (nbags/tp, D)
        hot_slice = jax.lax.dynamic_slice_in_dim(
            hot_out, my * (nbags // tp_size), nbags // tp_size, 0)
        out = cold_sc + hot_slice
        return out.reshape(b // tp_size, G, -1)

    # ---------------------------------------------------------------- observe
    def observe(self, state: EngineState, indices: jax.Array,
                weights: Optional[jax.Array] = None) -> EngineState:
        """Update the replicated page-access histogram (paper's profiler).

        Optional ``weights`` (same shape as ``indices``) gate what counts:
        an entry contributes 1 iff its weight is non-zero.  The serving
        batcher passes its SLS pad weights here so bucket padding (weight-0
        entries, replicated pad rows) never skews the hotness ranking."""
        c, axes = self.cfg, self.axes
        dp = axes.dp
        key = ("observe", tuple(indices.shape),
               jnp.dtype(indices.dtype).name, weights is not None)
        f = self._plans.get(key)
        if f is None:
            idx_spec = P(dp, None, None) if dp else P(None, None, None)
            w_specs = (idx_spec,) if weights is not None else ()

            def block(counts, idx, *w):
                with jax.named_scope("observe"):
                    page = idx.reshape(-1) // c.page_size
                    inc = (jnp.where(w[0].reshape(-1) != 0, 1.0, 0.0) if w
                           else 1.0)
                    local = jnp.zeros_like(counts).at[page].add(inc)
                    if dp:
                        local = jax.lax.psum(local, dp)
                    return counts + local

            f = jax.jit(shard_map(block, mesh=self.mesh,
                                  in_specs=(P(), idx_spec) + w_specs,
                                  out_specs=P(), check_vma=False))
            self._plans[key] = f
        args = (state.counts, indices)
        if weights is not None:
            args = args + (weights,)
        new_counts = f(*args)
        if not isinstance(new_counts, jax.core.Tracer):
            # host copy for dedup='auto' plan resolution under outer traces
            self._host_counts = np.asarray(new_counts)
        return self.commit(dataclasses.replace(state, counts=new_counts))

    # ------------------------------------------------------- plan + migration
    def plan_and_migrate(self, state: EngineState) -> Tuple[EngineState, dict]:
        """Host-side plan (hotness + spreading), then pure-gather migration."""
        counts = np.asarray(jax.device_get(state.counts))
        self._host_counts = counts
        new_table, stats = plan(self.cfg, state.page_table, counts, self.planner)
        new_state = self.migrate(state, new_table)
        return new_state, stats

    def migrate(self, state: EngineState, new_table: PageTable,
                count_decay: float = 0.5) -> EngineState:
        """Execute a placement change: cache-line-granular gather (IV-B4).

        ``storage='int8'`` uses a typed gather: cold->cold moves int8 codes
        verbatim (scales are global per-page metadata and never move),
        cold->hot promotion dequantizes the page into the fp32 hot tier,
        and hot->cold demotion re-quantizes with the page's *carried* scale
        — which recovers the original codes bit-for-bit when the hot values
        came from an earlier promotion, so lookups are placement-invariant
        exactly in the quantized domain (property-tested).

        ``count_decay`` scales the access histogram after the move (the
        planner's EWMA).  Maintenance migrations that are not replans —
        the update subsystem's requant-demotions — pass 1.0 so demoting a
        drifted page never perturbs the hotness ranking the next real
        replan sees.
        """
        c = self.cfg
        cold_src, hot_src = placement_gather_indices(
            c, state.page_table, new_table)

        if self.quantized:
            new_cold, new_hot = self._migrate_quantized(
                state, new_table, cold_src, hot_src)
        else:
            # the gather plan is shape-stable across migrations — build once
            # so the periodic replans of a live serving loop never recompile.
            # The gather runs inside shard_map with an *explicit* all-gather
            # of the cold shards: arbitrary cross-shard page moves need the
            # full source table, and letting GSPMD infer the collective is
            # unsound here — it compiles per input sharding, and the
            # second migration (whose inputs arrive tp-sharded from the
            # first) silently corrupted the store.
            if self._migrate_plan is None:
                tp = self.axes.tp

                def block(cold, hot, cs, hs):
                    full = jax.lax.all_gather(cold, tp, axis=0, tiled=True)
                    return (_take_two_tier(full, hot, cs),
                            _take_two_tier(full, hot, hs))

                self._migrate_plan = jax.jit(shard_map(
                    block, mesh=self.mesh,
                    in_specs=(P(tp), P(), P(tp), P()),
                    out_specs=(P(tp), P()), check_vma=False))

            new_cold, new_hot = self._migrate_plan(
                state.cold, state.hot,
                jnp.asarray(cold_src.astype(np.int32)),
                jnp.asarray(hot_src.astype(np.int32)))
        return self.commit(EngineState(
            cold=new_cold, hot=new_hot, page_scales=state.page_scales,
            page_to_shard=jnp.asarray(np.asarray(new_table.page_to_shard), jnp.int32),
            page_to_slot=jnp.asarray(np.asarray(new_table.page_to_slot), jnp.int32),
            counts=state.counts * count_decay))  # decay after replan (EWMA)

    def _migrate_quantized(self, state: EngineState, new_table: PageTable,
                           cold_src: np.ndarray, hot_src: np.ndarray):
        """Typed migration for the int8 cold tier (same gather structure as
        the fp32 path, but the hot tier is bridged through quantize/dequant
        at the tier boundary instead of a mixed-dtype concat)."""
        c = self.cfg
        ps, C = c.page_size, c.cold_rows_total
        old = state.page_table
        pages = np.arange(c.num_pages, dtype=np.int64)

        def hot_slot_pages(table: PageTable) -> np.ndarray:
            """Per hot *row*: the global page occupying that hot slot (0 for
            empty slots — their content is unused)."""
            shard = np.asarray(table.page_to_shard)
            slot = np.asarray(table.page_to_slot)
            per_slot = np.zeros(c.hot_pages, dtype=np.int64)
            m = shard == HOT_SHARD
            per_slot[slot[m]] = pages[m]
            return np.repeat(per_slot, ps)                      # (hot_rows,)

        from_hot = hot_src >= C
        args = (jnp.asarray(cold_src.astype(np.int32)),
                jnp.asarray(np.where(from_hot, 0, hot_src).astype(np.int32)),
                jnp.asarray(np.where(from_hot, hot_src - C, 0).astype(np.int32)),
                jnp.asarray(from_hot),
                jnp.asarray(hot_slot_pages(old).astype(np.int32)),
                jnp.asarray(hot_slot_pages(new_table).astype(np.int32)))

        if self._migrate_plan is None:
            tp = self.axes.tp

            def block(cold, hot, scales, cs, hs_cold, hs_hot, hs_from_hot,
                      old_hot_page, new_hot_page):
                # explicit all-gather (see the fp32 path for why GSPMD must
                # not infer this); int8 codes make it 1/4 the fp32 bytes
                full = jax.lax.all_gather(cold, tp, axis=0, tiled=True)
                # demotions: re-quantize the (small) hot tier with each
                # row's carried page scale; rows whose page stays hot are
                # computed-but-unused (static shapes beat a data-dependent
                # gather).  A previously promoted page holds exactly
                # q * scale, so round(q * scale / scale) == q: lossless.
                hot_q = quant.quantize_rows(hot, scales[old_hot_page][:, None])
                new_cold = _take_two_tier(full, hot_q, cs)
                # promotions: dequantize cold codes into the fp32 hot tier
                promoted = quant.dequantize_rows(
                    jnp.take(full, hs_cold, axis=0),
                    scales[new_hot_page][:, None])
                stayed = jnp.take(hot, hs_hot, axis=0)
                new_hot = jnp.where(hs_from_hot[:, None], stayed, promoted)
                return new_cold, new_hot

            self._migrate_plan = jax.jit(shard_map(
                block, mesh=self.mesh,
                in_specs=(P(tp), P(), P(), P(tp), P(), P(), P(), P(), P()),
                out_specs=(P(tp), P()), check_vma=False))

        return self._migrate_plan(state.cold, state.hot, state.page_scales,
                                  *args)

    # ------------------------------------------------------ streaming updates
    def apply_deltas(self, state: EngineState, rows: jax.Array,
                     deltas: jax.Array) -> EngineState:
        """Apply a batch of per-row additive deltas to the live tables.

        ``rows``: (U,) int32 global row ids, ``repro.core.updates.PAD_ROW``
        (= -1) for pad entries; rows must be *unique* (callers coalesce
        duplicates host-side — scatter-add ordering over duplicate targets
        is unspecified, and WAL replay must be bit-identical).  ``deltas``:
        (U, D) float32.

        Tier semantics: a row resident in the replicated hot tier gets an
        exact fp32 add; an fp32 cold row likewise; an int8 cold row is
        updated *in the quantized domain* — dequantize with the page's
        carried scale, add, re-quantize with the same scale — so the code
        stays on the page's grid and a later migration still moves it
        verbatim.  (Hot rows updated in fp32 drift off their page's grid;
        that drift is what the requant-demote scheduler tracks.)  Pad
        entries and rows gathered by non-owning shards are routed to an
        out-of-bounds scatter target and dropped, so every device mutates
        exactly the rows it owns and replicas stay identical — no
        ``x + 0.0`` writes that could flip a ``-0.0``.

        One compiled plan per (storage, U) signature, through the same
        traced-counter wrapper as lookups: steady-state streaming updates
        cause zero retraces and the retrace gates cover them.
        """
        if rows.ndim != 1 or deltas.ndim != 2 or deltas.shape[0] != rows.shape[0]:
            raise ValueError(
                f"rows must be (U,), deltas (U, D); got {rows.shape} / "
                f"{deltas.shape}")
        if deltas.shape[1] != self.cfg.dim:
            raise ValueError(f"delta dim {deltas.shape[1]} != table dim "
                             f"{self.cfg.dim}")
        if not isinstance(rows, jax.core.Tracer):
            r = np.asarray(rows)
            if (r >= self.cfg.padded_rows).any():
                bad = int(r[r >= self.cfg.padded_rows][0])
                raise ValueError(
                    f"apply_deltas: row id {bad} outside the padded address "
                    f"space [0, {self.cfg.padded_rows})")
        key = ("update", self.cfg.storage, int(rows.shape[0]),
               jnp.dtype(rows.dtype).name, jnp.dtype(deltas.dtype).name)
        plan = self._plans.get(key)
        if plan is None:
            plan = self._build_update_plan()
            self._plans[key] = plan
        self._plan_calls += 1
        new_cold, new_hot = plan(state.cold, state.hot, state.page_scales,
                                 state.page_to_shard, state.page_to_slot,
                                 rows, self._pad_lanes(deltas))
        return self.commit(dataclasses.replace(state, cold=new_cold,
                                               hot=new_hot))

    def _build_update_plan(self):
        """shard_map + jit closure for one apply_deltas signature."""
        axes, mesh = self.axes, self.mesh
        tp = axes.tp
        c = self.cfg

        def block(cold, hot, scales, p2s, p2slot, rows, deltas):
            ps = c.page_size
            valid = rows >= 0
            r = jnp.where(valid, rows, 0)
            page = r // ps
            offset = r % ps
            shard = p2s[page]
            local = p2slot[page] * ps + offset                  # (U,)
            my = jax.lax.axis_index(tp)
            is_hot = valid & (shard == HOT_SHARD)
            owned = valid & (shard == my)
            # hot tier is replicated: every device applies the identical
            # scatter-add; non-hot entries target row hot_rows (OOB, drop)
            hot_tgt = jnp.where(is_hot, local, hot.shape[0])
            new_hot = hot.at[hot_tgt].add(deltas.astype(hot.dtype),
                                          mode="drop")
            cold_tgt = jnp.where(owned, local, cold.shape[0])
            if self.quantized:
                # quantized-domain read-modify-write with the carried
                # scale: gathered codes for unowned entries are garbage
                # but their scatter target is OOB, so they drop out
                scale = scales[page][:, None]                   # (U, 1)
                q_old = jnp.take(cold, jnp.minimum(local, cold.shape[0] - 1),
                                 axis=0)
                v = quant.dequantize_rows(q_old, scale) + deltas
                # a zero carried scale (never emitted by quant.page_scales,
                # but representable in a hand-built or restored state) has
                # no quantized domain to write into: dividing by it would
                # turn the codes into ±127 or NaN casts — keep the old
                # codes instead
                safe = jnp.where(scale > 0, scale, 1.0)
                q_new = jnp.where(scale > 0,
                                  quant.quantize_rows(v, safe), q_old)
                new_cold = cold.at[cold_tgt].set(q_new, mode="drop")
            else:
                new_cold = cold.at[cold_tgt].add(
                    deltas.astype(cold.dtype), mode="drop")
            return new_cold, new_hot

        f = shard_map(block, mesh=mesh,
                      in_specs=(P(tp), P(), P(), P(), P(), P(), P()),
                      out_specs=(P(tp), P()), check_vma=False)

        def traced(*args):
            self._trace_count += 1
            return f(*args)

        return jax.jit(traced)

    def requant_hot_pages(self, state: EngineState, pages: jax.Array
                          ) -> EngineState:
        """Snap listed hot-resident pages back onto their carried-scale
        quantized grid, in place (no migration).

        ``pages``: (K,) int32 global page ids, -1 for pads.  Each listed
        page's hot rows are replaced by ``dequantize(quantize(x, s), s)``
        with the page's carried scale — exactly the value a demote-then-
        promote round trip through the int8 cold tier would produce, in
        one replicated scatter.  This is the "fused" form of requant-
        demote for pages that should *stay* hot: after a snap, a later
        planner demotion is bit-exact again (the idempotency property),
        no matter how much the page drifted under streaming updates.

        No-op for fp32 storage (there is no quantized domain to snap to).
        Entries for pages not currently hot-resident are dropped.  One
        compiled plan per K, through the traced counter."""
        if not self.quantized:
            return state
        if pages.ndim != 1:
            raise ValueError(f"pages must be (K,); got {pages.shape}")
        key = ("requant", int(pages.shape[0]),
               jnp.dtype(pages.dtype).name)
        plan = self._plans.get(key)
        if plan is None:
            plan = self._build_requant_plan()
            self._plans[key] = plan
        self._plan_calls += 1
        new_hot = plan(state.hot, state.page_scales, state.page_to_shard,
                       state.page_to_slot, pages)
        return self.commit(dataclasses.replace(state, hot=new_hot))

    def _build_requant_plan(self):
        c = self.cfg

        def block(hot, scales, p2s, p2slot, pages):
            ps = c.page_size
            valid = pages >= 0
            pg = jnp.where(valid, pages, 0)
            is_hot = valid & (p2s[pg] == HOT_SHARD)
            rows = (p2slot[pg][:, None] * ps
                    + jnp.arange(ps, dtype=pages.dtype)[None, :])   # (K, ps)
            rows_flat = rows.reshape(-1)
            take = jnp.take(hot, jnp.minimum(rows_flat, hot.shape[0] - 1),
                            axis=0)                                 # (K*ps, D)
            s = jnp.repeat(scales[pg], ps)[:, None]
            snapped = quant.dequantize_rows(quant.quantize_rows(take, s), s)
            tgt = jnp.where(jnp.repeat(is_hot, ps), rows_flat, hot.shape[0])
            return hot.at[tgt].set(snapped, mode="drop")

        f = shard_map(block, mesh=self.mesh,
                      in_specs=(P(), P(), P(), P(), P()),
                      out_specs=P(), check_vma=False)

        def traced(*args):
            self._trace_count += 1
            return f(*args)

        return jax.jit(traced)

    def page_checksums(self, state: EngineState, pages: jax.Array
                       ) -> jax.Array:
        """Per-page Fletcher-pair checksums over native-domain content.

        ``pages``: (K,) int32 global page ids, -1 for pads.  Returns
        (K, 2) uint32 ``[s1, s2]`` per page (zeros for pads) — the
        definition shared bit-for-bit with the numpy twin in
        ``repro.core.integrity.page_checksum_host``: uint32 wraparound
        sums over the page's rows reinterpreted as unsigned lanes (int8
        codes -> uint8, fp32 values -> IEEE bit patterns) plus the page
        scale's fp32 bits, with a 1-based position weight on ``s2``.

        Each tp shard computes both tier candidates for every listed
        page; exactly one shard contributes per page (the owning shard
        for cold pages, shard 0 for the replicated hot tier) and a psum
        collects the replicated result.  One compiled plan per K,
        through the traced counter — callers chunk every request through
        a single fixed K so steady-state scrubbing never retraces.
        """
        if pages.ndim != 1:
            raise ValueError(f"pages must be (K,); got {pages.shape}")
        key = ("checksum", self.cfg.storage, int(pages.shape[0]),
               jnp.dtype(pages.dtype).name)
        plan = self._plans.get(key)
        if plan is None:
            plan = self._build_checksum_plan()
            self._plans[key] = plan
        self._plan_calls += 1
        return plan(state.cold, state.hot, state.page_scales,
                    state.page_to_shard, state.page_to_slot, pages)

    def _build_checksum_plan(self):
        axes, mesh = self.axes, self.mesh
        tp = axes.tp
        c = self.cfg

        def lanes_of(rows_flat):
            # (K*ps, D) native rows -> (K, N) uint32 lane stream
            if rows_flat.dtype == jnp.int8:
                u = jax.lax.bitcast_convert_type(rows_flat, jnp.uint8)
                return u.astype(jnp.uint32)
            return jax.lax.bitcast_convert_type(
                rows_flat.astype(jnp.float32), jnp.uint32)

        def fold(lanes, scale_bits):
            # lanes (K, N) uint32, scale_bits (K,) uint32 -> (K, 2) uint32
            n = lanes.shape[1]
            w = jnp.arange(1, n + 1, dtype=jnp.uint32)[None, :]
            s1 = lanes.sum(axis=1, dtype=jnp.uint32) + scale_bits
            s2 = ((lanes * w).sum(axis=1, dtype=jnp.uint32)
                  + scale_bits * jnp.uint32(n + 1))
            return jnp.stack([s1, s2], axis=1)

        def block(cold, hot, scales, p2s, p2slot, pages):
            ps = c.page_size
            k = pages.shape[0]
            valid = pages >= 0
            pg = jnp.where(valid, pages, 0)
            shard = p2s[pg]
            is_hot = shard == HOT_SHARD
            my = jax.lax.axis_index(tp)
            rows = (p2slot[pg][:, None] * ps
                    + jnp.arange(ps, dtype=pages.dtype)[None, :])  # (K, ps)
            rows_flat = rows.reshape(-1)
            # gather both tier candidates (index-clamped: non-resident
            # gathers read garbage but are masked out of the psum)
            hot_rows = jnp.take(hot,
                                jnp.minimum(rows_flat, hot.shape[0] - 1),
                                axis=0)
            cold_rows = jnp.take(cold,
                                 jnp.minimum(rows_flat, cold.shape[0] - 1),
                                 axis=0)
            sb = jax.lax.bitcast_convert_type(
                scales[pg].astype(jnp.float32), jnp.uint32)
            cs_hot = fold(lanes_of(hot_rows).reshape(k, -1), sb)
            cs_cold = fold(lanes_of(cold_rows).reshape(k, -1), sb)
            cs = jnp.where(is_hot[:, None], cs_hot, cs_cold)
            # exactly one contributor per valid page: the owning shard
            # for cold pages, shard 0 for the replicated hot tier
            contrib = valid & jnp.where(is_hot, my == 0, shard == my)
            cs = cs * contrib[:, None].astype(jnp.uint32)
            return jax.lax.psum(cs, tp)

        f = shard_map(block, mesh=mesh,
                      in_specs=(P(tp), P(), P(), P(), P(), P()),
                      out_specs=P(), check_vma=False)

        def traced(*args):
            self._trace_count += 1
            return f(*args)

        return jax.jit(traced)

    def write_page(self, state: EngineState, page, cold_rows: jax.Array,
                   hot_rows: jax.Array, scale) -> EngineState:
        """Surgically overwrite ONE page's resident rows and scale (the
        repair path: page content fetched from a snapshot + WAL tail).

        ``page``: a scalar global page id (or -1: compile-only no-op —
        every scatter target lands out of bounds and drops, leaving the
        state bit-untouched, which is what warmup uses).  ``cold_rows``:
        (page_size, D) in the cold tier's native dtype, ``hot_rows``:
        (page_size, D) fp32, ``scale``: the page's carried scale.  Only
        the payload matching the page's *current* tier lands (the other
        tier's scatter drops); callers pass zeros for the unused one.
        One compiled plan per storage mode, through the traced counter.
        """
        key = ("page_write", self.cfg.storage)
        plan = self._plans.get(key)
        if plan is None:
            plan = self._build_page_write_plan()
            self._plans[key] = plan
        self._plan_calls += 1
        pg = jnp.asarray(np.asarray(page, np.int32).reshape(1))
        sc = jnp.asarray(np.asarray(scale, np.float32).reshape(1))
        new_cold, new_hot, new_scales = plan(
            state.cold, state.hot, state.page_scales, state.page_to_shard,
            state.page_to_slot, pg,
            self._pad_lanes(jnp.asarray(cold_rows, self.cold_dtype)),
            self._pad_lanes(jnp.asarray(hot_rows, jnp.float32)), sc)
        return self.commit(dataclasses.replace(
            state, cold=new_cold, hot=new_hot, page_scales=new_scales))

    def _build_page_write_plan(self):
        axes, mesh = self.axes, self.mesh
        tp = axes.tp
        c = self.cfg

        def block(cold, hot, scales, p2s, p2slot, page, pc, ph, sc):
            ps = c.page_size
            pg0 = page[0]
            valid = pg0 >= 0
            pg = jnp.where(valid, pg0, 0)
            shard = p2s[pg]
            is_hot = shard == HOT_SHARD
            my = jax.lax.axis_index(tp)
            rows = p2slot[pg] * ps + jnp.arange(ps, dtype=jnp.int32)
            # hot tier is replicated: every device writes the identical
            # rows (or drops, for cold/pad pages)
            hot_tgt = jnp.where(valid & is_hot, rows, hot.shape[0])
            new_hot = hot.at[hot_tgt].set(ph.astype(hot.dtype), mode="drop")
            cold_tgt = jnp.where(valid & (shard == my), rows, cold.shape[0])
            new_cold = cold.at[cold_tgt].set(pc.astype(cold.dtype),
                                             mode="drop")
            sc_tgt = jnp.where(valid, pg, scales.shape[0])
            new_scales = scales.at[sc_tgt].set(sc[0], mode="drop")
            return new_cold, new_hot, new_scales

        f = shard_map(block, mesh=mesh,
                      in_specs=(P(tp), P(), P(), P(), P(), P(), P(), P(),
                                P()),
                      out_specs=(P(tp), P(), P()), check_vma=False)

        def traced(*args):
            self._trace_count += 1
            return f(*args)

        return jax.jit(traced)


class ServeBinding:
    """The serving subsystem's seam onto the engine.

    ``repro.serving`` never touches engine internals: it drives this
    quadruple of (engine, mutable state, model params, jitted serve step).
    ``execute`` runs one bucket-shaped micro-batch and blocks until the
    device is done; ``observe``/``replan`` fold the paper's live page
    management (§IV-B4: profile -> re-plan -> pure-gather migration) into
    the serving cadence — lookups are placement-invariant, so a replan
    between micro-batches never perturbs in-flight numerics; and
    ``plan_stats`` exposes the compiled-plan cache contract the batcher's
    bucket set is built around (one signature per bucket, zero steady-state
    retraces once warmed).

    Robustness seams (all opt-in, all off by default):

      * ``steps`` — named serve-step *variants* (the brown-out ladder's
        quality rungs: split front end, dedup off, hot-tier-only, ...);
        ``set_mode`` switches between them without retracing once each
        variant's buckets are warmed, because every variant is its own
        jitted executable over the same input signatures.
      * ``validate_ids`` — host-side strict OOB check on the batch's index
        stream *before* it enters the jitted step (the device gather would
        clamp silently).
      * ``scrub_scores`` — NaN/Inf score scrub with per-batch poisoned-row
        accounting: a corrupted store (or injected NaN features) degrades
        to zero-scored rows instead of shipping NaN downstream, and the
        poison counters give the recovery controller its signal.
      * ``attach_checkpointer``/``restore`` — mid-serving state recovery:
        reload the EngineState from the last committed checkpoint between
        micro-batches (the observe/replan seam).  State shapes/dtypes are
        unchanged, so a restore never retraces the serve step.
      * ``attach_remesher``/``remesh`` — mid-serving *elastic* recovery
        from a lost tp shard: quiesce, pick a survivor mesh
        (``runtime/elastic.scale_plan``), re-mesh the EngineState in the
        quantized domain (codes + carried per-page scales move verbatim),
        and rebuild every jitted serve-step variant against the new shard
        count.  The caller (the serving runtime) re-warms the rebuilt
        variants and resumes; steady-state trace counts accumulated before
        the swap carry across it, so ``plan_stats()`` stays a whole-run
        ledger.
    """

    def __init__(self, engine: PIFSEmbeddingEngine, state: EngineState,
                 params, step, idx_key: Optional[str] = "indices",
                 track_dedup: bool = True,
                 steps: Optional[dict] = None,
                 validate_ids: bool = False,
                 scrub_scores: bool = False):
        self.engine = engine
        self.state = state
        self.params = params
        self.step = step                   # (params, state, batch) -> scores
        self.idx_key = idx_key             # batch entry feeding the profiler
        self.replans = 0
        # per-bucket duplicate-access accounting, fed by observe() on the
        # maintenance path (never the timed service path): bucket index
        # shape -> accumulated entries / unique rows over observed batches.
        # The probe copies the page tables to the host and replays the
        # batch in numpy: about 1.5 ms per observed 512-row RMC3 batch on
        # one TPU v5e, 7.2 ms for RMC4 widths x 32 tables on four;
        # ``track_dedup=False`` disables it for deployments that do not
        # want the maintenance-path cost.
        self.track_dedup = track_dedup
        self.dedup_stats: dict = {}
        # named serve-step variants (brown-out rungs); "full" is the
        # configured-quality step and always present
        self.steps = dict(steps or {})
        self.steps.setdefault("full", step)
        self.active = "full"
        self.validate_ids = validate_ids
        self.scrub_scores = scrub_scores
        # poisoned-score accounting (scrub_scores): totals + last batch
        self.poisoned_rows = 0
        self.poisoned_batches = 0
        self.last_poisoned = 0
        # mid-serving recovery
        self.checkpointer = None
        self.ckpt_step = 0
        self.restores = 0
        # silent-corruption detection: per-page checksum ledger, kept
        # incrementally consistent by every mutation path below (see
        # repro.core.integrity); None = integrity checking disarmed
        self.integrity = None
        # streaming updates: write-ahead log + fixed apply capacity (one
        # plan signature) + applied-batch sequence number.  The WAL is the
        # delta counterpart of the checkpointer: every applied batch is
        # logged *before* it touches the device, snapshots record the
        # sequence point and truncate, and restore() replays the suffix.
        self.wal = None
        self.update_capacity = 256
        self.update_seq = 0          # seq of the last applied delta batch
        self.updates_applied = 0     # total unique rows applied
        # elastic re-mesh (mid-serving tp-shard-loss recovery): the
        # rebinder rebuilds the jitted serve-step variants for a new
        # engine/mesh pair (only loadgen knows model families, so it owns
        # the callable); prefer_tp parameterizes the survivor-mesh policy
        self._rebind = None   # (engine, mesh) -> (step, steps, shardings)
        self.prefer_tp = 4
        self.remeshes = 0
        self.remesh_events: list = []
        self._carried_traces = 0     # pre-remesh steady traces (see remesh)
        # (variant, batch shapes) -> where execute stages that batch
        # (see _batch_shardings).  Each is checked once, after its first
        # call: moved_inputs is the most step-argument leaves one of them
        # found off the executable's input shardings (jit copies each of
        # those on every call)
        self._staging: dict = {}
        self.moved_inputs = 0

    # ------------------------------------------------------------ variants
    def modes(self) -> tuple:
        """The available serve-step variant labels ('full' first)."""
        rest = [k for k in self.steps if k != "full"]
        return ("full",) + tuple(rest)

    def set_mode(self, label: str) -> None:
        """Switch the active serve-step variant (a brown-out ladder rung).

        Unknown labels fall back to 'full' — model families that lack a
        given degraded datapath (e.g. Rec configs have no DLRM front end)
        simply keep serving at the nearest quality they have."""
        self.active = label if label in self.steps else "full"

    def execute(self, batch: dict):
        """Run one bucket-shaped batch and block until the device is done.

        Spans (``repro.core.spans``): ``serve.execute`` over ``serve.stage``
        (id check, host to device of the batch onto the mesh, each leaf
        where the step takes it), ``serve.dispatch`` (the jitted step's
        call) and ``serve.block``."""
        SPANS.refresh()
        with SPANS.span("serve.execute"):
            with SPANS.span("serve.stage"):
                if (self.validate_ids and self.idx_key
                        and self.idx_key in batch):
                    # the serve step is jitted: the OOB check must see the
                    # concrete host batch, before tracing swallows it
                    self.engine._check_ids(np.asarray(batch[self.idx_key]))
                sig = (self.active,) + tuple(v.shape for v in batch.values())
                where = self._staging.get(sig)
                first = where is None
                if first:
                    where = self._staging[sig] = self._batch_shardings(batch)
                jb = jax.device_put(batch, where)
            step = self.steps[self.active]
            with SPANS.span("serve.dispatch"):
                out = step(self.params, self.state, jb)
            with SPANS.span("serve.block"):
                jax.block_until_ready(out)
            if first:
                self._count_moved(step, jb)
            if self.scrub_scores:
                scores = np.asarray(out)
                finite = np.isfinite(scores)
                self.last_poisoned = int(scores.size - finite.sum())
                if self.last_poisoned:
                    self.poisoned_rows += self.last_poisoned
                    self.poisoned_batches += 1
                    out = jnp.where(jnp.asarray(finite), out,
                                    jnp.zeros_like(out))
                return out
            self.last_poisoned = 0
            return out

    def _batch_shardings(self, batch: dict) -> dict:
        """Where each leaf of a batch is staged: ``dense`` on the dense
        towers' full-batch layout, over dp and tp
        (``models.recsys._constrain_full_batch``), where its rows divide
        over them; ids and weights over dp, as the lookup takes them; the
        rest of each leaf replicated.  These are the layouts the compiler
        picks for the serve steps' batch, which ``bind_model`` leaves to
        it, so the step's call copies none of the batch."""
        mesh, axes = self.engine.mesh, self.engine.axes
        dp = NamedSharding(mesh, P(tuple(axes.dp) or None))
        full = NamedSharding(mesh, P(tuple(axes.ep)))
        n = axes.ep_size(mesh)
        return {k: full if k == "dense" and v.shape[0] % n == 0 else dp
                for k, v in batch.items()}

    def _count_moved(self, step, jb: dict) -> None:
        """Count the step's argument leaves placed other than its
        executable takes them.  Lowering a signature that has been called
        reuses its trace and its executable."""
        args = (self.params, self.state, jb)
        want = jax.tree.structure(args).flatten_up_to(
            step.lower(*args).compile().input_shardings[0])
        moved = sum(1 for a, s in zip(jax.tree.leaves(args), want)
                    if s is not None
                    and not a.sharding.is_equivalent_to(s, a.ndim))
        self.moved_inputs = max(self.moved_inputs, moved)

    # ------------------------------------------------------------ integrity
    def attach_integrity(self, ledger=None, chunk: int = 64) -> None:
        """Arm the per-page checksum ledger over the live state.

        Builds a fully-populated ``repro.core.integrity``
        ``PageChecksumLedger`` (or adopts the one passed in).  From this
        point every mutation path — :meth:`apply_deltas`, :meth:`replan`
        migrations, :meth:`requant_hot_pages`, :meth:`remesh` — keeps the
        ledger consistent, so any divergence a scrub sweep finds is
        silent corruption by construction."""
        from repro.core.integrity import PageChecksumLedger
        if ledger is None:
            ledger = PageChecksumLedger.build(self.engine, self.state,
                                              chunk=chunk)
        self.integrity = ledger

    # ------------------------------------------------------------ recovery
    def attach_checkpointer(self, checkpointer, save_now: bool = True
                            ) -> None:
        """Wire a ``repro.checkpoint.Checkpointer`` for mid-serving state
        recovery; ``save_now`` commits the current (healthy) EngineState
        synchronously so ``restore`` always has a baseline."""
        self.checkpointer = checkpointer
        if save_now:
            self.snapshot()

    def snapshot(self) -> None:
        """Commit the current EngineState (blocking — callers sit on the
        maintenance path, never the timed service path).

        With a WAL attached the snapshot manifest records the last applied
        update sequence number, then the WAL truncates: every logged delta
        is already inside the committed state, so the log restarts empty
        and restore-time replay never double-applies.

        The manifest's ``extra`` additionally records the writing engine's
        mesh shape, shard count, and cold-tier storage mode; ``restore``
        validates them so a mismatched-mesh (or mismatched-storage)
        restore fails loudly with a pointer at the elastic path instead of
        silently mis-placing shards."""
        if self.checkpointer is None:
            raise RuntimeError("no checkpointer attached")
        self.ckpt_step += 1
        extra = {"update_seq": self.update_seq,
                 "mesh": {str(a): int(s)
                          for a, s in self.engine.mesh.shape.items()},
                 "n_shards": int(self.engine.cfg.n_shards),
                 "storage": self.engine.cfg.storage}
        if self.integrity is not None:
            # snapshot-time ledger: page repair verifies the rows it reads
            # back out of this snapshot against these entries, so a rotted
            # snapshot fails loudly instead of being written into the store
            extra["page_checksums"] = self.integrity.export()
        self.checkpointer.save(self.ckpt_step, self.state, blocking=True,
                               extra=extra)
        if self.wal is not None:
            self.wal.truncate()

    def _check_restore_extra(self, extra: dict) -> None:
        """Manifest mesh/storage guard: a checkpoint written under a
        different shard count cannot be restored in place — the cold tier's
        physical layout is a function of ``n_shards`` and the page table
        maps pages to shard ids, so a silent restore would mis-place every
        shard.  Fail loudly and name the elastic route instead.  (The
        generic per-leaf dtype/shape guard in the checkpointer would also
        trip, but with an opaque shape diff; this check explains *why* and
        *what to do*.)  Pre-metadata manifests (no ``n_shards`` key)
        validate vacuously."""
        snap_shards = extra.get("n_shards")
        if (snap_shards is not None
                and int(snap_shards) != int(self.engine.cfg.n_shards)):
            raise ValueError(
                f"checkpoint was written with n_shards={snap_shards} "
                f"(mesh {extra.get('mesh')}), but this engine has "
                f"n_shards={self.engine.cfg.n_shards} (mesh "
                f"{ {str(a): int(s) for a, s in self.engine.mesh.shape.items()} }"
                "): an in-place restore would silently mis-place shards. "
                "Route through the elastic path instead — restore on an "
                "engine matching the snapshot's mesh, then re-mesh via "
                "ServeBinding.remesh() / repro.runtime.elastic."
                "remesh_engine().")
        snap_storage = extra.get("storage")
        if (snap_storage is not None
                and snap_storage != self.engine.cfg.storage):
            raise ValueError(
                f"checkpoint was written with storage={snap_storage!r} but "
                f"this engine uses storage={self.engine.cfg.storage!r}: "
                "int8 codes and fp32 rows are not interchangeable — "
                "rebuild the engine with the snapshot's storage mode.")

    def restore(self) -> None:
        """Reload EngineState from the latest committed checkpoint (the
        mid-serving heal path, run between micro-batches on the
        observe/replan seam).  Restored leaves have identical shapes,
        dtypes, and shardings, so no serve-step plan ever retraces; the
        checkpointer's per-leaf CRC check makes an on-disk corruption fail
        loudly here rather than serve garbage.

        With a WAL attached, every delta batch logged *after* the
        restored snapshot's sequence point is replayed through the same
        coalesce + fixed-capacity apply path that ran live, so the healed
        state is bit-identical to the uninterrupted one — a mid-serving
        restore loses no updates."""
        if self.checkpointer is None:
            raise RuntimeError("no checkpointer attached")
        self._check_restore_extra(self.checkpointer.extra())
        self.state = self.checkpointer.restore(
            self.state, shardings=self.engine.state_shardings())
        self.restores += 1
        if self.integrity is not None:
            # adopt the snapshot-time ledger (it describes exactly the
            # state just loaded); the WAL replay below routes through
            # apply_deltas, which keeps it consistent from here on.  A
            # pre-ledger snapshot forces a full rebuild instead.
            rec = self.checkpointer.extra().get("page_checksums")
            if rec is not None:
                self.integrity.load(rec)
            else:
                self.integrity.note_pages(
                    self.state,
                    np.arange(self.engine.cfg.num_pages, dtype=np.int64))
        if self.wal is not None:
            snap_seq = int(self.checkpointer.extra().get("update_seq", 0))
            self.update_seq = snap_seq
            self.replay_wal(after_seq=snap_seq)

    # ----------------------------------------------------- elastic re-mesh
    def attach_remesher(self, rebind, prefer_tp: int = 4) -> None:
        """Arm mid-serving elastic recovery.

        ``rebind(engine, mesh) -> (step, steps|None, param_shardings)``
        rebuilds the jitted serve-step callable(s) for a re-meshed engine
        and says where they take the params — only the model binder
        (``serving.loadgen.bind_model``) knows the model family, so it owns
        this closure.  ``prefer_tp`` parameterizes the
        survivor-mesh policy (``runtime/elastic.scale_plan``)."""
        self._rebind = rebind
        self.prefer_tp = int(prefer_tp)

    @property
    def can_remesh(self) -> bool:
        return self._rebind is not None

    def remesh(self, lost_shard=None, new_mesh=None, heal: bool = False,
               batch_granule: int = 0) -> dict:
        """Mid-serving elastic recovery from a lost tp shard.

        Maintenance-seam call (between micro-batches, like observe/replan
        — its wall time is recovery, never service time).  The sequence:

          1. *Quiesce*: block on the in-flight EngineState so no device
             work straddles the swap.
          2. Optionally *heal* first: reload the last committed checkpoint
             and replay the WAL tail **on the old mesh** (the snapshot was
             written under the old placement; ``_check_restore_extra``
             enforces exactly this ordering).
          3. Pick the survivor mesh: one tp shard is gone, so
             ``dp * (tp - 1)`` devices survive; ``scale_plan(survivors,
             prefer_tp, batch_granule)`` chooses the new (dp, tp) split
             unless the caller pins ``new_mesh`` explicitly —
             ``batch_granule`` (the gcd of the batcher's bucket batch
             sizes, supplied by the serving runtime) keeps dp a divisor
             of every micro-batch the rebuilt step must shard.
          4. Re-mesh the EngineState in the quantized domain
             (``runtime/elastic.remesh_engine``: int8 codes and carried
             per-page scales move verbatim — bit-stable, no requantize).
          5. Rebuild every jitted serve-step variant via the attached
             rebinder, and move the params onto the new mesh (they were
             committed to the old one) and the batch's staging with it; the
             caller re-warms them (warmup traces are not steady-state) and
             resumes.
          6. If a checkpointer is attached, commit a post-remesh baseline
             snapshot — the old-mesh checkpoint can no longer restore in
             place, and the snapshot truncates the already-replayed WAL.

        Steady-state trace counts accumulated before the swap move into a
        carried ledger so ``plan_stats()['traces']`` stays a whole-run
        zero-retrace measure across the re-mesh.  Returns the event record
        (also appended to ``remesh_events``)."""
        if self._rebind is None:
            raise RuntimeError(
                "no rebinder attached — call attach_remesher() (or "
                "bind_model(elastic=True)) before remesh()")
        # deferred: elastic imports this module at its top level
        from repro.runtime.elastic import remesh_engine, scale_plan
        from repro.distributed.sharding import make_mesh
        old_engine = self.engine
        # 1. quiesce: nothing may straddle the placement swap
        jax.block_until_ready((self.state.cold, self.state.hot))
        if heal:
            # 2. heal on the *old* mesh: checkpoint + WAL tail were written
            # under the old placement, and restore validates exactly that
            self.restore()
        if new_mesh is None:
            old_tp = old_engine.axes.tp_size(old_engine.mesh)
            old_dp = old_engine.axes.dp_size(old_engine.mesh)
            if old_tp < 2:
                raise RuntimeError(
                    f"cannot drop a tp shard from mesh "
                    f"{dict(old_engine.mesh.shape)}: tp={old_tp} has no "
                    "survivor — shard loss at tp=1 is total loss")
            survivors = old_dp * (old_tp - 1)
            shape, names = scale_plan(survivors, prefer_tp=self.prefer_tp,
                                      batch_granule=batch_granule)
            new_mesh = make_mesh(shape, names)
        old_p2s = (np.asarray(self.state.page_to_shard)
                   if self.integrity is not None else None)
        new_engine, new_state = remesh_engine(
            old_engine, new_mesh, self.state)
        # pre-swap steady traces move to the carried ledger (the new
        # engine's counter starts at zero and the caller's post-warm
        # reset only clears engine-level counts)
        self._carried_traces += old_engine._trace_count
        self.engine = new_engine
        self.state = new_state
        if self.integrity is not None:
            # page geometry is shard-count-invariant, so the checksum
            # ledger survives the re-mesh verbatim — only pages the
            # re-planned placement flipped across tiers need refreshing
            self.integrity.rebind(new_engine)
            self.integrity.note_tier_changes(
                self.state, old_p2s, np.asarray(self.state.page_to_shard))
        step, steps, param_shardings = self._rebind(new_engine, new_mesh)
        self.params = jax.device_put(self.params, param_shardings)
        self._staging.clear()
        self.moved_inputs = 0
        self.steps = dict(steps or {})
        self.steps.setdefault("full", step)
        self.step = self.steps["full"]
        if self.active not in self.steps:
            self.active = "full"
        if self.checkpointer is not None:
            # 6. new baseline: the pre-remesh checkpoint is now
            # mesh-mismatched (restore would refuse it) and any WAL tail
            # was replayed in step 2 — snapshot commits + truncates
            self.snapshot()
        event = {"from_mesh": dict(old_engine.mesh.shape),
                 "to_mesh": dict(new_mesh.shape),
                 "lost_shard": lost_shard,
                 "n_shards": int(new_engine.cfg.n_shards),
                 "healed": bool(heal)}
        self.remeshes += 1
        self.remesh_events.append(event)
        return event

    # ----------------------------------------------------- streaming updates
    def attach_wal(self, wal) -> None:
        """Wire a ``repro.checkpoint.WriteAheadLog``: every delta batch
        applied through :meth:`apply_deltas` is appended (write-ahead)
        before it touches the device, :meth:`snapshot` truncates, and
        :meth:`restore` replays the suffix past the snapshot's sequence
        point."""
        self.wal = wal

    def apply_deltas(self, rows, deltas, log: bool = True) -> int:
        """Apply one streaming delta batch to the live EngineState.

        Maintenance-path call (between micro-batches, like observe/replan):
        blocks until the device is done so the wall time is charged where
        the runtime measures it.  Host-side the batch is coalesced
        (duplicate rows summed deterministically), logged to the WAL if
        one is attached, then applied in fixed-``update_capacity`` chunks
        so the engine sees exactly one plan signature.  Returns the number
        of unique rows applied."""
        from repro.core import updates as upd
        rows, deltas = upd.coalesce_deltas(rows, deltas)
        if rows.size == 0:
            return 0
        if log:
            self.update_seq += 1
            if self.wal is not None:
                self.wal.append(self.update_seq, rows, deltas)
        for r_chunk, d_chunk in upd.chunk_delta_batch(
                rows, deltas, self.update_capacity):
            new = self.engine.apply_deltas(
                self.state, jnp.asarray(r_chunk), jnp.asarray(d_chunk))
            jax.block_until_ready((new.cold, new.hot))
            self.state = new
        self.updates_applied += int(rows.size)
        if self.integrity is not None:
            # every page a delta landed in gets its ledger entry refreshed
            # from the post-apply state (maintenance-path device work, one
            # fixed-chunk checksum signature — no retraces)
            self.integrity.note_rows(self.state, rows)
        return int(rows.size)

    def replay_wal(self, after_seq: int = 0) -> int:
        """Re-apply WAL records with seq > ``after_seq`` (restore path).

        Replayed batches are not re-logged; they go through the identical
        coalesce/chunk/apply path as the live stream, so the replayed
        state matches the live one bit-for-bit.  Returns the number of
        batches replayed."""
        if self.wal is None:
            raise RuntimeError("no WAL attached")
        n = 0
        for seq, rows, deltas in self.wal.replay():
            if seq <= after_seq:
                continue
            self.apply_deltas(rows, deltas, log=False)
            self.update_seq = max(self.update_seq, int(seq))
            n += 1
        return n

    def observe(self, batch: dict) -> None:
        """Fold one batch into the page-access counts (span
        ``observe.count``), then, with ``track_dedup``, into the per-bucket
        duplicate-factor probe (span ``observe.probe``)."""
        if self.idx_key and self.idx_key in batch:
            w = batch.get("weights")
            with SPANS.span("observe.count"):
                new = self.engine.observe(
                    self.state, jnp.asarray(batch[self.idx_key]),
                    weights=None if w is None else jnp.asarray(w))
                # block here so the profiler update is charged to
                # maintenance, not leaked into the next micro-batch's
                # measured service time
                jax.block_until_ready(new.counts)
                self.state = new
            if not self.track_dedup:
                return
            # dedup probe rides the same maintenance cadence: the measured
            # per-bucket duplicate factor makes serving-side bytes wins
            # attributable without touching the timed service path
            with SPANS.span("observe.probe"):
                d = self.engine.dedup_factor(
                    self.state, batch[self.idx_key], weights=w)
                key = tuple(np.asarray(batch[self.idx_key]).shape)
                rec = self.dedup_stats.setdefault(
                    key, {"batches": 0, "entries": 0, "unique_rows": 0})
                rec["batches"] += 1
                rec["entries"] += d["entries"]
                rec["unique_rows"] += d["unique_rows"]

    def dedup_report(self) -> dict:
        """Measured per-bucket duplicate-access factors (from the observe
        cadence): ``{bucket_shape_str: {batches, entries, unique_rows,
        factor}}`` — ``factor`` is the bytes-moved reduction a dedup'd
        datapath realizes on that bucket's traffic."""
        out = {}
        for shape, rec in self.dedup_stats.items():
            out["x".join(map(str, shape))] = {
                **rec,
                "factor": rec["entries"] / max(rec["unique_rows"], 1)}
        return out

    def requant_hot_pages(self, pages) -> int:
        """Snap listed hot pages onto their carried-scale grid
        (maintenance-path wrapper around the engine op: blocks, notes the
        ledger, and WAL-fences — see :meth:`replan` for why).  Returns
        the number of non-pad pages listed."""
        pages = np.asarray(pages, np.int32).ravel()
        new = self.engine.requant_hot_pages(self.state, jnp.asarray(pages))
        jax.block_until_ready(new.hot)
        self.state = new
        valid = pages[pages >= 0]
        if self.integrity is not None and valid.size:
            self.integrity.note_pages(self.state, valid)
            if (self.engine.quantized and self.wal is not None
                    and self.checkpointer is not None):
                # a requant snap mutates pages outside the WAL: fence with
                # a snapshot so page repair never replays across it
                self.snapshot()
        return int(valid.size)

    def replan(self) -> dict:
        old_p2s = (np.asarray(self.state.page_to_shard)
                   if self.integrity is not None else None)
        new, stats = self.engine.plan_and_migrate(self.state)
        jax.block_until_ready((new.cold, new.hot))   # same: no timing leak
        self.state = new
        self.replans += 1
        if self.integrity is not None:
            # pages that flipped tier changed native-domain content
            # (promote/demote through the carried scale): refresh them
            flipped = self.integrity.note_tier_changes(
                self.state, old_p2s, np.asarray(self.state.page_to_shard))
            if (flipped.size and self.engine.quantized
                    and self.wal is not None
                    and self.checkpointer is not None):
                # WAL fence: quantized-domain RMW (cold) and fp32 adds
                # (hot) do not commute through a tier flip, so a WAL tail
                # spanning one cannot be replayed bit-exactly onto a
                # snapshot page.  Committing a fresh snapshot (which
                # truncates the WAL) pins every future page repair to a
                # post-flip baseline.
                self.snapshot()
        return stats

    def plan_stats(self) -> dict:
        """Engine plan-cache stats plus the carried trace ledger: traces
        counted on pre-remesh engines accumulate here, so the zero-
        steady-state-retrace contract is measured across the whole run,
        re-meshes included.  ``moved_inputs`` (see ``__init__``) describes
        the placement on the current mesh, so ``reset_plan_stats`` keeps
        it."""
        out = self.engine.plan_stats()
        out["traces"] = out["traces"] + self._carried_traces
        out["moved_inputs"] = self.moved_inputs
        return out

    def reset_plan_stats(self) -> None:
        self.engine.reset_plan_stats()
        self._carried_traces = 0


def engine_for_tables(vocab_sizes, dim, mesh, hot_fraction=0.05,
                      page_bytes=4096, dtype=jnp.float32,
                      storage: str = "fp32", dedup: str = "off",
                      axes: Optional[MeshAxes] = None,
                      planner: Optional[PlannerConfig] = None,
                      ) -> Tuple[PIFSEmbeddingEngine, np.ndarray]:
    """Stack multiple tables into one engine address space.

    Returns (engine, offsets) where offsets[t] is added to table-t indices.
    Page alignment: each table starts on a page boundary, so pages never
    straddle tables.  ``storage='int8'`` selects the quantized cold tier
    (per-page scales, fused dequant in the SLS datapath); note an int8 page
    of the same ``page_bytes`` holds 4x the rows.  ``dedup`` sets the
    engine-wide default for gather-once duplicate coalescing
    (off/auto/on — see ``PIFSEmbeddingEngine.lookup``).
    """
    axes = axes or axes_for(mesh)
    n_shards = axes.tp_size(mesh)
    itemsize = jnp.dtype(dtype).itemsize
    cfg0 = PagingConfig(total_rows=1, dim=dim, n_shards=n_shards,
                        page_bytes=page_bytes, itemsize=itemsize,
                        hot_fraction=hot_fraction, storage=storage)
    ps = cfg0.page_size
    offsets = []
    total = 0
    for v in vocab_sizes:
        offsets.append(total)
        total += -(-v // ps) * ps  # round table size up to page boundary
    cfg = dataclasses.replace(cfg0, total_rows=total)
    # model index math downcasts global row ids to int32 (device-side
    # gathers), and the cold tier's flat address space is even larger than
    # the padded rows (headroom over-provisioning: cold_pos = shard *
    # rows_per_shard + local_row in to_dense/migration) — past this bound
    # either cast silently truncates and lookups read the wrong rows, so
    # fail at construction instead.
    largest = max(cfg.padded_rows, cfg.cold_rows_total)
    if largest > np.iinfo(np.int32).max:
        raise ValueError(
            f"table address space ({total} padded rows, "
            f"{cfg.cold_rows_total} cold-tier rows incl. headroom) exceeds "
            f"int32 range ({np.iinfo(np.int32).max}); row indices are "
            "int32 on device — shard the tables across engines or reduce "
            "the padded vocab sizes")
    return (PIFSEmbeddingEngine(cfg, mesh, axes=axes, planner=planner,
                                dtype=dtype, dedup=dedup),
            np.asarray(offsets, dtype=np.int64))
