"""Pallas TPU kernels for SparseLengthSum — the operator PIFS-Rec accelerates.

TPU-native rethink of the paper's fabric-switch datapath (not a CUDA port):

  * The embedding table stays in HBM ("CXL memory pool") and is *not* streamed
    by the automatic Pallas pipeline: each grid step manually DMAs the rows it
    needs into a double-buffered VMEM landing pad, so the *next* row's DMA
    overlaps the current accumulate — the hardware double-buffer plays the
    role of the paper's swap-register / out-of-order engine: row arrival
    order never stalls the accumulator.
  * Each step's slice of the index stream (rows, per-entry factors, dequant
    scales) rides in SMEM as an auto-pipelined ``(BB, L)`` block — the
    analogue of the instruction-ingress registry: the indices must be
    resident before the table DMAs they drive can be issued.  Blocking the
    stream per step (rather than scalar-prefetching the whole batch) keeps
    SMEM use independent of the batch: a serve_p99 batch of 512 samples x
    8 tables x pooling 8 is 32K entries per operand, past the 1 MiB SMEM.
  * The accumulator lives in VMEM, written back once per bag block
    (revisiting: the out block index depends only on the bag block, so
    Pallas keeps it resident across the pooling-tile steps — the
    Accumulation Configuration Register).

Blocking (bag-tiled): grid = (ceil(B / BB), ceil(L / block_l)).  Each grid
step owns ``BB`` bags (a sublane-aligned ``(BB, D)`` output block, BB a
multiple of 8) and one *tile* of ``block_l`` pooling entries of each, and
runs a double-buffered DMA loop over the ``BB * block_l`` rows.  Tail tiles
(L % block_l != 0) are masked: out-of-range entries fold into factor 0 and
their DMA is clamped to the last valid entry.  Each bag accumulates its
entries in ascending l — the fixed order the jnp oracles reproduce, which
is why kernel and oracle agree bit-for-bit in fp32.

Row DMAs follow the chip's HBM tiling: D must be a 128-lane multiple
(the engine stores rows ``PagingConfig.store_dim`` wide), and an int8 table is DMA'd in 8-row tiles, the
wanted row selected in VMEM (``_RowGather``).

Ownership masking for the sharded engine (``masked_sls_pallas``): a shard
that does not own a row folds the miss into factor 0 and remaps the index to
row 0 — the DMA still happens but targets a single always-resident line,
mirroring how the paper's switch drops non-local candidates without stalling
(section IV-C1).  Semantics match ``core/sls.masked_partial_sls`` on dense
(B, L) bags.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SUBLANES = 8
LANES = 128     # vector lane width: the chip DMAs rows in whole lane tiles


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _check_lanes(D: int, interpret: bool) -> None:
    if not interpret and D % LANES:
        raise ValueError(f"row width {D} is not a multiple of {LANES} lanes; "
                         "store rows lane-aligned (PagingConfig.store_dim)")


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _bag_blocks(B: int, block_b: int) -> tuple:
    """(padded B, bags per grid step): the step's bag count is a multiple
    of the 8-row sublane tile that divides the padded batch."""
    Bp = _round_up(max(B, 1), SUBLANES)
    BB = min(_round_up(max(block_b, 1), SUBLANES), Bp)
    while Bp % BB:
        BB -= SUBLANES
    return Bp, BB


def _pad_rows(a: jax.Array, n: int) -> jax.Array:
    """Zero-pad the leading dim of ``a`` to ``n`` (pad bags gather row 0
    with factor 0, so they contribute nothing and are sliced off)."""
    if a.shape[0] == n:
        return a
    return jnp.pad(a, [(0, n - a.shape[0])] + [(0, 0)] * (a.ndim - 1))


def _tri_pack(z: jax.Array, F: int, self_interaction: bool = False
              ) -> jax.Array:
    """Packed lower triangle of ``z`` (BB, F, F) -> (BB, P) from static
    slices, in ``np.tril_indices`` order (row-major)."""
    k = 1 if self_interaction else 0
    return jnp.concatenate([z[:, a, :a + k] for a in range(1 - k, F)],
                           axis=1)


def _interact(feats: jax.Array, out_dtype, self_interaction: bool = False
              ) -> jax.Array:
    """Pairwise dots of the (BB, F, D) features, packed (BB, P) — the op
    structure of ``ref.dot_interaction_ref`` (one batched matmul, then a
    selection), so kernel and oracle agree bit-for-bit."""
    z = jax.lax.dot_general(feats, feats, (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=out_dtype)   # (BB, F, F)
    return _tri_pack(z, feats.shape[1], self_interaction)


class _RowGather:
    """Double-buffered single-row gather from an HBM table into VMEM.

    ``land`` is the ``(2, R, D)`` landing pad.  4-byte rows are DMA'd one
    at a time (R = 1); narrower dtypes pack several rows per sublane, so
    the chip only DMAs them in aligned 8-row tiles — the tile holding the
    row lands and :meth:`read` selects it (an exact masked sum)."""

    def __init__(self, table_ref, land, sem):
        self.table, self.land, self.sem = table_ref, land, sem
        self.V = table_ref.shape[0]
        self.R = land.shape[1]

    @staticmethod
    def rows_per_dma(table_rows: int, dtype) -> int:
        if jnp.dtype(dtype).itemsize >= 4:
            return 1
        return max(1, min(SUBLANES, table_rows))

    def _start_row(self, r):
        if self.R == 1:
            return r
        return jnp.minimum((r // self.R) * self.R, self.V - self.R)

    def copy(self, slot, r):
        return pltpu.make_async_copy(
            self.table.at[pl.ds(self._start_row(r), self.R)],
            self.land.at[slot], self.sem.at[slot])

    def read(self, slot, r, dtype) -> jax.Array:
        """The landed row ``r`` as a (1, D) ``dtype`` value."""
        blk = self.land[slot].astype(dtype)                     # (R, D)
        if self.R == 1:
            return blk
        sel = (jax.lax.broadcasted_iota(jnp.int32, blk.shape, 0)
               == r - self._start_row(r))
        return jnp.sum(jnp.where(sel, blk, 0), axis=0, keepdims=True)


def _landing(table: jax.Array, D: int):
    R = _RowGather.rows_per_dma(table.shape[0], table.dtype)
    return pltpu.VMEM((2, R, D), table.dtype)


def _stage_unique(uniq_ref, n_ref, scale_ref, gather: _RowGather, staging,
                  acc_dtype) -> None:
    """Gather-once prologue: DMA each live unique row (sentinel and padded
    slots clamped into range; at least one slot so a nothing-owned plan
    still reads initialized staging), dequantize it once, and store it into
    the persistent ``(U, D)`` VMEM staging."""
    n = jnp.maximum(n_ref[0], 1)

    def row(u):
        return jnp.minimum(uniq_ref[u], gather.V - 1)

    gather.copy(0, row(0)).start()

    def body(u, carry):
        slot = u % 2

        @pl.when(u + 1 < n)
        def _prefetch_next():
            gather.copy((u + 1) % 2, row(u + 1)).start()

        gather.copy(slot, row(u)).wait()
        r = gather.read(slot, row(u), acc_dtype)
        if scale_ref is not None:
            r = r * scale_ref[u].astype(acc_dtype)
        staging[pl.ds(u, 1)] = r
        return carry

    jax.lax.fori_loop(0, n, body, 0)


def _factors(owned: Optional[jax.Array], weights: Optional[jax.Array],
             shape) -> jax.Array:
    """Per-entry accumulate factor ``owned * w`` (the oracles' order)."""
    f = (jnp.ones(shape, jnp.float32) if owned is None
         else owned.astype(jnp.float32))
    if weights is not None:
        f = f * weights.astype(jnp.float32)
    return f


# ---------------------------------------------------------------------------
# Masked partial SLS (split datapath)
# ---------------------------------------------------------------------------


def _make_sls_kernel(L: int, block_l: int, BB: int, has_scales: bool,
                     dedup: bool):
    """Bag-tiled masked SLS body for a static (L, block_l, BB, flags).

    ``dedup``: the gather-once variant — the very first grid step stages
    each *unique* row (``_stage_unique``), and every entry then reads its
    row from VMEM staging through its slot; the accumulate sees the same
    operands in the same order, so both variants agree bit-for-bit."""
    n_entries = BB * block_l

    def kernel(*refs):
        it = iter(refs)
        if dedup:
            uniq_ref = next(it)       # (U,) unique rows, sentinel-padded
            n_ref = next(it)          # (1,) live staging slots
            us_ref = next(it) if has_scales else None   # (U,) scales
            slots_ref = next(it)      # (BB, L) SMEM staging slot per entry
        else:
            rows_ref = next(it)       # (BB, L) SMEM row per entry
        f_ref = next(it)              # (BB, L) SMEM accumulate factor
        s_ref = next(it) if has_scales and not dedup else None
        table_ref = next(it)          # (V, D) in HBM — manually DMA'd
        out_ref = next(it)            # (BB, D) accumulator, revisited per t
        if dedup:
            staging = next(it)        # (U, D) VMEM, persists across steps
        land = next(it)               # (2, R, D) VMEM DMA double buffer
        sem = next(it)                # (2,) DMA semaphores

        b = pl.program_id(0)
        t = pl.program_id(1)
        l0 = t * block_l
        acc_dtype = out_ref.dtype
        gather = _RowGather(table_ref, land, sem)

        if dedup:
            @pl.when((b == 0) & (t == 0))
            def _fill_staging():
                _stage_unique(uniq_ref, n_ref, us_ref, gather, staging,
                              acc_dtype)

        @pl.when(t == 0)
        def _init():
            out_ref[...] = jnp.zeros_like(out_ref)

        def entry(k):
            i = k // block_l
            return i, jnp.minimum(l0 + k % block_l, L - 1)

        if not dedup:
            def dma(slot, k):
                i, l = entry(k)
                return gather.copy(slot, rows_ref[i, l])

            dma(0, 0).start()

        def body(k, carry):
            i, lc = entry(k)
            valid = (l0 + k % block_l < L).astype(acc_dtype)
            f = valid * f_ref[i, lc].astype(acc_dtype)
            if dedup:
                row = staging[pl.ds(slots_ref[i, lc], 1)]     # VMEM read
            else:
                slot = k % 2

                @pl.when(k + 1 < n_entries)
                def _prefetch_next():
                    dma((k + 1) % 2, k + 1).start()

                dma(slot, k).wait()
                row = gather.read(slot, rows_ref[i, lc], acc_dtype)
                if has_scales:
                    # fused dequant: the int8 row is scaled to fp32 after
                    # its DMA landed — an fp32 copy of the cold shard
                    # never exists, only this (1, D) working row
                    row = row * s_ref[i, lc].astype(acc_dtype)
            out_ref[pl.ds(i, 1)] = out_ref[pl.ds(i, 1)] + f * row
            return carry

        jax.lax.fori_loop(0, n_entries, body, 0)

    return kernel


def _sls_call(table: jax.Array, indices: jax.Array,
              owned: Optional[jax.Array], weights: Optional[jax.Array],
              scales: Optional[jax.Array], out_dtype, interpret: bool,
              block_l: int, dedup_plan=None) -> jax.Array:
    B, L = indices.shape
    V, D = table.shape
    if B == 0 or L == 0:
        return jnp.zeros((B, D), out_dtype)
    _check_lanes(D, interpret)
    block_l = max(1, min(block_l, L))
    Bp, BB = _bag_blocks(B, SUBLANES)
    f = _pad_rows(_factors(owned, weights, (B, L)), Bp)
    stream = pl.BlockSpec((BB, L), lambda b, t, *_: (b, 0),
                          memory_space=pltpu.SMEM)
    scratch = [_landing(table, D), pltpu.SemaphoreType.DMA((2,))]
    if dedup_plan is not None:
        uniq, slots, n_slots, uscales = dedup_plan
        prefetch = [uniq.astype(jnp.int32),
                    n_slots.astype(jnp.int32).reshape(1)]
        if uscales is not None:
            prefetch.append(uscales.astype(jnp.float32))
        blocked = [_pad_rows(slots.astype(jnp.int32), Bp), f]
        scratch = [pltpu.VMEM((uniq.shape[0], D), out_dtype)] + scratch
        has_scales = uscales is not None
    else:
        prefetch = []
        rows = indices.astype(jnp.int32)
        if owned is not None:
            rows = jnp.where(owned, rows, 0)
        blocked = [_pad_rows(rows, Bp), f]
        if scales is not None:
            blocked.append(_pad_rows(scales.astype(jnp.float32), Bp))
        has_scales = scales is not None

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(Bp // BB, pl.cdiv(L, block_l)),
        in_specs=[stream] * len(blocked)
        + [pl.BlockSpec(memory_space=pl.ANY)],           # table stays in HBM
        out_specs=pl.BlockSpec((BB, D), lambda b, t, *_: (b, 0)),
        scratch_shapes=scratch,
    )
    kernel = _make_sls_kernel(L, block_l, BB, has_scales=has_scales,
                              dedup=dedup_plan is not None)
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Bp, D), out_dtype),
        interpret=interpret,
    )(*prefetch, *blocked, table)
    return out[:B]


@functools.partial(jax.jit,
                   static_argnames=("out_dtype", "interpret", "block_l"))
def masked_sls_dedup_pallas(table: jax.Array, unique_rows: jax.Array,
                            slots: jax.Array, owned: jax.Array,
                            n_slots: jax.Array,
                            weights: Optional[jax.Array] = None,
                            unique_scales: Optional[jax.Array] = None,
                            out_dtype=jnp.float32,
                            interpret: Optional[bool] = None,
                            block_l: int = 8) -> jax.Array:
    """Gather-once dedup'd masked partial SLS (oracle:
    ``kernels/ref.py:masked_sls_dedup_ref``).

    ``unique_rows (U,)`` / ``slots (B, L)`` / ``n_slots (1,)`` come from
    ``core/sls.dedup_plan`` (U = B*L capacity, sentinel-padded).  Grid and
    accumulate structure match ``masked_sls_pallas``; the table DMA happens
    once per unique row in a prologue on the first grid step instead of
    once per pooling entry.  Both grid dims must execute sequentially
    (staging is written at the first step and read by all later ones) —
    they are "arbitrary" semantics, the Pallas TPU default.
    """
    if interpret is None:
        interpret = _default_interpret()
    return _sls_call(table, slots, owned, weights, None, out_dtype,
                     interpret, block_l,
                     dedup_plan=(unique_rows, slots, n_slots, unique_scales))


@functools.partial(jax.jit,
                   static_argnames=("out_dtype", "interpret", "block_l"))
def sls_pallas(table: jax.Array, indices: jax.Array,
               weights: Optional[jax.Array] = None,
               out_dtype=jnp.float32, interpret: Optional[bool] = None,
               block_l: int = 8) -> jax.Array:
    """SLS via pl.pallas_call. indices: (B, L) int32 -> (B, D) pooled."""
    if interpret is None:
        interpret = _default_interpret()
    return _sls_call(table, indices, None, weights, None, out_dtype,
                     interpret, block_l)


@functools.partial(jax.jit,
                   static_argnames=("out_dtype", "interpret", "block_l"))
def masked_sls_pallas(table: jax.Array, indices: jax.Array, owned: jax.Array,
                      weights: Optional[jax.Array] = None,
                      scales: Optional[jax.Array] = None,
                      out_dtype=jnp.float32, interpret: Optional[bool] = None,
                      block_l: int = 8) -> jax.Array:
    """Masked partial SLS: out[b] = sum_l owned[b,l]*w[b,l]*table[idx[b,l]].

    The per-shard operator of the PIFS engine: ``owned`` marks the pooling
    entries whose rows live on this shard; everything else contributes zero
    (and its gather is remapped to row 0, which must exist).

    Optional ``scales`` (B, L): per-entry dequant scales for a quantized
    (int8) ``table``.  Each DMA'd row is dequantized in VMEM
    (``float(row) * scale``) right before the weighted accumulate — the
    tiered-precision store's fused-dequant datapath (oracle:
    ``kernels/ref.py:masked_sls_quant_ref``).
    """
    if interpret is None:
        interpret = _default_interpret()
    return _sls_call(table, indices, owned, weights, scales, out_dtype,
                     interpret, block_l)


# ---------------------------------------------------------------------------
# Fused front end: SLS -> dot-interaction
# ---------------------------------------------------------------------------


def _make_fused_front_end_kernel(L: int, block_l: int, G: int, BB: int,
                                 has_scales: bool, dedup: bool,
                                 emit: str = "interact"):
    """Fused DLRM front-end kernel body: SLS -> dot-interaction, one kernel.

    Three phases over grid ``(Bp // BB, G, ceil(L / block_l))``:

      * phase 1 (``dedup`` only, very first grid step): gather-once DMA of
        each unique cold/hot row into persistent ``(U, D)`` VMEM row
        staging, fused per-row dequant — ``_stage_unique``, once per tier.
      * phase 2 (every step): the bag-tiled fixed-l-order masked accumulate
        of table ``g``, writing pooled rows into persistent VMEM *feature
        staging* ``(BB, F, D)`` (one accumulator per tier so the final
        ``cold + hot`` add matches the split datapath's
        ``psum(cold_part) + hot_out`` bit-for-bit).  Feature row 0 of each
        sample is the bottom-MLP output (``x``), stored once per batch
        tile.
      * phase 3 (last ``(g, t)`` step of each batch tile): the
        dot-interaction matmul + static triangle pack (``_interact``) on
        the resident features.

    ``emit`` selects where the pipeline stops:

      * ``"interact"`` — the full three-phase kernel above; one ``(BB, P)``
        packed-triangle output.  ``x`` lands in *cold* staging row 0.
      * ``"tiles"`` — stop at the phase-2/3 seam for tensor-parallel
        execution: emit the per-tier partial feature tiles ``(BB, F, D)``
        (cold, hot) instead of interacting.  ``x`` lands in *hot* staging
        row 0 here — the hot tier is replicated across tp shards and is
        *not* psum'd, so ``x`` is counted exactly once; the cold tile's
        row 0 stays zero and is safe to all-reduce.  The reduced tile
        resumes phase 3 in :func:`fused_resume_pallas`.

    The pooled-features tensor never exists in HBM: the only HBM traffic is
    the row gather (phase 1/2) plus the ``(BB, D)`` x block in and the
    ``(BB, P)`` packed triangle (or the two ``(BB, F, D)`` partial tiles)
    out.
    """
    interact = emit == "interact"
    n_entries = BB * block_l

    def kernel(*refs):
        it = iter(refs)
        if dedup:
            cuniq_ref = next(it)    # (U,) unique cold rows, sentinel-padded
            cn_ref = next(it)       # (1,) live cold staging slots
            cs_ref = next(it) if has_scales else None   # (U,) dequant scales
            huniq_ref = next(it)    # (U,) unique hot rows, sentinel-padded
            hn_ref = next(it)       # (1,) live hot staging slots
        crow_ref = next(it)         # (BB, L) SMEM cold row / staging slot
        hrow_ref = next(it)         # (BB, L) SMEM hot row / staging slot
        fc_ref = next(it)           # (BB, L) SMEM cold factor owned * w
        fh_ref = next(it)           # (BB, L) SMEM hot factor is_hot * w
        s_ref = next(it) if has_scales and not dedup else None
        cold_ref = next(it)         # (Vc, D) in HBM — manually DMA'd
        hot_table_ref = next(it)    # (Vh, D) in HBM — manually DMA'd
        x_ref = next(it)            # (BB, D) bottom-MLP block (auto-piped)
        if interact:
            out_ref = next(it)      # (BB, P) packed-triangle block
            acc_dtype = out_ref.dtype
        else:
            out_c_ref = next(it)    # (BB, F, D) cold partial feature tile
            out_h_ref = next(it)    # (BB, F, D) hot partial feature tile
            acc_dtype = out_c_ref.dtype
        if dedup:
            crows = next(it)        # (U, D) VMEM cold row staging (dequant'd)
            hrows = next(it)        # (U, D) VMEM hot row staging
        stage_c = next(it)          # (BB, F, D) VMEM cold feature staging
        stage_h = next(it)          # (BB, F, D) VMEM hot feature staging
        cland = next(it)            # (2, R, D) cold DMA double buffer
        hland = next(it)            # (2, 1, D) hot DMA double buffer
        csem = next(it)             # (2,) cold DMA semaphores
        hsem = next(it)             # (2,) hot DMA semaphores

        bt = pl.program_id(0)
        g = pl.program_id(1)
        t = pl.program_id(2)
        n_tl = pl.num_programs(2)
        l0 = t * block_l
        cgather = _RowGather(cold_ref, cland, csem)
        hgather = _RowGather(hot_table_ref, hland, hsem)

        if dedup:
            @pl.when((bt == 0) & (g == 0) & (t == 0))
            def _fill_row_staging():
                # gather-once per tier: each unique row crosses the memory
                # interface exactly once; phase 2 reads VMEM only.
                _stage_unique(cuniq_ref, cn_ref, cs_ref, cgather, crows,
                              acc_dtype)
                _stage_unique(huniq_ref, hn_ref, None, hgather, hrows,
                              acc_dtype)

        @pl.when((g == 0) & (t == 0))
        def _init_features():
            # per batch tile: zero both accumulators, land the bottom-MLP
            # output in feature row 0 of the cold staging (the hot
            # staging's row 0 stays zero, so the phase-3 add reproduces the
            # split path's `concat([x, pooled])` exactly).  In tiles mode x
            # rides the *hot* staging instead: hot is replicated across tp
            # shards while the cold tile is psum'd, so this is the
            # placement that counts x once.
            stage_c[...] = jnp.zeros_like(stage_c)
            stage_h[...] = jnp.zeros_like(stage_h)
            with_x = stage_c if interact else stage_h
            with_x[:, 0, :] = x_ref[...].astype(acc_dtype)

        def entry(k):
            i = k // block_l
            return i, jnp.minimum(l0 + k % block_l, L - 1)

        if not dedup:
            def start(slot, k):
                # one DMA per tier per entry; out-of-tier entries were
                # remapped to the always-resident line 0 of that tier's
                # table by the wrapper (their factor is zero)
                i, l = entry(k)
                cgather.copy(slot, crow_ref[i, l]).start()
                hgather.copy(slot, hrow_ref[i, l]).start()

            start(0, 0)

        def body(k, carry):
            i, lc = entry(k)
            valid = (l0 + k % block_l < L).astype(acc_dtype)
            fc = valid * fc_ref[i, lc].astype(acc_dtype)
            fh = valid * fh_ref[i, lc].astype(acc_dtype)
            if dedup:
                row_c = crows[pl.ds(crow_ref[i, lc], 1)]
                row_h = hrows[pl.ds(hrow_ref[i, lc], 1)]
            else:
                slot = k % 2

                @pl.when(k + 1 < n_entries)
                def _prefetch_next():
                    start((k + 1) % 2, k + 1)

                cgather.copy(slot, crow_ref[i, lc]).wait()
                hgather.copy(slot, hrow_ref[i, lc]).wait()
                row_c = cgather.read(slot, crow_ref[i, lc], acc_dtype)
                if has_scales:
                    row_c = row_c * s_ref[i, lc].astype(acc_dtype)
                row_h = hgather.read(slot, hrow_ref[i, lc], acc_dtype)
            feat = pl.ds(g + 1, 1)
            stage_c[i, feat] = stage_c[i, feat] + fc * row_c
            stage_h[i, feat] = stage_h[i, feat] + fh * row_h
            return carry

        jax.lax.fori_loop(0, n_entries, body, 0)

        @pl.when((g == G - 1) & (t == n_tl - 1))
        def _finish():
            if interact:
                # phase 3: dot-interaction on the resident features
                out_ref[...] = _interact(stage_c[...] + stage_h[...],
                                         out_ref.dtype)
            else:
                # phase-2/3 seam: hand the per-tier partial tiles to the
                # cross-shard psum; the cold/hot add happens after the
                # reduction in the resume kernel, preserving the split
                # path's `psum(cold_part) + hot_out` operand order.
                out_c_ref[...] = stage_c[...]
                out_h_ref[...] = stage_h[...]

    return kernel


def _fused_call(cold: jax.Array, hot: jax.Array, x: jax.Array,
                rows: Optional[jax.Array], owned: jax.Array,
                is_hot: jax.Array, weights: Optional[jax.Array],
                scales: Optional[jax.Array], dedup_plans, out_dtype,
                interpret: bool, block_l: int, block_b: int, emit: str):
    """Shared pallas_call builder of the four fused front-end entry points.

    ``dedup_plans`` is ``((unique, slots, n, scales), (unique, slots, n))``
    for the cold and hot tier, or None.  The per-entry streams are laid out table-major ``(G * Bp, L)`` so one
    grid step's ``(BB, L)`` SMEM block is contiguous."""
    B, G, L = owned.shape
    D = cold.shape[-1]
    _check_lanes(D, interpret)
    F = G + 1
    P = F * (F - 1) // 2
    Bp, BB = _bag_blocks(B, block_b)
    block_l = max(1, min(block_l, L))
    nbt = Bp // BB

    def stream(a):                       # (B, G, L) -> (G * Bp, L)
        return _pad_rows(a, Bp).transpose(1, 0, 2).reshape(G * Bp, L)

    fc = _factors(owned, weights, owned.shape)
    fh = _factors(is_hot, weights, owned.shape)
    scratch = [pltpu.VMEM((BB, F, D), out_dtype),          # cold features
               pltpu.VMEM((BB, F, D), out_dtype),          # hot features
               _landing(cold, D), _landing(hot, D),
               pltpu.SemaphoreType.DMA((2,)), pltpu.SemaphoreType.DMA((2,))]
    if dedup_plans is not None:
        (c_uniq, c_slots, c_n, c_scales), (h_uniq, h_slots, h_n) = dedup_plans
        prefetch = [c_uniq.astype(jnp.int32),
                    c_n.astype(jnp.int32).reshape(1)]
        if c_scales is not None:
            prefetch.append(c_scales.astype(jnp.float32))
        prefetch += [h_uniq.astype(jnp.int32),
                     h_n.astype(jnp.int32).reshape(1)]
        blocked = [stream(c_slots.astype(jnp.int32)),
                   stream(h_slots.astype(jnp.int32)),
                   stream(fc), stream(fh)]
        U = c_uniq.shape[0]
        scratch = [pltpu.VMEM((U, D), out_dtype),           # cold row staging
                   pltpu.VMEM((U, D), out_dtype)] + scratch  # hot row staging
        has_scales = c_scales is not None
    else:
        prefetch = []
        r = rows.astype(jnp.int32)
        blocked = [stream(jnp.where(owned, r, 0)),
                   stream(jnp.where(is_hot, r, 0)), stream(fc), stream(fh)]
        if scales is not None:
            blocked.append(stream(scales.astype(jnp.float32)))
        has_scales = scales is not None

    smem = pl.BlockSpec((BB, L), lambda bt, g, t, *_: (g * nbt + bt, 0),
                        memory_space=pltpu.SMEM)
    if emit == "interact":
        out_specs = pl.BlockSpec((BB, P), lambda bt, g, t, *_: (bt, 0))
        out_shape = jax.ShapeDtypeStruct((Bp, P), out_dtype)
    else:
        tile = pl.BlockSpec((BB, F, D), lambda bt, g, t, *_: (bt, 0, 0))
        out_specs = [tile, tile]
        out_shape = [jax.ShapeDtypeStruct((Bp, F, D), out_dtype)] * 2
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(nbt, G, pl.cdiv(L, block_l)),
        in_specs=[smem] * len(blocked)
        + [pl.BlockSpec(memory_space=pl.ANY),              # cold stays in HBM
           pl.BlockSpec(memory_space=pl.ANY),              # hot stays in HBM
           pl.BlockSpec((BB, D), lambda bt, g, t, *_: (bt, 0))],
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    kernel = _make_fused_front_end_kernel(
        L, block_l, G, BB, has_scales=has_scales,
        dedup=dedup_plans is not None, emit=emit)
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=out_shape,
        interpret=interpret,
    )(*prefetch, *blocked, cold, hot, _pad_rows(x, Bp))
    if emit == "interact":
        return out[:B]
    return out[0][:B], out[1][:B]


def _empty_tiles(B: int, F: int, D: int, x: jax.Array, out_dtype):
    zc = jnp.zeros((B, F, D), out_dtype)
    return zc, jnp.concatenate([x[:, None, :].astype(out_dtype), zc[:, 1:]],
                               axis=1)


@functools.partial(jax.jit, static_argnames=("out_dtype", "interpret",
                                             "block_l", "block_b"))
def fused_front_end_pallas(cold: jax.Array, hot: jax.Array, x: jax.Array,
                           rows: jax.Array, owned: jax.Array,
                           is_hot: jax.Array,
                           weights: Optional[jax.Array] = None,
                           scales: Optional[jax.Array] = None,
                           out_dtype=jnp.float32,
                           interpret: Optional[bool] = None,
                           block_l: int = 8, block_b: int = 32) -> jax.Array:
    """Fused SLS -> dot-interaction front end (oracle:
    ``kernels/ref.py:fused_front_end_ref``).

    rows/owned/is_hot (B, G, L): per-entry local row + tier masks (cold /
    hot; entries in neither tier contribute zero).  x (B, D): the bottom-MLP
    output, feature row 0.  Returns the (B, P) packed lower triangle of the
    (B, F, D) features' pairwise dots, F = G + 1, without ever writing the
    pooled features to HBM.  Bit-for-bit equal to the split pipeline
    (masked SLS per tier -> add -> concat -> dot-interaction) in fp32.
    """
    if interpret is None:
        interpret = _default_interpret()
    B, G, L = rows.shape
    if B == 0 or L == 0 or G == 0:
        return jnp.zeros((B, (G + 1) * G // 2), out_dtype)
    return _fused_call(cold, hot, x, rows, owned, is_hot, weights, scales,
                       None, out_dtype, interpret, block_l, block_b,
                       "interact")


@functools.partial(jax.jit, static_argnames=("out_dtype", "interpret",
                                             "block_l", "block_b"))
def fused_front_end_dedup_pallas(cold: jax.Array, hot: jax.Array,
                                 x: jax.Array,
                                 c_unique: jax.Array, c_slots: jax.Array,
                                 c_n: jax.Array, h_unique: jax.Array,
                                 h_slots: jax.Array, h_n: jax.Array,
                                 owned: jax.Array, is_hot: jax.Array,
                                 weights: Optional[jax.Array] = None,
                                 c_scales: Optional[jax.Array] = None,
                                 out_dtype=jnp.float32,
                                 interpret: Optional[bool] = None,
                                 block_l: int = 8, block_b: int = 32
                                 ) -> jax.Array:
    """Gather-once dedup'd fused front end: phase 1 stages each unique
    cold/hot row once (fused dequant), phases 2-3 as
    :func:`fused_front_end_pallas` with VMEM staging reads instead of
    per-entry DMA.  ``c_*`` / ``h_*`` come from one ``core/sls.dedup_plan``
    per tier (slots reshaped to (B, G, L)); bit-for-bit equal to the
    non-dedup kernel.
    """
    if interpret is None:
        interpret = _default_interpret()
    B, G, L = c_slots.shape
    if B == 0 or L == 0 or G == 0:
        return jnp.zeros((B, (G + 1) * G // 2), out_dtype)
    plans = ((c_unique, c_slots, c_n, c_scales), (h_unique, h_slots, h_n))
    return _fused_call(cold, hot, x, None, owned, is_hot, weights, None,
                       plans, out_dtype, interpret, block_l, block_b,
                       "interact")


@functools.partial(jax.jit, static_argnames=("out_dtype", "interpret",
                                             "block_l", "block_b"))
def fused_partial_pool_pallas(cold: jax.Array, hot: jax.Array, x: jax.Array,
                              rows: jax.Array, owned: jax.Array,
                              is_hot: jax.Array,
                              weights: Optional[jax.Array] = None,
                              scales: Optional[jax.Array] = None,
                              out_dtype=jnp.float32,
                              interpret: Optional[bool] = None,
                              block_l: int = 8, block_b: int = 32):
    """Phases 1-2 of the fused front end, stopped at the phase-2/3 seam
    (oracle: ``kernels/ref.py:fused_partial_pool_ref``).

    Returns the per-tier partial feature tiles ``(B, F, D)``:

      * ``part_c`` — this shard's cold-tier partial pools, feature row 0
        all-zero (safe to ``psum`` across tp shards), and
      * ``part_h`` — the hot-tier pools with the bottom-MLP output ``x`` in
        feature row 0 (hot is replicated, never reduced).

    ``psum(part_c) + part_h`` reproduces the split datapath's
    ``psum(cold_part) + hot_out`` / ``concat([x, pooled])`` features
    bit-for-bit; :func:`fused_resume_pallas` finishes phase 3 on the
    reduced tile.
    """
    if interpret is None:
        interpret = _default_interpret()
    B, G, L = rows.shape
    if B == 0 or L == 0 or G == 0:
        return _empty_tiles(B, G + 1, cold.shape[-1], x, out_dtype)
    return _fused_call(cold, hot, x, rows, owned, is_hot, weights, scales,
                       None, out_dtype, interpret, block_l, block_b, "tiles")


@functools.partial(jax.jit, static_argnames=("out_dtype", "interpret",
                                             "block_l", "block_b"))
def fused_partial_pool_dedup_pallas(cold: jax.Array, hot: jax.Array,
                                    x: jax.Array,
                                    c_unique: jax.Array, c_slots: jax.Array,
                                    c_n: jax.Array, h_unique: jax.Array,
                                    h_slots: jax.Array, h_n: jax.Array,
                                    owned: jax.Array, is_hot: jax.Array,
                                    weights: Optional[jax.Array] = None,
                                    c_scales: Optional[jax.Array] = None,
                                    out_dtype=jnp.float32,
                                    interpret: Optional[bool] = None,
                                    block_l: int = 8, block_b: int = 32):
    """Gather-once dedup'd partial pool: phase 1 stages each unique cold/hot
    row once per shard (dedup staging stays per-shard — only the pooled
    tile crosses the fabric), phase 2 as :func:`fused_partial_pool_pallas`.
    Bit-for-bit equal to the non-dedup tiles.
    """
    if interpret is None:
        interpret = _default_interpret()
    B, G, L = c_slots.shape
    if B == 0 or L == 0 or G == 0:
        return _empty_tiles(B, G + 1, cold.shape[-1], x, out_dtype)
    plans = ((c_unique, c_slots, c_n, c_scales), (h_unique, h_slots, h_n))
    return _fused_call(cold, hot, x, None, owned, is_hot, weights, None,
                       plans, out_dtype, interpret, block_l, block_b,
                       "tiles")


def _make_fused_resume_kernel():
    """Phase-3 resume body: cold/hot add on the *reduced* tile, then the
    dot-interaction matmul + static triangle pack — the same op sequence
    the ``emit='interact'`` kernel runs on its resident staging, so the
    tp-sharded composition stays bit-for-bit against the one-shard fusion.
    """

    def kernel(c_ref, h_ref, out_ref):
        out_ref[...] = _interact(c_ref[...] + h_ref[...], out_ref.dtype)

    return kernel


@functools.partial(jax.jit, static_argnames=("out_dtype", "interpret",
                                             "block_b"))
def fused_resume_pallas(part_c: jax.Array, part_h: jax.Array,
                        out_dtype=jnp.float32,
                        interpret: Optional[bool] = None,
                        block_b: int = 32) -> jax.Array:
    """Resume phase 3 on the psum-reduced ``(B, F, D)`` tiles: feats =
    part_c + part_h, dot-interaction, packed lower triangle ``(B, P)``.
    The features stay VMEM-resident on this side of the collective too —
    the tiles stream in as blocks, the interaction never round-trips a
    concat'd features tensor through HBM.
    """
    if interpret is None:
        interpret = _default_interpret()
    B, F, D = part_c.shape
    P = F * (F - 1) // 2
    if B == 0 or F == 1:
        return jnp.zeros((B, P), out_dtype)
    Bp, BB = _bag_blocks(B, block_b)
    tile_spec = pl.BlockSpec((BB, F, D), lambda bt: (bt, 0, 0))
    out = pl.pallas_call(
        _make_fused_resume_kernel(), grid=(Bp // BB,),
        in_specs=[tile_spec, tile_spec],
        out_specs=pl.BlockSpec((BB, P), lambda bt: (bt, 0)),
        out_shape=jax.ShapeDtypeStruct((Bp, P), out_dtype),
        interpret=interpret,
    )(_pad_rows(part_c, Bp), _pad_rows(part_h, Bp))
    return out[:B]
