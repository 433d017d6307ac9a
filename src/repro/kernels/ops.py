"""jit'd public wrappers around the Pallas kernels.

On this CPU container the kernels execute in interpret mode (the kernel body
runs as Python/jnp on CPU); on TPU set ``interpret=False`` (the default picks
by backend).  ``impl='jnp'`` falls back to the oracle — models use that path
for fast CPU smoke tests, while tests sweep the pallas path against ref.

Lane alignment: on the chip the SLS and fused kernels DMA table rows in
whole 128-lane tiles, so their tables (and ``x``) must be lane-aligned
there.  The engine stores its rows padded once (``PagingConfig.store_dim``);
these wrappers never pad, and a misaligned compiled call is refused.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.interaction import dot_interaction_pallas
from repro.kernels.sls import (_default_interpret,
                               fused_front_end_dedup_pallas,
                               fused_front_end_pallas,
                               fused_partial_pool_dedup_pallas,
                               fused_partial_pool_pallas, fused_resume_pallas,
                               masked_sls_dedup_pallas, masked_sls_pallas,
                               sls_pallas)


def sls(table: jax.Array, indices: jax.Array,
        weights: Optional[jax.Array] = None, out_dtype=jnp.float32,
        impl: str = "pallas", interpret: Optional[bool] = None,
        block_l: int = 8) -> jax.Array:
    """Pooled embedding lookup: indices (B, L) -> (B, D)."""
    if impl == "jnp":
        return ref.sls_ref(table, indices, weights, out_dtype)
    if interpret is None:
        interpret = _default_interpret()
    return sls_pallas(table, indices, weights, out_dtype=out_dtype,
                      interpret=interpret, block_l=block_l)


def masked_sls(table: jax.Array, indices: jax.Array, owned: jax.Array,
               weights: Optional[jax.Array] = None, out_dtype=jnp.float32,
               impl: str = "pallas", interpret: Optional[bool] = None,
               block_l: int = 8, scales: Optional[jax.Array] = None) -> jax.Array:
    """Masked partial SLS (the PIFS per-shard operator): (B, L) -> (B, D).

    ``scales`` (B, L, optional) dequantizes a quantized (int8) ``table``
    per gathered row inside the kernel (fused dequant; see kernels/sls.py).
    """
    if impl == "jnp":
        return ref.masked_sls_ref(table, indices, owned, weights, out_dtype,
                                  scales=scales)
    if interpret is None:
        interpret = _default_interpret()
    return masked_sls_pallas(table, indices, owned, weights, scales,
                             out_dtype=out_dtype, interpret=interpret,
                             block_l=block_l)


def masked_sls_dedup(table: jax.Array, plan, owned: jax.Array,
                     weights: Optional[jax.Array] = None,
                     out_dtype=jnp.float32, impl: str = "pallas",
                     interpret: Optional[bool] = None, block_l: int = 8
                     ) -> jax.Array:
    """Gather-once dedup'd masked partial SLS: each unique owned row is
    DMA'd (and dequantized) exactly once into VMEM staging, then the
    bag-tiled accumulate reads through the plan's slot indirection.

    ``plan`` is a ``core/sls.DedupPlan`` (unique_rows/slots/n_slots/
    unique_scales); build it with ``core/sls.dedup_plan``.  Bit-for-bit
    equal to :func:`masked_sls` in fp32 (oracle:
    ``ref.masked_sls_dedup_ref``).
    """
    if impl == "jnp":
        return ref.masked_sls_dedup_ref(
            table, plan.unique_rows, plan.slots, owned, weights,
            unique_scales=plan.unique_scales, out_dtype=out_dtype)
    if interpret is None:
        interpret = _default_interpret()
    return masked_sls_dedup_pallas(
        table, plan.unique_rows, plan.slots, owned, plan.n_slots, weights,
        plan.unique_scales, out_dtype=out_dtype, interpret=interpret,
        block_l=block_l)


def dot_interaction(feats: jax.Array, self_interaction: bool = False,
                    impl: str = "pallas", block_b: int = 128,
                    interpret: Optional[bool] = None) -> jax.Array:
    """DLRM pairwise-dot interaction.  ``interpret=None`` defers to the
    kernel's backend detection (interpret only off-TPU); passing a bool
    threads an explicit override through to ``dot_interaction_pallas``."""
    if impl == "jnp":
        return ref.dot_interaction_ref(feats, self_interaction)
    return dot_interaction_pallas(feats, self_interaction, block_b=block_b,
                                  interpret=interpret)


def fused_front_end(cold: jax.Array, hot: jax.Array, x: jax.Array,
                    rows: jax.Array, owned: jax.Array, is_hot: jax.Array,
                    weights: Optional[jax.Array] = None,
                    scales: Optional[jax.Array] = None,
                    dedup_plans=None, out_dtype=jnp.float32,
                    impl: str = "pallas", interpret: Optional[bool] = None,
                    block_l: int = 8, block_b: int = 32) -> jax.Array:
    """Fused DLRM front end: masked two-tier SLS -> dot-interaction in one
    kernel — the pooled (B, F, D) features tensor never exists in HBM.

    ``rows``/``owned``/``is_hot`` (B, G, L) are per-entry local rows + tier
    masks, ``x`` (B, D) the bottom-MLP output.  ``dedup_plans`` is an
    optional ``(cold_plan, hot_plan)`` pair of ``core/sls.DedupPlan``s
    (slots reshaped (B, G, L) by the caller) selecting the gather-once
    kernel variant.  The (B, P) output is D-free: zero pad lanes in the
    three dense operands add exact +0 terms to every pairwise dot.
    Bit-for-bit equal to the split pipeline in fp32 (oracle:
    ``ref.fused_front_end_ref``).
    """
    if impl == "jnp":
        if dedup_plans is not None:
            # the coalesced gather never changes the accumulate (PR 4);
            # the jnp oracle is the per-entry formulation
            dedup_plans = None
        return ref.fused_front_end_ref(cold, hot, x, rows, owned, is_hot,
                                       weights, scales, out_dtype)
    if interpret is None:
        interpret = _default_interpret()
    if dedup_plans is not None:
        cp, hp = dedup_plans
        return fused_front_end_dedup_pallas(
            cold, hot, x, cp.unique_rows, cp.slots, cp.n_slots,
            hp.unique_rows, hp.slots, hp.n_slots, owned, is_hot,
            weights, cp.unique_scales, out_dtype=out_dtype,
            interpret=interpret, block_l=block_l, block_b=block_b)
    return fused_front_end_pallas(
        cold, hot, x, rows, owned, is_hot, weights, scales,
        out_dtype=out_dtype, interpret=interpret, block_l=block_l,
        block_b=block_b)


def fused_partial_pool(cold: jax.Array, hot: jax.Array, x: jax.Array,
                       rows: jax.Array, owned: jax.Array, is_hot: jax.Array,
                       weights: Optional[jax.Array] = None,
                       scales: Optional[jax.Array] = None,
                       dedup_plans=None, out_dtype=jnp.float32,
                       impl: str = "pallas", interpret: Optional[bool] = None,
                       block_l: int = 8, block_b: int = 32):
    """Phases 1-2 of :func:`fused_front_end`, stopped at the phase-2/3 seam:
    returns the per-tier partial feature tiles ``(B, F, D)`` — cold (row 0
    zero, the tile a tp dispatch psums across shards) and hot (``x`` in
    row 0; replicated, never reduced).  ``fused_resume`` finishes the
    interaction on the reduced tile.  Oracle:
    ``ref.fused_partial_pool_ref``.
    """
    if impl == "jnp":
        if dedup_plans is not None:
            dedup_plans = None
        return ref.fused_partial_pool_ref(cold, hot, x, rows, owned, is_hot,
                                          weights, scales, out_dtype)
    if interpret is None:
        interpret = _default_interpret()
    if dedup_plans is not None:
        cp, hp = dedup_plans
        return fused_partial_pool_dedup_pallas(
            cold, hot, x, cp.unique_rows, cp.slots, cp.n_slots,
            hp.unique_rows, hp.slots, hp.n_slots, owned, is_hot,
            weights, cp.unique_scales, out_dtype=out_dtype,
            interpret=interpret, block_l=block_l, block_b=block_b)
    return fused_partial_pool_pallas(
        cold, hot, x, rows, owned, is_hot, weights, scales,
        out_dtype=out_dtype, interpret=interpret, block_l=block_l,
        block_b=block_b)


def fused_resume(part_c: jax.Array, part_h: jax.Array,
                 out_dtype=jnp.float32, impl: str = "pallas",
                 interpret: Optional[bool] = None, block_b: int = 32
                 ) -> jax.Array:
    """Phase 3 of the fused front end on the psum-reduced ``(B, F, D)``
    tiles: cold/hot add, dot-interaction, packed lower triangle ``(B, P)``.
    Oracle: ``ref.fused_resume_ref``.
    """
    if impl == "jnp":
        return ref.fused_resume_ref(part_c, part_h)
    if interpret is None:
        interpret = _default_interpret()
    return fused_resume_pallas(part_c, part_h, out_dtype=out_dtype,
                               interpret=interpret, block_b=block_b)
