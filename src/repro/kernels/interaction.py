"""Pallas TPU kernel for DLRM pairwise-dot feature interaction.

The interaction op sits right after SLS in the DLRM pipeline (Fig. 1) and is
the only other op the paper's end-to-end model weights at scale ("non-SLS
operators", section VI-C4).  Z = X X^T per sample, packed lower triangle.

Blocking: grid over batch blocks; one (BB, F, D) activation block in VMEM per
step.  F, D are small (F <= ~40 fields, D <= 128), so a batch block of 128
keeps the MXU busy with a (F, D) x (D, F) matmul per sample batch while the
working set stays ~ BB*F*D*4 = 128*32*128*4 = 2 MB << VMEM.  The triangle
pack is a concatenation of static slices of the (BB, F, F) product, fused
into the same kernel to avoid a round trip of the (B, F, F) tensor to HBM —
that round trip is 2x the kernel's entire output traffic.
"""
from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl

from repro.kernels.sls import (_bag_blocks, _default_interpret, _interact,
                               _pad_rows)


@functools.partial(jax.jit, static_argnames=("self_interaction", "block_b",
                                             "interpret"))
def dot_interaction_pallas(feats: jax.Array, self_interaction: bool = False,
                           block_b: int = 128,
                           interpret: bool | None = None) -> jax.Array:
    """feats: (B, F, D) -> (B, P) packed triangle, P = F*(F-1)/2 (+F with
    self_interaction).  The batch is padded to whole ``block_b`` tiles.

    ``interpret=None`` (the default) detects the backend once at trace
    time: compiled on TPU, interpreter elsewhere; pass an explicit bool to
    override detection."""
    if interpret is None:
        interpret = _default_interpret()
    B, F, D = feats.shape
    P = F * (F + 1) // 2 if self_interaction else F * (F - 1) // 2
    Bp, BB = _bag_blocks(B, block_b)

    def kernel(x_ref, out_ref):
        out_ref[...] = _interact(x_ref[...], out_ref.dtype, self_interaction)

    out = pl.pallas_call(
        kernel, grid=(Bp // BB,),
        in_specs=[pl.BlockSpec((BB, F, D), lambda b: (b, 0, 0))],
        out_specs=pl.BlockSpec((BB, P), lambda b: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((Bp, P), feats.dtype),
        interpret=interpret,
    )(_pad_rows(feats, Bp))
    return out[:B]
