"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs ref.py oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.interaction import dot_interaction_pallas
from repro.kernels.sls import LANES, sls_pallas


def _pad_lanes(a):
    """Zero-pad the minor dim to the lane boundary, as the engine stores
    its rows (``PagingConfig.store_dim``)."""
    pad = -a.shape[-1] % LANES
    return jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, pad)])


@pytest.mark.parametrize("B,L,V,D", [
    (4, 2, 64, 16),
    (8, 8, 256, 64),
    (16, 4, 1024, 128),
    (3, 5, 100, 32),          # non-power-of-two
    (1, 1, 8, 16),            # degenerate
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_sls_kernel_matches_ref(B, L, V, D, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(B * L + V))
    table = jax.random.normal(k1, (V, D), dtype)
    idx = jax.random.randint(k2, (B, L), 0, V).astype(jnp.int32)
    out = sls_pallas(table, idx, interpret=True)
    want = ref.sls_ref(table, idx)
    tol = 1e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("B,L,V,D", [(8, 8, 256, 64), (4, 3, 64, 16)])
def test_sls_kernel_weighted(B, L, V, D):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    table = jax.random.normal(k1, (V, D))
    idx = jax.random.randint(k2, (B, L), 0, V).astype(jnp.int32)
    w = jax.random.uniform(k3, (B, L))
    out = sls_pallas(table, idx, w, interpret=True)
    want = ref.sls_ref(table, idx, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("B,L,V,D,block_l", [
    (8, 8, 256, 64, 8),       # exact tiling: L == block_l
    (8, 8, 256, 64, 3),       # tail tile: L % block_l = 2
    (4, 9, 128, 32, 4),       # tail tile of 1
    (2, 5, 64, 24, 16),       # block_l > L (clamped to one tile)
    (3, 7, 100, 130, 4),      # odd D, non-128-multiple
    (4, 6, 64, 16, 1),        # degenerate one-row tiles
])
@pytest.mark.parametrize("weighted", [False, True])
def test_masked_sls_kernel_matches_ref(B, L, V, D, block_l, weighted):
    """Masked-partial kernel vs oracle across blocking edge cases."""
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(B * L + D), 4)
    table = jax.random.normal(k1, (V, D))
    idx = jax.random.randint(k2, (B, L), 0, V).astype(jnp.int32)
    owned = jax.random.bernoulli(k3, 0.5, (B, L))
    w = jax.random.uniform(k4, (B, L)) if weighted else None
    out = ops.masked_sls(table, idx, owned, w, interpret=True,
                         block_l=block_l)
    want = ref.masked_sls_ref(table, idx, owned, w)
    assert out.shape == (B, D)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("block_l", [1, 3, 8, 16])
def test_sls_kernel_bag_tiling_invariant(block_l):
    """Pooling result must not depend on the tile size (fixed l order)."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(7), 3)
    table = jax.random.normal(k1, (128, 48))
    idx = jax.random.randint(k2, (6, 11), 0, 128).astype(jnp.int32)
    w = jax.random.uniform(k3, (6, 11))
    base = ops.sls(table, idx, w, interpret=True, block_l=11)
    out = ops.sls(table, idx, w, interpret=True, block_l=block_l)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(base))


def test_masked_sls_empty_and_full_masks():
    """Empty bags (all entries masked out) pool to exactly zero."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    table = jax.random.normal(k1, (64, 32))
    idx = jax.random.randint(k2, (4, 6), 0, 64).astype(jnp.int32)
    none = jnp.zeros((4, 6), bool)
    out = ops.masked_sls(table, idx, none, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.zeros((4, 32)))
    # mask rows 2..: only a *sub-bag* survives
    part = jnp.asarray([[True] * 2 + [False] * 4] * 4)
    out2 = ops.masked_sls(table, idx, part, interpret=True)
    want = ref.sls_ref(table, idx[:, :2])
    np.testing.assert_allclose(np.asarray(out2), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    # all-owned mask degenerates to plain SLS
    out3 = ops.masked_sls(table, idx, jnp.ones((4, 6), bool), interpret=True)
    np.testing.assert_array_equal(np.asarray(out3),
                                  np.asarray(ops.sls(table, idx,
                                                     interpret=True)))


@pytest.mark.parametrize("B,L,V,D,block_l", [
    (8, 8, 256, 64, 8),       # exact tiling
    (8, 8, 256, 64, 3),       # tail tile
    (4, 9, 128, 32, 4),       # tail tile of 1
    (3, 7, 100, 130, 4),      # odd D, non-128-multiple
])
@pytest.mark.parametrize("weighted", [False, True])
def test_masked_sls_quant_kernel_matches_oracle_bitwise(B, L, V, D, block_l,
                                                        weighted):
    """int8 table + per-entry dequant scales: the kernel's fused dequant
    must match the fixed-l-order quantized oracle bit-for-bit in fp32."""
    k1, k2, k3, k4, k5 = jax.random.split(jax.random.PRNGKey(B + L + D), 5)
    table_q = jax.random.randint(k1, (V, D), -127, 128).astype(jnp.int8)
    idx = jax.random.randint(k2, (B, L), 0, V).astype(jnp.int32)
    owned = jax.random.bernoulli(k3, 0.5, (B, L))
    scales = jax.random.uniform(k4, (B, L), minval=1e-4, maxval=2e-2)
    w = jax.random.uniform(k5, (B, L)) if weighted else None
    out = ops.masked_sls(table_q, idx, owned, w, scales=scales,
                         interpret=True, block_l=block_l)
    want = ref.masked_sls_quant_ref(table_q, idx, owned, scales, w)
    assert out.dtype == jnp.float32 and out.shape == (B, D)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


def test_masked_sls_quant_jnp_dispatch_matches_oracle():
    """The jnp fallback (ops.masked_sls impl='jnp') dequantizes with the
    same semantics as the quantized oracle (sum order may differ)."""
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(11), 4)
    table_q = jax.random.randint(k1, (64, 16), -127, 128).astype(jnp.int8)
    idx = jax.random.randint(k2, (4, 6), 0, 64).astype(jnp.int32)
    owned = jax.random.bernoulli(k3, 0.5, (4, 6))
    scales = jax.random.uniform(k4, (4, 6), minval=1e-4, maxval=1e-2)
    a = ops.masked_sls(table_q, idx, owned, scales=scales, impl="jnp")
    want = ref.masked_sls_quant_ref(table_q, idx, owned, scales)
    np.testing.assert_allclose(np.asarray(a), np.asarray(want), rtol=1e-6,
                               atol=1e-7)
    # empty mask still pools to exactly zero through the dequant path
    none = jnp.zeros((4, 6), bool)
    z = ops.masked_sls(table_q, idx, none, scales=scales, interpret=True)
    np.testing.assert_array_equal(np.asarray(z), np.zeros((4, 16)))


@pytest.mark.parametrize("B,L,V,D,block_l", [
    (8, 8, 256, 64, 8),       # exact tiling
    (8, 8, 256, 64, 3),       # tail tile
    (4, 9, 128, 32, 4),       # tail tile of 1
    (3, 7, 100, 130, 4),      # odd D, non-128-multiple
])
@pytest.mark.parametrize("weighted", [False, True])
def test_masked_sls_dedup_kernel_bit_exact(B, L, V, D, block_l, weighted):
    """The two-phase gather-once kernel must match (a) its staging oracle
    and (b) the non-dedup kernel **bit-for-bit**: the dedup stage changes
    the gather, never the fixed-l accumulate order."""
    from repro.core.sls import dedup_plan
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(B * L + D), 4)
    table = jax.random.normal(k1, (V, D))
    idx = jax.random.randint(k2, (B, L), 0, V // 4).astype(jnp.int32)  # dups
    owned = jax.random.bernoulli(k3, 0.5, (B, L))
    w = jax.random.uniform(k4, (B, L)) if weighted else None
    plan = dedup_plan(idx, owned)
    out = ops.masked_sls_dedup(table, plan, owned, w, interpret=True,
                               block_l=block_l)
    base = ops.masked_sls(table, idx, owned, w, interpret=True,
                          block_l=block_l)
    want = ref.masked_sls_dedup_ref(table, plan.unique_rows, plan.slots,
                                    owned, w)
    assert out.shape == (B, D)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(base))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


@pytest.mark.parametrize("weighted", [False, True])
def test_masked_sls_dedup_quant_kernel_bit_exact(weighted):
    """int8 table: the per-unique-row fused dequant sees the same operands
    as the non-dedup kernel's per-entry dequant — bitwise equal, and both
    match the fixed-l-order quantized oracle."""
    from repro.core.sls import dedup_plan
    B, L, V, D = 6, 9, 128, 32
    k1, k2, k3, k4, k5 = jax.random.split(jax.random.PRNGKey(3), 5)
    table_q = jax.random.randint(k1, (V, D), -127, 128).astype(jnp.int8)
    idx = jax.random.randint(k2, (B, L), 0, V // 4).astype(jnp.int32)
    owned = jax.random.bernoulli(k3, 0.5, (B, L))
    # per-entry scales must be a function of the row (page scales are)
    row_scale = jax.random.uniform(k4, (V,), minval=1e-4, maxval=2e-2)
    scales = row_scale[idx]
    w = jax.random.uniform(k5, (B, L)) if weighted else None
    plan = dedup_plan(idx, owned, scales)
    out = ops.masked_sls_dedup(table_q, plan, owned, w, interpret=True,
                               block_l=4)
    base = ops.masked_sls(table_q, idx, owned, w, scales=scales,
                          interpret=True, block_l=4)
    want = ref.masked_sls_quant_ref(table_q, idx, owned, scales, w)
    assert out.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(out), np.asarray(base))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


def test_masked_sls_dedup_extremes():
    """All-duplicate bags collapse to one staging row; all-unique bags
    degrade gracefully to one DMA per entry; a fully-masked batch pools to
    exactly zero (the sentinel staging slot never contributes)."""
    from repro.core.sls import dedup_plan
    B, L, V, D = 4, 6, 64, 16
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    table = jax.random.normal(k1, (V, D))
    w = jax.random.uniform(k2, (B, L))
    all_dup = jnp.full((B, L), 7, jnp.int32)
    all_unique = jnp.arange(B * L, dtype=jnp.int32).reshape(B, L)
    ones = jnp.ones((B, L), bool)
    for idx, owned in [(all_dup, ones), (all_unique, ones),
                       (all_dup, jnp.zeros((B, L), bool))]:
        plan = dedup_plan(idx, owned)
        out = ops.masked_sls_dedup(table, plan, owned, w, interpret=True)
        base = ops.masked_sls(table, idx, owned, w, interpret=True)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(base))
    assert int(dedup_plan(all_dup, ones).n_unique) == 1
    assert int(dedup_plan(all_unique, ones).n_unique) == B * L
    assert int(dedup_plan(all_dup, jnp.zeros((B, L), bool)).n_unique) == 0


def test_dedup_plan_invariants():
    """Plan structure: slots route every owned entry to a staging slot
    holding exactly its row; non-owned entries route to the sentinel run;
    padded capacity beyond n_slots stays sentinel."""
    from repro.core.sls import DEDUP_SENTINEL, dedup_plan
    rng = np.random.default_rng(0)
    idx = jnp.asarray(rng.integers(0, 10, (5, 7)), jnp.int32)
    owned = jnp.asarray(rng.random((5, 7)) < 0.6)
    plan = dedup_plan(idx, owned)
    uniq = np.asarray(plan.unique_rows)
    slots = np.asarray(plan.slots)
    n_slots, n_unique = int(plan.n_slots), int(plan.n_unique)
    o = np.asarray(owned)
    routed = uniq[slots]
    np.testing.assert_array_equal(routed[o], np.asarray(idx)[o])
    assert (routed[~o] == DEDUP_SENTINEL).all()
    assert (slots < n_slots).all()
    assert n_unique == len(np.unique(np.asarray(idx)[o]))
    assert (uniq[n_slots:] == DEDUP_SENTINEL).all()


def test_sls_zero_length_bags():
    table = jnp.ones((8, 16))
    idx = jnp.zeros((4, 0), jnp.int32)
    out = ops.sls(table, idx, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.zeros((4, 16)))
    outm = ops.masked_sls(table, idx, jnp.zeros((4, 0), bool), interpret=True)
    np.testing.assert_array_equal(np.asarray(outm), np.zeros((4, 16)))


@pytest.mark.parametrize("D", [16, 100, 130])
def test_sls_lane_padding_is_transparent(D):
    """Storing rows 128-lane padded must not change the logical lanes, and
    the pad lanes pool to exact zeros."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(D), 3)
    table = jax.random.normal(k1, (64, D))
    idx = jax.random.randint(k2, (5, 4), 0, 64).astype(jnp.int32)
    owned = jax.random.bernoulli(k3, 0.7, (5, 4))
    padded = _pad_lanes(table)
    assert padded.shape[1] % LANES == 0
    a = ops.masked_sls(padded, idx, owned, interpret=True)
    b = ops.masked_sls(table, idx, owned, interpret=True)
    assert a.shape == (5, padded.shape[1]) and b.shape == (5, D)
    np.testing.assert_array_equal(np.asarray(a[:, :D]), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(a[:, D:]), 0.0)


@pytest.mark.parametrize("D", [64, 130])
def test_compiled_kernels_refuse_misaligned_rows(D):
    """Off interpret mode a row width off the 128-lane tile is refused
    before any kernel is built (the chip DMAs rows in whole lane tiles)."""
    table = jnp.zeros((64, D))
    idx = jnp.zeros((8, 4), jnp.int32)
    with pytest.raises(ValueError, match="lanes"):
        ops.masked_sls(table, idx, idx >= 0, interpret=False)
    rows = jnp.zeros((8, 2, 4), jnp.int32)
    with pytest.raises(ValueError, match="lanes"):
        ops.fused_front_end(table, table, jnp.zeros((8, D)), rows,
                            rows >= 0, rows < 0, interpret=False)


# ---------------------------------------------------------------------------
# Fused front end: SLS -> dot-interaction in one kernel
# ---------------------------------------------------------------------------


def _fe_inputs(B, G, L, V, D, weighted, quantized, hot_rows=32, seed=0):
    """Random two-tier inputs: every entry is cold-owned, hot, or neither
    (the sharded-engine reality); hot local rows stay in range."""
    ks = jax.random.split(jax.random.PRNGKey(seed + B + G + L + D), 7)
    hot = jax.random.normal(ks[1], (hot_rows, D))
    rows = jax.random.randint(ks[2], (B, G, L), 0, min(V, hot_rows)
                              ).astype(jnp.int32)
    if quantized:
        cold = jax.random.randint(ks[0], (V, D), -127, 128).astype(jnp.int8)
        # page-aligned scale addressing: duplicates of a row share its
        # page's scale (the dedup contract), so derive scales per *row*
        row_scales = jax.random.uniform(ks[5], (V,), minval=1e-4,
                                        maxval=2e-2)
        scales = row_scales[rows]
    else:
        cold = jax.random.normal(ks[0], (V, D))
        scales = None
    tier = jax.random.randint(ks[3], (B, G, L), 0, 3)   # 0=cold 1=hot 2=none
    owned, is_hot = tier == 0, tier == 1
    x = jax.random.normal(ks[4], (B, D))
    w = jax.random.uniform(ks[6], (B, G, L)) if weighted else None
    return cold, hot, x, rows, owned, is_hot, w, scales


@pytest.mark.parametrize("B,G,L,D,block_l,block_b", [
    (8, 2, 8, 16, 8, 4),       # exact tiling
    (8, 4, 7, 32, 3, 8),       # F=5 not a multiple of the sublane tile;
    #                            tail pooling tile
    (4, 2, 5, 16, 4, 32),      # B < block_b (batch tile clamps to B)
    (6, 3, 4, 24, 8, 4),       # odd D, B not a multiple of block_b
    (1, 2, 1, 16, 8, 128),     # degenerate batch
])
@pytest.mark.parametrize("weighted", [False, True])
def test_fused_front_end_kernel_bit_exact(B, G, L, D, block_l, block_b,
                                          weighted):
    """The fused SLS -> interaction kernel must match the split-pipeline
    oracle (fixed-l-order per-tier SLS -> add -> concat -> interaction)
    bit-for-bit in fp32."""
    cold, hot, x, rows, owned, is_hot, w, _ = _fe_inputs(
        B, G, L, 128, D, weighted, quantized=False)
    out = ops.fused_front_end(cold, hot, x, rows, owned, is_hot, w,
                              interpret=True, block_l=block_l,
                              block_b=block_b)
    want = ref.fused_front_end_ref(cold, hot, x, rows, owned, is_hot, w)
    F = G + 1
    assert out.shape == (B, F * (F - 1) // 2)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


@pytest.mark.parametrize("weighted", [False, True])
@pytest.mark.parametrize("dedup", [False, True])
def test_fused_front_end_quant_kernel_bit_exact(weighted, dedup):
    """int8 cold tier: the fused kernel's per-row dequant (per-entry or
    gather-once) matches the quantized split oracle bit-for-bit."""
    from repro.core import sls as core_sls
    B, G, L, V, D = 6, 2, 5, 96, 16
    cold, hot, x, rows, owned, is_hot, w, scales = _fe_inputs(
        B, G, L, V, D, weighted, quantized=True)
    plans = None
    if dedup:
        nb = B * G
        cp = core_sls.dedup_plan(rows.reshape(nb, L), owned.reshape(nb, L),
                                 scales.reshape(nb, L))
        hp = core_sls.dedup_plan(rows.reshape(nb, L), is_hot.reshape(nb, L))
        plans = (cp._replace(slots=cp.slots.reshape(B, G, L)),
                 hp._replace(slots=hp.slots.reshape(B, G, L)))
    out = ops.fused_front_end(cold, hot, x, rows, owned, is_hot, w,
                              scales=scales, dedup_plans=plans,
                              interpret=True, block_l=3, block_b=2)
    want = ref.fused_front_end_ref(cold, hot, x, rows, owned, is_hot, w,
                                   scales=scales)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


@pytest.mark.parametrize("extreme", ["all_cold", "all_hot", "none"])
def test_fused_front_end_mask_extremes(extreme):
    """Degenerate tier masks: everything cold, everything hot, or nothing
    owned (the pooled features are then all-zero and the interaction is
    x-only) — all bit-exact against the oracle."""
    B, G, L, V, D = 4, 3, 6, 64, 16
    cold, hot, x, rows, _, _, _, _ = _fe_inputs(B, G, L, V, D, False, False)
    full = jnp.ones((B, G, L), bool)
    empty = jnp.zeros((B, G, L), bool)
    owned, is_hot = {"all_cold": (full, empty), "all_hot": (empty, full),
                     "none": (empty, empty)}[extreme]
    out = ops.fused_front_end(cold, hot, x, rows, owned, is_hot,
                              interpret=True, block_l=4, block_b=4)
    want = ref.fused_front_end_ref(cold, hot, x, rows, owned, is_hot)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


def test_fused_front_end_dedup_matches_nondedup_bitwise():
    """The gather-once fused variant only changes where rows come from —
    identical output bits to the per-entry-DMA fused kernel."""
    from repro.core import sls as core_sls
    B, G, L, V, D = 8, 2, 6, 64, 16
    cold, hot, x, rows, owned, is_hot, w, _ = _fe_inputs(
        B, G, L, V, D, True, False)
    a = core_sls.fused_front_end_dense(cold, hot, x, rows, owned, is_hot, w,
                                       impl="pallas", interpret=True,
                                       dedup=False)
    b = core_sls.fused_front_end_dense(cold, hot, x, rows, owned, is_hot, w,
                                       impl="pallas", interpret=True,
                                       dedup=True)
    c = core_sls.fused_front_end_dense(cold, hot, x, rows, owned, is_hot, w,
                                       impl="jnp")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


def test_fused_front_end_lane_padding_is_transparent():
    """D=24 is not lane-aligned: padding the three dense operands must not
    change any output bit (zero lanes add exact +0 to every pairwise dot)."""
    B, G, L, V, D = 4, 2, 5, 64, 24
    cold, hot, x, rows, owned, is_hot, w, _ = _fe_inputs(
        B, G, L, V, D, True, False)
    a = ops.fused_front_end(_pad_lanes(cold), _pad_lanes(hot), _pad_lanes(x),
                            rows, owned, is_hot, w, interpret=True)
    b = ops.fused_front_end(cold, hot, x, rows, owned, is_hot, w,
                            interpret=True)
    assert a.shape == b.shape
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# Tensor-parallel fused front end: partial-pool + resume kernel halves
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,G,L,D,block_l,block_b", [
    (8, 2, 8, 16, 8, 4),       # exact tiling
    (8, 4, 7, 32, 3, 8),       # F=5 not a multiple of the sublane tile
    (4, 2, 5, 16, 4, 32),      # B < block_b (batch tile clamps to B)
    (6, 3, 4, 24, 8, 4),       # odd D, B not a multiple of block_b
    (1, 2, 1, 16, 8, 128),     # degenerate batch
])
@pytest.mark.parametrize("weighted", [False, True])
def test_fused_partial_pool_resume_bit_exact(B, G, L, D, block_l, block_b,
                                             weighted):
    """Splitting the fused kernel at the phase-2/3 seam must be free:
    partial-pool -> resume equals the one-kernel fused front end (and the
    split-composition oracle) bit-for-bit, and the tiles themselves match
    the partial-pool oracle — cold row 0 zero, x riding the hot tile."""
    cold, hot, x, rows, owned, is_hot, w, _ = _fe_inputs(
        B, G, L, 128, D, weighted, quantized=False)
    pc, ph = ops.fused_partial_pool(cold, hot, x, rows, owned, is_hot, w,
                                    interpret=True, block_l=block_l,
                                    block_b=block_b)
    rc, rh = ref.fused_partial_pool_ref(cold, hot, x, rows, owned, is_hot, w)
    F = G + 1
    assert pc.shape == ph.shape == (B, F, D)
    np.testing.assert_array_equal(np.asarray(pc), np.asarray(rc))
    np.testing.assert_array_equal(np.asarray(ph), np.asarray(rh))
    assert not np.asarray(pc)[:, 0, :].any()      # psum-safe cold row 0
    out = ops.fused_resume(pc, ph, interpret=True, block_b=block_b)
    want = ref.fused_front_end_ref(cold, hot, x, rows, owned, is_hot, w)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


@pytest.mark.parametrize("weighted", [False, True])
@pytest.mark.parametrize("dedup", [False, True])
def test_fused_partial_pool_quant_dedup_bit_exact(weighted, dedup):
    """int8 cold tier through the partial-pool half (per-entry or
    gather-once dequant staging): resume of the tiles matches the
    quantized fused oracle bit-for-bit."""
    from repro.core import sls as core_sls
    B, G, L, V, D = 6, 2, 5, 96, 16
    cold, hot, x, rows, owned, is_hot, w, scales = _fe_inputs(
        B, G, L, V, D, weighted, quantized=True)
    pc, ph = core_sls.fused_partial_pool_dense(
        cold, hot, x, rows, owned, is_hot, w, scales=scales, impl="pallas",
        interpret=True, block_l=3, block_b=2, dedup=dedup)
    out = core_sls.fused_resume_dense(pc, ph, impl="pallas", interpret=True,
                                      block_b=2)
    want = ref.fused_front_end_ref(cold, hot, x, rows, owned, is_hot, w,
                                   scales=scales)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


def test_fused_partial_pool_simulated_psum_bit_exact():
    """The tp contract, single-host: split the cold ownership across two
    simulated shards, partial-pool each, sum the cold tiles (the psum),
    resume — bit-identical to the same two-shard composition through the
    oracle.  (Each shard keeps fixed l-order over *its* rows; the split
    path under tp masks identically, which is why engine-level fused_tp
    == split holds bitwise.)  The hot tile comes from one shard only —
    replicated, never reduced."""
    B, G, L, V, D = 8, 3, 6, 64, 16
    cold, hot, x, rows, owned, is_hot, w, _ = _fe_inputs(
        B, G, L, V, D, True, False)
    shard0 = owned & (rows % 2 == 0)
    shard1 = owned & (rows % 2 == 1)
    no_hot = jnp.zeros_like(is_hot)
    c0, h0 = ops.fused_partial_pool(cold, hot, x, rows, shard0, is_hot, w,
                                    interpret=True)
    c1, _ = ops.fused_partial_pool(cold, hot, x, rows, shard1, no_hot, w,
                                   interpret=True)
    out = ops.fused_resume(c0 + c1, h0, interpret=True)   # psum, then resume
    rc0, rh0 = ref.fused_partial_pool_ref(cold, hot, x, rows, shard0,
                                          is_hot, w)
    rc1, _ = ref.fused_partial_pool_ref(cold, hot, x, rows, shard1,
                                        no_hot, w)
    want = ref.fused_resume_ref(rc0 + rc1, rh0)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
    # and the reduced tile is the full-ownership pool up to reorder only
    full_c, _ = ref.fused_partial_pool_ref(cold, hot, x, rows, owned,
                                           is_hot, w)
    np.testing.assert_allclose(np.asarray(c0 + c1), np.asarray(full_c),
                               rtol=1e-5, atol=1e-6)


def test_fused_partial_pool_lane_padding_is_transparent():
    """D=24 is not lane-aligned: pooling from padded operands gives the
    unpadded partial tiles plus zero pad lanes (the engine slices them off
    before the collective, which ships exactly B*F*D elements), and the
    resume on padded tiles changes no output bit."""
    B, G, L, V, D = 4, 2, 5, 64, 24
    cold, hot, x, rows, owned, is_hot, w, _ = _fe_inputs(
        B, G, L, V, D, True, False)
    pc_a, ph_a = ops.fused_partial_pool(
        _pad_lanes(cold), _pad_lanes(hot), _pad_lanes(x), rows, owned,
        is_hot, w, interpret=True)
    pc_b, ph_b = ops.fused_partial_pool(cold, hot, x, rows, owned, is_hot, w,
                                        interpret=True)
    assert pc_a.shape == (B, G + 1, LANES) and pc_b.shape == (B, G + 1, D)
    np.testing.assert_array_equal(np.asarray(pc_a), _pad_lanes(pc_b))
    np.testing.assert_array_equal(np.asarray(ph_a), _pad_lanes(ph_b))
    a = ops.fused_resume(pc_a, ph_a, interpret=True)
    b = ops.fused_resume(pc_b, ph_b, interpret=True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_interaction_interpret_default_detects_backend():
    """dot_interaction_pallas defaulted interpret=True forever — on a CPU
    container the None default must resolve to the interpreter (and on TPU
    it would resolve to compiled; here we can only pin the off-TPU leg and
    that an explicit override still threads through)."""
    feats = jax.random.normal(jax.random.PRNGKey(0), (8, 4, 16))
    want = ref.dot_interaction_ref(feats)
    out_default = dot_interaction_pallas(feats)              # None -> detect
    out_forced = dot_interaction_pallas(feats, interpret=True)
    np.testing.assert_array_equal(np.asarray(out_default),
                                  np.asarray(out_forced))
    np.testing.assert_allclose(np.asarray(out_default), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    out_ops = ops.dot_interaction(feats, impl="pallas", interpret=True)
    np.testing.assert_array_equal(np.asarray(out_ops),
                                  np.asarray(out_default))


@pytest.mark.parametrize("B,F,D", [
    (8, 4, 16), (16, 8, 32), (128, 27, 16), (32, 9, 64),
])
@pytest.mark.parametrize("self_int", [False, True])
def test_interaction_kernel_matches_ref(B, F, D, self_int):
    feats = jax.random.normal(jax.random.PRNGKey(F), (B, F, D))
    out = ops.dot_interaction(feats, self_interaction=self_int,
                              impl="pallas", interpret=True)
    want = ref.dot_interaction_ref(feats, self_interaction=self_int)
    assert out.shape == want.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-5,
                               atol=1e-5)


def test_interaction_output_size():
    B, F, D = 4, 6, 8
    feats = jnp.ones((B, F, D))
    out = ref.dot_interaction_ref(feats)
    assert out.shape == (B, F * (F - 1) // 2)
    out2 = ref.dot_interaction_ref(feats, self_interaction=True)
    assert out2.shape == (B, F * (F + 1) // 2)
