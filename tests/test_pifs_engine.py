"""PIFS engine behaviour: mode equivalence, placement invariance, planner
balance, migration correctness — the paper's system invariants."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import sls as sls_ops
from repro.core.paging import PagingConfig, initial_page_table
from repro.core.pifs import PIFSEmbeddingEngine, engine_for_tables
from repro.core.paging import HOT_SHARD
from repro.core.planner import (PlannerConfig, needs_migration, plan,
                                shard_loads)


@pytest.fixture()
def engine(mesh):
    eng, offs = engine_for_tables([500, 300], dim=16, mesh=mesh,
                                  hot_fraction=0.06)
    return eng


@pytest.fixture()
def engine_q(mesh):
    """int8 cold tier with per-page scales (the tiered-precision store)."""
    eng, offs = engine_for_tables([500, 300], dim=16, mesh=mesh,
                                  hot_fraction=0.06, storage="int8")
    return eng


def _ref_lookup(eng, state, idx):
    dense = eng.to_dense(state)
    B, G, L = idx.shape
    flat = idx.reshape(B * G, L)
    return sls_ops.sls_dense_ref(dense, flat).reshape(B, G, -1)


def test_modes_agree_with_dense_reference(engine, mesh):
    state = engine.init_state(jax.random.PRNGKey(0))
    idx = jax.random.randint(jax.random.PRNGKey(1), (8, 2, 4), 0, 500
                             ).astype(jnp.int32)
    want = _ref_lookup(engine, state, idx)
    with mesh:
        for mode in ("pifs", "pond", "beacon"):
            got = engine.lookup(state, idx, mode=mode)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-5, atol=1e-5)


def test_weighted_lookup(engine, mesh):
    state = engine.init_state(jax.random.PRNGKey(0))
    idx = jax.random.randint(jax.random.PRNGKey(1), (4, 2, 4), 0, 500
                             ).astype(jnp.int32)
    w = jax.random.uniform(jax.random.PRNGKey(2), (4, 2, 4))
    dense = engine.to_dense(state)
    want = sls_ops.sls_dense_ref(dense, idx.reshape(8, 4), w.reshape(8, 4)
                                 ).reshape(4, 2, 16)
    with mesh:
        got = engine.lookup(state, idx, weights=w, mode="pifs")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5)


def test_placement_invariance_under_migration(engine, mesh):
    """The planner may move pages at any time; lookups must not change —
    including across *repeated* migrations on already-sharded state (a
    regression for the GSPMD-inferred migrate gather, which corrupted the
    store on the second call)."""
    state = engine.init_state(jax.random.PRNGKey(0))
    idx = jax.random.randint(jax.random.PRNGKey(1), (8, 2, 4), 0, 500
                             ).astype(jnp.int32)
    with mesh:
        before = np.asarray(engine.lookup(state, idx))
        st = engine.observe(state, idx)
        st2, stats = engine.plan_and_migrate(st)
        after = np.asarray(engine.lookup(st2, idx))
        # second cycle with a different hot set: demotions + promotions on
        # state whose storage is now tp-sharded by the first migration
        st3 = engine.observe(st2, (idx * 7 + 3) % 500)
        st4, _ = engine.plan_and_migrate(st3)
        after2 = np.asarray(engine.lookup(st4, idx))
    assert stats["hot_pages"] > 0
    np.testing.assert_allclose(before, after, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(before, after2, rtol=1e-5, atol=1e-5)


def test_hot_pages_become_local(engine, mesh):
    """Pages hammered by the trace must be promoted to the hot tier."""
    state = engine.init_state(jax.random.PRNGKey(0))
    hot_rows = jnp.asarray([[ [0, 1, 2, 3] ]], jnp.int32)  # page 0
    with mesh:
        st = state
        for _ in range(5):
            st = engine.observe(st, jnp.tile(hot_rows, (8, 1, 1)))
        st2, stats = engine.plan_and_migrate(st)
    shard0 = int(np.asarray(st2.page_to_shard)[0])
    assert shard0 == -1  # HOT_SHARD


def test_gradients_flow_through_lookup(engine, mesh):
    state = engine.init_state(jax.random.PRNGKey(0))
    idx = jax.random.randint(jax.random.PRNGKey(1), (4, 2, 4), 0, 500
                             ).astype(jnp.int32)

    def loss(cold, hot):
        st = dataclasses.replace(state, cold=cold, hot=hot)
        return engine.lookup(st, idx).sum()

    with mesh:
        gc, gh = jax.grad(loss, argnums=(0, 1))(state.cold, state.hot)
    # every accessed row contributes gradient 1 per accessed element
    total = float(np.asarray(gc).sum() + np.asarray(gh).sum())
    assert total == pytest.approx(4 * 2 * 4 * 16, rel=1e-3)


def _plan_loop_reference(cfg, table, counts, pcfg):
    """The planner's hot-set hysteresis and sticky placement as the
    per-page Python loops they replaced (placement only, no stats)."""
    counts = np.asarray(counts, np.float64)
    old_shard = np.asarray(table.page_to_shard)
    old_slot = np.asarray(table.page_to_slot)
    order = np.argsort(-counts, kind="stable")
    resident_hot = set(np.nonzero(old_shard == HOT_SHARD)[0].tolist())
    hot_set = set(order[: cfg.hot_pages].tolist())
    if pcfg.sticky and resident_hot:
        hot_set = set(resident_hot)
        for c in [p for p in order[: 4 * cfg.hot_pages]
                  if p not in resident_hot]:
            if len(hot_set) < cfg.hot_pages:
                hot_set.add(int(c))
                continue
            victim = min(hot_set, key=lambda p: counts[p])
            if counts[c] > counts[victim] * (1.0 + pcfg.cold_age_threshold):
                hot_set.discard(victim)
                hot_set.add(int(c))
    hot_list = sorted(hot_set, key=lambda p: -counts[p])[: cfg.hot_pages]
    hot_mask = np.zeros(cfg.num_pages, bool)
    hot_mask[hot_list] = True
    new_shard = np.full(cfg.num_pages, HOT_SHARD, np.int32)
    new_slot = np.zeros(cfg.num_pages, np.int32)
    new_slot[hot_list] = np.arange(len(hot_list), dtype=np.int32)
    cold_pages = np.nonzero(~hot_mask)[0]
    loads = np.zeros(cfg.n_shards)
    fill = np.zeros(cfg.n_shards, np.int64)
    unplaced = cold_pages
    if pcfg.sticky and not needs_migration(cfg, table, counts, pcfg):
        for p in cold_pages:
            s = old_shard[p]
            if s != HOT_SHARD:
                new_shard[p], new_slot[p] = s, old_slot[p]
                loads[s] += counts[p]
                fill[s] = max(fill[s], old_slot[p] + 1)
        unplaced = cold_pages[new_shard[cold_pages] == HOT_SHARD]
    for p in unplaced[np.argsort(-counts[unplaced], kind="stable")]:
        cands = np.nonzero(fill < cfg.pages_per_shard)[0]
        s = cands[np.argmin(loads[cands])]
        new_shard[p], new_slot[p] = s, fill[s]
        fill[s] += 1
        loads[s] += counts[p]
    return new_shard, new_slot


@pytest.mark.parametrize("skew", [1.05, 1.6])
def test_planner_matches_loop_reference(skew):
    """Repeated replans (hot set resident, sticky and full LPT branches)
    place every page exactly as the per-page loops did; counts are
    distinct, so no tie decides a victim."""
    cfg = PagingConfig(total_rows=8192, dim=16, n_shards=4, hot_fraction=0.05)
    table = initial_page_table(cfg)
    rng = np.random.default_rng(3)
    for _ in range(4):
        counts = rng.zipf(skew, cfg.num_pages) + rng.random(cfg.num_pages)
        new_table, _ = plan(cfg, table, counts, PlannerConfig())
        want_shard, want_slot = _plan_loop_reference(cfg, table, counts,
                                                     PlannerConfig())
        np.testing.assert_array_equal(np.asarray(new_table.page_to_shard),
                                      want_shard)
        np.testing.assert_array_equal(np.asarray(new_table.page_to_slot),
                                      want_slot)
        table = new_table


def test_planner_balances_loads():
    cfg = PagingConfig(total_rows=4096, dim=16, n_shards=4, hot_fraction=0.02)
    table = initial_page_table(cfg)
    rng = np.random.default_rng(0)
    counts = rng.zipf(1.3, cfg.num_pages).astype(np.float64)
    new_table, stats = plan(cfg, table, counts, PlannerConfig())
    assert stats["load_std_after"] <= stats["load_std_before"] + 1e-9
    # LPT bound: max load <= mean + heaviest single item (pages are atomic)
    loads = shard_loads(cfg, new_table, counts)
    hot = np.asarray(new_table.page_to_shard) == -1
    heaviest_cold = counts[~hot].max()
    assert loads.max() <= loads.mean() + heaviest_cold + 1e-9


def test_planner_sticky_when_balanced():
    cfg = PagingConfig(total_rows=4096, dim=16, n_shards=4, hot_fraction=0.02)
    table = initial_page_table(cfg)
    counts = np.ones(cfg.num_pages)
    new_table, stats = plan(cfg, table, counts, PlannerConfig())
    # uniform traffic: nothing needs to move except hot promotions
    assert stats["moved_fraction"] < 0.1


@pytest.mark.parametrize("mode", ["pifs", "pond", "beacon"])
@pytest.mark.parametrize("combine", ["psum", "psum_scatter"])
def test_pallas_impl_agrees_with_jnp_exactly(engine, mesh, mode, combine):
    """The kernel datapath must match the jnp path bit-for-bit in fp32:
    both accumulate in the same fixed l-order (impl-invariance)."""
    state = engine.init_state(jax.random.PRNGKey(0))
    idx = jax.random.randint(jax.random.PRNGKey(1), (8, 2, 4), 0, 500
                             ).astype(jnp.int32)
    w = jax.random.uniform(jax.random.PRNGKey(2), (8, 2, 4))
    with mesh:
        a = engine.lookup(state, idx, mode=mode, combine=combine, impl="jnp")
        b = engine.lookup(state, idx, mode=mode, combine=combine,
                          impl="pallas")
        aw = engine.lookup(state, idx, weights=w, mode=mode, combine=combine,
                           impl="jnp")
        bw = engine.lookup(state, idx, weights=w, mode=mode, combine=combine,
                           impl="pallas")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(aw), np.asarray(bw))
    # and it is still the right answer
    want = _ref_lookup(engine, state, idx)
    np.testing.assert_allclose(np.asarray(b), np.asarray(want), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_lookup_plan_cache_compiles_once(engine, mesh, impl):
    """Repeated lookups of one signature must trace/compile exactly once;
    new signatures add exactly one plan each."""
    state = engine.init_state(jax.random.PRNGKey(0))
    idx = jax.random.randint(jax.random.PRNGKey(1), (8, 2, 4), 0, 500
                             ).astype(jnp.int32)
    engine.reset_plan_stats()
    with mesh:
        outs = [np.asarray(engine.lookup(state, idx, impl=impl))
                for _ in range(5)]
    stats = engine.plan_stats()
    assert stats == {"plans": 1, "traces": 1, "calls": 5}
    for o in outs[1:]:
        np.testing.assert_array_equal(o, outs[0])
    # a different shape is a new plan — but still exactly one more trace
    idx2 = idx[:, :, :2]
    with mesh:
        engine.lookup(state, idx2, impl=impl)
        engine.lookup(state, idx2, impl=impl)
    assert engine.plan_stats() == {"plans": 2, "traces": 2, "calls": 7}
    # weighted lookups and mode changes key separate plans
    w = jax.random.uniform(jax.random.PRNGKey(2), (8, 2, 4))
    with mesh:
        engine.lookup(state, idx, weights=w, impl=impl)
        engine.lookup(state, idx, mode="pond", impl=impl)
    assert engine.plan_stats()["plans"] == 4
    assert engine.plan_stats()["traces"] == 4


# ---------------------------------------------------------------------------
# Tiered-precision store (storage='int8')
# ---------------------------------------------------------------------------


def test_quantized_lookup_matches_dequantized_oracle(engine_q, mesh):
    """Every mode/impl must agree with the dequantized dense reference
    (to_dense is the effective table: int8 codes * per-page scales)."""
    state = engine_q.init_state(jax.random.PRNGKey(0))
    assert state.cold.dtype == jnp.int8
    assert state.page_scales.shape == (engine_q.cfg.num_pages,)
    idx = jax.random.randint(jax.random.PRNGKey(1), (8, 2, 4), 0, 500
                             ).astype(jnp.int32)
    want = _ref_lookup(engine_q, state, idx)
    with mesh:
        for mode in ("pifs", "pond", "beacon"):
            got = engine_q.lookup(state, idx, mode=mode)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-5, atol=1e-5)


def test_quantized_lookup_tracks_fp32_within_error_bound(mesh):
    """int8 vs fp32 lookups on the same dense table differ by at most the
    summed per-entry half-scale quantization error."""
    eng32, _ = engine_for_tables([500, 300], dim=16, mesh=mesh,
                                 hot_fraction=0.06)
    eng8, _ = engine_for_tables([500, 300], dim=16, mesh=mesh,
                                hot_fraction=0.06, storage="int8")
    dense = jax.random.normal(jax.random.PRNGKey(0), (800, 16)) * 0.05
    s32 = eng32.from_dense(dense)
    s8 = eng8.from_dense(dense)
    L = 4
    idx = jax.random.randint(jax.random.PRNGKey(1), (8, 2, L), 0, 500
                             ).astype(jnp.int32)
    with mesh:
        a = np.asarray(eng32.lookup(s32, idx))
        b = np.asarray(eng8.lookup(s8, idx))
    bound = L * float(np.asarray(s8.page_scales).max()) * 0.5 * 1.01
    assert np.abs(a - b).max() <= bound


@pytest.mark.parametrize("mode", ["pifs", "pond"])
def test_quantized_pallas_impl_agrees_with_jnp_exactly(engine_q, mesh, mode):
    """Fused dequant must not break impl-invariance: both datapaths scale
    each gathered row then accumulate in the same fixed l-order."""
    state = engine_q.init_state(jax.random.PRNGKey(0))
    idx = jax.random.randint(jax.random.PRNGKey(1), (8, 2, 4), 0, 500
                             ).astype(jnp.int32)
    w = jax.random.uniform(jax.random.PRNGKey(2), (8, 2, 4))
    with mesh:
        a = engine_q.lookup(state, idx, mode=mode, impl="jnp")
        b = engine_q.lookup(state, idx, mode=mode, impl="pallas")
        aw = engine_q.lookup(state, idx, weights=w, mode=mode, impl="jnp")
        bw = engine_q.lookup(state, idx, weights=w, mode=mode, impl="pallas")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(aw), np.asarray(bw))


def test_quantized_placement_invariance_is_exact(engine_q, mesh):
    """Migration is *bit-exact* in the quantized domain: cold->cold moves
    codes and their (global, per-page) scales verbatim, promotion stores
    exactly q*scale in fp32, and demotion re-quantizes with the carried
    scale, recovering the codes — through multiple observe/replan cycles
    with hot-set churn (promotions AND demotions)."""
    state = engine_q.init_state(jax.random.PRNGKey(0))
    idx = jax.random.randint(jax.random.PRNGKey(1), (8, 2, 4), 0, 500
                             ).astype(jnp.int32)
    w = jax.random.uniform(jax.random.PRNGKey(2), (8, 2, 4))
    with mesh:
        st = state
        before = np.asarray(engine_q.lookup(st, idx))
        before_w = np.asarray(engine_q.lookup(st, idx, weights=w))
        promoted = 0
        for cycle in range(3):
            hammer = idx if cycle % 2 == 0 else (idx * 7 + 3) % 500
            st = engine_q.observe(st, hammer)
            st, stats = engine_q.plan_and_migrate(st)
            promoted = max(promoted, stats["hot_pages"])
            after = np.asarray(engine_q.lookup(st, idx))
            after_w = np.asarray(engine_q.lookup(st, idx, weights=w))
            np.testing.assert_array_equal(before, after)
            np.testing.assert_array_equal(before_w, after_w)
        # scales never move: they are global per-page metadata
        np.testing.assert_array_equal(np.asarray(state.page_scales),
                                      np.asarray(st.page_scales))
    assert promoted > 0


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_quantized_plan_cache_compiles_once(engine_q, mesh, impl):
    """storage='int8' signatures share the plan-cache contract: one trace
    per signature, zero steady-state retraces."""
    state = engine_q.init_state(jax.random.PRNGKey(0))
    idx = jax.random.randint(jax.random.PRNGKey(1), (8, 2, 4), 0, 500
                             ).astype(jnp.int32)
    engine_q.reset_plan_stats()
    with mesh:
        outs = [np.asarray(engine_q.lookup(state, idx, impl=impl))
                for _ in range(5)]
    assert engine_q.plan_stats() == {"plans": 1, "traces": 1, "calls": 5}
    for o in outs[1:]:
        np.testing.assert_array_equal(o, outs[0])


def test_quantized_roundtrip_through_from_to_dense(engine_q, mesh):
    """to_dense(from_dense(x)) is exactly the quantize->dequantize of x for
    the all-cold initial placement."""
    from repro.core import quant
    c = engine_q.cfg
    dense = jax.random.normal(jax.random.PRNGKey(3), (c.padded_rows, c.dim))
    state = engine_q.from_dense(dense)
    got = np.asarray(engine_q.to_dense(state))
    q, scales = quant.quantize_pages(
        dense.reshape(c.num_pages, c.page_size, c.dim))
    want = np.asarray(quant.dequantize_pages(q, scales)).reshape(
        c.padded_rows, c.dim)
    np.testing.assert_array_equal(got, want)


def test_engine_address_space_must_fit_int32(mesh):
    """Regression: engine_for_tables returns int64 offsets and model code
    downcasts the summed global index to int32 — construction must refuse
    address spaces where that cast would silently truncate."""
    with pytest.raises(ValueError, match="int32"):
        engine_for_tables([2 ** 31], dim=16, mesh=mesh)
    # int8 packs 4x the rows per page but the row *count* is what must fit
    with pytest.raises(ValueError, match="int32"):
        engine_for_tables([2 ** 30, 2 ** 30, 2 ** 30], dim=16, mesh=mesh,
                          storage="int8")
    # just under the bound constructs fine (no arrays are allocated)
    eng, offs = engine_for_tables([2 ** 30], dim=16, mesh=mesh)
    assert offs.dtype == np.int64


# ---------------------------------------------------------------------------
# Gather-once duplicate coalescing (dedup knob)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_dedup_lookup_bit_exact_pinned(engine, engine_q, mesh, impl):
    """dedup=on equals dedup=off bit-for-bit: the coalesced stage changes
    the gather (each unique owned row fetched/dequantized once), never the
    fixed-l accumulate order — pinned here for fp32 and int8 storage,
    weighted and unweighted (the hypothesis sweep covers the rest)."""
    idx = jax.random.randint(jax.random.PRNGKey(1), (8, 2, 16), 0, 300
                             ).astype(jnp.int32)   # small range => many dups
    w = jax.random.uniform(jax.random.PRNGKey(2), (8, 2, 16))
    for eng in (engine, engine_q):
        state = eng.init_state(jax.random.PRNGKey(0))
        with mesh:
            a = eng.lookup(state, idx, impl=impl, dedup="off")
            b = eng.lookup(state, idx, impl=impl, dedup="on")
            aw = eng.lookup(state, idx, weights=w, impl=impl, dedup="off")
            bw = eng.lookup(state, idx, weights=w, impl=impl, dedup="on")
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(aw), np.asarray(bw))


def test_dedup_grows_plan_cache_key(engine, mesh):
    """The requested dedup knob is part of the lookup-plan signature: each
    distinct value keys its own plan (one trace each), repeated calls hit
    the cache, and plan_stats() reports the resolution records — but only
    when a dedup-requesting plan exists (off-only callers see the exact
    legacy stats shape)."""
    state = engine.init_state(jax.random.PRNGKey(0))
    idx = jax.random.randint(jax.random.PRNGKey(1), (8, 2, 4), 0, 500
                             ).astype(jnp.int32)
    engine.reset_plan_stats(clear_plans=True)
    with mesh:
        engine.lookup(state, idx, dedup="off")
        engine.lookup(state, idx, dedup="off")
    stats = engine.plan_stats()
    assert stats == {"plans": 1, "traces": 1, "calls": 2}  # no "dedup" key
    with mesh:
        engine.lookup(state, idx, dedup="on")
        engine.lookup(state, idx, dedup="on")
        engine.lookup(state, idx, dedup="auto")
    stats = engine.plan_stats()
    assert (stats["plans"], stats["traces"], stats["calls"]) == (3, 3, 5)
    recs = stats["dedup"]
    assert len(recs) == 2         # the 'on' and 'auto' plans
    by_req = {r["requested"]: r for r in recs.values()}
    assert by_req["on"]["resolved"] is True
    assert by_req["on"]["measured_factor"] > 1.0
    # zero histogram => uniform prior => essentially duplicate-free => off
    assert by_req["auto"]["resolved"] is False
    assert by_req["auto"]["expected_factor"] is not None


def test_dedup_auto_no_retrace_across_observe_replan(engine, mesh):
    """dedup='auto' freezes its per-plan decision at first build (the cache
    key carries the *requested* knob), so observe/replan cycles — which
    change the histogram the decision came from — never retrace, and
    results stay placement-invariant."""
    state = engine.init_state(jax.random.PRNGKey(0))
    # hammer a narrow id range so the histogram is skewed when 'auto' looks
    hot_idx = (jax.random.randint(jax.random.PRNGKey(1), (8, 2, 16), 0, 64)
               ).astype(jnp.int32)
    engine.reset_plan_stats(clear_plans=True)
    with mesh:
        state = engine.observe(state, hot_idx)
        before = np.asarray(engine.lookup(state, hot_idx, dedup="auto"))
        for _ in range(2):
            state = engine.observe(state, hot_idx)
            state, _stats = engine.plan_and_migrate(state)
            after = np.asarray(engine.lookup(state, hot_idx, dedup="auto"))
            np.testing.assert_allclose(before, after, rtol=1e-5, atol=1e-5)
    stats = engine.plan_stats()
    # exactly one lookup trace (the observe histogram plan is separate)
    assert stats["traces"] == 1 and len(stats["dedup"]) == 1
    rec = next(iter(stats["dedup"].values()))
    # 64 hot rows hammered by 256 entries: auto must have turned dedup on
    assert rec["requested"] == "auto" and rec["resolved"] is True
    assert rec["expected_factor"] >= engine.dedup_auto_threshold


def test_dedup_on_capacity_fallback_is_exact(mesh):
    """dedup='on' with a staging budget smaller than the signature's
    worst case resolves to the non-dedup datapath — recorded in the plan
    stats, bit-exact by construction."""
    eng, _ = engine_for_tables([500, 300], dim=16, mesh=mesh,
                               hot_fraction=0.06)
    eng.dedup_staging_bytes = 64          # far below (8*2*4) * 16 * 4
    base, _ = engine_for_tables([500, 300], dim=16, mesh=mesh,
                                hot_fraction=0.06)
    s1 = eng.init_state(jax.random.PRNGKey(0))
    s2 = base.init_state(jax.random.PRNGKey(0))
    idx = jax.random.randint(jax.random.PRNGKey(1), (8, 2, 4), 0, 500
                             ).astype(jnp.int32)
    with mesh:
        got = np.asarray(eng.lookup(s1, idx, dedup="on"))
        want = np.asarray(base.lookup(s2, idx, dedup="off"))
    np.testing.assert_array_equal(got, want)
    rec = next(iter(eng.plan_stats()["dedup"].values()))
    assert rec == {**rec, "requested": "on", "resolved": False,
                   "capacity_ok": False}


def test_dedup_engine_default_and_validation(mesh):
    """engine_for_tables threads the engine-wide dedup default; bad knob
    values fail loudly at construction and lookup."""
    eng, _ = engine_for_tables([500, 300], dim=16, mesh=mesh, dedup="on")
    assert eng.default_dedup == "on"
    state = eng.init_state(jax.random.PRNGKey(0))
    idx = jax.random.randint(jax.random.PRNGKey(1), (8, 2, 4), 0, 500
                             ).astype(jnp.int32)
    with mesh:
        eng.lookup(state, idx)            # default knob = 'on'
    assert next(iter(eng.plan_stats()["dedup"].values()))["resolved"] is True
    with pytest.raises(ValueError, match="dedup"):
        engine_for_tables([500], dim=16, mesh=mesh, dedup="sometimes")
    with mesh, pytest.raises(ValueError, match="dedup"):
        eng.lookup(state, idx, dedup="bogus")


def test_dedup_factor_counts_weighted_entries(engine, mesh):
    """The measured duplicate factor replays the per-(dp-group, shard)
    uniques the dedup'd datapath gathers, and weight-0 (serving pad)
    entries are excluded from the entry count."""
    state = engine.init_state(jax.random.PRNGKey(0))
    idx = jnp.asarray(np.full((8, 2, 4), 17, np.int32))
    d = engine.dedup_factor(state, idx)
    # one row, hammered by every entry, owned by one shard per dp group
    assert d["entries"] == 8 * 2 * 4
    assert d["unique_rows"] == 2          # dp=2 groups gather it once each
    assert d["factor"] == pytest.approx(32.0)
    w = np.zeros((8, 2, 4), np.float32)
    w[0, 0, 0] = 1.0
    dw = engine.dedup_factor(state, idx, weights=w)
    assert dw["entries"] == 1 and dw["unique_rows"] == 1


def test_psum_scatter_combine(engine, mesh):
    state = engine.init_state(jax.random.PRNGKey(0))
    # bags per device must divide tp=4: B=8 over dp=2 -> 4 local x G=2 = 8 bags
    idx = jax.random.randint(jax.random.PRNGKey(1), (8, 2, 4), 0, 500
                             ).astype(jnp.int32)
    want = _ref_lookup(engine, state, idx)
    with mesh:
        got = engine.lookup(state, idx, mode="pifs", combine="psum_scatter")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5)


# ---------------------------------------------------------------------------
# Fused front end (lookup_interact): resolution, plan cache, stability
# ---------------------------------------------------------------------------


@pytest.fixture()
def mesh_dp():
    """Replicated/dp-sharded mesh — the config where fusion resolves fused."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 host devices")
    from repro.distributed.sharding import make_mesh
    return make_mesh((8, 1), ("data", "model"))


@pytest.fixture()
def engine_dp(mesh_dp):
    eng, offs = engine_for_tables([500, 300], dim=16, mesh=mesh_dp,
                                  hot_fraction=0.06)
    return eng


def _fe_args(engine, seed=1):
    state = engine.init_state(jax.random.PRNGKey(0))
    idx = jax.random.randint(jax.random.PRNGKey(seed), (8, 2, 4), 0, 500
                             ).astype(jnp.int32)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (8, engine.cfg.dim))
    return state, idx, x


def test_front_end_matches_lookup_plus_interaction(engine_dp, mesh_dp):
    """lookup_interact == lookup -> concat -> dot_interaction oracle, and
    fused == split bitwise, on the dp-only mesh."""
    from repro.kernels import ref as kernel_ref
    state, idx, x = _fe_args(engine_dp)
    with mesh_dp:
        pooled = engine_dp.lookup(state, idx)
        want = np.asarray(kernel_ref.dot_interaction_ref(
            jnp.concatenate([x[:, None, :], pooled], axis=1)))
        for impl in ("jnp", "pallas"):
            s = np.asarray(engine_dp.lookup_interact(
                state, idx, x, impl=impl, front_end="split"))
            f = np.asarray(engine_dp.lookup_interact(
                state, idx, x, impl=impl, front_end="fused"))
            np.testing.assert_array_equal(s, f)
            np.testing.assert_array_equal(f, want)


def test_front_end_grows_plan_cache_key(engine_dp, mesh_dp):
    """front_end is part of the interact-plan signature: each knob value
    keys its own plan (one trace each), repeated calls hit the cache, and
    plan_stats() grows a 'front_end' entry with the resolution records —
    interact plans never collide with lookup plans."""
    state, idx, x = _fe_args(engine_dp)
    engine_dp.reset_plan_stats(clear_plans=True)
    with mesh_dp:
        engine_dp.lookup_interact(state, idx, x, front_end="split")
        engine_dp.lookup_interact(state, idx, x, front_end="split")
        engine_dp.lookup_interact(state, idx, x, front_end="fused")
        engine_dp.lookup_interact(state, idx, x, front_end="fused")
    stats = engine_dp.plan_stats()
    assert (stats["plans"], stats["traces"], stats["calls"]) == (2, 2, 4)
    recs = stats["front_end"]
    assert len(recs) == 2
    by_req = {r["requested"]: r for r in recs.values()}
    assert by_req["split"]["resolved"] == "split"
    assert by_req["fused"]["resolved"] == "fused"
    assert all(label.startswith("interact:") for label in recs)
    with mesh_dp:
        engine_dp.lookup(state, idx)          # lookup plan is a distinct key
    assert engine_dp.plan_stats()["plans"] == 3


def test_front_end_tp_resolves_fused_tp_and_is_recorded(engine, mesh):
    """tp-sharded masked partials resolve 'fused_tp': each shard partial-
    pools its (B, F, D) cold tile, the psum lands between the partial-pool
    and resume kernels, and the result stays bit-exact vs split (both
    paths psum fixed-l-order cold partials in the same mesh order).  The
    resolution record distinguishes fused_tp from a split fallback so
    benches can assert the datapath they time."""
    state, idx, x = _fe_args(engine)
    with mesh:
        for impl in ("jnp", "pallas"):
            s = np.asarray(engine.lookup_interact(state, idx, x, impl=impl,
                                                  front_end="split"))
            f = np.asarray(engine.lookup_interact(state, idx, x, impl=impl,
                                                  front_end="fused"))
            np.testing.assert_array_equal(s, f)
    recs = [r for r in engine.plan_stats()["front_end"].values()
            if r["requested"] == "fused"]
    assert recs and all(r["resolved"] == "fused_tp" for r in recs)
    assert "psum" in recs[0]["reason"]
    assert recs[0]["tp"] == 4
    split_recs = [r for r in engine.plan_stats()["front_end"].values()
                  if r["requested"] == "split"]
    assert split_recs and all(r["resolved"] == "split" for r in split_recs)


def test_front_end_pond_resolves_fused_tp(engine_dp, mesh_dp):
    """pond requesting fusion opts into pooling its cold partials before
    the hot/cold add (partial-pool -> psum -> resume): the knob resolves
    'fused_tp' even on the dp-only mesh, and the result equals the fixed
    l-order split composition (the pifs split path) bitwise — pond-split's
    own segment-sum order only agrees to tolerance."""
    state, idx, x = _fe_args(engine_dp)
    with mesh_dp:
        pifs_split = np.asarray(engine_dp.lookup_interact(
            state, idx, x, mode="pifs", front_end="split"))
        pond_split = np.asarray(engine_dp.lookup_interact(
            state, idx, x, mode="pond", front_end="split"))
        pond_fused = np.asarray(engine_dp.lookup_interact(
            state, idx, x, mode="pond", front_end="fused"))
    np.testing.assert_array_equal(pond_fused, pifs_split)
    np.testing.assert_allclose(pond_fused, pond_split, rtol=1e-5, atol=1e-5)
    recs = [r for r in engine_dp.plan_stats()["front_end"].values()
            if r["requested"] == "fused"]
    assert recs and recs[0]["resolved"] == "fused_tp"
    assert "pool" in recs[0]["reason"]


def test_front_end_tp_no_retrace_and_quantized(mesh):
    """fused_tp on the (2, 4) mesh: int8 cold tier + dedup + weights stay
    bit-exact vs split, and steady state holds zero retraces across
    observe/replan cycles (the serving contract under tp)."""
    eng, _ = engine_for_tables([500, 300], dim=16, mesh=mesh,
                               hot_fraction=0.06, storage="int8")
    state, idx, x = _fe_args(eng)
    w = jax.random.uniform(jax.random.PRNGKey(5), (8, 2, 4))
    with mesh:
        for impl in ("jnp", "pallas"):
            for dedup in ("off", "on"):
                s = np.asarray(eng.lookup_interact(
                    state, idx, x, weights=w, impl=impl, dedup=dedup,
                    front_end="split"))
                f = np.asarray(eng.lookup_interact(
                    state, idx, x, weights=w, impl=impl, dedup=dedup,
                    front_end="fused"))
                np.testing.assert_array_equal(s, f)
        warm = eng.plan_stats()["traces"]
        for _ in range(3):
            state = eng.observe(state, idx)
            state, _ = eng.plan_and_migrate(state)
            f = np.asarray(eng.lookup_interact(
                state, idx, x, weights=w, impl="pallas", front_end="fused"))
            s = np.asarray(eng.lookup_interact(
                state, idx, x, weights=w, impl="pallas", front_end="split"))
            np.testing.assert_array_equal(f, s)
    assert eng.plan_stats()["traces"] == warm


def test_front_end_no_retrace_across_observe_replan(engine_dp, mesh_dp):
    """Zero steady-state retraces across observe/replan cycles with
    front_end='fused' (the serving contract), and lookups stay bit-stable
    against their own split shadow after every migration."""
    state, idx, x = _fe_args(engine_dp)
    with mesh_dp:
        engine_dp.lookup_interact(state, idx, x, impl="pallas",
                                  front_end="fused")
        engine_dp.lookup_interact(state, idx, x, impl="pallas",
                                  front_end="split")
        warm = engine_dp.plan_stats()["traces"]
        for _ in range(3):
            state = engine_dp.observe(state, idx)
            state, _ = engine_dp.plan_and_migrate(state)
            f = np.asarray(engine_dp.lookup_interact(
                state, idx, x, impl="pallas", front_end="fused"))
            s = np.asarray(engine_dp.lookup_interact(
                state, idx, x, impl="pallas", front_end="split"))
            np.testing.assert_array_equal(f, s)
    assert engine_dp.plan_stats()["traces"] == warm


def test_front_end_validation(engine_dp, mesh_dp):
    state, idx, x = _fe_args(engine_dp)
    with pytest.raises(ValueError, match="front_end"):
        engine_dp.lookup_interact(state, idx, x, front_end="bogus")
    with pytest.raises(ValueError, match="dense_feature"):
        engine_dp.lookup_interact(state, idx, x[:, :4], front_end="fused")


def test_front_end_quantized_bit_exact(mesh_dp):
    """int8 cold tier through the fused front end: fused == split bitwise
    (the per-row dequant rides the same VMEM staging)."""
    eng, _ = engine_for_tables([500, 300], dim=16, mesh=mesh_dp,
                               hot_fraction=0.06, storage="int8")
    state, idx, x = _fe_args(eng)
    w = jax.random.uniform(jax.random.PRNGKey(5), (8, 2, 4))
    with mesh_dp:
        for impl in ("jnp", "pallas"):
            for dedup in ("off", "on"):
                s = np.asarray(eng.lookup_interact(
                    state, idx, x, weights=w, impl=impl, dedup=dedup,
                    front_end="split"))
                f = np.asarray(eng.lookup_interact(
                    state, idx, x, weights=w, impl=impl, dedup=dedup,
                    front_end="fused"))
                np.testing.assert_array_equal(s, f)
