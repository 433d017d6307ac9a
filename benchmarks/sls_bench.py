"""SLS hot-path benchmark: latency, retraces, *bytes moved*, and — since
the gather-once coalescing PR — duplicate-access dedup under realistic
(zipfian) index traffic.

Sweeps ``{distribution} x {storage} x {dedup} x {impl} x {mode} x
{B, L, D}`` on a real ``PIFSEmbeddingEngine`` (8 fake CPU devices,
dp=2 x tp=4 mesh), measuring per-lookup wall latency (p50/p90 over timed
reps after warmup), retrace behaviour of the compiled-lookup plan cache,
and the bandwidth ledger of each datapath.  Two independent retrace
probes:

  * ``engine.plan_stats()`` — the engine's own jit-trace counter (fires once
    per shape-signature trace; steady state must stay flat), and
  * ``jax.monitoring`` compile events (``/jax/.../backend_compile``-style) —
    an XLA-level cross-check counted per measurement phase.

Index streams (the seed bench only timed **uniform** ``jax.random.randint``
ids, which understates every locality optimization in this repo):

  * ``uniform`` — i.i.d. uniform row ids (the seed behaviour; the traces
    module calls this family "random" — its "uniform" is a duplicate-free
    round-robin sweep, which is not what a bandwidth bench should time), and
  * ``zipfian`` — ``data/traces.py``'s calibrated zipfian generator
    (``--alpha``, default the Meta-trace-like 1.1), per-table preference
    permutations included.  Ids stay within the first table's page-aligned
    prefix so one index tensor is valid for both storage layouts (int8
    pages hold 4x the rows, so table offsets differ between storages).

Correctness gates before timing anything:

  * pallas matches jnp **bit-for-bit in fp32** for every storage mode, and
    ``dedup=on`` matches ``dedup=off`` bit-for-bit for both impls (the
    coalesced path changes the gather, never the accumulate order), and
  * every datapath agrees with the dequantized dense oracle
    (``engine.to_dense`` + ``sls_dense_ref``).

Bandwidth ledger (the point — DLRM inference is bandwidth-bound, so the
stored bytes crossing the memory interface are the cost that matters):

  * ``bytes_moved_per_lookup`` — stored bytes DMA'd from the embedding
    store per lookup.  ``dedup=off``: one row of ``D * cold_itemsize``
    bytes per pooling entry plus (int8) one 4-byte page scale per entry.
    ``dedup=on``: one row (+ scale) per *measured unique* owned row per
    (dp-group, shard) — the realized gather-once traffic, replayed
    host-side by ``engine.dedup_factor`` against the actual placement.
    Analytic and exact for the all-cold initial placement the bench uses;
    index/mask/slot SMEM traffic is identical across datapaths and
    excluded (as is the one clamped sentinel line per shard).
  * ``unique_rows_per_lookup`` / ``dup_factor`` — the realized duplicate
    statistics per config (recorded for every row, including dedup=off,
    where they quantify the traffic left on the table).
  * ``eff_bandwidth_mbps`` — fp32-equivalent payload served per second
    (``B*G*L*D*4 / p50``): what a bandwidth-bound deployment gains.
  * ``int8_vs_fp32`` comparison rows (dedup=off basis, as before):
    ``bw_improvement_x`` gated ``>= 2x``, ``bytes_ratio`` gated ``< 0.35``.
  * ``dedup_vs_off`` comparison rows: ``bytes_ratio = bytes_on /
    bytes_off`` per (config, distribution, storage), gated ``<= 0.5`` on
    zipfian configs with ``B*G*L >= 2048`` pooled entries (where the
    analytic duplicate model predicts a >= 2x factor at alpha=1.1 —
    smaller configs are recorded, not gated); ``p50_ratio`` per impl is
    recorded, not gated (interpret-mode caveat below: the sort-unique adds
    interpreter work that TPU hardware amortizes against the DMA savings).

Fused front end (schema 5, ``--front-end sweep``, the default): a separate
section on the *default DLRM shape* (8 tables x pooling 8, D=64) over a
dp-only (8, 1) mesh — the replicated/dp-sharded serving config where
``front_end='fused'`` resolves fused — gating (a) fused == split bit-for-bit
per {impl, storage, dedup}, (b) the front-end bytes ledger
(``front_end_bytes``: gather + pooled/features HBM round trips for split,
gather only for fused) at ``fused <= 0.72x split``, and (c) zero
steady-state retraces.  A tp-sharded (4, 2) subsection exercises the
``fused_tp`` resolution (partial-pool the owned rows per shard, psum only
the small (B, F, D) cold tile between the kernel halves, resume the
interaction on the reduced tile — checked via
``plan_stats()['front_end']``, never silently counted): fused_tp == split
bit-for-bit per {impl, storage, dedup}, zero steady-state retraces across
observe/replan cycles, and the tp bytes ledger — split under tp
materializes cold-partial / psum-output / hot / pooled (B, G, D) round
trips plus the (B, F, D) features round trip, fused_tp only the three
(B, F, D) tiles — gated ``fused_tp <= 0.8x split`` on int8 configs (the
fp32 rows are recorded ungated: the row gather dominates there and the
analytic ratio is marginal).  An ``e2e`` block times the full DLRM serve
step (bottom MLP -> lookup -> interaction -> top MLP as one jitted step)
for both pipelines on both meshes and pins scores bit-equal per mesh.

Writes ``BENCH_sls.json`` (schema 5); documented in EXPERIMENTS.md §Perf,
§Quantized cold-tier storage, §Duplicate-access coalescing and §Fused
front end.

Caveat: on CPU containers the Pallas kernel runs in *interpret mode* — its
absolute latency here reflects the interpreter, not TPU hardware; the numbers
that transfer are the jnp baseline, the retrace counts, the bytes ledger
(measured against the real placement), and the sweep structure itself.

Usage: ``PYTHONPATH=src python -m benchmarks.sls_bench [--out BENCH_sls.json]
[--quick|--smoke] [--storage fp32|int8|both] [--dedup off|on|both]
[--distribution uniform|zipfian|both] [--alpha 1.1 ...]``
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import platform
import time

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import sls as sls_ops  # noqa: E402
from repro.core.pifs import engine_for_tables  # noqa: E402
from repro.launch import compile_cache  # noqa: E402
from repro.data.traces import TraceConfig, TraceGenerator  # noqa: E402
from repro.distributed.sharding import make_mesh  # noqa: E402

MODES = ("pifs", "pond", "beacon")
IMPLS = ("jnp", "pallas")
# (B, L, D): batch, pooling factor, embedding dim — small enough for the
# CPU interpreter, shaped like the paper's DLRM configs (G=2 tables).
# (16, 64, 16) is the dedup gate config: 2048 pooled entries, where the
# calibrated zipfian stream realizes a ~2.4x duplicate factor at alpha=1.1.
SWEEP = [(8, 4, 16), (8, 16, 16), (8, 8, 64), (16, 64, 16)]
SWEEP_QUICK = [(16, 64, 16)]
G = 2  # tables per lookup
VOCAB = 4096  # first-table rows — the shared id space for every storage

BYTES_RATIO_GATE = 0.35   # int8 stored bytes must be < 0.35x fp32
BW_IMPROVEMENT_GATE = 2.0  # bytes-moved-basis effective-bandwidth gain
DEDUP_BYTES_GATE = 0.5     # dedup=on gathered bytes vs off (zipfian gate)
DEDUP_GATE_MIN_ENTRIES = 2048  # pooled entries below which the gate is off

# ---- fused front end (schema 5) ----
# Default DLRM shape (paper evaluation setup: 8 tables x pooling 8, D=64 —
# the RMC1/2/3 embedding dim), dp-only mesh (8, 1): the replicated/
# dp-sharded serving config where the fused front end resolves fused.
# The tp subsection reruns the sweep on a (4, 2) mesh where it resolves
# fused_tp (partial-pool -> psum the (B, F, D) cold tile -> resume).
FE_SHAPE = dict(B=16, G=8, L=8, D=64)
FE_VOCAB = 2048            # rows per table (page-aligned for both storages)
FE_BYTES_GATE = 0.72       # fused front-end bytes must be <= 0.72x split
FE_TP_MESH = (4, 2)        # dp x tp mesh for the fused_tp subsection
FE_TP_BYTES_GATE = 0.8     # fused_tp bytes vs split-under-tp, int8 configs
#                            (fp32 is gather-dominated: recorded, not gated)


class CompileEventCounter:
    """Counts XLA compile events via jax.monitoring between mark() calls."""

    COMPILE_MARKERS = ("compile", "jit")

    def __init__(self):
        self.count = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **kwargs) -> None:
        if any(m in event.lower() for m in self.COMPILE_MARKERS):
            self.count += 1

    def take(self) -> int:
        c = self.count
        self.count = 0
        return c


def make_indices(B: int, L: int, distribution: str, alpha: float
                 ) -> jax.Array:
    """One (B, G, L) index tensor in the shared [0, VOCAB) id space."""
    if distribution == "uniform":
        return jax.random.randint(jax.random.PRNGKey(1), (B, G, L), 0,
                                  VOCAB).astype(jnp.int32)
    gen = TraceGenerator(TraceConfig(
        n_rows=VOCAB, n_tables=G, pooling=L, batch=B,
        distribution="zipfian", zipf_alpha=alpha, seed=1))
    return jnp.asarray(gen.next_batch().astype(np.int32))


def bytes_moved_per_lookup(B: int, L: int, D: int, storage: str,
                           dedup_info=None, g: int = G) -> int:
    """Stored bytes DMA'd from the embedding store for one (B, g, L, D)
    lookup.  dedup=off (``dedup_info=None``): every pooling entry fetches
    its row once across the mesh (each row is owned by exactly one shard;
    the bench state is all-cold), plus one fp32 page scale per entry for
    int8.  dedup=on: one fetch per measured unique (dp-group, shard) row
    — ``dedup_info`` is ``engine.dedup_factor``'s replay against the
    engine's actual placement."""
    row_bytes = D * (1 if storage == "int8" else 4)
    scale_bytes = 4 if storage == "int8" else 0
    if dedup_info is None:
        return B * g * L * (row_bytes + scale_bytes)
    return (dedup_info["unique_cold"] * (row_bytes + scale_bytes)
            + dedup_info["unique_hot"] * D * 4)   # hot tier is always fp32


def front_end_bytes(B: int, Gt: int, L: int, D: int, storage: str,
                    front_end: str, dedup_info=None, tp: int = 1) -> int:
    """Total bytes the DLRM front end (SLS gather -> pooled features ->
    dot-interaction) moves per lookup.

    Both pipelines pay the same row-gather traffic (``bytes_moved_per_
    lookup``, dedup-aware — each row lives on exactly one shard under any
    mesh), the (B, D) bottom-MLP read and the (B, P) packed-triangle
    write.  The *split* pipeline additionally round-trips the pooled
    features through HBM twice: the SLS writes (B, G, D) pooled and the
    concat reads it back (one round trip), then the concat writes the
    (B, F, D) features tensor and the interaction kernel reads it back
    (the second) — the ``2 + 2`` x ``B*F*D*4`` traffic the fused kernel's
    persistent VMEM staging eliminates (kernels/sls.py phase 2/3).

    Under tensor parallelism (``tp > 1``) both pipelines must cross a
    psum, so the ledger counts what each materializes around it.  Split
    stages four (B, G, D) tensors through HBM — the per-shard cold
    partial (write + psum read), the psum output (write + hot-add read),
    the hot contribution (write + add read) and the pooled result (write
    + concat read), ``8 * B*G*D*4`` — plus the same (B, F, D) features
    round trip, ``2 * B*F*D*4``.  fused_tp stages exactly three (B, F, D)
    tiles: the partial cold tile (kernel write + psum read), the reduced
    tile (psum write + resume read) and the hot tile (kernel write +
    resume read), ``6 * B*F*D*4`` — the psum ships a *pooled* tile whose
    size is independent of L, never raw rows (reduce-then-communicate,
    paper §IV-B)."""
    F = Gt + 1
    Pp = F * (F - 1) // 2
    gather = bytes_moved_per_lookup(B, L, D, storage, dedup_info, g=Gt)
    stage = B * D * 4 + B * Pp * 4              # x in + packed triangle out
    unit = B * D * 4
    if tp > 1:
        if front_end == "fused_tp":
            return gather + stage + 6 * F * unit
        return gather + stage + (8 * Gt + 2 * F) * unit
    if front_end == "fused":
        return gather + stage
    pooled_rt = 2 * B * Gt * D * 4              # pooled write + concat read
    feats_rt = 2 * B * F * D * 4                # concat write + kernel read
    return gather + stage + pooled_rt + feats_rt


def bench_group(setups, idx, *, impl: str, mode: str, dedup: str, events,
                reps: int, warmup: int = 2) -> dict:
    """Benchmark one (impl, mode, dedup) row for every storage at once.

    Timed reps are *interleaved* across the storages (rep i of fp32 runs
    right next to rep i of int8), so host-load drift on shared machines
    cancels out of the p50 ratio instead of dominating it.
    """
    recs = {}
    for storage, (engine, state) in setups.items():
        engine.reset_plan_stats(clear_plans=True)  # cold start: must trace
        events.take()
        for _ in range(warmup):
            jax.block_until_ready(
                engine.lookup(state, idx, mode=mode, impl=impl, dedup=dedup))
        recs[storage] = {"warmup_traces": engine.plan_stats()["traces"],
                         "warmup_compile_events": events.take(),
                         "lat": []}
    for _ in range(reps):
        for storage, (engine, state) in setups.items():
            t0 = time.perf_counter()
            jax.block_until_ready(
                engine.lookup(state, idx, mode=mode, impl=impl, dedup=dedup))
            recs[storage]["lat"].append(time.perf_counter() - t0)
    steady_compiles = events.take()  # XLA-level check, shared by the group
    out = {}
    for storage, (engine, state) in setups.items():
        stats = engine.plan_stats()
        rec = recs[storage]
        out[storage] = {
            "p50_ms": float(np.percentile(rec["lat"], 50) * 1e3),
            "p90_ms": float(np.percentile(rec["lat"], 90) * 1e3),
            "warmup_traces": rec["warmup_traces"],
            "warmup_compile_events": rec["warmup_compile_events"],
            "steady_traces": stats["traces"] - rec["warmup_traces"],
            "steady_compile_events": steady_compiles,
            "lookups_timed": reps,
        }
    return out


def check_oracles(eng, state, idx, storage: str) -> None:
    """(a) pallas == jnp bit-for-bit; (b) dedup=on == dedup=off bit-for-bit
    per impl (the coalesced gather changes *where* rows come from, never
    the accumulate order); (c) everything matches the dequantized dense
    oracle (engine.to_dense computes the effective table both datapaths
    must reproduce — for int8 that *is* the ref.py quantized semantics:
    dequant after the gather, per-page scales)."""
    dense = eng.to_dense(state)
    B, Gt, L = idx.shape
    want = np.asarray(sls_ops.sls_dense_ref(
        dense, idx.reshape(B * Gt, L)).reshape(B, Gt, -1))
    for mode in MODES:
        outs = {}
        for impl in IMPLS:
            for dedup in ("off", "on"):
                outs[(impl, dedup)] = np.asarray(eng.lookup(
                    state, idx, mode=mode, impl=impl, dedup=dedup))
        a = outs[("jnp", "off")]
        if not np.array_equal(a, outs[("pallas", "off")]):
            raise AssertionError(
                f"pallas != jnp (fp32 exact) for storage={storage} "
                f"mode={mode} shape={idx.shape}")
        for impl in IMPLS:
            if not np.array_equal(outs[(impl, "off")], outs[(impl, "on")]):
                raise AssertionError(
                    f"dedup=on != dedup=off (fp32 exact) for "
                    f"storage={storage} impl={impl} mode={mode} "
                    f"shape={idx.shape}")
        if not np.allclose(a, want, rtol=1e-5, atol=1e-5):
            raise AssertionError(
                f"{storage} lookup disagrees with the dense oracle for "
                f"mode={mode}: max|d|={np.abs(a - want).max()}")


def fe_make_indices(B: int, Gt: int, L: int, distribution: str, alpha
                    ) -> jax.Array:
    """(B, Gt, L) ids in the shared first-table prefix (valid for both
    storage layouts — same trick as :func:`make_indices`)."""
    if distribution == "uniform":
        return jax.random.randint(jax.random.PRNGKey(2), (B, Gt, L), 0,
                                  FE_VOCAB).astype(jnp.int32)
    gen = TraceGenerator(TraceConfig(
        n_rows=FE_VOCAB, n_tables=Gt, pooling=L, batch=B,
        distribution="zipfian", zipf_alpha=alpha, seed=2))
    return jnp.asarray(gen.next_batch().astype(np.int32))


def run_front_end_section(args, events, storages) -> dict:
    """Schema-5 front-end sweep: fused vs split on the default DLRM shape.

    Engine-level rows (dp-only (8, 1) mesh, where fusion resolves fused):
    bitwise equality fused == split per {impl, storage, dedup}, p50/p90
    per (front_end, impl), zero steady-state retraces, and the front-end
    bytes ledger gated ``fused <= FE_BYTES_GATE x split``.  A tp-sharded
    ``FE_TP_MESH`` subsection reruns the sweep where the knob resolves
    ``fused_tp`` (asserted via ``plan_stats()['front_end']`` — a silent
    fallback to split would fake the bytes win): fused_tp == split
    bit-for-bit per {impl, storage, dedup}, zero steady-state retraces
    across observe/replan cycles, a pond partial-pool row (bitwise equal
    to the fixed-l-order split composition), and the tp bytes ledger
    gated ``fused_tp <= FE_TP_BYTES_GATE x split`` on int8 configs (fp32
    rows recorded ungated — the row gather dominates them).  An
    end-to-end ``e2e`` block times the full DLRM serve step (bottom MLP
    -> lookup -> interaction -> top MLP, one jitted step) for both
    pipelines on both meshes.
    """
    from repro.configs import get_config
    from repro.models import dlrm as dlrm_mod
    from repro.models import params as prm

    B, Gt, L, D = (FE_SHAPE[k] for k in ("B", "G", "L", "D"))
    mesh = make_mesh((8, 1), ("data", "model"))
    results, comparisons = [], []
    dists = [("uniform", None), ("zipfian", 1.1)]
    if args.quick:
        dists = [("zipfian", 1.1)]
    reps = args.reps

    setups = {}
    for storage in storages:
        eng, _ = engine_for_tables([FE_VOCAB] * Gt, dim=D, mesh=mesh,
                                   hot_fraction=0.05, storage=storage)
        state = eng.init_state(jax.random.PRNGKey(0))
        setups[storage] = (eng, state)
    x = jax.random.normal(jax.random.PRNGKey(3), (B, D))

    for dist, alpha in dists:
        idx = fe_make_indices(B, Gt, L, dist, alpha)
        dlabel = dist if alpha is None else f"{dist}(a={alpha})"
        for storage, (eng, state) in setups.items():
            dedups = ("off",) if dist == "uniform" else ("off", "on")
            if args.dedup == "off":
                dedups = ("off",)
            dup = eng.dedup_factor(state, idx)
            for dedup in dedups:
                # ---- correctness gate: fused == split bit-for-bit ----
                with mesh:
                    outs = {}
                    for impl in IMPLS:
                        for fe in ("split", "fused"):
                            outs[(impl, fe)] = np.asarray(eng.lookup_interact(
                                state, idx, x, impl=impl, dedup=dedup,
                                front_end=fe))
                    base = outs[("jnp", "split")]
                    for k, v in outs.items():
                        if not np.array_equal(base, v):
                            raise AssertionError(
                                f"front end not bit-exact for {k} "
                                f"(storage={storage} dedup={dedup})")
                    # oracle: the split composition from engine primitives
                    pooled = eng.lookup(state, idx, impl="jnp", dedup=dedup)
                    feats = jnp.concatenate([x[:, None, :], pooled], axis=1)
                    from repro.kernels import ref as kernel_ref
                    want = np.asarray(kernel_ref.dot_interaction_ref(feats))
                if not np.array_equal(base, want):
                    raise AssertionError(
                        f"front end disagrees with the lookup+interaction "
                        f"oracle (storage={storage} dedup={dedup})")
                # ---- timing + retrace probes ----
                p50 = {}
                for impl in IMPLS:
                    for fe in ("split", "fused"):
                        eng.reset_plan_stats(clear_plans=True)
                        events.take()
                        with mesh:
                            for _ in range(2):
                                jax.block_until_ready(eng.lookup_interact(
                                    state, idx, x, impl=impl, dedup=dedup,
                                    front_end=fe))
                            warm_traces = eng.plan_stats()["traces"]
                            lat = []
                            for _ in range(reps):
                                t0 = time.perf_counter()
                                jax.block_until_ready(eng.lookup_interact(
                                    state, idx, x, impl=impl, dedup=dedup,
                                    front_end=fe))
                                lat.append(time.perf_counter() - t0)
                        stats = eng.plan_stats()
                        steady = stats["traces"] - warm_traces
                        if steady:
                            raise AssertionError(
                                f"front-end steady-state retrace: "
                                f"impl={impl} fe={fe} storage={storage}")
                        fe_recs = [r for r in stats["front_end"].values()
                                   if r["requested"] == fe]
                        resolved = fe_recs[0]["resolved"]
                        if fe == "fused" and resolved != "fused":
                            raise AssertionError(
                                "fused plan did not resolve fused on the "
                                f"dp-only mesh (storage={storage}): the "
                                "bytes ledger would claim unrealized wins")
                        if dedup == "on":
                            drecs = [r for r in
                                     stats.get("dedup", {}).values()
                                     if r["requested"] == "on"]
                            if not all(r["resolved"] for r in drecs):
                                raise AssertionError(
                                    "fe dedup=on fell back (capacity?)")
                        info = dup if dedup == "on" else None
                        nbytes = front_end_bytes(B, Gt, L, D, storage, fe,
                                                 info)
                        r = {"B": B, "G": Gt, "L": L, "D": D,
                             "storage": storage, "impl": impl,
                             "front_end": fe, "resolved": resolved,
                             "dedup": dedup, "distribution": dist,
                             "alpha": alpha,
                             "p50_ms": float(np.percentile(lat, 50) * 1e3),
                             "p90_ms": float(np.percentile(lat, 90) * 1e3),
                             "steady_traces": steady,
                             "bytes_moved_per_lookup": nbytes,
                             "dup_factor": dup["factor"]}
                        results.append(r)
                        p50[(impl, fe)] = r["p50_ms"]
                        print(f"FE {dlabel:16s} storage={storage:5s} "
                              f"dedup={dedup:3s} impl={impl:6s} "
                              f"fe={fe:5s} p50={r['p50_ms']:8.2f}ms "
                              f"bytes/lookup={nbytes:8d}")
                # ---- bytes gate ----
                info = dup if dedup == "on" else None
                b_split = front_end_bytes(B, Gt, L, D, storage, "split", info)
                b_fused = front_end_bytes(B, Gt, L, D, storage, "fused", info)
                comp = {"B": B, "G": Gt, "L": L, "D": D, "storage": storage,
                        "dedup": dedup, "distribution": dist, "alpha": alpha,
                        "bytes_split": b_split, "bytes_fused": b_fused,
                        "bytes_ratio": b_fused / b_split,
                        "resolved": "fused", "gated": True,
                        "p50_ratio_jnp": (p50[("jnp", "fused")]
                                          / p50[("jnp", "split")]),
                        "p50_ratio_pallas": (p50[("pallas", "fused")]
                                             / p50[("pallas", "split")])}
                comparisons.append(comp)
                print(f"FE fused vs split @ {dlabel} {storage} dedup={dedup}: "
                      f"bytes {comp['bytes_ratio']:.3f}x, p50 jnp "
                      f"{comp['p50_ratio_jnp']:.2f}x / pallas "
                      f"{comp['p50_ratio_pallas']:.2f}x")
                if comp["bytes_ratio"] > FE_BYTES_GATE:
                    raise AssertionError(
                        f"front-end bytes gate failed at {dlabel} "
                        f"storage={storage} dedup={dedup}: "
                        f"{comp['bytes_ratio']:.3f} > {FE_BYTES_GATE}")

    # ---- tp-sharded subsection: partial-pool -> psum -> resume ----
    mesh_tp = make_mesh(FE_TP_MESH, ("data", "model"))
    tp = FE_TP_MESH[1]
    tp_results, tp_comparisons = [], []
    tp_dists = [("zipfian", 1.1)] if args.quick else dists
    for storage in storages:
        eng_tp, _ = engine_for_tables([FE_VOCAB] * Gt, dim=D, mesh=mesh_tp,
                                      hot_fraction=0.05, storage=storage)
        st_tp = eng_tp.init_state(jax.random.PRNGKey(0))
        for dist, alpha in tp_dists:
            idx = fe_make_indices(B, Gt, L, dist, alpha)
            dlabel = dist if alpha is None else f"{dist}(a={alpha})"
            dup = eng_tp.dedup_factor(st_tp, idx)
            dedups = ("off",) if dist == "uniform" or args.dedup == "off" \
                else ("off", "on")
            for dedup in dedups:
                # ---- correctness gate: fused_tp == split bit-for-bit ----
                with mesh_tp:
                    outs = {}
                    for impl in IMPLS:
                        for fe in ("split", "fused"):
                            outs[(impl, fe)] = np.asarray(
                                eng_tp.lookup_interact(
                                    st_tp, idx, x, impl=impl, dedup=dedup,
                                    front_end=fe))
                    base = outs[("jnp", "split")]
                    for k, v in outs.items():
                        if not np.array_equal(base, v):
                            raise AssertionError(
                                f"fused_tp not bit-exact for {k} "
                                f"(storage={storage} dedup={dedup})")
                    # pond partial-pool row: pools cold partials before the
                    # hot/cold add — bitwise equal to the fixed-l-order
                    # split composition above
                    pond = np.asarray(eng_tp.lookup_interact(
                        st_tp, idx, x, impl="pallas", dedup=dedup,
                        mode="pond", front_end="fused"))
                    if not np.array_equal(base, pond):
                        raise AssertionError(
                            f"pond fused_tp diverged from the fixed-l-order "
                            f"composition (storage={storage} dedup={dedup})")
                # ---- timing + resolution + retrace probes ----
                p50 = {}
                for impl in IMPLS:
                    for fe in ("split", "fused"):
                        eng_tp.reset_plan_stats(clear_plans=True)
                        events.take()
                        with mesh_tp:
                            for _ in range(2):
                                jax.block_until_ready(eng_tp.lookup_interact(
                                    st_tp, idx, x, impl=impl, dedup=dedup,
                                    front_end=fe))
                            warm_traces = eng_tp.plan_stats()["traces"]
                            lat = []
                            for _ in range(reps):
                                t0 = time.perf_counter()
                                jax.block_until_ready(eng_tp.lookup_interact(
                                    st_tp, idx, x, impl=impl, dedup=dedup,
                                    front_end=fe))
                                lat.append(time.perf_counter() - t0)
                            # steady state must survive the serving cadence:
                            # observe + replan between micro-batches
                            st2 = eng_tp.observe(st_tp, idx)
                            st2, _ = eng_tp.plan_and_migrate(st2)
                            jax.block_until_ready(eng_tp.lookup_interact(
                                st2, idx, x, impl=impl, dedup=dedup,
                                front_end=fe))
                        stats = eng_tp.plan_stats()
                        steady = stats["traces"] - warm_traces
                        if steady:
                            raise AssertionError(
                                f"fused_tp steady-state retrace: impl={impl} "
                                f"fe={fe} storage={storage} dedup={dedup}")
                        fe_recs = [r for r in stats["front_end"].values()
                                   if r["requested"] == fe]
                        resolved = fe_recs[0]["resolved"]
                        if fe == "fused" and resolved != "fused_tp":
                            raise AssertionError(
                                f"tp-sharded fused plan resolved "
                                f"{resolved!r}, not 'fused_tp' "
                                f"(storage={storage}): the bytes ledger "
                                "would claim unrealized wins")
                        if fe == "fused" and fe_recs[0]["tp"] != tp:
                            raise AssertionError(
                                f"front_end record tp={fe_recs[0]['tp']} "
                                f"!= mesh tp={tp}")
                        info = dup if dedup == "on" else None
                        fe_name = "fused_tp" if fe == "fused" else fe
                        nbytes = front_end_bytes(B, Gt, L, D, storage,
                                                 fe_name, info, tp=tp)
                        r = {"B": B, "G": Gt, "L": L, "D": D,
                             "storage": storage, "impl": impl,
                             "front_end": fe, "resolved": resolved,
                             "dedup": dedup, "distribution": dist,
                             "alpha": alpha,
                             "p50_ms": float(np.percentile(lat, 50) * 1e3),
                             "p90_ms": float(np.percentile(lat, 90) * 1e3),
                             "steady_traces": steady,
                             "bytes_moved_per_lookup": nbytes,
                             "dup_factor": dup["factor"]}
                        tp_results.append(r)
                        p50[(impl, fe)] = r["p50_ms"]
                        print(f"FE-tp {dlabel:16s} storage={storage:5s} "
                              f"dedup={dedup:3s} impl={impl:6s} "
                              f"fe={fe_name:8s} p50={r['p50_ms']:8.2f}ms "
                              f"bytes/lookup={nbytes:8d}")
                # ---- tp bytes gate (int8; fp32 gather-dominated) ----
                info = dup if dedup == "on" else None
                b_split = front_end_bytes(B, Gt, L, D, storage, "split",
                                          info, tp=tp)
                b_fused = front_end_bytes(B, Gt, L, D, storage, "fused_tp",
                                          info, tp=tp)
                gated = storage == "int8"
                comp = {"B": B, "G": Gt, "L": L, "D": D, "storage": storage,
                        "dedup": dedup, "distribution": dist, "alpha": alpha,
                        "bytes_split": b_split, "bytes_fused_tp": b_fused,
                        "bytes_ratio": b_fused / b_split,
                        "resolved": "fused_tp", "gated": gated,
                        "p50_ratio_jnp": (p50[("jnp", "fused")]
                                          / p50[("jnp", "split")]),
                        "p50_ratio_pallas": (p50[("pallas", "fused")]
                                             / p50[("pallas", "split")])}
                tp_comparisons.append(comp)
                print(f"FE-tp fused_tp vs split @ {dlabel} {storage} "
                      f"dedup={dedup}: bytes {comp['bytes_ratio']:.3f}x "
                      f"(gated={gated}), p50 jnp "
                      f"{comp['p50_ratio_jnp']:.2f}x / pallas "
                      f"{comp['p50_ratio_pallas']:.2f}x")
                if gated and comp["bytes_ratio"] > FE_TP_BYTES_GATE:
                    raise AssertionError(
                        f"fused_tp bytes gate failed at {dlabel} "
                        f"storage={storage} dedup={dedup}: "
                        f"{comp['bytes_ratio']:.3f} > {FE_TP_BYTES_GATE}")

    # ---- e2e: bottom MLP -> lookup -> interaction -> top MLP, one step ----
    cfg = dataclasses.replace(get_config("rmc1"), emb_num=FE_VOCAB)
    e2e = []
    from repro.data.synth import dlrm_batches
    batch = next(dlrm_batches(cfg, batch=B, n_batches=1))
    jb = {"dense": jnp.asarray(batch["dense"]),
          "indices": jnp.asarray(batch["indices"])}
    e2e_reps = max(3, reps)
    for dims, m in (((8, 1), mesh), (FE_TP_MESH, mesh_tp)):
        eng, _ = dlrm_mod.build_engine(cfg, m)
        state = eng.init_state(jax.random.PRNGKey(0))
        params = prm.initialize(dlrm_mod.model_specs(cfg, m),
                                jax.random.PRNGKey(1))
        outs = {}
        for fe in ("split", "fused"):
            for impl in IMPLS:
                step = jax.jit(dlrm_mod.make_serve_step(
                    cfg, eng, m, impl=impl, interaction_impl=impl,
                    front_end=fe))
                eng.reset_plan_stats(clear_plans=True)
                with m:
                    for _ in range(2):
                        jax.block_until_ready(step(params, state, jb))
                    warm = eng.plan_stats()["traces"]
                    lat = []
                    for _ in range(e2e_reps):
                        t0 = time.perf_counter()
                        jax.block_until_ready(step(params, state, jb))
                        lat.append(time.perf_counter() - t0)
                    outs[(fe, impl)] = np.asarray(step(params, state, jb))
                steady = eng.plan_stats()["traces"] - warm
                if steady:
                    raise AssertionError(
                        f"e2e steady-state retrace: mesh={dims} fe={fe} "
                        f"impl={impl}")
                r = {"arch": cfg.name, "B": B, "front_end": fe,
                     "impl": impl,
                     "mesh": {"data": dims[0], "model": dims[1]},
                     "p50_ms": float(np.percentile(lat, 50) * 1e3),
                     "p90_ms": float(np.percentile(lat, 90) * 1e3),
                     "steady_traces": steady}
                e2e.append(r)
                print(f"FE e2e {cfg.name} mesh={dims} fe={fe:5s} "
                      f"impl={impl:6s} p50={r['p50_ms']:8.2f}ms")
        # scores pin within a mesh only: per-shard fixed l-order differs
        # across placements, so cross-mesh equality is not a contract
        base = outs[("split", "jnp")]
        for k, v in outs.items():
            if not np.array_equal(base, v):
                raise AssertionError(
                    f"e2e scores not bit-exact for {k} on mesh={dims}")

    return {"shape": dict(FE_SHAPE, vocab=FE_VOCAB),
            "mesh": {"data": 8, "model": 1},
            "bytes_gate": FE_BYTES_GATE,
            "results": results, "fused_vs_split": comparisons,
            "tp": {"mesh": {"data": FE_TP_MESH[0], "model": FE_TP_MESH[1]},
                   "bytes_gate": FE_TP_BYTES_GATE,
                   "gated_storages": ["int8"],
                   "results": tp_results,
                   "fused_tp_vs_split": tp_comparisons},
            "e2e": e2e}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_sls.json")
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--quick", "--smoke", dest="quick", action="store_true",
                    help="single config smoke (CI)")
    ap.add_argument("--storage", default="both",
                    choices=["fp32", "int8", "both"],
                    help="cold-tier storage modes to sweep; 'both' also "
                         "emits the int8-vs-fp32 bandwidth comparison")
    ap.add_argument("--dedup", default="both", choices=["off", "on", "both"],
                    help="gather-once duplicate coalescing; 'both' also "
                         "emits the dedup-vs-off bytes comparison (gated "
                         "on large zipfian configs)")
    ap.add_argument("--distribution", default="both",
                    choices=["uniform", "zipfian", "both"],
                    help="index stream: i.i.d. uniform (the seed bench "
                         "behaviour) and/or the calibrated zipfian trace "
                         "generator")
    ap.add_argument("--alpha", type=float, nargs="+", default=[1.1],
                    help="zipfian skew(s) to sweep (traces.py calibration: "
                         "1.1 ~ Meta-trace-like)")
    ap.add_argument("--front-end", dest="front_end", default="sweep",
                    choices=["sweep", "off"],
                    help="schema-5 fused-front-end section: fused vs split "
                         "on the default DLRM shape (dp-only mesh, bytes "
                         "gate), the tp-sharded fused_tp subsection "
                         "(partial-pool -> psum -> resume, its own bytes "
                         "gate on int8), and the end-to-end "
                         "lookup->interaction->top-MLP step timing on both "
                         "meshes")
    args = ap.parse_args()
    compile_cache.enable()

    mesh = make_mesh((2, 4), ("data", "model"))
    events = CompileEventCounter()
    sweep = SWEEP_QUICK if args.quick else SWEEP
    storages = ("fp32", "int8") if args.storage == "both" else (args.storage,)
    dedups = ("off", "on") if args.dedup == "both" else (args.dedup,)
    if args.distribution == "both":
        dists = [("uniform", None)] + [("zipfian", a) for a in args.alpha]
    elif args.distribution == "zipfian":
        dists = [("zipfian", a) for a in args.alpha]
    else:
        dists = [("uniform", None)]
    results = []
    comparisons = []
    dedup_comparisons = []
    for (B, L, D) in sweep:
        setups = {}
        for storage in storages:
            eng, _ = engine_for_tables([VOCAB, VOCAB // 2], dim=D, mesh=mesh,
                                       hot_fraction=0.05, storage=storage)
            state = eng.init_state(jax.random.PRNGKey(0))
            setups[storage] = (eng, state)
        for dist, alpha in dists:
            idx = make_indices(B, L, dist, alpha)
            dlabel = dist if alpha is None else f"{dist}(a={alpha})"
            dup = {}     # storage -> measured dedup replay
            for storage, (eng, state) in setups.items():
                with mesh:
                    check_oracles(eng, state, idx, storage)
                dup[storage] = eng.dedup_factor(state, idx)
            p50 = {}     # (storage, impl, dedup) -> p50 of mode=pifs
            for impl in IMPLS:
                for mode in MODES:
                    for dedup in dedups:
                        if dedup == "on" and mode != "pifs":
                            # pond's cold path ships raw rows (no coalescing
                            # by construction) and beacon shares the pifs
                            # datapath — timing them again buys nothing
                            continue
                        with mesh:
                            group = bench_group(
                                setups, idx, impl=impl, mode=mode,
                                dedup=dedup, events=events, reps=args.reps)
                        if dedup == "on":
                            # the bytes ledger below is the dedup replay:
                            # it is only honest if the datapath actually
                            # coalesced — a silent capacity fallback must
                            # fail the bench, not report unrealized savings
                            for storage, (eng, _) in setups.items():
                                recs = eng.plan_stats().get("dedup", {})
                                bad = [k for k, v in recs.items()
                                       if v["requested"] == "on"
                                       and not v["resolved"]]
                                if bad:
                                    raise AssertionError(
                                        f"dedup=on fell back (capacity?) "
                                        f"for storage={storage}: {bad} — "
                                        "the bytes ledger would overstate "
                                        "savings")
                        for storage, r in group.items():
                            info = dup[storage] if dedup == "on" else None
                            nbytes = bytes_moved_per_lookup(
                                B, L, D, storage, info)
                            r.update(
                                impl=impl, mode=mode, B=B, L=L, D=D,
                                storage=storage, dedup=dedup,
                                distribution=dist, alpha=alpha,
                                bags_per_lookup=B * G,
                                unique_rows_per_lookup=dup[storage][
                                    "unique_rows"],
                                dup_factor=dup[storage]["factor"],
                                bytes_moved_per_lookup=nbytes,
                                eff_bandwidth_mbps=(
                                    B * G * L * D * 4 / (r["p50_ms"] * 1e-3)
                                    / 1e6))
                            results.append(r)
                            if mode == "pifs":
                                p50[(storage, impl, dedup)] = r["p50_ms"]
                            print(f"{dlabel:16s} storage={storage:5s} "
                                  f"dedup={dedup:3s} impl={impl:6s} "
                                  f"mode={mode:6s} B={B:3d} L={L:3d} "
                                  f"D={D:3d}  p50={r['p50_ms']:8.2f}ms "
                                  f"bytes/lookup={nbytes:7d}  "
                                  f"steady_traces={r['steady_traces']}")
                            if r["steady_traces"]:
                                raise AssertionError(
                                    "plan cache failed: steady-state retrace "
                                    f"for storage={storage} dedup={dedup} "
                                    f"impl={impl} mode={mode} B={B} L={L} "
                                    f"D={D}")
            if len(storages) == 2 and "off" in dedups:
                b_fp32 = bytes_moved_per_lookup(B, L, D, "fp32")
                b_int8 = bytes_moved_per_lookup(B, L, D, "int8")
                comp = {
                    "B": B, "L": L, "D": D, "distribution": dist,
                    "alpha": alpha,
                    "bytes_fp32": b_fp32, "bytes_int8": b_int8,
                    "bytes_ratio": b_int8 / b_fp32,
                    "bw_improvement_x": b_fp32 / b_int8,
                    "p50_ratio_jnp": (p50[("int8", "jnp", "off")]
                                      / p50[("fp32", "jnp", "off")]),
                    "p50_ratio_pallas": (p50[("int8", "pallas", "off")]
                                         / p50[("fp32", "pallas", "off")]),
                }
                comparisons.append(comp)
                print(f"int8 vs fp32 @ {dlabel} B={B} L={L} D={D}: "
                      f"bytes {comp['bytes_ratio']:.3f}x "
                      f"(bw {comp['bw_improvement_x']:.2f}x), "
                      f"p50 jnp {comp['p50_ratio_jnp']:.2f}x / "
                      f"pallas {comp['p50_ratio_pallas']:.2f}x")
                if comp["bytes_ratio"] >= BYTES_RATIO_GATE:
                    raise AssertionError(
                        f"int8 bytes-moved gate failed at B={B} L={L} D={D}: "
                        f"{comp['bytes_ratio']:.3f} >= {BYTES_RATIO_GATE}")
                if comp["bw_improvement_x"] < BW_IMPROVEMENT_GATE:
                    raise AssertionError(
                        f"int8 effective-bandwidth gate failed at B={B} "
                        f"L={L} D={D}: {comp['bw_improvement_x']:.2f}x < "
                        f"{BW_IMPROVEMENT_GATE}x")
            if len(dedups) == 2:
                entries = B * G * L
                gated = (dist == "zipfian" and (alpha or 0) >= 1.1
                         and entries >= DEDUP_GATE_MIN_ENTRIES)
                for storage in storages:
                    b_off = bytes_moved_per_lookup(B, L, D, storage)
                    b_on = bytes_moved_per_lookup(B, L, D, storage,
                                                  dup[storage])
                    comp = {
                        "B": B, "L": L, "D": D, "storage": storage,
                        "distribution": dist, "alpha": alpha,
                        "entries": entries,
                        "unique_rows": dup[storage]["unique_rows"],
                        "dup_factor": dup[storage]["factor"],
                        "bytes_off": b_off, "bytes_on": b_on,
                        "bytes_ratio": b_on / b_off,
                        "gated": gated,
                        "p50_ratio_jnp": (p50[(storage, "jnp", "on")]
                                          / p50[(storage, "jnp", "off")]),
                        "p50_ratio_pallas": (
                            p50[(storage, "pallas", "on")]
                            / p50[(storage, "pallas", "off")]),
                    }
                    dedup_comparisons.append(comp)
                    print(f"dedup vs off @ {dlabel} {storage} B={B} L={L} "
                          f"D={D}: bytes {comp['bytes_ratio']:.3f}x "
                          f"(dup factor {comp['dup_factor']:.2f}x, "
                          f"gated={gated}), p50 jnp "
                          f"{comp['p50_ratio_jnp']:.2f}x / pallas "
                          f"{comp['p50_ratio_pallas']:.2f}x")
                    if gated and comp["bytes_ratio"] > DEDUP_BYTES_GATE:
                        raise AssertionError(
                            f"dedup bytes-moved gate failed at {dlabel} "
                            f"storage={storage} B={B} L={L} D={D}: "
                            f"{comp['bytes_ratio']:.3f} > "
                            f"{DEDUP_BYTES_GATE}")

    front_end = None
    if args.front_end == "sweep":
        front_end = run_front_end_section(args, events, storages)

    out = {
        "bench": "sls_lookup",
        "schema": 5,
        "backend": jax.default_backend(),
        "interpret_mode": jax.default_backend() != "tpu",
        "jax_version": jax.__version__,
        "platform": platform.platform(),
        "mesh": {"data": 2, "model": 4},
        "storage_modes": list(storages),
        "dedup_modes": list(dedups),
        "distributions": [d for d, _ in dists],
        "alphas": args.alpha,
        "fp32_exact_pallas_vs_jnp": True,
        "fp32_exact_dedup_vs_off": True,
        "oracle_agreement": True,
        "results": results,
        "int8_vs_fp32": comparisons,
        "dedup_vs_off": dedup_comparisons,
        "front_end": front_end,
    }
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2)
    print(f"\nwrote {args.out} ({len(results)} rows, "
          f"{len(comparisons)} int8 comparisons, "
          f"{len(dedup_comparisons)} dedup comparisons, "
          f"{0 if front_end is None else len(front_end['results'])} "
          f"front-end rows)")


if __name__ == "__main__":
    main()
