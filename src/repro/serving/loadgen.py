"""Load generation and model-binding glue for the serving runtime.

This is the only serving module that knows about model families: it builds
the ``ServeBinding`` (engine + params + jitted serve step) for a config,
provides the request->bucket padder, fabricates warmup dummies, and turns
trace distributions (``repro.data.traces``) into per-request open-loop or
closed-loop streams with SLO deadlines attached.

Request features are host numpy, one example each:

  * DLRM:           ``dense (n_dense,)``, ``indices (T, L_r)`` (global row
                    ids, variable per-request pooling ``L_r``)
  * field recsys:   ``fields (F,)`` (+ ``dense`` when the config has it)
  * sequence recsys:``seq (S,)``, ``target ()`` (+ ``dense`` for BST)
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import DLRMConfig, RecConfig
from repro.core.pifs import ServeBinding
from repro.data.synth import _padded_rows, _zipf_ids
from repro.data.traces import TraceConfig, TraceGenerator
from repro.models import dlrm as dlrm_mod
from repro.models import params as prm
from repro.models import recsys as rec_mod
from repro.serving.batcher import (Bucket, pad_pooled_indices, stack_feature)
from repro.serving.request import ArrivalConfig, Request, arrival_times
from repro.serving.updates import UpdateBatch

_DENSE_TAG = 0xD0
_FIELD_TAG = 0xF1
_DELTA_TAG = 0xDE17A


@dataclasses.dataclass(frozen=True)
class LoadConfig:
    """One offered-load experiment: how many requests, arriving how, with
    what SLO budget and (DLRM) per-request pooling mix."""
    n_requests: int
    arrival: ArrivalConfig
    slo_ms: float = 50.0
    poolings: Tuple[int, ...] = ()       # DLRM pooling choices; () = fixed
    distribution: str = "zipfian"
    drift_every: int = 256               # serve-stream hot-set churn period
    seed: int = 0
    storage: str = "fp32"                # engine cold-tier storage; DLRM
    #                                      table offsets depend on its page size
    dedup: str = "off"                   # gather-once duplicate coalescing
    #                                      (off/auto/on; bit-exact either way)
    front_end: str = "split"             # DLRM lookup->interaction pipeline:
    #                                      'fused' keeps pooled features in
    #                                      VMEM through the interaction; tp-
    #                                      sharded configs resolve 'fused_tp'
    #                                      (partial-pool -> psum -> resume)
    update_qps: float = 0.0              # streaming embedding updates: delta
    #                                      rows/second on the virtual clock
    #                                      (0 = no update stream)
    update_batch: int = 64               # rows per trainer-emitted delta batch


# ---------------------------------------------------------------------------
# Model binding
# ---------------------------------------------------------------------------


def _serve_jit(engine, param_shardings):
    """``jax.jit`` taking (params, state, batch) where ``bind_model`` and
    the engine put params and state, so a call moves neither.  The batch's
    layout is left to the compiler: ``ServeBinding.execute`` stages each
    leaf where it puts it."""
    return functools.partial(jax.jit, in_shardings=(
        param_shardings, engine.state_shardings(), None))


def _dlrm_steps(cfg, engine, mesh, *, mode, impl, block_l, dedup,
                front_end, degraded_variants):
    """Jitted serve-step variants for a DLRM (engine, mesh) pair, and the
    params' shardings they take — split out of :func:`bind_model` so an
    elastic re-mesh can rebuild them against the survivor mesh with
    identical knobs (``front_end`` re-resolves per mesh: tp>1 picks
    ``fused_tp``, tp=1 plain fused)."""
    param_shardings = prm.shardings(dlrm_mod.model_specs(cfg, mesh), mesh)
    jit = _serve_jit(engine, param_shardings)
    step = jit(dlrm_mod.make_serve_step(
        cfg, engine, mesh, mode=mode, impl=impl, block_l=block_l,
        dedup=dedup, front_end=front_end))
    steps = None
    if degraded_variants:
        def dlrm_step(**kw):
            return jit(dlrm_mod.make_serve_step(
                cfg, engine, mesh, mode=mode, impl=impl,
                block_l=block_l, **kw))
        hot_only = dlrm_step(dedup="off", front_end="split",
                             tiers="hot_only")
        steps = {
            "split_fe": dlrm_step(dedup=dedup, front_end="split"),
            "no_dedup": dlrm_step(dedup="off", front_end="split"),
            "hot_only": hot_only,
            "shed": hot_only,
        }
    return step, steps, param_shardings


def _rec_steps(cfg, engine, offs, mesh, *, mode, impl, block_l, dedup,
               degraded_variants):
    """Rec-family analogue of :func:`_dlrm_steps` (``offs`` are page-size
    offsets — a function of storage, not of the mesh, so they carry
    verbatim across a re-mesh)."""
    param_shardings = prm.shardings(rec_mod.model_specs(cfg, mesh), mesh)
    jit = _serve_jit(engine, param_shardings)
    step = jit(rec_mod.make_serve_step(
        cfg, engine, offs, mesh, mode=mode, impl=impl, block_l=block_l,
        dedup=dedup))
    steps = None
    if degraded_variants:
        no_dedup = jit(rec_mod.make_serve_step(
            cfg, engine, offs, mesh, mode=mode, impl=impl,
            block_l=block_l, dedup="off"))
        steps = {"split_fe": step, "no_dedup": no_dedup,
                 "hot_only": no_dedup, "shed": no_dedup}
    return step, steps, param_shardings


def bind_model(cfg, mesh, mode: str = "pifs", impl: str = "jnp",
               block_l: int = 8, hot_fraction: float = 0.05,
               seed: int = 0, storage: str = "fp32",
               dedup: str = "off", front_end: str = "split",
               degraded_variants: bool = False,
               validate_ids: bool = False,
               scrub_scores: bool = False,
               update_capacity: int = 0,
               elastic: bool = False,
               prefer_tp: int = 4) -> ServeBinding:
    """Build engine + params + jitted serve step for a DLRM or Rec config.

    ``storage`` selects the engine's cold-tier format (fp32 passthrough or
    int8 with per-page scales and fused dequant in the SLS datapath);
    ``dedup`` the gather-once duplicate-coalescing knob (off/auto/on —
    bit-exact either way; 'auto' resolves per shape bucket from the
    observe-phase histogram); ``front_end`` the DLRM lookup->interaction
    pipeline ('fused' keeps pooled features in VMEM through the dot
    interaction; tp-sharded meshes and pond mode resolve it to
    'fused_tp' — each shard partial-pools its owned rows and only the
    small (B, F, d) cold tile is psum'd between the kernel halves — still
    bit-exact vs split; Rec configs have no DLRM dot-interaction stage,
    so the knob is DLRM-only and ignored for them).  The brown-out rungs
    stay on the split path by construction: ``split_fe``/``no_dedup``
    pass ``front_end='split'`` and ``hot_only``/``shed`` force it (the
    fused path is all-tiers only).

    ``degraded_variants`` additionally builds the brown-out ladder's
    serve-step variants (``repro.serving.degradation.RUNGS``) as separate
    jitted executables sharing the engine/params/state — the degradation
    controller switches between them via ``binding.set_mode`` without
    retracing (each variant is warmed per bucket by the caller).  DLRM
    rungs: split_fe (split front end, bit-exact), no_dedup (split + dedup
    off, bit-exact), hot_only (hot-tier-only lookups, cold rows
    zero-filled — scores change), shed (same datapath as hot_only; the
    controller also tightens admission).  Rec configs have no DLRM front
    end or tiers knob, so split_fe aliases full and hot_only/shed alias
    no_dedup.  ``validate_ids``/``scrub_scores`` arm the binding's
    host-side guardrails (OOB-id raise, NaN/Inf score scrub).
    ``update_capacity`` (> 0) sets the binding's fixed streaming-update
    apply width (rows per device chunk — one plan signature, zero
    steady-state retraces; see ``repro.serving.updates``).

    ``elastic`` arms mid-serving shard-loss recovery: the binding gets a
    rebinder closure that rebuilds every serve-step variant (same knobs)
    for a re-meshed engine, so ``ServeBinding.remesh`` can survive losing
    a tp shard — ``prefer_tp`` parameterizes the survivor-mesh policy
    (``runtime/elastic.scale_plan``).
    """
    k_params, k_state = jax.random.split(jax.random.PRNGKey(seed), 2)
    if isinstance(cfg, DLRMConfig):
        engine, _ = dlrm_mod.build_engine(cfg, mesh,
                                          hot_fraction=hot_fraction,
                                          storage=storage, dedup=dedup)
        specs = dlrm_mod.model_specs(cfg, mesh)
        step, steps, param_shardings = _dlrm_steps(
            cfg, engine, mesh, mode=mode, impl=impl, block_l=block_l,
            dedup=dedup, front_end=front_end,
            degraded_variants=degraded_variants)
        idx_key = "indices"

        def rebind(new_engine, new_mesh):
            return _dlrm_steps(
                cfg, new_engine, new_mesh, mode=mode, impl=impl,
                block_l=block_l, dedup=dedup, front_end=front_end,
                degraded_variants=degraded_variants)
    elif isinstance(cfg, RecConfig):
        engine, offs = rec_mod.build_engine(cfg, mesh,
                                            hot_fraction=hot_fraction,
                                            storage=storage, dedup=dedup)
        specs = rec_mod.model_specs(cfg, mesh)
        step, steps, param_shardings = _rec_steps(
            cfg, engine, offs, mesh, mode=mode, impl=impl,
            block_l=block_l, dedup=dedup,
            degraded_variants=degraded_variants)
        idx_key = None     # field ids are table-local; profiler stays off

        def rebind(new_engine, new_mesh):
            return _rec_steps(
                cfg, new_engine, offs, new_mesh, mode=mode, impl=impl,
                block_l=block_l, dedup=dedup,
                degraded_variants=degraded_variants)
    else:
        raise TypeError(f"unsupported serving config {type(cfg)}")
    # placed once here: left uncommitted, jit would copy every parameter
    # from device 0 to the rest of the mesh on each call
    params = jax.device_put(prm.initialize(specs, k_params), param_shardings)
    state = engine.init_state(k_state)
    binding = ServeBinding(engine, state, params, step, idx_key=idx_key,
                           steps=steps, validate_ids=validate_ids,
                           scrub_scores=scrub_scores)
    if update_capacity > 0:
        binding.update_capacity = int(update_capacity)
    if elastic:
        binding.attach_remesher(rebind, prefer_tp=prefer_tp)
    return binding


def make_padder(cfg) -> Callable[[Sequence[Request], Bucket], dict]:
    """Request-list -> bucket-shaped host batch for the config's family."""
    if isinstance(cfg, DLRMConfig):
        def pad_dlrm(reqs, bucket):
            idx, w = pad_pooled_indices(reqs, bucket)
            return {"dense": stack_feature(reqs, bucket, "dense"),
                    "indices": idx, "weights": w}
        return pad_dlrm
    it = cfg.interaction
    if it in ("self-attn-seq", "transformer-seq"):
        def pad_seq(reqs, bucket):
            out = {"seq": stack_feature(reqs, bucket, "seq"),
                   "target": stack_feature(reqs, bucket, "target")}
            if cfg.n_dense:
                out["dense"] = stack_feature(reqs, bucket, "dense")
            return out
        return pad_seq

    def pad_fields(reqs, bucket):
        out = {"fields": stack_feature(reqs, bucket, "fields")}
        if cfg.n_dense:
            out["dense"] = stack_feature(reqs, bucket, "dense")
        return out
    return pad_fields


# ---------------------------------------------------------------------------
# Request fabrication
# ---------------------------------------------------------------------------


def _dlrm_features(cfg: DLRMConfig, ids: np.ndarray, rid: int,
                   seed: int, storage: str = "fp32") -> dict:
    # global-row offsets follow the engine's page rounding, which depends
    # on the cold-tier storage format (int8 pages hold 4x the rows)
    offs = (np.arange(cfg.n_tables, dtype=np.int64)
            * _padded_rows(cfg, storage=storage))[:, None]
    rng = np.random.default_rng([seed, _DENSE_TAG, rid])
    return {"dense": rng.normal(size=(cfg.n_dense,)).astype(np.float32),
            "indices": (ids + offs).astype(np.int32)}


def _rec_features(cfg: RecConfig, rid: int, seed: int) -> dict:
    rng = np.random.default_rng([seed, _FIELD_TAG, rid])
    it = cfg.interaction
    out: dict = {}
    if it in ("self-attn-seq", "transformer-seq"):
        V = cfg.vocab_sizes[0]
        out["seq"] = _zipf_ids(rng, V, (cfg.seq_len,)).astype(np.int32)
        out["target"] = _zipf_ids(rng, V, ()).astype(np.int32)
    else:
        out["fields"] = np.stack(
            [_zipf_ids(rng, v, ()) for v in cfg.vocab_sizes]
        ).astype(np.int32)
    if cfg.n_dense:
        out["dense"] = rng.normal(size=(cfg.n_dense,)).astype(np.float32)
    return out


def request_stream(cfg, load: LoadConfig) -> List[Request]:
    """Materialise an open-loop request list (arrival times + features)."""
    times = arrival_times(load.arrival, load.n_requests)
    slo_s = load.slo_ms * 1e-3
    reqs: List[Request] = []
    if isinstance(cfg, DLRMConfig):
        gen = TraceGenerator(TraceConfig(
            n_rows=cfg.emb_num, n_tables=cfg.n_tables, pooling=cfg.pooling,
            batch=1, distribution=load.distribution, seed=load.seed))
        it = gen.serve_requests(load.n_requests,
                                poolings=load.poolings or None,
                                drift_every=load.drift_every)
        for i, ids in enumerate(it):
            reqs.append(Request(
                rid=i, arrival_s=float(times[i]),
                deadline_s=float(times[i]) + slo_s,
                features=_dlrm_features(cfg, ids, i, load.seed,
                                        storage=load.storage),
                pooling=ids.shape[1]))
    else:
        for i in range(load.n_requests):
            reqs.append(Request(
                rid=i, arrival_s=float(times[i]),
                deadline_s=float(times[i]) + slo_s,
                features=_rec_features(cfg, i, load.seed),
                pooling=1))
    return reqs


def closed_loop_factory(cfg, load: LoadConfig
                        ) -> Callable[[int, int, float], Request]:
    """Request factory for ``ClosedLoopSource`` (same feature streams as
    the open-loop generator, arrival set by the completion that frees the
    virtual user)."""
    slo_s = load.slo_ms * 1e-3
    if isinstance(cfg, DLRMConfig):
        gen = TraceGenerator(TraceConfig(
            n_rows=cfg.emb_num, n_tables=cfg.n_tables, pooling=cfg.pooling,
            batch=1, distribution=load.distribution, seed=load.seed))
        it = gen.serve_requests(None, poolings=load.poolings or None,
                                drift_every=load.drift_every)

        def make_dlrm(rid: int, user: int, arrival_s: float) -> Request:
            ids = next(it)
            return Request(rid=rid, arrival_s=arrival_s,
                           deadline_s=arrival_s + slo_s,
                           features=_dlrm_features(cfg, ids, rid, load.seed,
                                                   storage=load.storage),
                           pooling=ids.shape[1], user=user)
        return make_dlrm

    def make_rec(rid: int, user: int, arrival_s: float) -> Request:
        return Request(rid=rid, arrival_s=arrival_s,
                       deadline_s=arrival_s + slo_s,
                       features=_rec_features(cfg, rid, load.seed),
                       pooling=1, user=user)
    return make_rec


def update_stream(cfg, load: LoadConfig, scale: float = 1e-3
                  ) -> List[UpdateBatch]:
    """Materialise the trainer-side delta stream for an offered load.

    Batches of ``load.update_batch`` rows arrive at ``load.update_qps``
    delta rows/second on the same virtual clock as the request stream,
    covering the request horizon (last arrival).  Rows follow the load's
    trace distribution — an independent TraceGenerator with its own
    popularity drift, so the update stream skews hot exactly like real
    trainer output (hot rows train most) and stresses the requant-demote
    path.  Deltas are small gaussians (``scale``), keyed deterministically
    per batch.

    Only DLRM configs carry the global row-id space the engine's
    ``apply_deltas`` addresses; Rec families keep table-local ids inside
    the model, so an update stream for them is a config error."""
    if load.update_qps <= 0:
        return []
    if not isinstance(cfg, DLRMConfig):
        raise TypeError(
            "update streams address engine-global row ids; only DLRM "
            f"configs are supported (got {type(cfg).__name__})")
    times = arrival_times(load.arrival, load.n_requests)
    horizon = float(times[-1]) if len(times) else 0.0
    interval = load.update_batch / load.update_qps
    n_batches = max(1, int(horizon / interval) + 1)
    per_table = -(-load.update_batch // cfg.n_tables)
    gen = TraceGenerator(TraceConfig(
        n_rows=cfg.emb_num, n_tables=cfg.n_tables, pooling=per_table,
        batch=1, distribution=load.distribution, seed=load.seed + 1))
    offs = (np.arange(cfg.n_tables, dtype=np.int64)
            * _padded_rows(cfg, storage=load.storage))[:, None]
    out: List[UpdateBatch] = []
    for k in range(n_batches):
        ids = gen.next_batch()[0] + offs             # (T, per_table)
        rows = ids.reshape(-1)[: load.update_batch].astype(np.int64)
        rng = np.random.default_rng([load.seed, _DELTA_TAG, k])
        deltas = (rng.normal(size=(rows.size, cfg.emb_dim)) * scale
                  ).astype(np.float32)
        out.append(UpdateBatch(seq=k + 1, t_gen=(k + 1) * interval,
                               rows=rows, deltas=deltas))
    return out


def prime_dedup_auto(binding: ServeBinding, requests: Sequence[Request],
                     n: int = 64) -> int:
    """Prime the engine's access histogram for serving ``dedup='auto'``.

    The 'auto' coalescing decision is frozen per lookup plan when the plan
    is first built — for a serving runtime that is during bucket *warmup*,
    before any live traffic has populated the observe-phase histogram, so
    every bucket would freeze to the uniform-prior answer (off) and the
    knob would be inert.  This feeds the first ``n`` requests' index
    streams through the profiler (maintenance path), then drops the
    compiled plans and probe state so the caller's **re-warmup** rebuilds
    every bucket against the primed histogram; the rebuild traces land
    before the caller resets plan stats, so the zero-steady-retrace
    contract is untouched.  Returns the number of requests observed (0
    for model families whose profiler is off — nothing was dropped)."""
    if binding.idx_key is None:
        return 0
    engine = binding.engine
    dp = max(1, engine.axes.dp_size(engine.mesh))
    seen = 0
    by_pooling: dict = {}
    for r in requests[:n]:
        feats = r.features.get(binding.idx_key)
        if feats is None:
            continue
        feats = np.asarray(feats)
        # observe shards its batch over dp: tile the single request to a
        # dp-divisible batch (uniform inflation — the histogram's relative
        # skew, which is all 'auto' reads, is unchanged)
        idx = np.broadcast_to(feats[None], (dp,) + feats.shape)
        binding.observe({binding.idx_key: idx})
        by_pooling.setdefault(feats.shape[-1], []).append(feats)
        seen += 1
    if seen:
        # measured-duplicate hint: the page-granular histogram is blind to
        # row-level skew scattered across pages (hashed production ids),
        # so replay the stacked prefix through the exact gather ledger the
        # dedup datapath realizes; 'auto' resolutions built under the
        # outer serve-step trace use this as evidence alongside the
        # analytic expectation.  Prefix batches (~n requests) are larger
        # than single buckets, so the hint leans optimistic — it is a
        # decision heuristic, not the gated ledger (which stays measured
        # per batch).
        entries = uniques = 0
        for feats_list in by_pooling.values():
            d = engine.dedup_factor(binding.state, np.stack(feats_list))
            entries += d["entries"]
            uniques += d["unique_rows"]
        engine.dedup_auto_hint = entries / max(uniques, 1)
        binding.engine.reset_plan_stats(clear_plans=True)
        # the engine's lookup plans are built while *tracing* the outer
        # jitted serve step — once that step is compiled, the engine layer
        # is bypassed entirely, so its cleared registry would never
        # repopulate: drop the outer executables too (every ladder-rung
        # variant, not just the active one), forcing the re-warmup to
        # re-trace through engine.lookup against the primed histogram
        for s in {id(v): v for v in binding.steps.values()}.values():
            if hasattr(s, "clear_cache"):
                s.clear_cache()
        binding.dedup_stats.clear()
    return seen


def dummy_request_factory(cfg, storage: str = "fp32"
                          ) -> Callable[[int, int], Request]:
    """Fabricate bucket-warmup dummies (valid ids, zero-ish features)."""
    if isinstance(cfg, DLRMConfig):
        def make_dlrm(rid: int, pooling: int) -> Request:
            ids = np.zeros((cfg.n_tables, pooling), dtype=np.int64)
            return Request(rid=-1 - rid, arrival_s=0.0, deadline_s=1e9,
                           features=_dlrm_features(cfg, ids, 0, 0,
                                                   storage=storage),
                           pooling=pooling)
        return make_dlrm

    def make_rec(rid: int, pooling: int) -> Request:
        return Request(rid=-1 - rid, arrival_s=0.0, deadline_s=1e9,
                       features=_rec_features(cfg, 0, 0), pooling=1)
    return make_rec
