"""Serving driver: online recsys inference through ``repro.serving``.

``python -m repro.launch.serve --arch rmc1 --qps 200 --requests 2000
--slo-ms 50 --impl pallas --block-l 8 --batcher dynamic``

Thin composition over the serving subsystem: binds the model to a
``ServeBinding`` (core/pifs.py), generates an open- or closed-loop request
stream from the trace distributions, warms every shape bucket (one
compile per bucket — afterwards the whole run does zero retraces), and
drives the deadline-aware dynamic micro-batcher.  The engine's access
profiler and periodic re-planning (paper §IV-B4) fold into the serving
cadence between micro-batches; migration is a pure gather with
placement-invariant lookups, so no query ever blocks on page management.
"""
from __future__ import annotations

import argparse
from typing import Callable, Dict, Optional, Tuple

import jax
import numpy as np

from repro.configs import get_config, reduced
from repro.runtime.fault_tolerance import StragglerWatchdog
from repro.serving import (BatcherConfig, BindingExecutor, BreakerConfig,
                           ClosedLoopSource, DegradationController,
                           DynamicBatcher, FaultConfig,
                           FaultInjectingExecutor, FixedBatcher,
                           LadderConfig, LoadConfig,
                           OpenLoopSource, RetryPolicy, RuntimeConfig,
                           ScrubConfig, ScrubController, ServingRuntime,
                           StreamingUpdater, UpdateConfig, bind_model,
                           closed_loop_factory, dummy_request_factory,
                           make_padder, prime_dedup_auto, request_stream,
                           update_stream)
from repro.checkpoint.checkpointer import Checkpointer
from repro.checkpoint.wal import WriteAheadLog
from repro.launch import compile_cache
from repro.launch.mesh import make_test_mesh
from repro.serving.request import ArrivalConfig


def build_serving(cfg, mesh, *, mode: str = "pifs", impl: str = "jnp",
                  block_l: int = 8, batcher: str = "dynamic",
                  batch_sizes: Tuple[int, ...] = (8, 16, 32),
                  poolings: Tuple[int, ...] = (),
                  slo_ms: float = 50.0, hot_fraction: float = 0.05,
                  storage: str = "fp32", dedup: str = "off",
                  front_end: str = "split",
                  runtime_cfg: RuntimeConfig = RuntimeConfig(),
                  validate_ids: bool = False,
                  elastic: bool = False, prefer_tp: int = 2,
                  ) -> Tuple[ServingRuntime, "object"]:
    """Compose (runtime, binding) for a config; buckets warmed by the
    caller via ``runtime.warmup``.  ``validate_ids`` arms the binding's
    host-side strict OOB-id check (raise loudly instead of letting the
    device gather clamp bad ids silently).  ``elastic`` additionally
    binds degraded serve-step variants and attaches the re-mesh rebinder
    so a persistent shard loss can recover mid-serving onto the
    survivors (tp preference ``prefer_tp``)."""
    binding = bind_model(cfg, mesh, mode=mode, impl=impl, block_l=block_l,
                         hot_fraction=hot_fraction, storage=storage,
                         dedup=dedup, front_end=front_end,
                         validate_ids=validate_ids,
                         degraded_variants=elastic, scrub_scores=elastic,
                         elastic=elastic, prefer_tp=prefer_tp)
    levels = tuple(sorted(set(poolings))) or (
        (cfg.pooling,) if hasattr(cfg, "pooling") else (1,))
    if batcher == "dynamic":
        b = DynamicBatcher(BatcherConfig(
            batch_sizes=tuple(sorted(batch_sizes)), poolings=levels,
            max_wait_ms=slo_ms / 2))
    elif batcher == "fixed":
        b = FixedBatcher(batch=max(batch_sizes), pooling=levels[-1])
    else:
        raise ValueError(f"unknown batcher {batcher!r}")
    runtime = ServingRuntime(BindingExecutor(binding), b, make_padder(cfg),
                             runtime_cfg)
    return runtime, binding


def serve_offered_load(cfg, mesh, load: LoadConfig, *, mode: str = "pifs",
                       impl: str = "jnp", block_l: int = 8,
                       batcher: str = "dynamic",
                       batch_sizes: Tuple[int, ...] = (8, 16, 32),
                       hot_fraction: float = 0.05,
                       runtime_cfg: RuntimeConfig = RuntimeConfig(),
                       closed_loop_users: int = 0,
                       validate_ids: bool = False,
                       update_cfg: Optional[UpdateConfig] = None,
                       wal_path: Optional[str] = None,
                       mesh_faults: bool = False, prefer_tp: int = 2,
                       fault_seed: int = 13,
                       scrub: bool = False, scrub_pages_per_cycle: int = 8,
                       after_run: Optional[Callable] = None,
                       ) -> Dict[str, object]:
    """End-to-end: bind, warm every bucket, serve the stream, and report
    metrics + the steady-state retrace count (must be 0).  The engine's
    cold-tier storage format rides in ``load.storage`` (the DLRM request
    streams need it for table-offset page rounding), the duplicate-
    coalescing knob in ``load.dedup``; the summary carries the measured
    per-bucket dedup factor so serving-side bytes wins are attributable.

    ``load.update_qps > 0`` arms the streaming-update subsystem: a
    trainer-side delta stream on the same virtual clock, drained between
    micro-batches by a ``StreamingUpdater`` (warmed *before* plan stats
    reset, so steady state stays retrace-free), with staleness p50/p99 in
    the summary and, when ``wal_path`` is given, every applied batch
    write-ahead-logged for mid-serving replay.

    ``mesh_faults`` arms the degraded-mesh regime: a ``shard_loss`` fault
    kills the highest tp shard at live attempt 2, the degradation
    controller attributes the same-shard streak and escalates past the
    brown-out ladder to an elastic re-mesh (quiesce, export, re-plan on
    the survivor mesh, re-pack, rebuild + re-warm the serve steps), and
    the run finishes on the survivors.  The summary carries the remesh
    record (MTTR = maintenance-seam wall time), watchdog trips, and the
    degradation report.

    ``scrub`` arms the integrity subsystem: a per-page checksum ledger
    over the live store, a snapshot (into a temp dir) whose manifest
    records the snapshot-time ledger, and a ``ScrubController`` on the
    runtime's maintenance seam auditing ``scrub_pages_per_cycle`` pages
    per micro-batch and repairing any diverged page surgically (snapshot
    page slice + filtered WAL replay).  The summary carries the scrub
    report (``scrub_run``: coverage, detections, per-page repair MTTR).

    ``after_run(binding)``, when given, is called once the stream has
    drained, still inside the mesh context — for checks on the served
    binding (probe scores, the lowered step) that must not count toward
    the steady window."""
    runtime, binding = build_serving(
        cfg, mesh, mode=mode, impl=impl, block_l=block_l, batcher=batcher,
        batch_sizes=batch_sizes, poolings=load.poolings, slo_ms=load.slo_ms,
        hot_fraction=hot_fraction, storage=load.storage, dedup=load.dedup,
        front_end=load.front_end, runtime_cfg=runtime_cfg,
        validate_ids=validate_ids, elastic=mesh_faults, prefer_tp=prefer_tp)
    if mesh_faults:
        if dict(mesh.shape).get("model", 1) < 2:
            raise ValueError(
                "--mesh-faults needs a tp-sharded mesh (model >= 2): "
                "losing the only model shard is total loss, not a "
                f"degraded mesh (got {dict(mesh.shape)})")
        runtime.controller = DegradationController(
            binding=binding,
            retry=RetryPolicy(max_attempts=3),
            breaker=BreakerConfig(trip_after=6, cooldown_s=0.02),
            ladder=LadderConfig(min_dwell_batches=4, remesh_after=3))
        runtime.watchdog = StragglerWatchdog(threshold=4.0, warmup=4)
    with mesh:
        if mesh_faults:
            # warm every ladder rung over every bucket through the clean
            # executor (fault schedules index live attempts only); the
            # fault wrapper is armed after all warmup, right before run
            factory = dummy_request_factory(cfg, storage=load.storage)
            for rung in binding.modes():
                binding.set_mode(rung)
                runtime.warmup(factory)
            binding.set_mode("full")
        else:
            runtime.warmup(dummy_request_factory(cfg, storage=load.storage))
        # the open-loop stream is only materialized when something uses it
        # (the serving source, or the 'auto' priming prefix) — closed-loop
        # runs draw from their own factory
        reqs = (request_stream(cfg, load)
                if load.dedup == "auto" or closed_loop_users <= 0 else None)
        if load.dedup == "auto" and prime_dedup_auto(binding, reqs):
            # per-bucket 'auto' decisions freeze at plan build: prime the
            # profiler with a prefix of the live stream, then rebuild the
            # buckets against the primed histogram (still pre-steady-state)
            runtime.warmup(dummy_request_factory(cfg, storage=load.storage))
        updater = None
        if load.update_qps > 0:
            ucfg = update_cfg or UpdateConfig()
            wal = WriteAheadLog(wal_path) if wal_path else None
            updater = StreamingUpdater(binding, update_stream(cfg, load),
                                       ucfg, wal=wal)
            updater.warmup()              # compile the apply plan now
            runtime.updater = updater
        if scrub:
            # arm the ledger over the live store, snapshot it (manifest
            # records the snapshot-time checksums the repair path
            # verifies against), and ride the maintenance seam
            import tempfile
            binding.attach_integrity()
            if binding.checkpointer is None:
                binding.attach_checkpointer(
                    Checkpointer(tempfile.mkdtemp(prefix="serve_scrub_")),
                    save_now=True)
            scrubber = ScrubController(
                binding,
                ScrubConfig(pages_per_cycle=scrub_pages_per_cycle),
                controller=runtime.controller)
            scrubber.warmup()             # compile audit/repair plans now
            runtime.scrubber = scrubber
        if mesh_faults:
            runtime.executor = FaultInjectingExecutor(
                runtime.executor,
                FaultConfig(seed=fault_seed, shard_loss_at=(2,)),
                idx_key=binding.idx_key)
        binding.reset_plan_stats()        # steady state begins here
        binding.dedup_stats.clear()       # drop warmup-dummy observations
        warm_replans = binding.replans
        if closed_loop_users > 0:
            source = ClosedLoopSource(
                closed_loop_users, load.n_requests,
                closed_loop_factory(cfg, load),
                think_time_s=closed_loop_users / load.arrival.rate_qps)
        else:
            source = OpenLoopSource(reqs)
        summary = runtime.run(source)
        stats = binding.plan_stats()
        if after_run is not None:
            after_run(binding)
    summary["steady_traces"] = stats["traces"]
    service_ms = [b.service_s * 1e3 for b in runtime.metrics.batches]
    if service_ms:
        summary["service_p50_ms"] = float(np.percentile(service_ms, 50.0))
        summary["service_p99_ms"] = float(np.percentile(service_ms, 99.0))
    if mesh_faults:
        summary["remeshes"] = binding.remeshes
        summary["faults_fired"] = runtime.executor.report()
    summary["plans"] = stats["plans"]
    summary["front_end"] = stats.get("front_end", {})
    summary["replans"] = binding.replans - warm_replans
    summary["dedup_factors"] = binding.dedup_report()
    if updater is not None:
        summary["updates"] = updater.report()
    return summary


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="rmc1")
    ap.add_argument("--requests", type=int, default=1024)
    ap.add_argument("--qps", type=float, default=200.0,
                    help="offered load (virtual-clock requests/second)")
    ap.add_argument("--slo-ms", type=float, default=50.0)
    ap.add_argument("--mode", default="pifs",
                    choices=["pifs", "pond", "beacon"])
    ap.add_argument("--impl", default="jnp", choices=["jnp", "pallas"],
                    help="engine SLS datapath (pallas = bag-tiled kernel)")
    ap.add_argument("--block-l", type=int, default=8,
                    help="pallas kernel pooling-tile size")
    ap.add_argument("--storage", default="fp32", choices=["fp32", "int8"],
                    help="cold-tier storage: fp32 passthrough or int8 with "
                         "per-page scales (dequant fused into the SLS "
                         "accumulate)")
    ap.add_argument("--dedup", default="off", choices=["off", "auto", "on"],
                    help="gather-once duplicate coalescing in the SLS "
                         "datapath (bit-exact; 'auto' decides per shape "
                         "bucket from the access histogram)")
    ap.add_argument("--front-end", default="split",
                    choices=["split", "fused"],
                    help="DLRM lookup->interaction pipeline: 'fused' keeps "
                         "pooled features in VMEM from the SLS accumulate "
                         "through the dot-interaction matmul (bit-exact; "
                         "tp-sharded meshes and pond mode resolve it to "
                         "'fused_tp' — partial-pool, psum the (B, F, d) "
                         "cold tile, resume)")
    ap.add_argument("--batcher", default="dynamic",
                    choices=["dynamic", "fixed"])
    ap.add_argument("--batch-sizes", type=int, nargs="+",
                    default=[8, 16, 32])
    ap.add_argument("--arrival", default="poisson",
                    choices=["poisson", "bursty", "uniform"])
    ap.add_argument("--closed-loop-users", type=int, default=0,
                    help="> 0 switches to a closed-loop load of N users")
    ap.add_argument("--validate-ids", action="store_true",
                    help="strict mode: raise host-side on out-of-range "
                         "embedding ids instead of letting the device "
                         "gather clamp them silently")
    ap.add_argument("--update-qps", type=float, default=0.0,
                    help="> 0 arms the streaming embedding-update stream "
                         "(delta rows/second on the virtual clock), drained "
                         "between micro-batches")
    ap.add_argument("--update-batch", type=int, default=64,
                    help="rows per trainer-emitted delta batch")
    ap.add_argument("--wal", default=None, metavar="PATH",
                    help="write-ahead-log applied update batches to PATH "
                         "(mid-serving restore replays it)")
    ap.add_argument("--mesh-faults", action="store_true",
                    help="degraded-mesh regime: inject a persistent "
                         "shard_loss fault (highest tp shard, live attempt "
                         "2) and require a mid-serving elastic re-mesh "
                         "onto the survivors — prints the remesh record "
                         "(MTTR, from/to mesh) and the degradation report")
    ap.add_argument("--prefer-tp", type=int, default=2,
                    help="tp preference handed to scale_plan when the "
                         "elastic re-mesh lays out the survivor mesh")
    ap.add_argument("--scrub", action="store_true",
                    help="arm the integrity scrubber: per-page checksum "
                         "ledger + snapshot, then audit a rotating page "
                         "window between micro-batches and repair any "
                         "diverged page from the snapshot + WAL tail "
                         "(prints the scrub report)")
    ap.add_argument("--scrub-pages-per-cycle", type=int, default=8,
                    help="pages audited per maintenance turn (--scrub); "
                         "a full store sweep every ceil(num_pages / K) "
                         "micro-batches")
    ap.add_argument("--observe-every", type=int, default=4)
    ap.add_argument("--replan-every", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()
    compile_cache.enable()

    cfg = get_config(args.arch)
    if not args.full:
        cfg = reduced(cfg)
    n_dev = len(jax.devices())
    mesh = make_test_mesh(n_dev, min(4, n_dev))
    load = LoadConfig(
        n_requests=args.requests,
        arrival=ArrivalConfig(rate_qps=args.qps, process=args.arrival,
                              seed=args.seed),
        slo_ms=args.slo_ms, seed=args.seed, storage=args.storage,
        dedup=args.dedup, front_end=args.front_end,
        update_qps=args.update_qps, update_batch=args.update_batch)
    out = serve_offered_load(
        cfg, mesh, load, mode=args.mode, impl=args.impl,
        block_l=args.block_l, batcher=args.batcher,
        batch_sizes=tuple(args.batch_sizes),
        runtime_cfg=RuntimeConfig(observe_every=args.observe_every,
                                  replan_every=args.replan_every),
        closed_loop_users=args.closed_loop_users,
        validate_ids=args.validate_ids, wal_path=args.wal,
        mesh_faults=args.mesh_faults, prefer_tp=args.prefer_tp,
        scrub=args.scrub,
        scrub_pages_per_cycle=args.scrub_pages_per_cycle)
    out.pop("latency_hist", None)
    fe_plans = out.pop("front_end", {})
    dedup_factors = out.pop("dedup_factors", {})
    staleness = out.pop("staleness", None)
    updates = out.pop("updates", None)
    scrub_run = out.pop("scrub_run", None)
    remesh = out.pop("remesh", None)
    watchdog = out.pop("watchdog", None)
    degradation = out.pop("degradation", None)
    for k, v in out.items():
        print(f"  {k:24s} {v}")
    if remesh is not None:
        print("  -- elastic re-mesh --")
        for k, v in remesh.items():
            print(f"  {k:24s} {v}")
    if watchdog is not None:
        print(f"  watchdog_trips           {watchdog['trips']} "
              f"(ewma={watchdog['ewma_s']:.4f}s)")
    if degradation is not None:
        print(f"  degradation              rung={degradation['rung']} "
              f"remeshes={degradation['remeshes']} "
              f"suspect_shard={degradation['suspect_shard']} "
              f"straggler_trips={degradation['straggler_trips']}")
    if updates is not None:
        print("  -- streaming updates --")
        for k, v in updates.items():
            print(f"  {k:24s} {v}")
    if scrub_run is not None:
        print("  -- scrub --")
        print(f"  audited                  "
              f"{scrub_run['pages_audited']} pages over "
              f"{scrub_run['cycles']} cycles "
              f"(window={scrub_run['pages_per_cycle']}, full sweep every "
              f"{scrub_run['sweep_cycles']} cycles, "
              f"{scrub_run['sweeps_completed']} sweeps, "
              f"coverage={scrub_run['coverage']:.2f})")
        print(f"  detected/repaired        "
              f"{scrub_run['pages_detected']}/"
              f"{scrub_run['pages_repaired']} "
              f"(quarantined={scrub_run['quarantined']})")
        if "repair_mttr_mean_s" in scrub_run:
            print(f"  repair_mttr              "
                  f"mean={scrub_run['repair_mttr_mean_s']:.4f}s "
                  f"max={scrub_run['repair_mttr_max_s']:.4f}s")
    if staleness is not None:
        print("  -- staleness (rows / seconds behind) --")
        print(f"  rows_behind   p50={staleness['rows_behind_p50']:.1f} "
              f"p99={staleness['rows_behind_p99']:.1f} "
              f"max={staleness['rows_behind_max']:.1f}")
        print(f"  seconds_behind p50={staleness['seconds_behind_p50']:.4f} "
              f"p99={staleness['seconds_behind_p99']:.4f} "
              f"max={staleness['seconds_behind_max']:.4f}")
    for label, rec in fe_plans.items():
        print(f"  front_end[{label}]  requested={rec['requested']} "
              f"resolved={rec['resolved']} (tp={rec['tp']})")
    for bucket, rec in dedup_factors.items():
        print(f"  dedup[{bucket}]  factor={rec['factor']:.2f} "
              f"({rec['entries']} entries -> {rec['unique_rows']} unique "
              f"rows over {rec['batches']} observed batches)")


if __name__ == "__main__":
    main()
