"""Serving benchmark: deadline-aware dynamic batcher vs the fixed-batch
baseline at equal offered load, on a real PIFSEmbeddingEngine.

The paper's headline claim is *online-inference latency under concurrent
production-style access streams*; this bench measures the quantities that
regime is judged by — p50/p99/p99.9 latency, sustained QPS, SLO-violation
rate, batch occupancy — for two batching policies over the same engine,
the same compiled serve step, and the **same arrival stream** (same seed):

  * ``dynamic`` — the deadline-aware shape-bucket micro-batcher
    (repro.serving.batcher.DynamicBatcher), and
  * ``fixed``   — the old serve-loop policy (wait for a full fixed batch).

Offered load is calibrated against the measured capacity of the largest
bucket (``frac * B_max / service(B_max)``), so the comparison is at an
apples-to-apples utilization on any host.  Each run sweeps load regimes;
hard gates:

  * zero steady-state retraces (``engine.plan_stats()`` delta stays 0
    across every shape bucket after warmup, for both policies, in every
    regime);
  * **trough** regime (sub-saturation, where fixed-batch fill time
    dominates the tail): dynamic p99 < fixed p99 at equal offered load —
    the structural win of deadline-aware flushing;
  * **sustained** regime (both policies serve full buckets; the tail
    difference there is measurement noise): dynamic must sustain >= 80 %
    of the offered QPS.

``--faults`` switches to the fault-injection regimes instead (straggler,
transient executor failures, corrupted data/store, a forced brown-out
burst), each run under the retry/circuit-breaker/degradation-ladder
controller with gates on availability, zero steady-state retraces, and
recorded degradation/recovery transitions (EXPERIMENTS.md §Serving fault
tolerance).

``--mesh-faults`` switches to the degraded-mesh regime: a tp shard is
deterministically killed mid-serving (``shard_loss`` fault class); the
degradation controller attributes the consecutive same-shard failures,
escalates past the brown-out ladder to the ``remesh`` recovery action,
and the runtime re-meshes the engine onto the survivors on the
maintenance seam (quiesce -> export -> re-plan -> re-pack -> rebuild +
re-warm the jitted serve-step variants) and re-attempts the stranded
micro-batch.  Hard gates per config (fp32/split and int8+dedup+fused):
exactly one recorded re-mesh, availability >= 0.99, bounded MTTR
(recorded in the artifact; wall time is compile-dominated on CPU
containers), zero steady-state retraces across the whole run — the
pre-loss *and* post-recovery steady states share one gate, read before
any probe executes — and post-recovery probe scores bit-identical to a
fresh engine packed onto the same survivor mesh (fused configs
additionally assert the front end re-resolved ``fused_tp`` at the new
tp).

``--updates`` switches to the streaming-embedding-update regime: the same
offered load served twice — once clean, once with a WAL-logged trainer
delta stream drained between micro-batches on the background-maintenance
seam (same accounting model as observe/replan).  Hard gates: updates
never blow the service tail (measured p99 regression < 10 % vs the clean
run at equal offered load), every drain's wall cost fits inside one SLO
budget, zero steady-state retraces in both runs, staleness p99 bounded,
and a mid-serving corrupt -> restore -> WAL-replay probe whose state AND
lookups are bit-identical to the pre-corruption engine (EXPERIMENTS.md
§Online embedding updates).

``--scrub`` switches to the silent-corruption regime: the same offered
load served twice — once clean, once with deterministic *finite* bit
flips seeded into live store pages (``bit_flip`` fault class — the case
the NaN score scrub structurally misses) while a ``ScrubController``
audits a rotating page window against the per-page checksum ledger on
the maintenance seam and repairs divergent pages surgically (snapshot
page slice + filtered WAL replay).  Hard gates: every flipped page
detected within one full sweep of the store, repaired pages == detected
pages with bounded per-page MTTR, availability >= 0.99, measured p99
within 10 % of the no-scrub leg at equal offered load, zero
steady-state retraces in both legs, and the post-run store leaves AND
probe scores bitwise identical to the never-corrupted engine
(EXPERIMENTS.md §Silent-corruption scrubbing).

The policy-comparison section also runs a fused front-end leg on a
(4, 2) dp x tp mesh (DLRM archs): ``front_end='fused'`` — resolved
``fused_tp`` by the engine (partial-pool per shard, psum the (B, F, d)
cold tile, resume; asserted via ``plan_stats()['front_end']``) — served
against the ``front_end='split'`` control on the same arrival stream,
gated on zero steady-state retraces in both runs and probe-batch scores
bit-equal between the bindings.

Writes ``BENCH_serve.json`` (schema 7); schema documented in
EXPERIMENTS.md §Serving.

Service times are real measured device executions (interpret-mode caveat
from BENCH_sls applies to pallas impl on CPU); arrivals/queueing run on
the virtual clock, which is what makes tail-latency comparisons meaningful
on CPU containers.

Usage: ``PYTHONPATH=src python -m benchmarks.serve_bench [--smoke]
[--impl pallas] [--out BENCH_serve.json]``
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import platform

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.checkpoint.checkpointer import Checkpointer  # noqa: E402
from repro.checkpoint.wal import WriteAheadLog  # noqa: E402
from repro.configs import get_config, reduced  # noqa: E402
from repro.distributed.sharding import make_mesh  # noqa: E402
from repro.launch import compile_cache  # noqa: E402
from repro.runtime.fault_tolerance import StragglerWatchdog  # noqa: E402
from repro.serving import (ArrivalConfig, BatcherConfig,  # noqa: E402
                           BindingExecutor, BreakerConfig, Bucket,
                           DegradationController,
                           DynamicBatcher, FaultConfig,
                           FaultInjectingExecutor, FixedBatcher,
                           LadderConfig, LoadConfig, OpenLoopSource,
                           RetryPolicy, RuntimeConfig, ScrubConfig,
                           ScrubController, ServiceModel,
                           ServingRuntime,
                           StreamingUpdater, UpdateConfig, bind_model,
                           corrupt_store, dummy_request_factory,
                           flip_store_bits, make_padder, prime_dedup_auto,
                           request_stream, update_stream)


def run_policy(binding, cfg, batcher, load, runtime_cfg, updater=None) -> dict:
    """One (policy, arrival-stream) serving run over a warmed binding."""
    runtime = ServingRuntime(BindingExecutor(binding), batcher,
                             make_padder(cfg), runtime_cfg, updater=updater)
    runtime.warmup(dummy_request_factory(cfg, storage=load.storage))
    # ^ no-op cost once plans warm
    reqs = request_stream(cfg, load)
    if load.dedup == "auto" and prime_dedup_auto(binding, reqs):
        # 'auto' freezes per bucket at plan build — rebuild the buckets
        # against a histogram primed with the live stream's prefix
        runtime.warmup(dummy_request_factory(cfg, storage=load.storage))
    if updater is not None:
        updater.warmup()   # compile the apply plan before steady state
    binding.reset_plan_stats()
    warm_replans = binding.replans
    binding.dedup_stats.clear()
    summary = runtime.run(OpenLoopSource(reqs))
    stats = binding.plan_stats()
    summary["steady_traces"] = stats["traces"]
    summary["replans"] = binding.replans - warm_replans
    # measured per-bucket duplicate factor (observe-cadence probe): makes
    # serving wins attributable in bytes, not just p50
    summary["dedup_factors"] = binding.dedup_report()
    return summary


# ---------------------------------------------------------------------------
# Fault regimes (--faults): chaos-hardened serving
# ---------------------------------------------------------------------------

# one regime per injected fault class (ISSUE: straggler, transient executor
# failure, corrupted store, OOB/NaN data) plus a forced brown-out burst that
# exercises the breaker + degradation ladder end to end.  Availability gates
# apply to the chaos classes (rare, retryable faults: healthy traffic must
# see >= 0.99); the brownout burst deliberately fails batches past the retry
# budget, so its gate is the *recorded recovery*, not availability.
FAULT_REGIMES = [
    # scheduled steps guarantee each class actually fires at smoke scale
    # (~10-20 micro-batches); the chaos probabilities ride on top so the
    # longer full-size runs also see unscheduled faults
    dict(label="straggler", avail_gate=0.99,
         faults=dict(straggler_at=(4, 12), straggler_prob=0.05,
                     straggler_factor=8.0, stall_at=(1,), stall_prob=0.1,
                     stall_s=0.01)),
    dict(label="transient", avail_gate=0.99,
         faults=dict(transient_at=(6,), transient_prob=0.05)),
    dict(label="corrupt_data", avail_gate=0.99,
         faults=dict(corrupt_oob_at=(3, 9), corrupt_oob_prob=0.05,
                     corrupt_nan_at=(5, 11), corrupt_nan_prob=0.05)),
    dict(label="corrupt_store", avail_gate=0.99, corrupt_store=True,
         faults=dict()),
    # forced failure burst past the retry budget: this regime exists to
    # exercise the breaker + ladder end to end (its gate is the recorded
    # degradation AND recovery, not availability — the burst deliberately
    # fails whole batches); 2x requests so recovery completes in-run
    dict(label="brownout", avail_gate=0.50, gate_transitions=True,
         n_mult=2, faults=dict(transient_at=(5,), transient_runs=6)),
]


def _warm_all_rungs(binding, cfg, bat_cfg, runtime_cfg, svc_model, storage):
    """Warm every ladder-rung variant over every bucket so mid-serving rung
    switches stay retrace-free (the same contract the plain bench gates)."""
    warm_rt = ServingRuntime(BindingExecutor(binding), DynamicBatcher(bat_cfg),
                             make_padder(cfg), runtime_cfg,
                             service_model=svc_model)
    factory = dummy_request_factory(cfg, storage=storage)
    for rung in binding.modes():
        binding.set_mode(rung)
        warm_rt.warmup(factory)
    binding.set_mode("full")


def run_fault_regime(binding, cfg, bat_cfg, load, runtime_cfg, svc_model,
                     regime: dict, ckpt_dir: str) -> dict:
    """One fault class: fresh controller + fault wrapper over the warmed
    binding, full open-loop run, degradation report attached."""
    binding.set_mode("full")          # fresh ladder per fault class
    fault_cfg = FaultConfig(seed=13, **regime["faults"])
    ctrl = DegradationController(
        binding=binding,
        # short virtual-time cooldown + eager step-up so trip/recovery both
        # complete within a smoke-sized run (hysteresis band stays wide)
        breaker=BreakerConfig(trip_after=5, cooldown_s=0.02),
        ladder=LadderConfig(min_dwell_batches=4, step_up_at=0.15,
                            poison_restore_after=2))
    fex = FaultInjectingExecutor(BindingExecutor(binding), fault_cfg,
                                 idx_key=binding.idx_key)
    # per-batch service-time watchdog feeding the controller: a straggling
    # shard walks the ladder down before it ever fails outright.  The 4x
    # threshold sits safely above shared-host jitter and safely below the
    # 8x injected straggler factor.
    watchdog = StragglerWatchdog(threshold=4.0)
    runtime = ServingRuntime(fex, DynamicBatcher(bat_cfg), make_padder(cfg),
                             runtime_cfg, service_model=svc_model,
                             controller=ctrl, watchdog=watchdog)
    reqs = request_stream(cfg, load)
    if regime.get("corrupt_store"):
        # promote hot pages with the live stream's prefix (a corrupted hot
        # tier nobody reads poisons nothing), snapshot the healthy state,
        # then scribble NaNs the restore path must heal
        dp = max(1, binding.engine.axes.dp_size(binding.engine.mesh))
        for r in reqs[:16]:
            idx = np.asarray(r.features[binding.idx_key])
            binding.observe({binding.idx_key:
                             np.broadcast_to(idx[None], (dp,) + idx.shape)})
        binding.replan()
        binding.attach_checkpointer(Checkpointer(ckpt_dir), save_now=True)
        # explicit mode="nan": this regime heals through the NaN score
        # scrub -> poison-restore path; finite flips are --scrub's job
        corrupt_store(binding, frac=0.5, seed=3, mode="nan")
    elif binding.checkpointer is None:
        binding.attach_checkpointer(Checkpointer(ckpt_dir), save_now=True)
    binding.reset_plan_stats()
    base_poisoned = binding.poisoned_batches
    summary = runtime.run(OpenLoopSource(reqs))
    summary["steady_traces"] = binding.plan_stats()["traces"]
    summary["faults_fired"] = fex.report()
    summary["poisoned_batches"] = binding.poisoned_batches - base_poisoned
    return summary


def run_fault_section(binding, cfg, bat_cfg, runtime_cfg, svc_model,
                      n_requests, capacity_qps, slo_ms, storage, dedup,
                      ckpt_dir) -> dict:
    runs: dict = {}
    for regime in FAULT_REGIMES:
        arrival = ArrivalConfig(rate_qps=0.3 * capacity_qps,
                                process="poisson", seed=7)
        load = LoadConfig(n_requests=n_requests * regime.get("n_mult", 1),
                          arrival=arrival, slo_ms=slo_ms, seed=7,
                          storage=storage, dedup=dedup)
        r = run_fault_regime(binding, cfg, bat_cfg, load, runtime_cfg,
                             svc_model, regime, ckpt_dir)
        deg = r["degradation"]
        label = regime["label"]
        print(f"[{label:13s}] avail={r['availability']:.4f} "
              f"goodput={r['goodput_qps']:7.1f} qps "
              f"p99={r['p99_ms']:8.2f} served={r['served']} "
              f"failed={r['failed']} retries={r['retries']} "
              f"rung={deg['rung']} transitions={deg['n_transitions']} "
              f"trips={deg['breaker_trips']} restores={deg['restores']} "
              f"wd_trips={r['watchdog']['trips']} "
              f"fired={r['faults_fired']} "
              f"steady_traces={r['steady_traces']}")
        # ---- gates ----
        if r["steady_traces"]:
            raise AssertionError(
                f"plan cache failed under faults: steady-state retrace in "
                f"{label}")
        if r["availability"] < regime["avail_gate"]:
            raise AssertionError(
                f"availability gate failed in {label}: "
                f"{r['availability']:.4f} < {regime['avail_gate']}")
        if regime.get("gate_transitions") and deg["n_transitions"] < 2:
            raise AssertionError(
                f"{label}: expected degradation AND recovery transitions, "
                f"recorded {deg['transitions']}")
        if label == "transient" and not r["retries"]:
            raise AssertionError("transient regime exercised no retries")
        if label == "straggler" and not r["watchdog"]["trips"]:
            raise AssertionError(
                "straggler regime: the 8x injected stragglers never "
                "tripped the service-time watchdog")
        if label == "corrupt_data" and not r["poisoned_batches"]:
            raise AssertionError(
                "corrupt_data regime: NaN injection never reached the "
                "score scrub")
        if label == "corrupt_store" and not deg["restores"]:
            raise AssertionError(
                "corrupt_store regime: poisoned store never triggered a "
                "checkpoint restore")
        runs[label] = {"avail_gate": regime["avail_gate"], **r}
    return runs


# ---------------------------------------------------------------------------
# Degraded-mesh regime (--mesh-faults): survive shard loss via elastic remesh
# ---------------------------------------------------------------------------

# one run per serving configuration: the plain control and the full
# feature stack (int8 cold tier + gather-once dedup + fused front end) —
# the re-mesh must carry quantized pages verbatim, re-prime dedup, and
# re-resolve fused_tp at the survivor tp, all mid-serving
MESH_FAULT_CONFIGS = [
    dict(label="fp32_split", storage="fp32", dedup="off", front_end="split"),
    dict(label="int8_fused", storage="int8", dedup="on", front_end="fused"),
]


def run_mesh_fault_config(cfg, args, conf: dict, n_requests: int,
                          prefer_tp: int) -> dict:
    """One shard-loss -> elastic-remesh serving run, fully gated.

    Starts on a (2, 4) dp x tp mesh, kills the highest tp shard at
    attempt 2, and requires the runtime to detect (same-shard streak),
    re-mesh onto the survivors (tp 4 -> 2 under ``prefer_tp=2`` with the
    bucket-granule constraint), re-warm, and finish the offered load with
    availability intact and zero steady-state retraces.  The retrace gate
    is read *before* the bit-exactness probe: probe batches are fresh jit
    signatures, and sampling the counter after them would conflate probe
    traces with steady-state ones."""
    fe = conf["front_end"] if hasattr(cfg, "n_tables") else "split"
    mesh = make_mesh((2, 4), ("data", "model"))
    bat_cfg = BatcherConfig(batch_sizes=(8, 16), poolings=(cfg.pooling,))
    runtime_cfg = RuntimeConfig(observe_every=4, replan_every=32)
    with mesh:
        binding = bind_model(cfg, mesh, mode=args.mode, impl=args.impl,
                             block_l=args.block_l, storage=conf["storage"],
                             dedup=conf["dedup"], front_end=fe,
                             degraded_variants=True, scrub_scores=True,
                             elastic=True, prefer_tp=prefer_tp)
        ctrl = DegradationController(
            binding=binding,
            retry=RetryPolicy(max_attempts=3),
            # trip_after > retry budget x remesh_after: the breaker must
            # not fail-fast the stranded batch before attribution
            # escalates to remesh
            breaker=BreakerConfig(trip_after=6, cooldown_s=0.02),
            ladder=LadderConfig(min_dwell_batches=4, remesh_after=3))
        inner = BindingExecutor(binding)
        fex = FaultInjectingExecutor(
            inner, FaultConfig(seed=13, shard_loss_at=(2,)),
            idx_key=binding.idx_key)
        watchdog = StragglerWatchdog(threshold=4.0, warmup=4)
        runtime = ServingRuntime(inner, DynamicBatcher(bat_cfg),
                                 make_padder(cfg), runtime_cfg,
                                 controller=ctrl, watchdog=watchdog)
        factory = dummy_request_factory(cfg, storage=conf["storage"])
        # warm every ladder rung over every bucket through the *clean*
        # executor (fault schedules index live attempts only), then arm
        # the fault wrapper
        for rung in binding.modes():
            binding.set_mode(rung)
            runtime.warmup(factory)
        binding.set_mode("full")
        padder = make_padder(cfg)
        big = Bucket(bat_cfg.batch_sizes[-1], bat_cfg.poolings[-1])
        cal = padder([factory(i, big.pooling)
                      for i in range(big.batch)], big)
        svc = float(np.median([inner.run_batch(big, cal)
                               for _ in range(5)]))
        capacity_qps = big.batch / svc
        slo_ms = args.slo_ms or 5.0 * svc * 1e3
        runtime.executor = fex
        binding.reset_plan_stats()
        load = LoadConfig(
            n_requests=n_requests,
            arrival=ArrivalConfig(rate_qps=0.3 * capacity_qps,
                                  process="poisson", seed=7),
            slo_ms=slo_ms, seed=7, storage=conf["storage"],
            dedup=conf["dedup"], front_end=fe)
        summary = runtime.run(OpenLoopSource(request_stream(cfg, load)))

        # ---- gates (retrace gate FIRST — before any probe executes) ----
        label = conf["label"]
        steady_traces = binding.plan_stats()["traces"]
        if steady_traces:
            raise AssertionError(
                f"[{label}] plan cache failed across the re-mesh: "
                f"{steady_traces} steady-state retraces (the carried-trace "
                f"ledger spans both sides of the recovery)")
        rec = summary.get("remesh")
        if binding.remeshes != 1 or rec is None:
            raise AssertionError(
                f"[{label}] expected exactly one elastic re-mesh, recorded "
                f"{binding.remeshes} (remesh record: {rec})")
        if summary["availability"] < 0.99:
            raise AssertionError(
                f"[{label}] availability gate failed across shard loss: "
                f"{summary['availability']:.4f} < 0.99")
        # MTTR = maintenance-seam wall time of the recovery (quiesce +
        # export/re-plan/re-pack + rebuild & re-warm every serve-step
        # variant).  On CPU containers the re-warm recompiles dominate, so
        # the bound is deliberately loose: generous in SLO multiples,
        # floored at 60 s wall.
        mttr_bound = max(100.0 * slo_ms * 1e-3, 60.0)
        if not (0.0 < rec["mttr_s"] < mttr_bound):
            raise AssertionError(
                f"[{label}] MTTR unbounded: {rec['mttr_s']:.2f} s "
                f">= {mttr_bound:.1f} s")
        new_shape = dict(binding.engine.mesh.shape)
        if new_shape.get("model") != 2 or rec["to_mesh"] != new_shape:
            raise AssertionError(
                f"[{label}] survivor mesh mismatch: engine on {new_shape}, "
                f"record says {rec['to_mesh']} (expected model=2)")
        if fe == "fused":
            recs = [r for r in
                    binding.engine.plan_stats().get("front_end", {}).values()
                    if r["requested"] == "fused"]
            if not recs or any(r["resolved"] != "fused_tp" or r["tp"] != 2
                               for r in recs):
                raise AssertionError(
                    f"[{label}] front end did not re-resolve fused_tp at "
                    f"the survivor tp: "
                    f"{[(r['resolved'], r['tp']) for r in recs]}")

        # ---- bit-exactness probe: recovered engine vs a fresh engine
        # packed onto the *same* survivor mesh from the same logical
        # (codes, values, scales) triple and the same page table
        codes, values, scales = binding.engine.export_state(binding.state)
        fresh = bind_model(cfg, binding.engine.mesh, mode=args.mode,
                           impl=args.impl, block_l=args.block_l,
                           storage=conf["storage"], dedup=conf["dedup"],
                           front_end=fe)
        fresh.params = binding.params
        fresh.state = fresh.engine.pack_state(
            codes, values, scales, table=binding.state.page_table,
            counts=np.asarray(jax.device_get(binding.state.counts)))
        for bucket in (Bucket(b, cfg.pooling)
                       for b in bat_cfg.batch_sizes):
            probe = padder([factory(i, bucket.pooling)
                            for i in range(bucket.batch)], bucket)
            a = np.asarray(jax.device_get(binding.execute(probe)))
            b = np.asarray(jax.device_get(fresh.execute(probe)))
            if not np.array_equal(a, b):
                raise AssertionError(
                    f"[{label}] post-recovery scores diverge from a fresh "
                    f"engine on the degraded mesh at bucket {bucket}")

    deg = summary["degradation"]
    print(f"[{label:11s}] avail={summary['availability']:.4f} "
          f"served={summary['served']} failed={summary['failed']} "
          f"remeshes={binding.remeshes} "
          f"mttr={rec['mttr_s']:.2f}s at_batch={rec['at_batch']} "
          f"{rec['from_mesh']} -> {rec['to_mesh']} "
          f"lost_shard={rec['lost_shard']} "
          f"steady_traces={steady_traces} "
          f"fired={fex.report()['shard_loss']} "
          f"rung={deg['rung']} probe=bit-identical")
    summary.pop("latency_hist", None)
    summary.pop("dedup_factors", None)
    return {
        "label": label, "storage": conf["storage"], "dedup": conf["dedup"],
        "front_end": fe, "prefer_tp": prefer_tp,
        "capacity_qps": capacity_qps, "slo_ms": slo_ms,
        "offered_qps": 0.3 * capacity_qps,
        "steady_traces": steady_traces,
        "mttr_bound_s": mttr_bound,
        "faults_fired": fex.report(),
        "probe_bit_identical": True,
        "run": summary,
    }


def run_mesh_fault_section(cfg, args, n_requests: int,
                           prefer_tp: int) -> dict:
    return {c["label"]: run_mesh_fault_config(cfg, args, c, n_requests,
                                              prefer_tp)
            for c in MESH_FAULT_CONFIGS}


# ---------------------------------------------------------------------------
# Streaming-update regime (--updates): serving-concurrent embedding updates
# ---------------------------------------------------------------------------


def _state_leaves(binding):
    st = binding.state
    return [np.asarray(jax.device_get(x))
            for x in (st.cold, st.hot, st.page_scales,
                      st.page_to_shard, st.page_to_slot)]


def run_update_section(binding, cfg, bat_cfg, runtime_cfg, n_requests,
                       capacity_qps, slo_ms, svc_max, storage, dedup,
                       update_batch, ckpt_dir) -> dict:
    """Clean run vs updates run at the same offered load, then the
    mid-serving recovery probe.

    Updates drain on the maintenance seam under the same background-
    stream model as observe/replan (``account_maintenance=False``, the
    bench-wide convention — on CPU containers the ~ms jit-dispatch floor
    of a single apply would otherwise swamp the virtual-clock tail).
    The cost is still gated twice: the measured service p99 of the
    updates run must stay within 10 % of the clean run at equal offered
    load (dispatch interference is real wall time in both runs), and
    every drain's wall cost must fit inside one SLO budget — the slack a
    real deployment has between micro-batches."""
    rt_cfg = runtime_cfg
    arrival = ArrivalConfig(rate_qps=0.3 * capacity_qps, process="poisson",
                            seed=7)
    load = LoadConfig(n_requests=n_requests, arrival=arrival, slo_ms=slo_ms,
                      seed=7, storage=storage, dedup=dedup)
    base = run_policy(binding, cfg, DynamicBatcher(bat_cfg), load, rt_cfg)

    # trainer stream: one update_batch roughly every two service times —
    # a stiff but sub-saturating delta rate relative to engine capacity
    update_qps = 0.5 * update_batch / svc_max
    upd_load = dataclasses.replace(load, update_qps=update_qps,
                                   update_batch=update_batch)
    wal = WriteAheadLog(os.path.join(ckpt_dir, "updates.wal"))
    ucfg = UpdateConfig(capacity=2 * update_batch, drift_threshold=0.0,
                        max_demotions=4)
    updater = StreamingUpdater(binding, update_stream(cfg, upd_load), ucfg,
                               wal=wal)
    if binding.checkpointer is None:
        binding.attach_checkpointer(Checkpointer(ckpt_dir), save_now=True)
    upd = run_policy(binding, cfg, DynamicBatcher(bat_cfg), upd_load, rt_cfg,
                     updater=updater)
    upd["updates"] = updater.report()

    print(f"[updates   ] base   p99={base['p99_ms']:8.2f} "
          f"qps={base['qps']:8.1f} steady_traces={base['steady_traces']}")
    st = upd.get("staleness", {})
    print(f"[updates   ] stream p99={upd['p99_ms']:8.2f} "
          f"qps={upd['qps']:8.1f} steady_traces={upd['steady_traces']} "
          f"applied={upd['updates']['applied_batches']}/"
          f"{upd['updates']['generated_batches']} batches "
          f"stale_rows_p99={st.get('rows_behind_p99', 0.0):.1f} "
          f"stale_s_p99={st.get('seconds_behind_p99', 0.0):.4f}")

    # ---- gates: the update stream must be invisible to the service tail
    for name, r in (("base", base), ("updates", upd)):
        if r["steady_traces"]:
            raise AssertionError(
                f"plan cache failed under updates: steady-state retrace "
                f"in the {name} run")
    if not upd["updates"]["applied_batches"]:
        raise AssertionError("update regime applied no delta batches")
    p99_gate = 1.10 * base["p99_ms"]
    if upd["p99_ms"] >= p99_gate:
        raise AssertionError(
            f"updates blew the service tail: p99 {upd['p99_ms']:.2f} ms "
            f">= 1.10 x clean-run p99 ({base['p99_ms']:.2f} ms) at equal "
            f"offered load")
    # per-drain cost must fit in one SLO budget (the inter-batch slack a
    # real deployment hides background maintenance in)
    drain_s = upd["maintenance_s"].get("updates", 0.0)
    drain_calls = upd["maintenance_calls"].get("updates", 0)
    drain_mean_s = drain_s / drain_calls if drain_calls else 0.0
    if drain_mean_s >= slo_ms * 1e-3:
        raise AssertionError(
            f"update drains do not fit the maintenance slack: mean "
            f"{drain_mean_s * 1e3:.2f} ms per drain >= slo {slo_ms:.1f} ms")
    # staleness SLO: the stream must never fall more than ~4 SLO budgets
    # behind (seconds), nor hold more unapplied rows than the stream can
    # emit in that window (+2 batches of draining slack)
    slo_s = slo_ms * 1e-3
    if not st:
        raise AssertionError("update run recorded no staleness samples")
    if st["seconds_behind_p99"] > 4.0 * slo_s:
        raise AssertionError(
            f"staleness SLO failed: seconds_behind_p99 "
            f"{st['seconds_behind_p99']:.4f} > {4.0 * slo_s:.4f}")
    rows_bound = update_qps * 4.0 * slo_s + 2.0 * update_batch
    if st["rows_behind_p99"] > rows_bound:
        raise AssertionError(
            f"staleness SLO failed: rows_behind_p99 "
            f"{st['rows_behind_p99']:.1f} > {rows_bound:.1f}")

    # ---- recovery probe: drain the tail of the stream, force one
    # requant-demote scan (drift_threshold=0 guarantees candidates exist
    # when any traffic-cold hot page drifted; the demote fences itself
    # with a WAL-truncating snapshot), apply what remains, then corrupt
    # the store and restore: snapshot + WAL replay must reproduce the
    # live state bit-for-bit, lookups included
    updater.drain()
    demoted = updater.requant_demote()
    # the demote fenced with a snapshot (truncating the WAL) — land one
    # more logged delta batch past it, so restore must actually *replay*
    # rather than just reload the snapshot
    rng = np.random.default_rng(11)
    tail_rows = rng.integers(0, binding.engine.cfg.total_rows,
                             size=update_batch).astype(np.int64)
    tail_deltas = (1e-3 * rng.standard_normal(
        (update_batch, binding.engine.cfg.dim))).astype(np.float32)
    binding.apply_deltas(tail_rows, tail_deltas)
    if not len(wal):
        raise AssertionError("recovery probe expected a non-empty WAL")
    factory = dummy_request_factory(cfg, storage=storage)
    probe_bucket = Bucket(bat_cfg.batch_sizes[-1], bat_cfg.poolings[-1])
    probe = make_padder(cfg)(
        [factory(i, probe_bucket.pooling)
         for i in range(probe_bucket.batch)], probe_bucket)
    before_scores = np.asarray(jax.device_get(binding.execute(probe)))
    before_leaves = _state_leaves(binding)
    corrupt_store(binding, frac=0.5, seed=5, mode="nan")
    binding.restore()
    after_leaves = _state_leaves(binding)
    after_scores = np.asarray(jax.device_get(binding.execute(probe)))
    leaves_ok = all(a.dtype == b.dtype and (a == b).all()
                    for a, b in zip(before_leaves, after_leaves))
    scores_ok = (before_scores == after_scores).all()
    print(f"[updates   ] recovery demoted={demoted} "
          f"wal_replayed_state_identical={bool(leaves_ok)} "
          f"lookups_identical={bool(scores_ok)}")
    if not leaves_ok:
        raise AssertionError(
            "mid-serving restore + WAL replay did not reproduce the "
            "engine state bit-for-bit")
    if not scores_ok:
        raise AssertionError(
            "mid-serving restore + WAL replay changed lookup results")

    return {
        "offered_qps": 0.3 * capacity_qps,
        "update_qps": update_qps,
        "update_batch": update_batch,
        "p99_gate_ms": p99_gate,
        "drain_mean_ms": drain_mean_s * 1e3,
        "staleness_rows_bound": rows_bound,
        "staleness_seconds_bound": 4.0 * slo_s,
        "demoted_pages_post_run": demoted,
        "recovery_bit_identical": bool(leaves_ok and scores_ok),
        "base": base,
        "updates": upd,
    }


# ---------------------------------------------------------------------------
# Silent-corruption regime (--scrub): checksum scrubbing + page-level repair
# ---------------------------------------------------------------------------


def run_scrub_section(binding, cfg, bat_cfg, runtime_cfg, n_requests,
                      capacity_qps, slo_ms, storage, dedup, pages_per_cycle,
                      ckpt_dir) -> dict:
    """Clean leg vs bit-flip + scrub leg at the same offered load.

    Both legs run with observe/replan disabled: the whole point is that
    the *only* store mutations in the treated leg are the injected flips
    and the scrubber's repairs, so the post-run store must be bitwise
    identical to the never-corrupted truth captured after the clean leg.
    Hot pages are promoted and a WAL-logged delta tail is landed *before*
    the legs, so repairs exercise both tiers and must actually replay
    WAL records past the snapshot rather than just reload it."""
    rt_cfg = dataclasses.replace(runtime_cfg, observe_every=0,
                                 replan_every=0)
    arrival = ArrivalConfig(rate_qps=0.3 * capacity_qps, process="poisson",
                            seed=7)
    load = LoadConfig(n_requests=n_requests, arrival=arrival, slo_ms=slo_ms,
                      seed=7, storage=storage, dedup=dedup)
    reqs = request_stream(cfg, load)

    # ---- arm the store: hot tier, ledger, snapshot (+ledger), WAL tail
    dp = max(1, binding.engine.axes.dp_size(binding.engine.mesh))
    for r in reqs[:16]:
        idx = np.asarray(r.features[binding.idx_key])
        binding.observe({binding.idx_key:
                         np.broadcast_to(idx[None], (dp,) + idx.shape)})
    binding.replan()
    binding.attach_integrity()
    binding.attach_wal(WriteAheadLog(os.path.join(ckpt_dir, "scrub.wal")))
    binding.attach_checkpointer(Checkpointer(ckpt_dir), save_now=True)
    # a logged delta batch past the snapshot: every repair below must
    # replay it (filtered to the repaired page) to reach the live state
    rng = np.random.default_rng(17)
    n_tail = binding.update_capacity
    tail_rows = rng.integers(0, binding.engine.cfg.total_rows,
                             size=n_tail).astype(np.int64)
    tail_deltas = (1e-3 * rng.standard_normal(
        (n_tail, binding.engine.cfg.dim))).astype(np.float32)
    binding.apply_deltas(tail_rows, tail_deltas)
    if not len(binding.wal):
        raise AssertionError("scrub regime expected a non-empty WAL")

    # ---- clean leg, then the never-corrupted truth
    base = run_policy(binding, cfg, DynamicBatcher(bat_cfg), load, rt_cfg)
    factory = dummy_request_factory(cfg, storage=storage)
    probe_bucket = Bucket(bat_cfg.batch_sizes[-1], bat_cfg.poolings[-1])
    probe = make_padder(cfg)(
        [factory(i, probe_bucket.pooling)
         for i in range(probe_bucket.batch)], probe_bucket)
    truth_scores = np.asarray(jax.device_get(binding.execute(probe)))
    truth_leaves = _state_leaves(binding)

    # ---- treated leg: seeded finite flips + scrubbing repairs
    flip_at = (2, 5)
    ctrl = DegradationController(binding=binding,
                                 ladder=LadderConfig(min_dwell_batches=4))
    inner = BindingExecutor(binding)
    fex = FaultInjectingExecutor(
        inner, FaultConfig(seed=13, bit_flip_at=flip_at, bit_flip_rows=2,
                           bit_flip_tier="both"),
        idx_key=binding.idx_key)
    scrub = ScrubController(
        binding, ScrubConfig(pages_per_cycle=pages_per_cycle),
        controller=ctrl)
    runtime = ServingRuntime(inner, DynamicBatcher(bat_cfg),
                             make_padder(cfg), rt_cfg, controller=ctrl,
                             scrubber=scrub)
    # warm through the clean executor (fault schedules index live
    # attempts only), compile the scrub/repair plans, then arm the flips
    runtime.warmup(factory)
    scrub.warmup()
    # the first serve step over device_put-committed state arrays is a
    # fresh executable signature on some backends (observed for the int8
    # cold tier), per bucket: absorb those one-time recompiles outside
    # the timed leg with a self-inverse double flip — same seed XORs the
    # same bits twice, so the store stays bit-identical while the arrays
    # round-trip through the injector's exact write-back path — then one
    # execute per bucket signature
    for _ in range(2):
        flip_store_bits(binding, n_rows=2, seed=29, tier="both")
    padder = make_padder(cfg)
    for bs in bat_cfg.batch_sizes:
        for pl in bat_cfg.poolings:
            wb = Bucket(bs, pl)
            wbatch = padder([factory(i, wb.pooling)
                             for i in range(wb.batch)], wb)
            jax.block_until_ready(binding.execute(wbatch))
    runtime.executor = fex
    binding.reset_plan_stats()
    treated = runtime.run(OpenLoopSource(request_stream(cfg, load)))
    # retrace gate read BEFORE any probe executes (probe batches reuse
    # warmed signatures, but the discipline matches the other sections)
    treated["steady_traces"] = binding.plan_stats()["traces"]
    rep = treated["scrub_run"]

    print(f"[scrub     ] base    p99={base['p99_ms']:8.2f} "
          f"qps={base['qps']:8.1f} steady_traces={base['steady_traces']}")
    print(f"[scrub     ] treated p99={treated['p99_ms']:8.2f} "
          f"qps={treated['qps']:8.1f} "
          f"steady_traces={treated['steady_traces']} "
          f"avail={treated['availability']:.4f} "
          f"cycles={rep['cycles']} sweep={rep['sweep_cycles']} "
          f"flips={fex.bit_flip_events} "
          f"detected={rep['pages_detected']} "
          f"repaired={rep['pages_repaired']} "
          f"mttr_max={rep.get('repair_mttr_max_s', 0.0):.4f}s "
          f"corruption_trips={ctrl.corruption_trips}")

    # ---- gates ----
    for name, r in (("base", base), ("treated", treated)):
        if r["steady_traces"]:
            raise AssertionError(
                f"plan cache failed under scrubbing: steady-state retrace "
                f"in the {name} leg")
    if len(fex.bit_flip_events) != len(flip_at):
        raise AssertionError(
            f"bit_flip schedule under-fired: {fex.bit_flip_events} "
            f"(expected one event per step in {flip_at})")
    flipped = sorted({int(p) for e in fex.bit_flip_events
                      for p in e["pages"]})
    # detection within one full sweep of the flip (+1 cycle slack for the
    # attempt-index/cycle-index offset: the flip lands mid-batch, the
    # audit runs on that batch's maintenance turn at the earliest)
    sweep = rep["sweep_cycles"]
    for e in fex.bit_flip_events:
        for p in e["pages"]:
            cyc = rep["detections"].get(int(p))
            if cyc is None:
                raise AssertionError(
                    f"page {p} flipped at step {e['step']} was never "
                    f"detected ({rep['cycles']} cycles run)")
            if cyc > e["step"] + sweep + 1:
                raise AssertionError(
                    f"detection latency gate failed: page {p} flipped at "
                    f"step {e['step']} detected at cycle {cyc} > one full "
                    f"sweep ({sweep} cycles) later")
    if rep["pages_repaired"] < rep["pages_detected"] or rep["quarantined"]:
        raise AssertionError(
            f"repair gate failed: detected={rep['pages_detected']} "
            f"repaired={rep['pages_repaired']} "
            f"still_quarantined={rep['quarantined']}")
    if not ctrl.corruption_trips:
        raise AssertionError(
            "detections never reached the degradation controller "
            "(on_corruption)")
    if treated["availability"] < 0.99:
        raise AssertionError(
            f"availability gate failed under scrubbing: "
            f"{treated['availability']:.4f} < 0.99")
    p99_gate = 1.10 * base["p99_ms"]
    if treated["p99_ms"] >= p99_gate:
        raise AssertionError(
            f"scrubbing blew the service tail: p99 "
            f"{treated['p99_ms']:.2f} ms >= 1.10 x clean-leg p99 "
            f"({base['p99_ms']:.2f} ms) at equal offered load")
    # per-page repair MTTR: snapshot slice + filtered WAL replay over warm
    # plans — bounded loosely in SLO multiples (floored for CPU hosts
    # where jit dispatch dominates), same convention as the mesh MTTR
    mttr_bound = max(100.0 * slo_ms * 1e-3, 60.0)
    for r in rep["repairs"]:
        if not (0.0 < r["mttr_s"] < mttr_bound):
            raise AssertionError(
                f"repair MTTR unbounded: page {r['page']} took "
                f"{r['mttr_s']:.3f} s >= {mttr_bound:.1f} s")
    if "scrub" not in treated["maintenance_s"]:
        raise AssertionError(
            "scrub wall time missing from maintenance accounting")

    # ---- bitwise truth: repaired store == never-corrupted store
    after_leaves = _state_leaves(binding)
    after_scores = np.asarray(jax.device_get(binding.execute(probe)))
    leaves_ok = all(a.dtype == b.dtype and (a == b).all()
                    for a, b in zip(truth_leaves, after_leaves))
    scores_ok = (truth_scores == after_scores).all()
    print(f"[scrub     ] repaired_state_identical={bool(leaves_ok)} "
          f"lookups_identical={bool(scores_ok)}")
    if not leaves_ok:
        raise AssertionError(
            "scrub repairs did not reproduce the never-corrupted store "
            "bit-for-bit")
    if not scores_ok:
        raise AssertionError("scrub repairs changed lookup results")

    treated.pop("latency_hist", None)
    treated.pop("dedup_factors", None)
    base.pop("latency_hist", None)
    base.pop("dedup_factors", None)
    return {
        "offered_qps": 0.3 * capacity_qps,
        "pages_per_cycle": pages_per_cycle,
        "sweep_cycles": sweep,
        "flip_at": list(flip_at),
        "flip_events": list(fex.bit_flip_events),
        "flipped_pages": flipped,
        "p99_gate_ms": p99_gate,
        "mttr_bound_s": mttr_bound,
        "corruption_trips": ctrl.corruption_trips,
        "repaired_bit_identical": bool(leaves_ok and scores_ok),
        "base": base,
        "treated": treated,
    }


def run_front_end_leg(cfg, args, bat_cfg, runtime_cfg, offered_qps, slo_ms,
                      max_wait_ms, n_requests, batch_sizes, poolings) -> dict:
    """Fused front end under tensor parallelism, end to end.

    Serves the same offered-load stream through two bindings on a (4, 2)
    dp x tp mesh — ``front_end='fused'`` (which the engine resolves
    ``fused_tp``: partial-pool per shard, psum the (B, F, d) cold tile,
    resume) and the ``front_end='split'`` control.  Hard gates: the fused
    binding's plans actually resolved ``fused_tp`` (a silent fallback to
    split would time the wrong datapath), zero steady-state retraces in
    both runs, and probe-batch scores bit-equal between the bindings."""
    mesh = make_mesh((4, 2), ("data", "model"))
    leg = {"mesh": {"data": 4, "model": 2}}
    with mesh:
        bindings = {
            fe: bind_model(cfg, mesh, mode=args.mode, impl=args.impl,
                           block_l=args.block_l, storage=args.storage,
                           dedup=args.dedup, front_end=fe)
            for fe in ("split", "fused")}
        # bit-equality probe: identical padded batches through both steps
        factory = dummy_request_factory(cfg, storage=args.storage)
        padder = make_padder(cfg)
        for bucket in (Bucket(batch_sizes[0], poolings[0]),
                       Bucket(batch_sizes[-1], poolings[-1])):
            batch = padder([factory(i, bucket.pooling)
                            for i in range(bucket.batch)], bucket)
            a = np.asarray(bindings["split"].execute(batch))
            b = np.asarray(bindings["fused"].execute(batch))
            if not np.array_equal(a, b):
                raise AssertionError(
                    f"fused_tp scores diverge from the split control on "
                    f"bucket {bucket}")
        for fe, binding in bindings.items():
            load = LoadConfig(
                n_requests=n_requests,
                arrival=ArrivalConfig(rate_qps=offered_qps,
                                      process="poisson", seed=11),
                slo_ms=slo_ms, poolings=(), seed=11,
                storage=args.storage, dedup=args.dedup, front_end=fe)
            dyn_cfg = dataclasses.replace(bat_cfg, max_wait_ms=max_wait_ms)
            r = run_policy(binding, cfg, DynamicBatcher(dyn_cfg), load,
                           runtime_cfg)
            if r["steady_traces"]:
                raise AssertionError(
                    f"plan cache failed: steady-state retrace in the "
                    f"front-end leg (front_end={fe})")
            recs = [rec for rec in
                    binding.engine.plan_stats().get("front_end", {}).values()
                    if rec["requested"] == fe]
            want = "fused_tp" if fe == "fused" else "split"
            if fe == "fused" and (
                    not recs
                    or any(rec["resolved"] != want for rec in recs)):
                # the split control composes lookup + interaction as
                # separate ops (no lookup_interact plan, no record); the
                # fused binding must have resolved every plan fused_tp
                raise AssertionError(
                    f"front-end leg resolution: requested={fe} expected "
                    f"{want!r}, got {[rec['resolved'] for rec in recs]}")
            r.pop("latency_hist", None)
            r.pop("dedup_factors", None)
            r["resolved"] = want
            leg[fe] = r
            print(f"[front-end] {fe:5s} -> {want:8s} "
                  f"qps={r['qps']:8.1f} p50={r['p50_ms']:7.2f} "
                  f"p99={r['p99_ms']:8.2f} "
                  f"steady_traces={r['steady_traces']}")
    leg["scores_bit_equal"] = True
    return leg


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_serve.json")
    ap.add_argument("--arch", default="rmc1")
    ap.add_argument("--requests", type=int, default=800)
    ap.add_argument("--load-frac", type=float, default=0.5,
                    help="sustained-regime offered load as a fraction of "
                         "measured capacity")
    ap.add_argument("--slo-ms", type=float, default=0.0,
                    help="SLO budget; 0 = auto (5x largest-bucket service)")
    ap.add_argument("--mode", default="pifs",
                    choices=["pifs", "pond", "beacon"])
    ap.add_argument("--impl", default="jnp", choices=["jnp", "pallas"])
    ap.add_argument("--block-l", type=int, default=8)
    ap.add_argument("--storage", default="fp32", choices=["fp32", "int8"],
                    help="engine cold-tier storage dtype (reported in the "
                         "run header so BENCH_serve.json entries stay "
                         "comparable across storage modes)")
    ap.add_argument("--dedup", default="off", choices=["off", "auto", "on"],
                    help="gather-once duplicate coalescing in the SLS "
                         "datapath (bit-exact; reported per bucket so "
                         "serving wins are attributable in bytes)")
    ap.add_argument("--smoke", action="store_true",
                    help="small CI configuration (fewer requests/buckets)")
    ap.add_argument("--faults", action="store_true",
                    help="run the fault-injection regimes (straggler, "
                         "transient, corrupt data/store, forced brown-out) "
                         "instead of the policy-comparison regimes")
    ap.add_argument("--updates", action="store_true",
                    help="run the streaming-embedding-update regime (clean "
                         "vs update-stream runs at equal offered load, "
                         "staleness SLOs, WAL-replay recovery probe) "
                         "instead of the policy-comparison regimes")
    ap.add_argument("--update-batch", type=int, default=32,
                    help="rows per trainer-emitted delta batch (--updates)")
    ap.add_argument("--mesh-faults", action="store_true",
                    help="run the degraded-mesh regime (kill a tp shard "
                         "mid-serving, gate on elastic re-mesh recovery: "
                         "availability, bounded MTTR, zero retraces, "
                         "bit-exact post-recovery scores) instead of the "
                         "policy-comparison regimes")
    ap.add_argument("--prefer-tp", type=int, default=2,
                    help="survivor-mesh tp preference for the elastic "
                         "re-mesh policy (--mesh-faults; "
                         "repro.runtime.elastic.scale_plan)")
    ap.add_argument("--scrub", action="store_true",
                    help="run the silent-corruption regime (clean vs "
                         "bit-flip + checksum-scrub legs at equal offered "
                         "load, page-granular snapshot/WAL repair, bitwise "
                         "post-repair equality) instead of the "
                         "policy-comparison regimes")
    ap.add_argument("--scrub-pages-per-cycle", type=int, default=8,
                    help="pages audited per maintenance turn (--scrub; "
                         "full sweep every ceil(num_pages / K) cycles)")
    args = ap.parse_args()
    compile_cache.enable()
    if sum((args.faults, args.updates, args.mesh_faults, args.scrub)) > 1:
        ap.error("--faults, --updates, --mesh-faults, and --scrub are "
                 "mutually exclusive sections")

    cfg = reduced(get_config(args.arch))

    if args.mesh_faults:
        # the section builds its own per-config meshes/bindings (the
        # whole point is that the mesh changes mid-run); --storage/--dedup
        # are superseded by the per-config matrix
        n_requests = 96 if args.smoke else 192
        print(f"serve bench: arch={args.arch} mode={args.mode} "
              f"impl={args.impl} section=mesh_faults "
              f"prefer_tp={args.prefer_tp}")
        runs = run_mesh_fault_section(cfg, args, n_requests, args.prefer_tp)
        out = {
            "bench": "serve",
            "schema": 7,
            "section": "mesh_faults",
            "backend": jax.default_backend(),
            "interpret_mode": jax.default_backend() != "tpu",
            "jax_version": jax.__version__,
            "platform": platform.platform(),
            "mesh": {"data": 2, "model": 4},
            "arch": args.arch, "mode": args.mode, "impl": args.impl,
            "block_l": args.block_l, "prefer_tp": args.prefer_tp,
            "n_requests": n_requests,
            "mesh_fault_runs": runs,
        }
        with open(args.out, "w") as f:
            json.dump(out, f, indent=2)
        print(f"\nwrote {args.out}")
        return
    mesh = make_mesh((2, 4), ("data", "model"))

    # Regimes: the tail-latency gate applies where the policies differ
    # *structurally* (sub-saturation load, where fixed-batch fill time
    # dominates the tail); at sustained load both policies serve full
    # buckets and the comparison is noise — there we gate throughput.
    if args.smoke:
        batch_sizes, poolings = (8, 16), (cfg.pooling,)
        n_requests = 160
        regimes = [
            dict(label="trough", process="poisson", frac=0.12,
                 gate_p99=True, gate_qps=False),
            # 0.4, not 0.5: shared CI runners execute slower than the
            # calibration pass, and the 0.8 sustain gate needs headroom
            dict(label="sustained", process="poisson", frac=0.4,
                 gate_p99=False, gate_qps=True),
        ]
    else:
        batch_sizes = (8, 16, 32)
        poolings = tuple(sorted({max(1, cfg.pooling // 2), cfg.pooling}))
        n_requests = args.requests
        regimes = [
            dict(label="trough", process="poisson", frac=0.12,
                 gate_p99=True, gate_qps=False),
            dict(label="sustained", process="poisson", frac=args.load_frac,
                 gate_p99=False, gate_qps=True),
            dict(label="bursty", process="bursty", frac=0.4,
                 gate_p99=False, gate_qps=False),
        ]

    print(f"serve bench: arch={args.arch} mode={args.mode} impl={args.impl} "
          f"storage={args.storage} (cold tier "
          f"{'int8+page-scales' if args.storage == 'int8' else 'fp32'}) "
          f"dedup={args.dedup}")
    binding = bind_model(cfg, mesh, mode=args.mode, impl=args.impl,
                         block_l=args.block_l, storage=args.storage,
                         dedup=args.dedup,
                         # fault runs need the ladder's serve-step variants
                         # and the NaN/Inf score scrub armed
                         degraded_variants=args.faults,
                         scrub_scores=args.faults)
    bat_cfg = BatcherConfig(batch_sizes=batch_sizes, poolings=poolings)
    fixed_bucket = Bucket(batch_sizes[-1], poolings[-1])
    runtime_cfg = RuntimeConfig(observe_every=4, replan_every=32)

    with mesh:
        # calibrate: warm all buckets once, read the largest bucket's
        # steady service time off the service model
        calib = ServingRuntime(BindingExecutor(binding),
                               DynamicBatcher(bat_cfg), make_padder(cfg),
                               runtime_cfg)
        warm = calib.warmup(dummy_request_factory(cfg, storage=args.storage))
        # calibrate the largest bucket's service time as a median over
        # several steady executions (a single sample is too noisy on
        # shared CPU hosts to anchor offered load on)
        factory = dummy_request_factory(cfg, storage=args.storage)
        cal_batch = make_padder(cfg)(
            [factory(i, fixed_bucket.pooling)
             for i in range(fixed_bucket.batch)], fixed_bucket)
        ex = BindingExecutor(binding)
        svc_max = float(np.median(
            [ex.run_batch(fixed_bucket, cal_batch) for _ in range(5)]))
        calib.service_model.update(fixed_bucket, svc_max)
        capacity_qps = fixed_bucket.batch / svc_max
        # auto SLO at 5 service times: both the dynamic deadline-bound tail
        # (~slo) and the fixed-batch fill tail (~svc/frac) scale with the
        # measured service time, so the trough-regime comparison is robust
        # to calibration error
        slo_ms = args.slo_ms or 5.0 * svc_max * 1e3
        # coalescing-wait cap: ~1.5 service times (waiting longer than that
        # buys occupancy the latency budget can't afford), never more than
        # half the SLO budget
        max_wait_ms = min(slo_ms / 2, max(2.0, 1.5 * svc_max * 1e3))
        print(f"capacity ~{capacity_qps:.0f} qps "
              f"(service({fixed_bucket.batch}x{fixed_bucket.pooling}) = "
              f"{svc_max * 1e3:.2f} ms), slo {slo_ms:.1f} ms, "
              f"coalesce cap {max_wait_ms:.1f} ms")

        if args.faults:
            import tempfile
            bat_cfg_f = dataclasses.replace(bat_cfg, max_wait_ms=max_wait_ms)
            _warm_all_rungs(binding, cfg, bat_cfg_f, runtime_cfg,
                            calib.service_model, args.storage)
            runs = run_fault_section(
                binding, cfg, bat_cfg_f, runtime_cfg, calib.service_model,
                n_requests, capacity_qps, slo_ms, args.storage, args.dedup,
                tempfile.mkdtemp(prefix="serve_bench_ckpt_"))
            out = {
                "bench": "serve",
                "schema": 7,
                "section": "faults",
                "backend": jax.default_backend(),
                "interpret_mode": jax.default_backend() != "tpu",
                "jax_version": jax.__version__,
                "platform": platform.platform(),
                "mesh": {"data": 2, "model": 4},
                "arch": args.arch, "mode": args.mode, "impl": args.impl,
                "block_l": args.block_l, "storage": args.storage,
                "dedup": args.dedup,
                "capacity_qps": capacity_qps, "slo_ms": slo_ms,
                "n_requests": n_requests,
                "fault_runs": {k: {kk: vv for kk, vv in v.items()
                                   if kk != "latency_hist"}
                               for k, v in runs.items()},
            }
            with open(args.out, "w") as f:
                json.dump(out, f, indent=2)
            print(f"\nwrote {args.out}")
            return

        if args.updates:
            import tempfile
            bat_cfg_u = dataclasses.replace(bat_cfg, max_wait_ms=max_wait_ms)
            section = run_update_section(
                binding, cfg, bat_cfg_u, runtime_cfg, n_requests,
                capacity_qps, slo_ms, svc_max, args.storage, args.dedup,
                args.update_batch,
                tempfile.mkdtemp(prefix="serve_bench_upd_"))
            for leg in ("base", "updates"):
                section[leg] = {k: v for k, v in section[leg].items()
                                if k != "latency_hist"}
            out = {
                "bench": "serve",
                "schema": 7,
                "section": "updates",
                "backend": jax.default_backend(),
                "interpret_mode": jax.default_backend() != "tpu",
                "jax_version": jax.__version__,
                "platform": platform.platform(),
                "mesh": {"data": 2, "model": 4},
                "arch": args.arch, "mode": args.mode, "impl": args.impl,
                "block_l": args.block_l, "storage": args.storage,
                "dedup": args.dedup,
                "capacity_qps": capacity_qps, "slo_ms": slo_ms,
                "n_requests": n_requests,
                "update_run": section,
            }
            with open(args.out, "w") as f:
                json.dump(out, f, indent=2)
            print(f"\nwrote {args.out}")
            return

        if args.scrub:
            import tempfile
            bat_cfg_s = dataclasses.replace(bat_cfg, max_wait_ms=max_wait_ms)
            section = run_scrub_section(
                binding, cfg, bat_cfg_s, runtime_cfg, n_requests,
                capacity_qps, slo_ms, args.storage, args.dedup,
                args.scrub_pages_per_cycle,
                tempfile.mkdtemp(prefix="serve_bench_scrub_"))
            out = {
                "bench": "serve",
                "schema": 7,
                "section": "scrub",
                "backend": jax.default_backend(),
                "interpret_mode": jax.default_backend() != "tpu",
                "jax_version": jax.__version__,
                "platform": platform.platform(),
                "mesh": {"data": 2, "model": 4},
                "arch": args.arch, "mode": args.mode, "impl": args.impl,
                "block_l": args.block_l, "storage": args.storage,
                "dedup": args.dedup,
                "capacity_qps": capacity_qps, "slo_ms": slo_ms,
                "n_requests": n_requests,
                "scrub_run": section,
            }
            with open(args.out, "w") as f:
                json.dump(out, f, indent=2)
            print(f"\nwrote {args.out}")
            return

        runs = {}
        for regime in regimes:
            offered_qps = regime["frac"] * capacity_qps
            arrival = ArrivalConfig(
                rate_qps=offered_qps, process=regime["process"], seed=7,
                burst_factor=4.0, mean_burst_s=0.05)
            load = LoadConfig(
                n_requests=n_requests, arrival=arrival, slo_ms=slo_ms,
                poolings=poolings if len(poolings) > 1 else (),
                seed=7, storage=args.storage, dedup=args.dedup)
            dyn_cfg = dataclasses.replace(bat_cfg, max_wait_ms=max_wait_ms)
            dyn = run_policy(binding, cfg, DynamicBatcher(dyn_cfg), load,
                             runtime_cfg)
            fix = run_policy(binding, cfg,
                             FixedBatcher(fixed_bucket.batch,
                                          fixed_bucket.pooling),
                             load, runtime_cfg)
            label = regime["label"]
            for name, r in (("dynamic", dyn), ("fixed", fix)):
                print(f"[{label:9s}] {name:8s} "
                      f"offered={offered_qps:7.1f} qps={r['qps']:8.1f} "
                      f"p50={r['p50_ms']:7.2f} p99={r['p99_ms']:8.2f} "
                      f"p99.9={r['p99.9_ms']:8.2f} "
                      f"slo_viol={r['slo_violation_rate']:.3f} "
                      f"occ={r['batch_occupancy_mean']:.2f} "
                      f"steady_traces={r['steady_traces']}")
                for bucket, rec in r.get("dedup_factors", {}).items():
                    print(f"            dedup[{bucket}] "
                          f"factor={rec['factor']:.2f} "
                          f"({rec['entries']} entries -> "
                          f"{rec['unique_rows']} unique)")
                if r["steady_traces"]:
                    raise AssertionError(
                        f"plan cache failed: steady-state retrace in "
                        f"{name}/{label} serving run")
            if regime["gate_p99"] and dyn["p99_ms"] >= fix["p99_ms"]:
                raise AssertionError(
                    f"dynamic batcher p99 ({dyn['p99_ms']:.2f} ms) not "
                    f"below fixed-batch p99 ({fix['p99_ms']:.2f} ms) in "
                    f"the {label} regime at {offered_qps:.0f} qps")
            if regime["gate_qps"] and dyn["qps"] < 0.8 * offered_qps:
                raise AssertionError(
                    f"dynamic batcher did not sustain offered load in "
                    f"{label}: {dyn['qps']:.1f} qps vs {offered_qps:.1f}")
            runs[label] = {"process": regime["process"],
                           "offered_qps": offered_qps,
                           "gate_p99": regime["gate_p99"],
                           "gate_qps": regime["gate_qps"],
                           "dynamic": dyn, "fixed": fix}

    # fused front end under tp (DLRM only: Rec configs have no
    # dot-interaction stage, so the knob is a no-op for them)
    front_end_leg = None
    if hasattr(cfg, "n_tables"):
        front_end_leg = run_front_end_leg(
            cfg, args, bat_cfg, runtime_cfg, 0.3 * capacity_qps, slo_ms,
            max_wait_ms, min(n_requests, 120), batch_sizes, poolings)

    out = {
        "bench": "serve",
        "schema": 7,
        "backend": jax.default_backend(),
        "interpret_mode": jax.default_backend() != "tpu",
        "jax_version": jax.__version__,
        "platform": platform.platform(),
        "mesh": {"data": 2, "model": 4},
        "arch": args.arch,
        "mode": args.mode,
        "impl": args.impl,
        "block_l": args.block_l,
        "storage": args.storage,
        "dedup": args.dedup,
        "batch_sizes": list(batch_sizes),
        "poolings": list(poolings),
        "warmup_service_s": warm,
        "capacity_qps": capacity_qps,
        "slo_ms": slo_ms,
        "max_wait_ms": max_wait_ms,
        "n_requests": n_requests,
        "runs": runs,
        "front_end_leg": front_end_leg,
    }
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2)
    print(f"\nwrote {args.out}")


if __name__ == "__main__":
    main()
