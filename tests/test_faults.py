"""Chaos-hardened serving: fault injection, retry/breaker/brown-out
degradation, guardrails (OOB validation, score scrub), and mid-serving
checkpoint recovery."""
import numpy as np
import pytest

from repro.runtime.fault_tolerance import FailureInjector
from repro.serving import (ArrivalConfig, BreakerConfig, ClosedLoopSource,
                           DegradationController, FaultConfig,
                           FaultInjectingExecutor, FixedBatcher,
                           FixedServiceModel, LadderConfig, LoadConfig,
                           OpenLoopSource, Request, RetryPolicy,
                           RuntimeConfig, ServingRuntime, SimulatedExecutor,
                           TransientServingFailure, corrupt_store)
from repro.serving.degradation import RUNGS, CircuitBreaker


# ---------------------------------------------------------------------------
# Injection vocabulary
# ---------------------------------------------------------------------------


def test_failure_injector_fires_scheduled_once_and_chaos_reproducibly():
    inj = FailureInjector(fail_at_steps=(2, 5))
    hits = [s for s in range(10) if inj.fires(s)]
    assert hits == [2, 5]
    assert not inj.fires(2)              # once each: retries must not loop
    a = FailureInjector(fail_prob=0.3, seed=7)
    b = FailureInjector(fail_prob=0.3, seed=7)
    pat_a = [a.fires(s) for s in range(200)]
    pat_b = [b.fires(s) for s in range(200)]
    assert pat_a == pat_b and any(pat_a) and not all(pat_a)
    assert not FailureInjector().armed
    assert FailureInjector(fail_prob=0.1).armed


def test_fault_executor_straggler_multiplies_and_transient_raises():
    model = FixedServiceModel(base_s=1e-3, per_row_s=0.0)
    fex = FaultInjectingExecutor(
        SimulatedExecutor(model),
        FaultConfig(straggler_at=(1,), straggler_factor=8.0,
                    transient_at=(3,), stall_at=(0,), stall_s=0.5))
    from repro.serving import Bucket
    bucket = Bucket(4, 4)
    base = fex.run_batch(bucket, {})                 # step 0: clean
    assert fex.run_batch(bucket, {}) == pytest.approx(8.0 * base)
    fex.run_batch(bucket, {})                        # step 2: clean
    with pytest.raises(TransientServingFailure):
        fex.run_batch(bucket, {})                    # step 3
    assert fex.observe({}) == pytest.approx(0.5)     # injected stall
    assert fex.report()["straggler"] == 1
    assert fex.report()["transient"] == 1


def test_fault_executor_corruption_copies_batch():
    """A retry of a corrupted micro-batch must see the ORIGINAL data (the
    re-read from the healthy feature store), so corruption may never
    mutate the caller's arrays in place."""
    model = FixedServiceModel(base_s=1e-3, per_row_s=0.0)
    fex = FaultInjectingExecutor(
        SimulatedExecutor(model),
        FaultConfig(corrupt_oob_at=(0,), corrupt_nan_at=(0,)))
    from repro.serving import Bucket
    idx = np.zeros((4, 2, 3), np.int32)
    dense = np.ones((4, 8), np.float32)
    fex.run_batch(Bucket(4, 4), {"indices": idx, "dense": dense})
    assert (idx == 0).all() and np.isfinite(dense).all()
    assert fex.corrupted_batches == [0]


def test_transient_burst_persists_across_attempts():
    model = FixedServiceModel(base_s=1e-3, per_row_s=0.0)
    fex = FaultInjectingExecutor(
        SimulatedExecutor(model),
        FaultConfig(transient_at=(0,), transient_runs=3))
    from repro.serving import Bucket
    for _ in range(3):
        with pytest.raises(TransientServingFailure):
            fex.run_batch(Bucket(4, 4), {})
    fex.run_batch(Bucket(4, 4), {})      # burst spent: healthy again
    assert fex.report()["transient"] == 3


# ---------------------------------------------------------------------------
# Circuit breaker + ladder (virtual clock, no runtime)
# ---------------------------------------------------------------------------


def test_circuit_breaker_trips_cools_down_and_probes():
    br = CircuitBreaker(BreakerConfig(trip_after=3, cooldown_s=1.0))
    for _ in range(3):
        assert br.allow(0.0)
        br.record_failure(0.0)
    assert br.state == "open" and br.trips == 1
    assert not br.allow(0.5)                   # cooling down: fail fast
    assert br.allow(1.0)                       # half-open probe admitted
    br.record_failure(1.0)                     # probe fails -> reopen
    assert br.state == "open" and not br.allow(1.5)
    assert br.allow(2.0)
    br.record_success()                        # probe succeeds -> closed
    assert br.state == "closed" and br.allow(2.1)


def test_ladder_steps_down_under_pressure_and_recovers_with_hysteresis():
    ctrl = DegradationController(
        ladder=LadderConfig(alpha=0.5, step_down_at=0.6, step_up_at=0.2,
                            min_dwell_batches=2))
    t = 0.0
    for _ in range(4):
        ctrl.on_batch_done(t, ok=False)
        t += 0.01
    assert ctrl.rung >= 1                      # stepped down under failures
    down = len(ctrl.transitions)
    rung_peak = ctrl.rung
    for _ in range(20):
        ctrl.on_batch_done(t, ok=True)
        t += 0.01
    assert ctrl.rung == 0                      # recovered all the way up
    ups = len(ctrl.transitions) - down
    assert ups == rung_peak                    # one recorded move per rung
    # hysteresis: dwell gate means moves never alternate on single batches
    times = [tr["t"] for tr in ctrl.transitions]
    assert all(b >= a for a, b in zip(times, times[1:]))
    rep = ctrl.report()
    assert rep["rung"] == "full" and rep["n_transitions"] == len(times)


def test_ladder_shed_rung_tightens_and_restores_admission():
    ctrl = DegradationController(
        ladder=LadderConfig(alpha=1.0, step_down_at=0.5, step_up_at=0.1,
                            min_dwell_batches=1, shed_capacity=2))
    from repro.serving import AdmissionQueue
    q = AdmissionQueue(100)
    ctrl.bind_queue(q)
    t = 0.0
    while ctrl.rung_label != "shed":
        ctrl.on_batch_done(t, ok=False)
        t += 0.01
    assert q.capacity == 2
    while ctrl.rung_label != "full":
        ctrl.on_batch_done(t, ok=True)
        t += 0.01
    assert q.capacity == 100
    assert [tr["from"] for tr in ctrl.transitions[:4]] == list(RUNGS[:4])


# ---------------------------------------------------------------------------
# Runtime integration (simulated executor): retry, fail-once accounting,
# closed-loop release, shed-everything overload
# ---------------------------------------------------------------------------


def _sim_runtime(fault_cfg, retry=None, breaker=None, queue_capacity=4096):
    model = FixedServiceModel(base_s=4e-3, per_row_s=0.0)
    ctrl = DegradationController(retry=retry, breaker=breaker)
    fex = FaultInjectingExecutor(SimulatedExecutor(model), fault_cfg)
    rt = ServingRuntime(
        fex, FixedBatcher(batch=4, pooling=4),
        padder=lambda reqs, bucket: {"n": len(reqs)},
        cfg=RuntimeConfig(observe_every=0, replan_every=0,
                          queue_capacity=queue_capacity),
        service_model=model, controller=ctrl)
    return rt, ctrl, fex


def _reqs(n, rate=1000.0, slo=0.05):
    times = np.arange(n) / rate
    return [Request(rid=i, arrival_s=float(times[i]),
                    deadline_s=float(times[i]) + slo, features={}, pooling=4)
            for i in range(n)]


def test_retry_recovers_transient_and_counts_retries():
    rt, ctrl, fex = _sim_runtime(FaultConfig(transient_at=(0,)))
    s = rt.run(OpenLoopSource(_reqs(8)))
    assert s["served"] == 8 and s["failed"] == 0
    assert s["retries"] == 1 and s["availability"] == 1.0
    assert s["failed_batches"] == 0


def test_retry_exhausted_requests_fail_once_in_slo_metrics():
    # a 3-attempt burst on the first micro-batch exhausts the default
    # 3-attempt retry budget: that batch's requests fail exactly once
    rt, ctrl, fex = _sim_runtime(FaultConfig(transient_at=(0,),
                                             transient_runs=3))
    s = rt.run(OpenLoopSource(_reqs(8)))
    assert s["failed"] == 4 and s["served"] == 4
    assert s["failed_batches"] == 1
    # failed requests are SLO violations exactly once: 4 failed + 0 of the
    # served 4 violated over 8 completed
    assert s["slo_violation_rate"] == pytest.approx(4 / 8)
    assert s["availability"] == pytest.approx(0.5)
    assert s["retries"] == 2            # two scheduled re-attempts, both lost
    assert s["goodput_qps"] <= s["qps"]


def test_breaker_failfast_then_recovery_serves_tail():
    # 8 consecutive failing attempts trip the 4-failure breaker mid-burst;
    # requests arriving while it is open fail fast (never reach the
    # executor), and the stream's tail is served after the cooldown
    rt, ctrl, fex = _sim_runtime(
        FaultConfig(transient_at=(0,), transient_runs=8),
        breaker=BreakerConfig(trip_after=4, cooldown_s=0.01))
    s = rt.run(OpenLoopSource(_reqs(40, rate=400.0)))
    assert s["failed_fast"] > 0
    assert ctrl.breaker.trips >= 1
    assert s["served"] > 0                       # recovered: tail healthy
    assert s["served"] + s["failed"] == 40       # nothing lost or doubled
    deg = s["degradation"]
    assert deg["breaker_trips"] == ctrl.breaker.trips


def test_closed_loop_users_released_on_failed_and_dropped_requests():
    # the first batch fails after exhausting its retry budget: if failure
    # did not release the issuing users the closed loop would starve and
    # the run would end short of n_requests
    rt, ctrl, fex = _sim_runtime(FaultConfig(transient_at=(0,),
                                             transient_runs=3))
    factory = lambda rid, user, t: Request(   # noqa: E731
        rid=rid, arrival_s=t, deadline_s=t + 0.05, features={}, pooling=4)
    src = ClosedLoopSource(n_users=4, n_requests=24, factory=factory,
                           think_time_s=0.001)
    s = rt.run(src)
    assert s["served"] + s["failed"] == 24
    assert s["failed"] > 0 and s["served"] > 0


def test_shed_everything_overload_summary_stays_finite():
    """All-shed regime (satellite: empty-window metrics guards): capacity 4
    with a same-instant burst far beyond it — most requests drop, and every
    summary rate must come back finite, never divide-by-zero."""
    model = FixedServiceModel(base_s=4e-3, per_row_s=0.0)
    rt = ServingRuntime(
        SimulatedExecutor(model), FixedBatcher(batch=4, pooling=4),
        padder=lambda reqs, bucket: {"n": len(reqs)},
        cfg=RuntimeConfig(observe_every=0, replan_every=0, queue_capacity=4),
        service_model=model)
    reqs = [Request(rid=i, arrival_s=0.0, deadline_s=0.05, features={},
                    pooling=4) for i in range(64)]
    s = rt.run(OpenLoopSource(reqs))
    assert s["dropped"] > 0
    assert s["served"] + s["dropped"] == 64
    for k in ("qps", "goodput_qps", "availability", "slo_violation_rate"):
        assert np.isfinite(s[k]), k


def test_metrics_guards_empty_window_and_nonfinite_samples():
    from repro.serving import LatencyHistogram, ServingMetrics
    m = ServingMetrics()
    s = m.summary()                      # zero requests, zero duration
    assert s["qps"] == 0.0 and s["goodput_qps"] == 0.0
    assert s["availability"] == 1.0 and s["slo_violation_rate"] == 0.0
    h = LatencyHistogram()
    h.record(float("nan"))
    h.record(float("inf"))
    h.record(1e-3)
    assert len(h) == 1 and h.nonfinite == 2
    assert np.isfinite(h.percentiles_ms()["p99_ms"])
    # a failed request that never started must not poison percentiles
    m2 = ServingMetrics()
    r = Request(rid=0, arrival_s=0.0, deadline_s=0.1, features={})
    m2.record_failure(r)
    assert m2.failed == 1 and len(m2.latency) == 0
    s2 = m2.summary()
    assert s2["availability"] == 0.0 and s2["slo_violation_rate"] == 1.0


def test_request_failed_is_never_slo_ok():
    r = Request(rid=0, arrival_s=0.0, deadline_s=10.0, features={})
    r.start_s = r.finish_s = 0.1
    assert r.slo_ok
    r.failed = True
    assert not r.slo_ok


def test_admission_queue_set_capacity_never_evicts():
    from repro.serving import AdmissionQueue
    q = AdmissionQueue(4)
    for i in range(4):
        assert q.offer(_reqs(4)[i])
    q.set_capacity(2)                    # shrink below current depth
    assert len(q) == 4                   # admitted requests survive
    assert not q.offer(_reqs(5)[4])      # but new offers shed
    q.set_capacity(8)
    assert q.offer(_reqs(6)[5])
    with pytest.raises(ValueError):
        q.set_capacity(0)


# ---------------------------------------------------------------------------
# Engine/binding guardrails + degraded rungs on a real mesh
# ---------------------------------------------------------------------------


def _dlrm_batch(cfg, B=8, seed=0):
    rng = np.random.default_rng(seed)
    return {"dense": rng.normal(size=(B, cfg.n_dense)).astype(np.float32),
            "indices": rng.integers(0, cfg.emb_num,
                                    (B, cfg.n_tables, cfg.pooling)
                                    ).astype(np.int32)}


@pytest.fixture(scope="module")
def rmc1():
    from repro.configs import get_config, reduced
    return reduced(get_config("rmc1"))


def test_validate_ids_raises_host_side_on_oob(mesh, rmc1):
    from repro.serving import bind_model
    binding = bind_model(rmc1, mesh, validate_ids=True)
    batch = _dlrm_batch(rmc1)
    with mesh:
        binding.execute(batch)                         # valid ids: fine
        bad = dict(batch)
        bad["indices"] = batch["indices"].copy()
        bad["indices"][0, 0, 0] = 2 ** 31 - 2
        with pytest.raises(ValueError, match="out-of-range"):
            binding.execute(bad)


def test_degraded_rungs_bitexact_and_hot_only_finite(mesh, rmc1):
    """The ladder's bit-exactness contract, test-pinned: split_fe and
    no_dedup (and shed's datapath twin hot_only aside) must produce
    bitwise-identical scores to full; hot_only/shed stay finite and
    well-shaped (scores may change — cold rows are zero-filled)."""
    from repro.serving import bind_model
    binding = bind_model(rmc1, mesh, dedup="on", front_end="fused",
                         degraded_variants=True)
    assert set(binding.modes()) == set(RUNGS)
    batch = _dlrm_batch(rmc1)
    out = {}
    with mesh:
        for rung in RUNGS:
            binding.set_mode(rung)
            out[rung] = np.asarray(binding.execute(batch))
    binding.set_mode("full")
    np.testing.assert_array_equal(out["full"], out["split_fe"])
    np.testing.assert_array_equal(out["full"], out["no_dedup"])
    np.testing.assert_array_equal(out["hot_only"], out["shed"])
    for rung in RUNGS:
        assert out[rung].shape == out["full"].shape
        assert np.isfinite(out[rung]).all()


def test_set_mode_unknown_rung_falls_back_to_full(mesh, rmc1):
    from repro.serving import bind_model
    binding = bind_model(rmc1, mesh)          # no variants built
    binding.set_mode("hot_only")
    assert binding.active == "full"


def test_scrub_and_checkpoint_restore_heal_corrupted_store(mesh, rmc1,
                                                           tmp_path):
    """Corrupted hot tier -> NaN scores -> scrub zero-fills with poisoned
    accounting -> restore() reloads the checkpoint -> scores bit-equal the
    healthy baseline, all without retracing the serve step."""
    from repro.checkpoint.checkpointer import Checkpointer
    from repro.serving import bind_model
    binding = bind_model(rmc1, mesh, scrub_scores=True)
    batch = _dlrm_batch(rmc1)
    dp = max(1, binding.engine.axes.dp_size(binding.engine.mesh))
    with mesh:
        # promote the batch's pages into the hot tier so corruption lands
        # on rows the lookup actually reads
        binding.observe(batch)
        binding.replan()
        healthy = np.asarray(binding.execute(batch))
        assert binding.last_poisoned == 0
        binding.reset_plan_stats()
        binding.attach_checkpointer(Checkpointer(str(tmp_path)),
                                    save_now=True)
        # explicit mode="nan": this scenario heals through the NaN score
        # scrub; finite flips are the checksum scrubber's territory
        # (test_integrity.py)
        n_bad = corrupt_store(binding, frac=1.0, seed=1, mode="nan")
        assert n_bad > 0
        poisoned = np.asarray(binding.execute(batch))
        assert binding.last_poisoned > 0 and binding.poisoned_batches == 1
        assert np.isfinite(poisoned).all()          # scrubbed, not NaN
        binding.restore()
        healed = np.asarray(binding.execute(batch))
    assert binding.restores == 1
    np.testing.assert_array_equal(healed, healthy)
    assert binding.engine.plan_stats()["traces"] == 0   # no retrace


def test_heal_replays_wal_for_post_snapshot_updates(mesh, rmc1, tmp_path):
    """The heal scenario above, extended with streaming updates: deltas
    applied AFTER the snapshot exist only in the write-ahead log, so a
    checkpoint reload alone would serve stale rows.  restore() must chase
    the snapshot with a WAL replay and land bit-exactly on the
    post-update scores — still without retracing the serve step."""
    from repro.checkpoint.checkpointer import Checkpointer
    from repro.checkpoint.wal import WriteAheadLog
    from repro.serving import bind_model
    binding = bind_model(rmc1, mesh, storage="int8")
    batch = _dlrm_batch(rmc1)
    rng = np.random.default_rng(8)
    total = int(binding.engine.cfg.total_rows)
    with mesh:
        binding.observe(batch)
        binding.replan()
        binding.attach_wal(WriteAheadLog(str(tmp_path / "u.wal")))
        binding.attach_checkpointer(Checkpointer(str(tmp_path / "ck")),
                                    save_now=True)
        stale = np.asarray(binding.execute(batch))  # pre-update scores
        for _ in range(2):
            binding.apply_deltas(
                rng.integers(0, total, 32),
                rng.normal(size=(32, rmc1.emb_dim)).astype(np.float32))
        fresh = np.asarray(binding.execute(batch))  # post-update scores
        assert not np.array_equal(stale, fresh)     # updates visible
        binding.reset_plan_stats()
        assert corrupt_store(binding, frac=1.0, seed=4, mode="nan") > 0
        binding.restore()
        healed = np.asarray(binding.execute(batch))
    assert binding.restores == 1
    np.testing.assert_array_equal(healed, fresh)    # not the stale snapshot
    assert binding.update_seq == 2
    assert binding.engine.plan_stats()["traces"] == 0


# ---------------------------------------------------------------------------
# Shard loss -> elastic re-mesh (degraded-mesh serving)
# ---------------------------------------------------------------------------


def test_shard_loss_persists_until_remesh():
    """Unlike a transient, shard loss keeps failing every attempt until
    the executor is told the dead shard left the mesh (on_remesh)."""
    from repro.serving import Bucket, ShardLossFailure
    model = FixedServiceModel(base_s=1e-3, per_row_s=0.0)
    fex = FaultInjectingExecutor(
        SimulatedExecutor(model),
        FaultConfig(shard_loss_at=(1,), shard_loss_shard=3))
    bucket = Bucket(4, 4)
    fex.run_batch(bucket, {})                 # step 0: healthy
    for _ in range(3):                        # persistent, not one-shot
        with pytest.raises(ShardLossFailure) as ei:
            fex.run_batch(bucket, {})
        assert ei.value.shard == 3
    assert fex.lost_shard == 3
    fex.on_remesh({})                         # the dead shard left the mesh
    fex.run_batch(bucket, {})                 # healthy again
    assert fex.lost_shard is None
    assert fex.report()["shard_loss"] == 3
    assert isinstance(ShardLossFailure("x", 0), TransientServingFailure)


def test_shard_loss_spares_replicated_only_rungs():
    """hot_only/shed run zero cross-shard work (replicated hot tier only),
    so a dead cold shard is invisible to them — the ladder can limp, but
    only a re-mesh recovers full quality."""
    from repro.serving import Bucket, ShardLossFailure

    class _Binding:
        active = "hot_only"

    class _Inner:
        binding = _Binding()

        def run_batch(self, bucket, batch):
            return 1e-3

    fex = FaultInjectingExecutor(
        _Inner(), FaultConfig(shard_loss_at=(0,), shard_loss_shard=1))
    bucket = Bucket(4, 4)
    fex.run_batch(bucket, {})            # fires, but hot_only passes through
    assert fex.lost_shard == 1           # ...the shard is still dead
    assert fex.report()["shard_loss"] == 0
    fex.inner.binding.active = "full"    # back on the cross-shard datapath
    with pytest.raises(ShardLossFailure):
        fex.run_batch(bucket, {})


def test_controller_shard_attribution_escalates_and_transient_clears():
    """The persistent/transient distinguisher: only a *consecutive*
    same-shard failure streak escalates to remesh; an interleaved
    non-attributed transient breaks the evidence chain (a genuinely flaky
    fabric does not blame one shard consistently)."""
    from repro.serving import ShardLossFailure

    class _Binding:
        can_remesh = True
        checkpointer = None

        def set_mode(self, label):
            pass

    ctrl = DegradationController(binding=_Binding(),
                                 ladder=LadderConfig(remesh_after=3))
    for _ in range(2):
        ctrl.on_attempt_failure(0.0, ShardLossFailure("x", shard=2))
    assert not ctrl.wants_remesh
    ctrl.on_attempt_failure(0.0, TransientServingFailure("flaky"))
    assert ctrl.suspect_shard is None          # chain broken
    for _ in range(3):
        ctrl.on_attempt_failure(0.0, ShardLossFailure("x", shard=2))
    assert ctrl.wants_remesh and ctrl.suspect_shard == 2
    ctrl.note_remeshed(0.0, {"to_mesh": {"data": 2, "model": 2}})
    assert not ctrl.wants_remesh
    assert ctrl.remeshes == 1 and ctrl.pressure == 0.0
    assert ctrl.breaker.state == "closed"
    rep = ctrl.report()
    assert rep["remeshes"] == 1 and rep["suspect_shard"] is None
    assert rep["remesh_events"][0]["shard"] == 2


def test_watchdog_trips_surface_in_summary_and_feed_controller():
    """One spiked micro-batch trips the service-time watchdog; the trip
    lands in the runtime summary and bumps the controller's pressure
    (half-weight: slow-but-correct is pressure, not failure)."""
    from repro.runtime.fault_tolerance import StragglerWatchdog

    class SpikyExecutor:
        def __init__(self):
            self.n = 0

        def run_batch(self, bucket, batch):
            self.n += 1
            return 0.1 if self.n == 10 else 0.004

        def observe(self, batch):
            return 0.0

        def replan(self):
            return 0.0

    ctrl = DegradationController()
    wd = StragglerWatchdog(threshold=4.0, warmup=2)
    rt = ServingRuntime(SpikyExecutor(), FixedBatcher(batch=4, pooling=4),
                        padder=lambda reqs, bucket: {"n": len(reqs)},
                        cfg=RuntimeConfig(observe_every=0, replan_every=0),
                        controller=ctrl, watchdog=wd)
    s = rt.run(OpenLoopSource(_reqs(64)))
    assert s["watchdog"]["trips"] == 1
    assert s["watchdog"]["events"][0]["dt"] == pytest.approx(0.1)
    assert ctrl.straggler_trips == 1
    assert s["degradation"]["straggler_trips"] == 1
    assert ctrl.pressure > 0.0


def test_binding_checkpoint_mesh_mismatch_routes_to_elastic(mesh, rmc1,
                                                            tmp_path):
    """A checkpoint written under tp=4 must refuse an in-place restore on
    a tp=2 binding — loudly, naming the elastic path — instead of
    silently mis-placing shards."""
    from repro.checkpoint.checkpointer import Checkpointer
    from repro.distributed.sharding import make_mesh
    from repro.serving import bind_model
    binding = bind_model(rmc1, mesh, storage="int8")
    with mesh:
        binding.attach_checkpointer(Checkpointer(str(tmp_path)),
                                    save_now=True)
    extra = binding.checkpointer.extra()
    assert extra["n_shards"] == 4
    assert extra["mesh"] == {"data": 2, "model": 4}
    assert extra["storage"] == "int8"
    m2 = make_mesh((4, 2), ("data", "model"))
    other = bind_model(rmc1, m2, storage="int8")
    other.attach_checkpointer(Checkpointer(str(tmp_path)), save_now=False)
    with pytest.raises(ValueError, match="elastic"):
        other.restore()
    # same mesh but mismatched storage fails loudly too
    other32 = bind_model(rmc1, mesh, storage="fp32")
    other32.attach_checkpointer(Checkpointer(str(tmp_path)), save_now=False)
    with pytest.raises(ValueError, match="storage"):
        other32.restore()


def test_serving_survives_shard_loss_with_elastic_remesh(mesh, rmc1):
    """The degraded-mesh tentpole end to end, on the full feature stack
    (int8 cold tier + dedup + fused front end): a tp shard dies
    mid-serving, the controller attributes the same-shard streak and
    escalates to remesh, the runtime re-meshes onto the survivors
    (tp 4 -> 2 under prefer_tp=2 with the bucket-granule constraint),
    re-warms every rung, re-attempts the stranded micro-batch —
    availability holds, zero steady-state retraces across BOTH sides of
    the re-mesh, the front end re-resolves fused_tp at the survivor tp,
    and the recovered engine serves scores bit-identical to a fresh
    engine packed onto the same degraded mesh.  The params move onto the
    survivor mesh with it, so no step argument is moved on a call, and a
    served batch scores as it did before the re-mesh."""
    import jax
    from repro.runtime.fault_tolerance import StragglerWatchdog
    from repro.serving import (BatcherConfig, BindingExecutor, Bucket,
                               DynamicBatcher, bind_model,
                               dummy_request_factory, make_padder,
                               request_stream)
    binding = bind_model(rmc1, mesh, storage="int8", dedup="on",
                         front_end="fused", degraded_variants=True,
                         scrub_scores=True, elastic=True, prefer_tp=2)
    bat = BatcherConfig(batch_sizes=(8, 16), poolings=(rmc1.pooling,))
    ctrl = DegradationController(
        binding=binding, retry=RetryPolicy(max_attempts=3),
        breaker=BreakerConfig(trip_after=6, cooldown_s=0.02),
        ladder=LadderConfig(min_dwell_batches=4, remesh_after=3))
    inner = BindingExecutor(binding)
    fex = FaultInjectingExecutor(
        inner, FaultConfig(seed=13, shard_loss_at=(2,)),
        idx_key=binding.idx_key)
    wd = StragglerWatchdog(threshold=4.0, warmup=4)
    rt = ServingRuntime(inner, DynamicBatcher(bat), make_padder(rmc1),
                        RuntimeConfig(observe_every=4, replan_every=8),
                        controller=ctrl, watchdog=wd)
    factory = dummy_request_factory(rmc1, storage="int8")
    load = LoadConfig(n_requests=96,
                      arrival=ArrivalConfig(rate_qps=400.0, seed=2),
                      slo_ms=500.0, seed=2, storage="int8", dedup="on",
                      front_end="fused")
    padder = make_padder(rmc1)
    probes = {}
    for b in bat.batch_sizes:
        bucket = Bucket(b, rmc1.pooling)
        probes[b] = padder([factory(i, bucket.pooling)
                            for i in range(bucket.batch)], bucket)
    with mesh:
        for rung in binding.modes():
            binding.set_mode(rung)
            rt.warmup(factory)
        binding.set_mode("full")
        before = {b: np.asarray(jax.device_get(binding.execute(p)))
                  for b, p in probes.items()}
        rt.executor = fex
        binding.reset_plan_stats()
        s = rt.run(OpenLoopSource(request_stream(rmc1, load)))

        # gates — the trace ledger FIRST: probe batches below are fresh
        # jit signatures and would pollute a later read
        assert binding.plan_stats()["traces"] == 0
        assert s["served"] + s["failed"] == 96
        assert s["availability"] >= 0.99
        assert binding.remeshes == 1
        rec = s["remesh"]
        assert rec["lost_shard"] == 3              # highest tp index died
        assert rec["from_mesh"] == {"data": 2, "model": 4}
        assert rec["to_mesh"] == {"data": 2, "model": 2}
        assert dict(binding.engine.mesh.shape) == {"data": 2, "model": 2}
        assert rec["mttr_s"] > 0.0
        assert fex.report()["shard_loss"] >= 3     # the attribution streak
        assert fex.lost_shard is None              # on_remesh cleared it
        assert s["degradation"]["remeshes"] == 1
        fe_recs = [r for r in
                   binding.engine.plan_stats().get("front_end", {}).values()
                   if r["requested"] == "fused"]
        assert fe_recs and all(r["resolved"] == "fused_tp" and r["tp"] == 2
                               for r in fe_recs)
        # placement: params committed to the survivor mesh, nothing moved
        for leaf in jax.tree.leaves(binding.params):
            assert leaf.committed
            assert leaf.sharding.mesh == binding.engine.mesh
        assert binding.plan_stats()["moved_inputs"] == 0

        # bit-exactness: recovered binding vs a fresh engine packed onto
        # the same survivor mesh from the same logical triple + page table
        codes, values, scales = binding.engine.export_state(binding.state)
        fresh = bind_model(rmc1, binding.engine.mesh, storage="int8",
                           dedup="on", front_end="fused")
        fresh.params = binding.params
        fresh.state = fresh.engine.pack_state(
            codes, values, scales, table=binding.state.page_table,
            counts=np.asarray(jax.device_get(binding.state.counts)))
        for b, probe in probes.items():
            got = np.asarray(jax.device_get(binding.execute(probe)))
            np.testing.assert_array_equal(
                got, np.asarray(jax.device_get(fresh.execute(probe))))
            np.testing.assert_array_equal(got, before[b])


def test_fault_injected_serving_run_end_to_end(mesh, rmc1):
    """Transient chaos + controller over a real binding: every request is
    accounted, availability holds, retries happen, and the plan cache
    keeps the zero-steady-retrace contract under injected faults."""
    from repro.serving import (BindingExecutor, DynamicBatcher,
                               BatcherConfig, bind_model,
                               dummy_request_factory, make_padder,
                               request_stream)
    binding = bind_model(rmc1, mesh, degraded_variants=True,
                         scrub_scores=True)
    bat = BatcherConfig(batch_sizes=(8, 16), poolings=(rmc1.pooling,))
    ctrl = DegradationController(
        binding=binding, breaker=BreakerConfig(trip_after=5,
                                               cooldown_s=0.02),
        ladder=LadderConfig(min_dwell_batches=4))
    inner = BindingExecutor(binding)
    fex = FaultInjectingExecutor(
        inner, FaultConfig(transient_at=(1,), transient_prob=0.02, seed=5))
    rt = ServingRuntime(inner, DynamicBatcher(bat), make_padder(rmc1),
                        RuntimeConfig(observe_every=4, replan_every=8),
                        controller=ctrl)
    load = LoadConfig(n_requests=48,
                      arrival=ArrivalConfig(rate_qps=400.0, seed=2),
                      slo_ms=200.0, seed=2)
    with mesh:
        # warm every rung through the clean executor (faults must never
        # fire during compile), then arm injection for the measured run
        for rung in binding.modes():
            binding.set_mode(rung)
            rt.warmup(dummy_request_factory(rmc1))
        binding.set_mode("full")
        rt.executor = fex
        binding.reset_plan_stats()
        s = rt.run(OpenLoopSource(request_stream(rmc1, load)))
    assert s["served"] + s["failed"] == 48
    assert s["availability"] >= 0.99
    assert s["retries"] >= 1
    assert binding.plan_stats()["traces"] == 0
    assert s["degradation"]["rung"] in RUNGS
