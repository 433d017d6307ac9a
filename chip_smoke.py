"""Chip smoke: serve the paper's RMC3 end to end on one TPU.

    python3 chip_smoke.py                  # one chip, phases (a)-(c)
    python3 chip_smoke.py --chips 4        # four chips: the sharded store
    python3 chip_smoke.py --cpu-rehearsal  # CPU, reduced widths, interpret

Each phase binds the model through the normal entry points
(``launch.serve.serve_offered_load``: ``bind_model`` -> ``ServingRuntime``
-> ``make_serve_step``), warms every bucket, serves a zipfian request
stream with observe/replan in the steady window, and checks:

  * zero steady-state retraces and at least one replan (none on four
    chips, see below), every request served;
  * the lowered serve step holds a Pallas kernel (``tpu_custom_call``) on
    the ``impl="pallas"`` paths;
  * scores on fixed probe batches (one per bucket), run through the very
    executables that served the window (no retrace), agree with a
    reference binding within ``SCORE_ATOL``;
  * with ``dedup="on"``, at least one served batch landed in a bucket
    where the gather-once kernel resolved on.

One chip (RMC3 at its Table I widths: 8 tables x 2^20 rows, D=64, pooling
8, MLPs 2048-1024-256 / 512-256-1):

  (a) fp32, impl=jnp, front_end=split  — the default path, the reference
  (b) fp32, impl=pallas, front_end=fused — compared with (a)
  (c) int8, impl=pallas, dedup=on, front_end=fused — compared with an
      int8 impl=jnp binding; served at a lower rate, so most batches fill
      the 32 bucket, where dedup fits its VMEM budget (it resolves off at
      512)

Four chips (``--chips 4``): RMC4 widths at 32 tables in fp32 — a 16 GiB
logical store that no single chip holds — on a (1, 4) mesh, served three
ways on one request stream: pifs jnp/split (the reference), pifs
pallas/fused (must resolve to ``fused_tp``) and pond.  Replans are off
there: the migration gather all-gathers the cold tier onto every device.

The last line of standard output is one JSON object, ``"ok": true`` only
after a chip run in which every check passed.  Without a TPU the script
exits non-zero before any phase.  ``--cpu-rehearsal`` runs the same phases
on ``reduced(rmc3)`` (``reduced(rmc4)`` at 32 tables with ``--chips 4``,
on virtual CPU devices) with the kernels interpreted; it never prints
``"ok": true``.
"""
from __future__ import annotations

import argparse
import dataclasses
import faulthandler
import gc
import json
import os
import sys
import time
from pathlib import Path

SCORE_ATOL = 1e-3          # absolute, on sigmoid scores in [0, 1]
N_REQUESTS = 1024          # requests served per phase
SEED = 0
DEADLINE_S = 850           # dump every thread's stack and exit past this


@dataclasses.dataclass(frozen=True)
class Phase:
    name: str
    storage: str = "fp32"
    impl: str = "jnp"
    front_end: str = "split"
    dedup: str = "off"
    mode: str = "pifs"
    qps: float = 20000.0             # offered load, virtual clock
    ref: str | None = None           # phase name, or "bind" for a fresh
    #                                  impl=jnp binding of the same storage
    want_kernel: bool = False
    want_front_end: str | None = None


ONE_CHIP = (
    Phase("a"),
    Phase("b", impl="pallas", front_end="fused", ref="a", want_kernel=True,
          want_front_end="fused"),
    Phase("c", storage="int8", impl="pallas", front_end="fused", dedup="on",
          qps=1000.0, ref="bind", want_kernel=True, want_front_end="fused"),
)
FOUR_CHIPS = (
    Phase("pifs-jnp"),
    Phase("pifs-pallas", impl="pallas", front_end="fused", ref="pifs-jnp",
          want_kernel=True, want_front_end="fused_tp"),
    Phase("pond", mode="pond", ref="pifs-jnp"),
)


def parse_args():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="reduced widths on the CPU, kernels interpreted")
    return ap.parse_args()


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def main() -> int:
    args = parse_args()
    # a hang must end inside the caller's time limit with a stack to read
    faulthandler.dump_traceback_later(DEADLINE_S, exit=True)
    if args.cpu_rehearsal and args.chips > 1:
        os.environ.setdefault(
            "XLA_FLAGS", f"--xla_force_host_platform_device_count={args.chips}")
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

    import jax
    import numpy as np

    devices = jax.devices()
    platform = devices[0].platform
    if not args.cpu_rehearsal and platform != "tpu":
        print(f"chip_smoke: no TPU: JAX found platform={platform!r} "
              f"({len(devices)} device(s)); run on a TPU host, or pass "
              "--cpu-rehearsal", file=sys.stderr)
        return 2
    check(len(devices) >= args.chips,
          f"--chips {args.chips} but JAX sees {len(devices)} device(s)")

    from repro.configs import get_config, reduced
    from repro.distributed.sharding import make_mesh
    from repro.launch import compile_cache
    from repro.launch.serve import serve_offered_load
    from repro.serving import LoadConfig, RuntimeConfig, bind_model
    from repro.serving import make_padder, request_stream
    from repro.serving.batcher import Bucket
    from repro.serving.request import ArrivalConfig

    cache_dir = compile_cache.enable()
    compile_s = [0.0]

    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compile_s[0] += secs

    jax.monitoring.register_event_duration_secs_listener(on_duration)

    if args.chips == 4:
        cfg = dataclasses.replace(get_config("rmc4"), n_tables=32)
        phases, replan_every, batch_sizes = FOUR_CHIPS, 0, (512,)
    else:
        cfg = get_config("rmc3")
        phases, replan_every, batch_sizes = ONE_CHIP, 4, (32, 512)
    if args.cpu_rehearsal:
        cfg = dataclasses.replace(reduced(cfg), n_tables=cfg.n_tables)
    mesh = make_mesh((1, args.chips), ("data", "model"),
                     devices=devices[:args.chips])
    kind = devices[0].device_kind
    print(f"chip_smoke: platform={platform} device_kind={kind} "
          f"devices={args.chips} config={cfg.name} tables={cfg.n_tables} "
          f"rows/table={cfg.emb_num} dim={cfg.emb_dim} pooling={cfg.pooling} "
          f"buckets={batch_sizes} compile_cache={cache_dir}", flush=True)

    def load(ph: Phase):
        return LoadConfig(
            n_requests=N_REQUESTS,
            arrival=ArrivalConfig(rate_qps=ph.qps, process="bursty",
                                  seed=SEED),
            slo_ms=50.0, seed=SEED, storage=ph.storage, dedup=ph.dedup,
            front_end=ph.front_end)

    def probes(ph: Phase):
        """One padded probe batch per bucket, from a stream the served
        one never sees (seed + 1)."""
        lc = dataclasses.replace(load(ph), n_requests=max(batch_sizes),
                                 seed=SEED + 1)
        reqs = request_stream(cfg, lc)
        pad = make_padder(cfg)
        return {b: pad(reqs[:b], Bucket(b, cfg.pooling))
                for b in batch_sizes}

    def score(binding, batches):
        return {b: np.asarray(binding.execute(x))
                for b, x in batches.items()}

    def store_bytes(state):
        """Bytes of the engine state on device 0, and on all devices."""
        per_dev, total = 0, 0
        for leaf in jax.tree.leaves(state):
            total += sum(s.data.nbytes for s in leaf.addressable_shards)
            per_dev += leaf.addressable_shards[0].data.nbytes
        return per_dev, total

    scores: dict = {}
    for ph in phases:
        t0 = time.perf_counter()
        compile_s[0] = 0.0
        print(f"phase {ph.name}: start", flush=True)
        batches = probes(ph)
        seen: dict = {}

        def after_run(binding, ph=ph, batches=batches, seen=seen):
            seen["store"] = store_bytes(binding.state)
            seen["dedup"] = {
                int(label.rsplit("idx=", 1)[-1].split("x")[0]):
                rec["resolved"]
                for label, rec in binding.plan_stats().get(
                    "dedup", {}).items()}
            traces = binding.plan_stats()["traces"]
            seen["scores"] = score(binding, batches)
            # a retrace here would mean the probe ran other executables
            seen["probe_traces"] = binding.plan_stats()["traces"] - traces
            b = max(batch_sizes)
            jb = {k: jax.numpy.asarray(v) for k, v in batches[b].items()}
            seen["kernel"] = "tpu_custom_call" in binding.steps["full"].lower(
                binding.params, binding.state, jb).as_text()

        summary = serve_offered_load(
            cfg, mesh, load(ph),
            mode=ph.mode, impl=ph.impl, batch_sizes=batch_sizes,
            runtime_cfg=RuntimeConfig(observe_every=1,
                                      replan_every=replan_every),
            after_run=after_run)
        scores[ph.name] = seen["scores"]
        if ph.ref == "bind":
            ref = bind_model(cfg, mesh, mode=ph.mode, storage=ph.storage,
                             seed=SEED)
            with mesh:
                want = score(ref, batches)
            del ref
            ref_label = f"{ph.storage}-jnp binding"
        elif ph.ref is not None:
            want, ref_label = scores[ph.ref], f"phase {ph.ref}"
        diff = (max(float(np.max(np.abs(seen["scores"][b] - want[b])))
                    for b in batch_sizes) if ph.ref else 0.0)
        finite = all(np.isfinite(v).all() for v in seen["scores"].values())
        resolved = sorted({r["resolved"]
                           for r in summary["front_end"].values()})
        per_dev, total = seen["store"]
        served_b = {int(k.split("x")[0]) for k in summary["bucket_mix"]}
        dedup_served = sorted(b for b in served_b if seen["dedup"].get(b))
        print(f"phase {ph.name}: storage={ph.storage} impl={ph.impl} "
              f"front_end={ph.front_end}->{','.join(resolved) or 'split'} "
              f"dedup={ph.dedup} mode={ph.mode}", flush=True)
        print(f"  platform={platform} device_kind={kind} "
              f"device_count={len(mesh.devices.flat)}")
        print(f"  store_bytes_per_device={per_dev} "
              f"store_bytes_total={total}")
        print(f"  compile_s={compile_s[0]:.3f} "
              f"setup_and_serve_s={time.perf_counter() - t0:.3f}")
        print(f"  steady_traces={summary['steady_traces']} "
              f"replans={summary['replans']} served={summary['served']}/"
              f"{N_REQUESTS} dropped={summary['dropped']} "
              f"failed={summary['failed']} bucket_mix={summary['bucket_mix']}")
        print(f"  service_p50_ms={summary['service_p50_ms']:.4f} "
              f"service_p99_ms={summary['service_p99_ms']:.4f}")
        print(f"  tpu_custom_call={seen['kernel']} "
              f"dedup_on_by_batch={seen['dedup'] or '-'} "
              f"dedup_on_served_buckets={dedup_served or '-'} "
              f"probe_traces={seen['probe_traces']}")
        if ph.ref:
            print(f"  max_abs_score_diff={diff:.3e} vs {ref_label} "
                  f"(tolerance {SCORE_ATOL:g})", flush=True)
        check(summary["steady_traces"] == 0, f"{ph.name}: steady retraces")
        check(summary["replans"] >= (1 if replan_every else 0),
              f"{ph.name}: no replan in the steady window")
        check(summary["served"] == N_REQUESTS,
              f"{ph.name}: served {summary['served']} of {N_REQUESTS}")
        check(seen["probe_traces"] == 0,
              f"{ph.name}: the probe retraced the serve step")
        if ph.dedup == "on":
            check(bool(dedup_served),
                  f"{ph.name}: no batch served with dedup resolved on")
        check(finite, f"{ph.name}: non-finite probe scores")
        check(diff <= SCORE_ATOL, f"{ph.name}: score diff {diff:.3e}")
        if ph.want_front_end:
            check(resolved == [ph.want_front_end],
                  f"{ph.name}: front end resolved to {resolved}")
        if ph.want_kernel and not args.cpu_rehearsal:
            check(seen["kernel"], f"{ph.name}: no tpu_custom_call in step")
        del summary, seen
        gc.collect()

    result = {"ok": not args.cpu_rehearsal,
              "device": {"platform": platform, "kind": kind,
                         "count": len(mesh.devices.flat)}}
    if args.cpu_rehearsal:
        result["rehearsal"] = True
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
