"""Run one benchmark cell on the accelerator this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is found by name: the cell in ``BENCHMARK.json``
names its configuration (``bench/configs/<config>.json``, whose ``family``
names ``bench/models/<family>/``: the program adapter, the plain reference
and the work count) and its traffic mix (``bench/traffic/<mix>.json``, read
by ``bench/traffic.py``); each metric is read by ``bench/metrics/<name>.py``.

Set-up (counted in ``setup_s``): traffic from the seed, the program bound
with weights and tables made on the device from the seed, exactly the mix's
buckets warmed.  Then one window of ``--seconds`` on the wall clock
(``bench/driver.py``), and after it, with the program's state freed, the
comparison with the reference that decides ``correct``.  With ``--trace 1``
the window is profiled and the per-layer metrics are
reported instead of the end-to-end ones.

The last line of standard output is one JSON object.  Without a TPU, with
fewer chips than the cell asks for, or on a device kind missing from
``bench/peaks.py``, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
SAMPLE = 4096                # requests compared with the reference per run


def _import(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str) -> tuple:
    """(benchmark spec, cell, model config, traffic mix) for a cell name."""
    from bench import traffic
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    model = json.loads((ROOT / configs[cell["config"]]["file"]).read_text())
    return spec, cell, model, traffic.load_mix(cell["traffic"])


def metrics_for(spec: dict, cell: dict, traced: bool) -> list:
    group = spec["per_layer"] if traced else spec["end_to_end"]
    return [m for m in group
            if "workloads" not in m or cell["name"] in m["workloads"]]


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


class _Trace:
    """Profiles the window into a private directory."""

    def __init__(self):
        import jax
        self.jax = jax
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")

    def start(self):
        opts = self.jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        self.jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self):
        self.jax.profiler.stop_trace()

    def path(self) -> str:
        found = sorted(Path(self.dir).rglob("*.xplane.pb"))
        if not found:
            raise FileNotFoundError(f"no profile written under {self.dir}")
        return str(found[-1])

    def remove(self):
        import shutil
        shutil.rmtree(self.dir, ignore_errors=True)


def run(args, require_tpu: bool = True, devices=None, cell_spec=None,
        build=None):
    """One run; returns (result line, diagnostics).  A test may drive the
    whole run on the CPU: ``require_tpu=False``, its own ``devices``, a
    ``cell_spec`` (benchmark spec, cell, model, mix) of its own, and
    ``build(make)``, which gets the program's constructor and returns the
    program, to break the timed path underneath."""
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax
    import numpy as np
    from bench import peaks as peaks_mod
    from bench import traffic
    from bench.driver import Driver
    from bench.stats import percentile

    if require_tpu:
        # a fixed directory of the benchmark's own in the checkout: only
        # the first run compiles
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache" / "bench"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    spec, cell, model, mix = cell_spec or load_cell(args.workload)
    devices = list(devices or jax.devices())
    platform = devices[0].platform
    if require_tpu and platform != "tpu":
        raise SystemExit(f"bench: no TPU: JAX found platform {platform!r} "
                         f"({len(devices)} device(s))")
    chips = int(cell["chips"])
    if len(devices) < chips:
        raise SystemExit(f"bench: {cell['name']} needs {chips} chips, JAX "
                         f"found {len(devices)}")
    devices = devices[:chips]
    kind = devices[0].device_kind
    try:
        peaks = peaks_mod.peaks_for(kind) if require_tpu else None
    except KeyError as e:
        raise SystemExit(f"bench: {e.args[0]}") from None

    family = BENCH / "models" / model["family"]
    program_mod = _import(family / "program.py", "bench_program")
    compiles = {"n": 0, "s": 0.0}

    def on_compile(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles["n"] += 1
            compiles["s"] += secs

    jax.monitoring.register_event_duration_secs_listener(on_compile)

    reqs = traffic.generate(mix, model, args.seed, args.seconds)
    def make():
        return program_mod.Program(model, mix, args.seed, devices)

    program = build(make) if build is not None else make()
    modules = program.step_modules() if args.trace else set()
    program.reset_plan_stats()
    warm_compiles = dict(compiles)
    trace = _Trace() if args.trace else None
    # set-up's objects are long-lived: keep them out of the collector's
    # scans, as a serving process would
    gc.collect()
    gc.freeze()
    pauses = []

    def on_gc(phase, _info, t=[0.0]):
        if phase == "start":
            t[0] = time.perf_counter()
        else:
            pauses.append(time.perf_counter() - t[0])

    gc.callbacks.append(on_gc)
    setup_s = time.perf_counter() - T_START
    try:
        with program.mesh:
            served = Driver(program, reqs, mix).run(args.seconds, trace)
    finally:
        gc.callbacks.remove(on_gc)
    window_traces = program.plan_traces()
    window_compiles = compiles["n"] - warm_compiles["n"]
    mem_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices)
    program.close()
    del program
    gc.unfreeze()
    gc.collect()

    reduction = None
    if trace is not None:
        from bench import trace as trace_mod
        try:
            reduction = trace_mod.reduce(trace.path(), modules, chips)
        finally:
            trace.remove()

    check = compare(model, reqs, served, args.seed, devices, family)
    ctx = Context(model=model, served=served, setup_s=setup_s,
                  trace=reduction, peaks=peaks, chips=chips, reqs=reqs,
                  family=family)
    values = {}
    for m in metrics_for(spec, cell, bool(args.trace)):
        reader = _import(BENCH / "metrics" / f"{m['name']}.py",
                         f"bench_metric_{m['name']}")
        v = reader.read(ctx)
        if v is not None:
            values[m["name"]] = {"value": float(v), "unit": m["unit"]}

    attempted = len(served.due)
    failed = int(np.count_nonzero(np.isnan(served.scores)))
    device = {"platform": platform, "kind": kind, "count": chips,
              "memory_peak_bytes": int(mem_peak)}
    result = {"correct": check["correct"], "attempted": attempted,
              "failed": failed, "metrics": values, "device": device}
    info = {"window_compiles": window_compiles,
            "window_plan_traces": window_traces,
            "replans": served.replans, "dropped": served.dropped,
            "setup_compiles": warm_compiles["n"],
            "setup_compile_s": round(warm_compiles["s"], 3),
            "admitted_late_p99_ms": ctx.admit_lag_p99_ms(),
            "requests_compared": check["compared"],
            "oversleep_max_ms": 1e3 * served.oversleep,
            "latency_ms": {f"p{q}": 1e3 * percentile(ctx.latencies_s(), q)
                           for q in (50, 90, 95, 99, 99.9, 100)}
            if ctx.latencies_s().size else None,
            "gc_pauses": len(pauses),
            "gc_pause_max_ms": 1e3 * max(pauses, default=0.0),
            "longest_span_ms": {
                n: 1e3 * max(b - a for m, a, b in served.spans if m == n)
                for n in sorted({m for m, _, _ in served.spans})}}
    if reduction is not None:
        device["busy_s"] = reduction.busy_s
        device["window_s"] = reduction.window_s
        result["breakdown"] = reduction.breakdown()
        info["exposed_collective_ms_per_step"] = (
            reduction.exposed_collective_ms_per_step())
        info["idle_gaps_longest"] = reduction.longest_gaps()
    result["checks"] = check["numbers"]
    return result, info


def compare(model, reqs, served, seed, devices, family) -> dict:
    """Scores the window returned, for a sample drawn from the seed of the
    requests it finished, against the plain reference."""
    import numpy as np
    ref = _import(family / "reference.py", "bench_reference")
    limits = model["limits"]
    done = np.nonzero(~np.isnan(served.scores))[0]
    if done.size == 0:
        return {"correct": False, "compared": 0,
                "numbers": {name: {"value": None, "limit": lim}
                            for name, lim in limits.items()}}
    rng = np.random.default_rng([seed, 0xC0])
    pick = np.sort(rng.choice(done, min(SAMPLE, done.size), replace=False))
    k = served.pool_index[pick]
    want = ref.scores(model, seed, reqs.dense[k], reqs.ids[k],
                      devices=devices, precision=model["precision"])
    numbers = ref.compared(served.scores[pick], want)
    ok = all(np.isfinite(v) and v <= limits[name]
             for name, v in numbers.items())
    return {"correct": bool(ok), "compared": int(pick.size),
            "numbers": {name: {"value": float(v), "limit": limits[name]}
                        for name, v in numbers.items()}}


class Context:
    """What a metric reader may read."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def spans(self, name: str) -> list:
        return [(a, b) for n, a, b in self.served.spans
                if n == name and a < self.served.t_end]

    def batches(self) -> list:
        """Batches flushed inside the window."""
        return [b for b in self.served.batches if b[0] < self.served.t_end]

    def traced_batches(self) -> list:
        """Batches served while the profiler ran, one per traced step."""
        t = self.served.trace_t
        return [b for b in self.served.batches if t and t[0] <= b[0] < t[1]]

    def least_time_s(self, batch) -> float:
        """Least time of a served batch's real requests at the chips'
        peaks, from the family's work count."""
        from bench.peaks import least_time_s
        work = _import(self.family / "work.py", "bench_work")
        k = self.served.pool_index[batch[3]]
        flops, nbytes = work.batch_work(self.model, self.reqs.ids[k])
        return least_time_s(flops, nbytes, self.peaks, self.chips)

    def latencies_s(self):
        import numpy as np
        s = self.served
        ok = ~np.isnan(s.done)
        return (s.done - s.due)[ok]

    def admit_lag_p99_ms(self):
        """How late the driver admitted requests after they fell due."""
        import numpy as np
        s = self.served
        lag = s.admitted - s.due
        return None if lag.size == 0 else float(
            1e3 * np.percentile(lag, 99))


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        result, info = run(args)
    except SystemExit as e:
        print(e, file=sys.stderr)
        return 2
    for k, v in info.items():
        print(f"bench: {k} = {v}", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"bench check: {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
