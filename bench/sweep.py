"""Find an open-loop cell's knee: the highest offered rate at which a window
drops no request and the backlog does not grow.  One process binds the
program once and steps through the rates, one window each.

    python3 bench/sweep.py --config rmc3 --traffic <mix> --chips 1 \\
        --seed 7 --seconds 8 --rates 4000 8000 12000

Prints one JSON line per rate: requests due, dropped, latency quantiles, and
the median latency of the window's first and last quarters (a last quarter
far above the first is a growing backlog).  Not run by the benchmark.
"""
from __future__ import annotations

import argparse
import copy
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args()
    for p in (str(ROOT / "src"), str(ROOT)):
        sys.path.insert(0, p)
    import jax
    import numpy as np
    from bench import run as bench_run
    from bench import traffic
    from bench.driver import Driver
    from bench.stats import percentile

    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 2
    model = json.loads(
        (ROOT / "bench" / "configs" / f"{args.config}.json").read_text())
    mix = traffic.load_mix(args.traffic)
    program_mod = bench_run._import(
        ROOT / "bench" / "models" / model["family"] / "program.py",
        "bench_program")
    program = program_mod.Program(model, mix, args.seed,
                                  devices[:args.chips])
    for i, rate in enumerate(args.rates):
        m = copy.deepcopy(mix)
        m["load"]["rate_per_s"] = rate
        reqs = traffic.generate(m, model, args.seed + i, args.seconds)
        with program.mesh:
            s = Driver(program, reqs, m).run(args.seconds)
        lat = (s.done - s.due) * 1e3
        ok = ~np.isnan(lat)
        off = s.due - s.t0
        q1 = ok & (off < args.seconds / 4)
        q4 = ok & (off >= 3 * args.seconds / 4)
        row = {"rate_per_s": rate, "due": int(len(s.due)),
               "dropped": s.dropped, "replans": s.replans,
               "p50_ms": percentile(lat[ok], 50),
               "p99_ms": percentile(lat[ok], 99),
               "max_ms": float(np.max(lat[ok])),
               "first_quarter_p50_ms": percentile(lat[q1], 50),
               "last_quarter_p50_ms": percentile(lat[q4], 50),
               "batches": len(s.batches),
               "mean_batch": float(np.mean([len(b[3]) for b in s.batches]))}
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
