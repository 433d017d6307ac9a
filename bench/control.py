"""The control of ``correct``: the plain reference put in the program's
place and computed one precision below the configuration's (bfloat16 for
float32), on the requests a run of the cell would compare.  It has to read
above the limit.  Not run by the benchmark's own runs.

    python3 bench/control.py --workload <cell> --seeds 11 12 13 --seconds 20

Prints one JSON line per seed: the compared numbers of the control against
the reference at the configuration's precision, and, for the record, the gap
between that reference and one at ``Precision.HIGHEST``.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def readings(model: dict, mix: dict, seed: int, seconds: float, devices,
             sample: int) -> dict:
    import numpy as np
    from bench import run as bench_run
    from bench import traffic
    ref = bench_run._import(
        ROOT / "bench" / "models" / model["family"] / "reference.py",
        "bench_reference")
    reqs = traffic.generate(mix, model, seed, seconds)
    rng = np.random.default_rng([seed, 0xC0])
    k = np.sort(rng.choice(len(reqs), min(sample, len(reqs)), replace=False))
    dense, ids = reqs.dense[k], reqs.ids[k]
    want = ref.scores(model, seed, dense, ids, devices,
                      precision=model["precision"])
    out = {"seed": seed, "compared": int(k.size)}
    for p in ("bfloat16", "highest"):
        got = ref.scores(model, seed, dense, ids, devices, precision=p)
        out[p] = ref.compared(got, want)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args()
    for p in (str(ROOT / "src"), str(ROOT)):
        sys.path.insert(0, p)
    import jax
    from bench import run as bench_run
    spec, cell, model, mix = bench_run.load_cell(args.workload)
    devices = jax.devices()[:int(cell["chips"])]
    for seed in args.seeds:
        r = readings(model, mix, seed, args.seconds, devices,
                     bench_run.SAMPLE)
        r["limits"] = model["limits"]
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
