"""Device time of the serve step's named stages, from one traced run.

    python3 bench/stages.py --workload <cell> --seed <n> --seconds <s>

Runs the cell as ``bench/run.py --trace 1`` does and prints one JSON line:
the run's per-layer metrics and breakdown and, for each chip, the device ms
per step of each stage that the step names with ``jax.named_scope``
(``bottom_mlp``, ``embed``, ``combine``, ``interaction``, ``top_mlp``).

On a TPU the step's ``XLA Ops`` events carry the HLO instruction's name and
no scope, so an operation's stage is read from the compiled step of each of
the mix's buckets: an instruction's ``op_name`` metadata holds the scope
path.  An operation with none of the five scopes is ``unscoped``; a name
that two buckets' steps give different stages is ``unknown``.  Each instant
inside a step module counts once: where operations overlap, it goes to the
one that started last (the inner one, where events nest), and ``idle`` is
step time that no operation covers, so a chip's stages and ``idle`` add up
to its ``step_ms``.
"""
from __future__ import annotations

import argparse
import bisect
import heapq
import json
import re
import sys
from pathlib import Path
from typing import Dict, Iterable, List, Tuple

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import trace  # noqa: E402

STAGES = ("bottom_mlp", "embed", "combine", "interaction", "top_mlp")
_SCOPE = re.compile(r"(?:^|/)(" + "|".join(STAGES) + r")(?=/|$)")
_INSTR = re.compile(r'^\s*(?:ROOT )?%?([\w.\-]+) = .*?op_name="([^"]*)"')


def stage_map(hlo_texts: Iterable[str]) -> Dict[str, str]:
    """HLO instruction name -> stage, from compiled modules' text."""
    out: Dict[str, str] = {}
    for text in hlo_texts:
        for line in text.splitlines():
            m = _INSTR.match(line)
            if m:
                s = _SCOPE.search(m.group(2))
                stage = s.group(1) if s else "unscoped"
                if out.setdefault(m.group(1), stage) != stage:
                    out[m.group(1)] = "unknown"
    return out


def exclusive(ops: List[Tuple[float, float, str]]) -> Dict[str, float]:
    """Seconds per label of ``(start, end, label)`` intervals, each instant
    of their union counted once, for the interval that started last among
    those covering it (the shorter one where two start together)."""
    ops = sorted(ops)
    bounds = sorted({t for a, b, _ in ops for t in (a, b)})
    out: Dict[str, float] = {}
    live: list = []
    i = 0
    for x, y in zip(bounds, bounds[1:]):
        while i < len(ops) and ops[i][0] <= x:
            a, b, label = ops[i]
            heapq.heappush(live, (-a, i, b, label))
            i += 1
        while live and live[0][2] <= x:
            heapq.heappop(live)
        if live:
            label = live[0][3]
            out[label] = out.get(label, 0.0) + (y - x)
    return out


def stage_ms(profile, step_modules, chips: int,
             stages: Dict[str, str]) -> Dict[int, dict]:
    """Per chip: steps counted, mean step ms, and ms per step of each
    stage and of ``idle``.  ``profile``: a ``jax.profiler.ProfileData``."""
    prefixes = tuple(f"{m}(" for m in step_modules)
    out: Dict[int, dict] = {}
    for plane in profile.planes:
        idx = plane.name[len(trace.DEVICE_PREFIX):]
        if (not plane.name.startswith(trace.DEVICE_PREFIX)
                or not idx.isdigit() or int(idx) >= chips):
            continue
        lines = {ln.name: trace._events(ln) for ln in plane.lines}
        steps = sorted((a, b) for n, a, b in lines.get("XLA Modules", [])
                       if n.startswith(prefixes))
        if not steps:
            continue
        starts = [a for a, _ in steps]
        ops = []
        for n, a, b in lines.get("XLA Ops", []):
            k = bisect.bisect_right(starts, a) - 1
            if k >= 0 and a < steps[k][1]:
                ops.append((a, min(b, steps[k][1]),
                            stages.get(trace.op_label(n), "unscoped")))
        per = exclusive(ops)
        step_s = sum(b - a for a, b in steps)
        per["idle"] = step_s - sum(per.values())
        n = len(steps)
        out[int(idx)] = {"steps": n, "step_ms": 1e3 * step_s / n,
                         **{k: 1e3 * v / n for k, v in sorted(per.items())}}
    return out


def step_texts(program) -> List[str]:
    """The compiled serve step of each of the mix's buckets, as text."""
    import jax.numpy as jnp
    b = program.binding
    out = []
    for bucket in program.batcher.buckets():
        reqs = [program.runtime.warmup_factory(i, bucket.pooling)
                for i in range(bucket.batch)]
        jb = {k: jnp.asarray(v) for k, v in program.pad(reqs, bucket).items()}
        out.append(b.steps[b.active].lower(b.params, b.state, jb)
                   .compile().as_text())
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    from bench import run as bench_run
    found: dict = {}

    def build(make):
        program = make()
        found["stages"] = stage_map(step_texts(program))
        return program

    def reduce_and_split(path, modules, chips):
        from jax.profiler import ProfileData
        profile = ProfileData.from_file(path)
        found["per_chip"] = stage_ms(profile, modules, chips,
                                     found["stages"])
        return trace.reduce_profile(profile, modules, chips)

    trace.reduce = reduce_and_split          # read while the profile exists
    try:
        result, _ = bench_run.run(bench_run.parse_args(
            ["--workload", args.workload, "--seed", str(args.seed),
             "--seconds", str(args.seconds), "--trace", "1"]), build=build)
    except SystemExit as e:
        print(e, file=sys.stderr)
        return 2
    print(json.dumps({
        "workload": args.workload, "seed": args.seed,
        "correct": result["correct"], "failed": result["failed"],
        "metrics": {k: v["value"] for k, v in result["metrics"].items()},
        "device": result["device"], "breakdown": result.get("breakdown"),
        "stages": found.get("per_chip")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
