import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

# NOTE: the lines above MUST precede every other import (jax locks the
# device count and platform on first init), which is why the docstring sits
# below them.  The dry-run is a CPU compile study: pinning the platform
# keeps this process and every cell subprocess (which inherits os.environ)
# off any attached accelerator, which only one process may hold.

_DOC = """Multi-pod dry-run: lower + compile every (architecture x
input-shape) cell on the production meshes and extract the roofline terms.

  single-pod  : (data=16, model=16)        = 256 chips
  multi-pod   : (pod=2, data=16, model=16) = 512 chips

Usage:
  python -m repro.launch.dryrun --arch llama3.2-3b --shape train_4k --mesh pod
      one cell, prints + writes JSON under results/dryrun/
  python -m repro.launch.dryrun --all [--mesh pod|multipod|both]
      orchestrates every cell in a fresh subprocess each (compile isolation),
      skipping cells whose JSON already exists (cache).

This module is the ONLY place that forces 512 host devices — smoke tests and
benchmarks see the real device count.
"""

import argparse
import json
import re
import subprocess
import sys
import time
from typing import Dict, Optional

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")

# TPU v5e hardware constants for the roofline terms
PEAK_FLOPS = 197e12          # bf16 FLOP/s per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link (~per-device effective)


_COLL_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*\(?([a-z0-9\[\],{}\s/]*?)\)?\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(", re.MULTILINE)
_SHAPE_RE = re.compile(r"(f64|f32|bf16|f16|f8e4m3fn|f8e5m2|s64|u64|s32|u32|"
                       r"s16|u16|s8|u8|pred)\[([\d,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{([^}]*)\}")
_GROUPS_V2_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")

_DTYPE_BYTES = {"f64": 8, "s64": 8, "u64": 8, "f32": 4, "s32": 4, "u32": 4,
                "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
                "f8e4m3fn": 1, "f8e5m2": 1, "s8": 1, "u8": 1, "pred": 1}


def _shape_bytes(sig: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(sig):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_stats(hlo_text: str) -> Dict[str, Dict[str, float]]:
    """Per-collective-kind wire bytes (per participating device, ring model).

    all-gather      : out x (G-1)/G      (each device receives the rest)
    reduce-scatter  : out x (G-1)        (ring: sends (G-1) output-sized chunks)
    all-reduce      : 2 x out x (G-1)/G  (reduce-scatter + all-gather)
    all-to-all      : out x (G-1)/G
    collective-permute : out
    """
    stats: Dict[str, Dict[str, float]] = {}
    for line in hlo_text.splitlines():
        m = re.search(
            r"=\s*(?:\([^)]*\)\s*)?([a-z0-9\[\],{}\s]*?)"
            r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|"
            r"collective-permute)(?:-start)?\(", line)
        if not m:
            continue
        kind = m.group(2)
        out_bytes = _shape_bytes(line.split("=")[0] + m.group(1))
        if out_bytes == 0:
            out_bytes = _shape_bytes(line)
        g = 1
        gm = _GROUPS_RE.search(line)
        if gm:
            first = gm.group(1).split("}")[0]
            g = max(1, first.count(",") + 1)
        else:
            gm2 = _GROUPS_V2_RE.search(line)
            if gm2:
                g = max(1, int(gm2.group(2)))
        if kind == "all-gather":
            wire = out_bytes * (g - 1) / max(g, 1)
        elif kind == "reduce-scatter":
            wire = out_bytes * (g - 1)
        elif kind == "all-reduce":
            wire = 2.0 * out_bytes * (g - 1) / max(g, 1)
        elif kind == "all-to-all":
            wire = out_bytes * (g - 1) / max(g, 1)
        else:
            wire = float(out_bytes)
        s = stats.setdefault(kind, {"count": 0, "wire_bytes": 0.0,
                                    "payload_bytes": 0.0})
        s["count"] += 1
        s["wire_bytes"] += wire
        s["payload_bytes"] += out_bytes
    return stats


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             out_dir: Optional[str] = None) -> Dict:
    import jax
    from repro.configs import get_config, iter_cells
    from repro.launch.cells import build_cell
    from repro.launch.mesh import make_production_mesh

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multipod"))
    n_chips = mesh.devices.size
    t0 = time.time()
    cell = build_cell(arch, shape_name, mesh)
    with mesh:
        jitted = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                         donate_argnums=cell.donate_argnums)
        lowered = jitted.lower(*cell.abstract_args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    # analytic pass with while-loop trip-count multipliers — XLA's
    # cost_analysis counts rolled scan bodies once (see hlo_stats.py)
    from repro.launch.hlo_stats import summarize
    summary = summarize(hlo)
    colls = summary.collectives
    del hlo

    flops_raw = float(cost.get("flops", 0.0))
    bytes_raw = float(cost.get("bytes accessed", 0.0))
    flops_total = summary.flops               # per device, loop-corrected
    # HBM traffic proxy: dot operand/output bytes (loop-corrected) — the
    # matmul share of traffic; elementwise fusions add a small constant
    # factor on top (documented in EXPERIMENTS.md §Roofline)
    bytes_total = summary.dot_bytes
    wire = summary.wire_bytes

    # XLA CPU upcasts bf16 tensors to f32 ("excess precision"), doubling the
    # byte counts of activations/grads that are bf16 on real TPU; halve the
    # byte-denominated terms for bf16-dtype models (flag recorded)
    model_dtype = str(getattr(get_config(arch), "dtype", "float32"))
    bf16_corr = 0.5 if model_dtype == "bfloat16" else 1.0

    compute_s = flops_total / PEAK_FLOPS
    memory_s = bytes_total * bf16_corr / HBM_BW
    collective_s = wire * bf16_corr / ICI_BW
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s}
    dominant = max(terms, key=terms.get)

    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "n_chips": int(n_chips),
        "ok": True,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
            "generated_code_bytes": int(
                getattr(mem, "generated_code_size_in_bytes", 0)),
        },
        "cost": {"flops": flops_total, "dot_bytes": bytes_total,
                 "xla_flops_raw": flops_raw,
                 "xla_bytes_raw": bytes_raw},
        "collectives": colls,
        "roofline": {
            **terms,
            "bf16_cpu_upcast_correction": bf16_corr,
            "dominant": dominant,
            "model_flops": cell.model_flops,
            "model_flops_per_chip": cell.model_flops / n_chips,
            "useful_flops_ratio": (cell.model_flops / n_chips / flops_total
                                   if flops_total else 0.0),
        },
    }
    # peak per-device bytes: arguments + temps must fit 16 GB
    rec["memory"]["total_bytes"] = (rec["memory"]["argument_bytes"]
                                    + rec["memory"]["temp_bytes"]
                                    + rec["memory"]["output_bytes"])
    rec["memory"]["fits_16gb"] = rec["memory"]["argument_bytes"] \
        + rec["memory"]["temp_bytes"] < 16e9

    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        path = os.path.join(out_dir, f"{arch}__{shape_name}__{mesh_kind}.json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def _cell_path(out_dir: str, arch: str, shape: str, mesh: str) -> str:
    return os.path.join(out_dir, f"{arch}__{shape}__{mesh}.json")


def orchestrate(mesh_kinds, out_dir: str, arch_filter=None,
                timeout_s: int = 3600) -> int:
    from repro.configs import iter_cells
    os.makedirs(out_dir, exist_ok=True)
    failures = 0
    for arch, shape, skip in iter_cells():
        if arch_filter and arch != arch_filter:
            continue
        for mk in mesh_kinds:
            path = _cell_path(out_dir, arch, shape, mk)
            if skip:
                with open(path, "w") as f:
                    json.dump({"arch": arch, "shape": shape, "mesh": mk,
                               "ok": True, "skipped": skip}, f, indent=1)
                print(f"[skip] {arch}:{shape}:{mk} — {skip}")
                continue
            if os.path.exists(path):
                with open(path) as f:
                    prev = json.load(f)
                if prev.get("ok"):
                    print(f"[cache] {arch}:{shape}:{mk}")
                    continue
            print(f"[run ] {arch}:{shape}:{mk} ...", flush=True)
            t0 = time.time()
            proc = subprocess.run(
                [sys.executable, "-m", "repro.launch.dryrun",
                 "--arch", arch, "--shape", shape, "--mesh", mk,
                 "--out", out_dir],
                capture_output=True, text=True, timeout=timeout_s,
                env={**os.environ, "PYTHONPATH": os.environ.get(
                    "PYTHONPATH", "src")})
            dt = time.time() - t0
            if proc.returncode != 0:
                failures += 1
                tail = proc.stderr.strip().splitlines()[-12:]
                with open(path, "w") as f:
                    json.dump({"arch": arch, "shape": shape, "mesh": mk,
                               "ok": False, "error": "\n".join(tail)},
                              f, indent=1)
                print(f"[FAIL] {arch}:{shape}:{mk} ({dt:.0f}s)\n  "
                      + "\n  ".join(tail))
            else:
                print(f"[ ok ] {arch}:{shape}:{mk} ({dt:.0f}s)")
    return failures


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="pod",
                    choices=["pod", "multipod", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=os.path.abspath(RESULTS_DIR))
    args = ap.parse_args()

    mesh_kinds = (["pod", "multipod"] if args.mesh == "both"
                  else [args.mesh])
    if args.all:
        failures = orchestrate(mesh_kinds, args.out, arch_filter=args.arch)
        sys.exit(1 if failures else 0)

    rec = run_cell(args.arch, args.shape, mesh_kinds[0], out_dir=args.out)
    print(json.dumps({k: v for k, v in rec.items()
                      if k not in ("collectives",)}, indent=1))
    print("collectives:", json.dumps(rec["collectives"], indent=1))


if __name__ == "__main__":
    main()
