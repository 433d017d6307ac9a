"""A CPU rehearsal of whole runs: the driver's open and closed loops drive
RMC3's widths (small tables) through the program for about a second, and the
result line keeps the contract."""
import json
import os
import subprocess
import sys

import jax
import pytest

from bench import run as bench_run
from bench.tests.conftest import BENCH, mix, tiny_cell

KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


def cpu_run(cell_spec, seconds=1.0, seed=2**31 + 11, build=None):
    args = bench_run.parse_args(["--workload", "tiny", "--seed", str(seed),
                                 "--seconds", str(seconds), "--trace", "0"])
    result, info = bench_run.run(args, require_tpu=False,
                                 cell_spec=cell_spec, build=build)
    json.dumps(result)                    # one JSON object, as printed
    return result, info


@pytest.mark.parametrize("traffic,loop", [
    ("zipf-poisson-14400", {"rate_per_s": 400}),
    ("random-closed-2048", {"outstanding": 1024, "pool": 4096}),
])
def test_open_and_closed_loops_keep_the_contract(tiny_rmc3, traffic, loop):
    result, info = cpu_run(tiny_cell(tiny_rmc3, mix(traffic, **loop)))
    assert list(result) == KEYS
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    for name in ("p50_ms", "p99_ms", "scored_per_s", "setup_s"):
        assert result["metrics"][name]["value"] > 0
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["count"] == 1
    assert list(result["checks"]) == ["score_gap"]
    assert info["requests_compared"] >= 1
    assert info["window_compiles"] == 0 and info["window_plan_traces"] == 0


def test_failed_counts_requests_the_queue_refused(tiny_rmc3):
    def tiny_queue(make):
        program = make()
        program.queue_capacity = 8
        return program

    cell = tiny_cell(tiny_rmc3, mix("zipf-poisson-14400", rate_per_s=4000))
    result, info = cpu_run(cell, build=tiny_queue)
    assert info["dropped"] > 0
    assert result["failed"] == info["dropped"]
    assert result["correct"] is True        # refused is not wrong


def test_run_refuses_a_cpu_platform_and_prints_no_result():
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         spec["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_fewer_devices_than_the_cell_asks_for_is_refused(tiny_rmc3):
    spec, cell, model, traffic = tiny_cell(
        tiny_rmc3, mix("zipf-poisson-14400", rate_per_s=400), chips=16)
    args = bench_run.parse_args(["--workload", "tiny", "--seed", "1",
                                 "--seconds", "1", "--trace", "0"])
    with pytest.raises(SystemExit, match="needs 16 chips"):
        bench_run.run(args, require_tpu=False, devices=jax.devices()[:1],
                      cell_spec=(spec, cell, model, traffic))
