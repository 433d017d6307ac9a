"""Small cells that the CPU can run, for the benchmark's own tests."""
import os

# the same virtual device count as tests/conftest.py: a four-chip cell runs
# on four of them
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import copy  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import pytest  # noqa: E402

BENCH = Path(__file__).resolve().parents[1]
if str(BENCH.parent / "src") not in sys.path:
    sys.path.insert(0, str(BENCH.parent / "src"))


def config(name: str, **changes) -> dict:
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    cfg.update(changes)
    return cfg


def mix(name: str, **load) -> dict:
    m = json.loads((BENCH / "traffic" / f"{name}.json").read_text())
    m["load"].update(load)
    return m


def tiny_cell(model: dict, traffic: dict, chips: int = 1, name="tiny"):
    """(spec, cell, model, mix) as ``bench.run.load_cell`` returns them."""
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    spec = copy.deepcopy(spec)
    for group in ("end_to_end", "per_layer"):
        for m in spec[group]:
            m.pop("workloads", None)
    cell = {"name": name, "config": model["name"], "traffic": "tiny",
            "chips": chips, "why": "test"}
    return spec, cell, model, traffic


@pytest.fixture
def tiny_rmc3():
    """RMC3's widths, 4,096 rows per table."""
    return config("rmc3", emb_num=4096)


@pytest.fixture
def tiny_rmc4_t32():
    """RMC4's widths on a (1, 4) mesh, 8 tables of 2,048 rows."""
    return config("rmc4-t32", emb_num=2048, n_tables=8)
