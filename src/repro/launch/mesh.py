"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 *before* any jax import,
and smoke tests must keep seeing 1 device.
"""
from __future__ import annotations

from repro.distributed.sharding import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips.  Multi-pod adds a leading
    pure-DP "pod" axis: (pod=2, data=16, model=16) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(n_devices: int = 8, tp: int = 4):
    """Small mesh for CPU smoke tests (same logical axes)."""
    return make_mesh((n_devices // tp, tp), ("data", "model"))
