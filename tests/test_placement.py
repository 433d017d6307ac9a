"""Placement guards behind the zero-retrace contract.

jit keys its cache on input shardings, so two things retrace every compiled
serve plan after maintenance: a mesh whose axes are not Auto (the models'
sharding constraints then fail outright), and an EngineState leaf that
comes back uncommitted or on a sharding other than ``state_shardings()``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.paging import initial_page_table
from repro.core.pifs import engine_for_tables
from repro.launch.mesh import make_test_mesh


def test_make_test_mesh_has_auto_axes():
    n = len(jax.devices())
    tp = 4 if n >= 4 else 1
    mesh = make_test_mesh(n, tp)
    assert mesh.axis_names == ("data", "model")
    assert tuple(mesh.axis_types) == (jax.sharding.AxisType.Auto,) * 2


def _observed(eng, state):
    idx = jax.random.randint(jax.random.PRNGKey(1), (8, 2, 4), 0,
                             eng.cfg.padded_rows).astype(jnp.int32)
    return eng.observe(state, idx)


def _produce(eng, state, producer):
    c = eng.cfg
    if producer == "init_state":
        return state
    if producer == "from_dense":
        return eng.from_dense(eng.to_dense(state), initial_page_table(c))
    if producer == "pack_state":
        return eng.pack_state(*eng.export_state(state))
    if producer == "observe":
        return _observed(eng, state)
    if producer == "plan_and_migrate":
        return eng.plan_and_migrate(_observed(eng, state))[0]
    if producer == "apply_deltas":
        rows = jnp.asarray([0, 5, 17, -1], jnp.int32)
        return eng.apply_deltas(state, rows,
                                jnp.full((4, c.dim), 1e-3, jnp.float32))
    if producer == "requant_hot_pages":
        moved = eng.plan_and_migrate(_observed(eng, state))[0]
        return eng.requant_hot_pages(moved, jnp.arange(4, dtype=jnp.int32))
    if producer == "write_page":
        ps = c.page_size
        return eng.write_page(state, 0, jnp.zeros((ps, c.dim), eng.cold_dtype),
                              jnp.zeros((ps, c.dim), jnp.float32), 1.0)
    raise ValueError(producer)


@pytest.mark.parametrize("storage", ["fp32", "int8"])
@pytest.mark.parametrize("producer", [
    "init_state", "from_dense", "pack_state", "observe", "plan_and_migrate",
    "apply_deltas", "requant_hot_pages", "write_page"])
def test_engine_state_leaves_committed_to_state_shardings(mesh, storage,
                                                          producer):
    """Every method that produces an EngineState returns each leaf on
    exactly ``state_shardings()`` — the int8 store runs the typed
    ``_migrate_quantized`` path under ``plan_and_migrate``."""
    eng, _ = engine_for_tables([300, 200], 16, mesh, storage=storage)
    with mesh:
        state = eng.init_state(jax.random.PRNGKey(0))
        out = _produce(eng, state, producer)
    for leaf, want in zip(jax.tree.leaves(out),
                          jax.tree.leaves(eng.state_shardings())):
        assert leaf.committed
        assert leaf.sharding == want, (leaf.sharding, want)


def test_init_state_equals_dense_draw(mesh):
    """init_state draws each shard's pages in place, yet equals packing
    the one dense draw it replaces, bit for bit."""
    for storage in ("fp32", "int8"):
        eng, _ = engine_for_tables([300, 200], 16, mesh, storage=storage)
        c = eng.cfg
        key = jax.random.PRNGKey(7)
        got = eng.init_state(key)
        dense = jax.random.normal(key, (c.padded_rows, c.dim)) * 0.01
        want = eng.from_dense(dense, initial_page_table(c))
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
