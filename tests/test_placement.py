"""Placement guards behind the zero-retrace contract.

jit keys its cache on input shardings, so two things retrace every compiled
serve plan after maintenance: a mesh whose axes are not Auto (the models'
sharding constraints then fail outright), and an EngineState leaf that
comes back uncommitted or on a sharding other than ``state_shardings()``.
A serve-step argument placed other than its executable takes it is copied
on every call instead: ``plan_stats()["moved_inputs"]`` counts those.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import DLRMConfig, get_config, reduced
from repro.core.paging import initial_page_table
from repro.core.pifs import engine_for_tables
from repro.distributed.sharding import make_mesh
from repro.launch.mesh import make_test_mesh
from repro.models import dlrm as dlrm_mod
from repro.models import params as prm
from repro.models import recsys as rec_mod


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


def _serving(arch, shape):
    """A bound model, one 8-row batch of real features, and the plain
    serve step: jitted with no input shardings, as the binding's were."""
    from repro.serving import (ArrivalConfig, Bucket, LoadConfig, bind_model,
                               closed_loop_factory, make_padder)
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 host devices")
    cfg = reduced(get_config(arch))
    mesh = make_mesh(shape, ("data", "model"))
    binding = bind_model(cfg, mesh)
    dlrm = isinstance(cfg, DLRMConfig)
    make = closed_loop_factory(cfg, LoadConfig(
        n_requests=8, arrival=ArrivalConfig(rate_qps=100.0, seed=1), seed=1))
    bucket = Bucket(8, cfg.pooling if dlrm else 1)
    batch = make_padder(cfg)([make(i, i, 0.0) for i in range(8)], bucket)
    if dlrm:
        specs = dlrm_mod.model_specs(cfg, mesh)
        plain = jax.jit(dlrm_mod.make_serve_step(cfg, binding.engine, mesh))
    else:
        specs = rec_mod.model_specs(cfg, mesh)
        offs = rec_mod.build_engine(cfg, mesh)[1]
        plain = jax.jit(rec_mod.make_serve_step(cfg, binding.engine, offs,
                                                mesh))
    return binding, mesh, batch, prm.shardings(specs, mesh), plain


def _uncommitted(tree):
    return jax.tree.map(lambda a: jnp.asarray(np.asarray(a)), tree)


@pytest.mark.parametrize("shape", [(1, 4), (2, 4)])
@pytest.mark.parametrize("arch", ["rmc1", "dcn-v2"])
def test_serve_step_arguments_arrive_placed(arch, shape):
    """bind_model commits every parameter to its spec's sharding, and
    execute stages the batch where the step takes it: after warm-up no
    step argument is moved on a call, and the scores equal, bit for bit,
    the plain step's on uncommitted inputs, which jit copies from device 0
    onto the mesh."""
    binding, mesh, batch, shardings, plain = _serving(arch, shape)
    for leaf, want in zip(jax.tree.leaves(binding.params),
                          jax.tree.leaves(shardings)):
        assert leaf.committed
        assert leaf.sharding == want, (leaf.sharding, want)
    with mesh:
        binding.execute(batch)                       # warm-up
        got = binding.execute(batch)
        assert binding.plan_stats()["moved_inputs"] == 0
        want = plain(_uncommitted(binding.params), binding.state,
                     {k: jnp.asarray(v) for k, v in batch.items()})
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_moved_inputs_counts_uncommitted_params():
    """The counter sees what it is there for: the plain step, given the
    parameters uncommitted on device 0, takes them replicated over the
    four devices, so each parameter leaf is moved on every call."""
    binding, mesh, batch, _, plain = _serving("rmc1", (1, 4))
    binding.params = _uncommitted(binding.params)
    binding.steps["full"] = plain
    with mesh:
        binding.execute(batch)
    assert (binding.plan_stats()["moved_inputs"]
            == len(jax.tree.leaves(binding.params)))
