"""Ahead-of-time compiles of the serving path's Pallas kernels for a
described (not attached) TPU v5e, at the paper's RMC3 widths: 8 tables of
2^20 rows, D=64, pooling 8, serve_p99 batch 512, fp32 and int8 stores.
Tables and ``x`` have the engine's stored row width (``store_dim``, D
padded to 128 lanes); the pooled tiles the resume and the interaction see
carry the logical D.

The TPU compiler refuses what interpret mode accepts — block shapes off the
(8, 128) tiling, vector loads from SMEM, in-kernel scatters, too much SMEM
or VMEM — so these catch a kernel the chip would refuse before any chip
time is spent.  A compile that passes is not a chip run.

The topology is described inside a module fixture (never at import), and
the persistent compilation cache is off around these compiles: an entry
written for a described chip cannot be read back without one.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import sls as sls_ops
from repro.core.paging import PagingConfig
from repro.kernels import ops

B, G, L, D = 512, 8, 8, 64      # serve_p99 batch, RMC3 tables/pooling/dim
W = PagingConfig(total_rows=1, dim=D, n_shards=1).store_dim   # 128
ROWS = 1 << 20                  # rows of one RMC3 table
HOT_ROWS = 1 << 14
# the largest power-of-two batch whose gather-once staging fits the
# engine's default 4 MiB dedup budget (PIFSEmbeddingEngine._resolve_dedup,
# rows staged W wide in fp32): split B*G*L*W*4 = 4 MiB at B=128; fused
# 2*B*G*L*W*4 + 2*BB*(G+1)*W*4 = 2.3 MiB at B=32 and 4.6 MiB at B=64.
# dedup resolves off above these, so these are the widths that run
SPLIT_DEDUP_B, FUSED_DEDUP_B = 128, 32
STORES = {"fp32": jnp.float32, "int8": jnp.int8}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text


def _spec(sharding):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    return s


@pytest.mark.parametrize("store", sorted(STORES))
@pytest.mark.parametrize("dedup", [False, True])
def test_masked_sls_compiles_for_v5e(one_chip, store, dedup):
    s = _spec(one_chip)
    nb = (SPLIT_DEDUP_B if dedup else B) * G
    scaled = store == "int8"

    def f(table, rows, owned, w, *scales):
        return sls_ops.masked_partial_sls_dense(
            table, rows, owned, w, impl="pallas", interpret=False,
            scales=scales[0] if scaled else None, out_dtype=jnp.float32,
            dedup=dedup)

    args = [s((ROWS, W), STORES[store]), s((nb, L), jnp.int32),
            s((nb, L), jnp.bool_), s((nb, L), jnp.float32)]
    if scaled:
        args.append(s((nb, L), jnp.float32))
    _compile(f, *args)


def _front_end_args(s, store, b):
    args = [s((ROWS, W), STORES[store]), s((HOT_ROWS, W), jnp.float32),
            s((b, W), jnp.float32), s((b, G, L), jnp.int32),
            s((b, G, L), jnp.bool_), s((b, G, L), jnp.bool_),
            s((b, G, L), jnp.float32)]
    if store == "int8":
        args.append(s((b, G, L), jnp.float32))
    return args


@pytest.mark.parametrize("store", sorted(STORES))
@pytest.mark.parametrize("dedup", [False, True])
@pytest.mark.parametrize("stage", ["fused", "partial_pool"])
def test_fused_front_end_compiles_for_v5e(one_chip, store, dedup, stage):
    fn = (sls_ops.fused_front_end_dense if stage == "fused"
          else sls_ops.fused_partial_pool_dense)

    def f(cold, hot, x, rows, owned, is_hot, w, *scales):
        return fn(cold, hot, x, rows, owned, is_hot, weights=w,
                  scales=scales[0] if scales else None, impl="pallas",
                  interpret=False, dedup=dedup)

    b = FUSED_DEDUP_B if dedup else B
    _compile(f, *_front_end_args(_spec(one_chip), store, b))


def test_fused_resume_compiles_for_v5e(one_chip):
    s = _spec(one_chip)
    _compile(lambda c, h: sls_ops.fused_resume_dense(
        c, h, impl="pallas", interpret=False),
        s((B, G + 1, D), jnp.float32), s((B, G + 1, D), jnp.float32))


def test_dot_interaction_compiles_for_v5e(one_chip):
    s = _spec(one_chip)
    _compile(lambda f: ops.dot_interaction(f, impl="pallas", interpret=False),
             s((B, G + 1, D), jnp.float32))


@pytest.mark.parametrize("front_end", ["split", "fused"])
def test_dedup_boundaries_match_engine_budget(front_end):
    """The dedup batches compiled above are the largest the engine resolves
    'on' at RMC3's stored width; twice them falls back to the plain path."""
    from repro.core.pifs import engine_for_tables
    from repro.distributed.sharding import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    eng, _ = engine_for_tables([64] * G, dim=D, mesh=mesh)
    assert eng.cfg.store_dim == W
    state = eng.init_state(jax.random.PRNGKey(0))
    b0, blocks = ((SPLIT_DEDUP_B, None) if front_end == "split"
                  else (FUSED_DEDUP_B, 32))
    got = [eng._resolve_dedup(("boundary", b), "on", state,
                              jnp.zeros((b, G, L), jnp.int32),
                              fused_blocks=blocks) for b in (b0, 2 * b0)]
    assert got == [True, False]
