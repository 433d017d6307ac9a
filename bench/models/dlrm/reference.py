"""Plain DLRM reference: the scores a served request should get.

Straight ``jax.numpy`` over the logical tables, with no engine, paging,
tiers, kernels or batching, and nothing imported from the program.  The
weights and tables are made again from the seed by the program's documented
initialisation, not read from the program:

* ``key = PRNGKey(seed)``; ``k_params, k_state = split(key)``.
* Dense weights are the leaves of ``{"bot_proj"?, "bottom", "top"}`` in
  sorted-key order (``layer{i}_b`` before ``layer{i}_w``), one key each from
  ``split(k_params, n_leaves)``: a matrix of fan-in ``a`` is
  ``normal(k, (a, b)) / sqrt(a)``, a bias is zeros.  ``bot_proj`` exists
  only where the bottom MLP does not end at the embedding width.
* Table ``t``'s row ``r`` is row ``t * P + r`` of
  ``normal(k_state, (T * P, D)) * 0.01``, with ``P`` the rows per table
  rounded up to whole 4096-byte pages of ``D`` float32 values.

The forward (PIFS-Rec Fig. 1): bottom MLP with ReLU after every layer, the
projection to ``D``, sum-pooled bags, the pairwise dots of the ``F = T + 1``
features (strict lower triangle, row-major), the top MLP with ReLU between
layers, and a sigmoid.

Precisions: ``"default"`` is float32 values with matmuls at the backend's
default precision (on a TPU one bfloat16 pass with float32 accumulation), the
precision the configuration states and the reference ``correct`` is decided
against; ``"highest"`` is float32 with matmuls at ``Precision.HIGHEST``;
``"bfloat16"`` is the control: weights, rows and activations in bfloat16.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

PAGE_BYTES = 4096
TABLE_SCALE = 0.01
PRECISIONS = ("default", "highest", "bfloat16")


def rows_per_table(cfg: dict) -> int:
    page = max(1, PAGE_BYTES // (cfg["emb_dim"] * 4))
    return -(-cfg["emb_num"] // page) * page


def _weight_shapes(cfg: dict) -> List[Tuple[str, Tuple[int, ...]]]:
    """(name, shape) of every dense leaf, in the order keys are dealt."""
    d, T = cfg["emb_dim"], cfg["n_tables"]
    F = T + 1
    bot = [cfg["n_dense"]] + list(cfg["bottom_mlp"])
    top = [F * (F - 1) // 2 + d] + list(cfg["top_mlp"])
    tree = {"bottom": bot, "top": top}
    leaves = []
    if bot[-1] != d:
        leaves.append(("bot_proj", (bot[-1], d)))
    for name in sorted(tree):
        dims = tree[name]
        for i, (a, b) in enumerate(zip(dims[:-1], dims[1:])):
            leaves.append((f"{name}.layer{i}_b", (b,)))
            leaves.append((f"{name}.layer{i}_w", (a, b)))
    return sorted(leaves, key=lambda x: x[0].replace(".", "\x00"))


def make_weights(cfg: dict, seed: int) -> Dict[str, jax.Array]:
    k_params, _ = jax.random.split(jax.random.PRNGKey(seed), 2)
    leaves = _weight_shapes(cfg)
    keys = jax.random.split(k_params, len(leaves))
    out = {}
    for (name, shape), k in zip(leaves, keys):
        if len(shape) == 1:
            out[name] = jnp.zeros(shape, jnp.float32)
        else:
            scale = np.float32(1.0 / np.sqrt(shape[0]))
            out[name] = jax.random.normal(k, shape, jnp.float32) * scale
    return out


def make_tables(cfg: dict, seed: int, mesh) -> jax.Array:
    """(T * P, D) float32 logical rows, made on the device in one call and
    split by rows over ``mesh``'s one axis."""
    _, k_state = jax.random.split(jax.random.PRNGKey(seed), 2)
    shape = (cfg["n_tables"] * rows_per_table(cfg), cfg["emb_dim"])
    if shape[0] % mesh.size:
        raise ValueError(f"{shape[0]} rows do not split over {mesh.size}")

    def build(k):
        return jax.random.normal(k, shape, jnp.float32) * TABLE_SCALE

    return jax.jit(build, out_shardings=NamedSharding(mesh, P("rows")))(
        k_state)


def _gather(tables: jax.Array, gids: jax.Array, mesh) -> jax.Array:
    """Rows ``gids`` of row-split ``tables``: each device reads the rows it
    holds, the others add zeros."""
    per = tables.shape[0] // mesh.size

    def block(t, g):
        lo = jax.lax.axis_index("rows") * per
        local = g - lo
        mine = (local >= 0) & (local < per)
        rows = jnp.take(t, jnp.where(mine, local, 0), axis=0)
        return jax.lax.psum(jnp.where(mine[..., None], rows, 0), "rows")

    return jax.shard_map(block, mesh=mesh, in_specs=(P("rows"), P()),
                         out_specs=P())(tables, gids)


def _mlp(x, w, name: str, n: int, final_act: bool, prec):
    for i in range(n):
        x = jnp.matmul(x, w[f"{name}.layer{i}_w"].astype(x.dtype),
                       precision=prec) + w[f"{name}.layer{i}_b"].astype(x.dtype)
        if i < n - 1 or final_act:
            x = jnp.maximum(x, 0)
    return x


def forward(w: Dict[str, jax.Array], rows: jax.Array, dense: jax.Array,
            cfg: dict, precision: str = "highest") -> jax.Array:
    """Scores (B,) from gathered rows (B, T, L, D) and dense (B, n_dense)."""
    if precision not in PRECISIONS:
        raise ValueError(f"unknown precision {precision!r}")
    dt = jnp.bfloat16 if precision == "bfloat16" else jnp.float32
    prec = (jax.lax.Precision.HIGHEST if precision == "highest"
            else jax.lax.Precision.DEFAULT)
    x = _mlp(dense.astype(dt), w, "bottom", len(cfg["bottom_mlp"]), True,
             prec)
    if "bot_proj" in w:
        x = jnp.matmul(x, w["bot_proj"].astype(dt), precision=prec)
    pooled = jnp.sum(rows.astype(dt), axis=2)                    # (B, T, D)
    feats = jnp.concatenate([x[:, None, :], pooled], axis=1)     # (B, F, D)
    z = jnp.einsum("bfd,bgd->bfg", feats, feats, precision=prec)
    i, j = np.tril_indices(feats.shape[1], k=-1)
    top_in = jnp.concatenate([x, z[:, i, j]], axis=-1)
    logit = _mlp(top_in, w, "top", len(cfg["top_mlp"]), False, prec)[:, 0]
    return jax.nn.sigmoid(logit.astype(jnp.float32))


def scores(cfg: dict, seed: int, dense: np.ndarray, ids: np.ndarray,
           devices, precision: str = "default",
           block: int = 1024) -> np.ndarray:
    """Reference scores for requests ``dense`` (N, n_dense) and table-local
    ``ids`` (N, T, L), ``block`` requests at a time, on ``devices``."""
    mesh = Mesh(np.asarray(devices), ("rows",))
    w = make_weights(cfg, seed)
    tables = make_tables(cfg, seed, mesh)
    offs = (np.arange(cfg["n_tables"]) * rows_per_table(cfg))[None, :, None]

    @jax.jit
    def run(tables, w, dense, gids):
        return forward(w, _gather(tables, gids, mesh), dense, cfg, precision)

    n = len(dense)
    out = np.empty(n, np.float32)
    for a in range(0, n, block):
        b = min(n, a + block)
        pad = block - (b - a)
        d = np.pad(dense[a:b], ((0, pad), (0, 0)))
        g = np.pad((ids[a:b] + offs).astype(np.int32),
                   ((0, pad), (0, 0), (0, 0)))
        out[a:b] = np.asarray(run(tables, w, d, g))[:b - a]
    del tables
    return out


def compared(got: np.ndarray, want: np.ndarray) -> dict:
    """The numbers ``correct`` is decided by, each held to its limit."""
    return {"score_gap": float(np.max(np.abs(got.astype(np.float64)
                                             - want.astype(np.float64))))}
