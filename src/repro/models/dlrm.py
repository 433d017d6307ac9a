"""DLRM (paper Fig. 1 / Table I): bottom MLP -> PIFS embedding lookup ->
pairwise-dot feature interaction -> top MLP -> CTR logit.

The embedding stage is the PIFSEmbeddingEngine: tables row-sharded over the
`model` axis (the "CXL memory pool"), partial SLS near the data, hot tier
replicated.  The interaction stage uses the Pallas kernel on TPU and its jnp
oracle on CPU.

Everything is a pure function over (params, engine_state, batch); batch =
{"dense": (B, n_dense) float, "indices": (B, T, L) int32} with T tables and
L = pooling lookups per bag.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.configs.base import DLRMConfig
from repro.core.pifs import PIFSEmbeddingEngine, engine_for_tables
from repro.kernels import ops as kernel_ops
from repro.models.layers import mlp_apply, mlp_specs
from repro.models.params import Spec


def build_engine(cfg: DLRMConfig, mesh: Mesh, hot_fraction: float = 0.05,
                 dtype=jnp.float32, storage: str = "fp32",
                 dedup: str = "off",
                 ) -> Tuple[PIFSEmbeddingEngine, np.ndarray]:
    """``storage='int8'`` selects the quantized cold tier (serving-only:
    the int8 store is not differentiable — train with fp32).  ``dedup``
    sets the engine default for gather-once duplicate coalescing."""
    vocabs = [cfg.emb_num] * cfg.n_tables
    return engine_for_tables(vocabs, cfg.emb_dim, mesh,
                             hot_fraction=hot_fraction, dtype=dtype,
                             storage=storage, dedup=dedup)


def model_specs(cfg: DLRMConfig, mesh: Mesh, dtype=jnp.float32) -> dict:
    d = cfg.emb_dim
    F = cfg.n_tables + 1                       # pooled tables + bottom-MLP out
    n_inter = F * (F - 1) // 2
    bot = (cfg.n_dense,) + cfg.bottom_mlp
    top_in = n_inter + d
    top = (top_in,) + cfg.top_mlp
    specs = {
        "bottom": mlp_specs(bot, dtype=dtype),
        "top": mlp_specs(top, dtype=dtype),
    }
    if cfg.bottom_mlp[-1] != d:
        # Table I widths don't always end at emb_dim (RMC1: 128 vs 64);
        # a linear projection aligns the dense feature with the embeddings
        specs["bot_proj"] = Spec((cfg.bottom_mlp[-1], d), dtype, P())
    return specs


def forward(params: dict, engine: PIFSEmbeddingEngine, state,
            batch: Dict[str, jax.Array], cfg: DLRMConfig,
            mode: str = "pifs", interaction_impl: str = "jnp",
            impl: str = "jnp", block_l: int = 8,
            dedup: Optional[str] = None,
            front_end: str = "split",
            tiers: str = "all") -> jax.Array:
    """Returns CTR logits (B,).

    ``impl``/``block_l`` select the engine's SLS datapath (jnp vs the
    bag-tiled Pallas kernel); ``dedup`` the gather-once duplicate
    coalescing knob (off/auto/on, None = engine default) — bit-exact
    either way.  An optional ``batch["weights"]`` (B, T, L)
    carries per-lookup SLS weights — the serving batcher uses weight-0
    entries to pad variable-pooling bags to a shape bucket exactly.

    ``front_end='fused'`` routes lookup + feature stacking + dot
    interaction through the engine's fused front end
    (``engine.lookup_interact``): the pooled (B, F, d) features stay in
    VMEM from the SLS accumulate through the interaction matmul.  On a
    tp-sharded mesh (and in pond mode) the engine resolves ``fused_tp``
    — each shard partial-pools its owned rows and only the small (B, F,
    d) cold tile is psum'd between the kernel halves (bit-identical
    logits vs split for pifs/beacon; the resolution is recorded in
    ``engine.plan_stats()['front_end']``).

    ``tiers='hot_only'`` is the brown-out rung: embedding lookups read the
    replicated hot tier only (cold contributions zero-filled, zero
    collectives) — NOT bit-exact; only the split path supports it, so it
    forces ``front_end='split'``.
    """
    if front_end not in PIFSEmbeddingEngine.FRONT_END_MODES:
        raise ValueError(f"unknown front_end {front_end!r}")
    if tiers != "all":
        front_end = "split"                    # fused path is all-tiers only
    dense, idx = batch["dense"], batch["indices"]
    B = dense.shape[0]
    x_bot = mlp_apply(params["bottom"], dense, len(cfg.bottom_mlp),
                      final_act=True)
    if "bot_proj" in params:
        x_bot = x_bot @ params["bot_proj"]                  # (B, d)
    # dense towers use the full (dp x tp) mesh, not just dp (see
    # recsys._constrain_full_batch)
    from repro.models.recsys import _constrain_full_batch
    if front_end == "fused":
        inter = engine.lookup_interact(
            state, idx, x_bot, weights=batch.get("weights"), mode=mode,
            impl=impl, block_l=block_l, dedup=dedup, front_end="fused")
        inter = _constrain_full_batch(inter, engine)        # (B, P)
    else:
        pooled = engine.lookup(state, idx, weights=batch.get("weights"),
                               mode=mode, impl=impl, block_l=block_l,
                               dedup=dedup, tiers=tiers)    # (B, T, d)
        pooled = _constrain_full_batch(pooled, engine)
        feats = jnp.concatenate([x_bot[:, None, :], pooled],
                                axis=1)                     # (B, F, d)
        inter = kernel_ops.dot_interaction(feats, impl=interaction_impl)
    z = jnp.concatenate([x_bot, inter], axis=-1)
    logit = mlp_apply(params["top"], z, len(cfg.top_mlp))
    return logit[:, 0]


def loss_fn(params, engine, state, batch, cfg, mode="pifs",
            interaction_impl: str = "jnp") -> jax.Array:
    logits = forward(params, engine, state, batch, cfg, mode=mode,
                     interaction_impl=interaction_impl)
    y = batch["labels"].astype(jnp.float32)
    return jnp.mean(
        jnp.maximum(logits, 0) - logits * y + jnp.log1p(jnp.exp(-jnp.abs(logits))))


def make_train_step(cfg: DLRMConfig, engine: PIFSEmbeddingEngine, mesh: Mesh,
                    optimizer, emb_optimizer, mode: str = "pifs",
                    interaction_impl: str = "jnp"):
    """Joint step: dense params via `optimizer`, embedding storage via
    `emb_optimizer` (row-wise adagrad by convention).  The embedding gradient
    flows through the engine lookup (gather -> scatter-add under AD) and
    arrives sharded exactly like the storage — no gradient communication for
    the cold shards beyond what the lookup itself psums."""
    def step(params, emb_state, opt_state, emb_opt_state, batch):
        def full_loss(p, cold, hot):
            st = dataclasses.replace(emb_state, cold=cold, hot=hot)
            return loss_fn(p, engine, st, batch, cfg, mode=mode,
                           interaction_impl=interaction_impl)

        loss, grads = jax.value_and_grad(full_loss, argnums=(0, 1, 2))(
            params, emb_state.cold, emb_state.hot)
        gp, gcold, ghot = grads
        new_params, new_opt = optimizer.update(gp, opt_state, params)
        new_state, new_emb_opt = engine.optimizer_step(
            emb_optimizer, emb_state, gcold, ghot, emb_opt_state)
        return new_params, new_state, new_opt, new_emb_opt, {"loss": loss}
    return step


def make_serve_step(cfg: DLRMConfig, engine: PIFSEmbeddingEngine, mesh: Mesh,
                    mode: str = "pifs", interaction_impl: str = "jnp",
                    impl: str = "jnp", block_l: int = 8,
                    dedup: Optional[str] = None,
                    front_end: str = "split",
                    tiers: str = "all"):
    def step(params, emb_state, batch):
        logits = forward(params, engine, emb_state, batch, cfg, mode=mode,
                         interaction_impl=interaction_impl, impl=impl,
                         block_l=block_l, dedup=dedup, front_end=front_end,
                         tiers=tiers)
        return jax.nn.sigmoid(logits)
    return step


def input_specs(cfg: DLRMConfig, batch: int, mesh: Mesh, with_labels: bool
                ) -> Dict[str, jax.ShapeDtypeStruct]:
    T, L = cfg.n_tables, cfg.pooling
    out = {
        "dense": jax.ShapeDtypeStruct((batch, cfg.n_dense), jnp.float32),
        "indices": jax.ShapeDtypeStruct((batch, T, L), jnp.int32),
    }
    if with_labels:
        out["labels"] = jax.ShapeDtypeStruct((batch,), jnp.int32)
    return out


def input_pspecs(cfg: DLRMConfig, mesh: Mesh, with_labels: bool) -> Dict[str, P]:
    dp = ("pod", "data") if "pod" in mesh.axis_names else (
        ("data",) if "data" in mesh.axis_names else None)
    out = {"dense": P(dp, None), "indices": P(dp, None, None)}
    if with_labels:
        out["labels"] = P(dp)
    return out
