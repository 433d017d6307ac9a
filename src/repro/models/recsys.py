"""Recsys architectures: SASRec, AutoInt, DCN-v2, BST.

All sparse-feature lookups go through the PIFSEmbeddingEngine (tables
row-sharded over `model`, hot tier replicated, partial SLS near the data).
Per-field / per-position embeddings are L=1 bags: indices (B, G, 1).

Model heads are small and replicated; the batch shards over dp.  The four
models share one train/serve/retrieval step factory; `forward` dispatches on
cfg.interaction:

  * "self-attn-seq"   (SASRec): causal self-attn over the item history;
                      next-item prediction with sampled softmax (pos/neg).
  * "self-attn"       (AutoInt): multi-head attention over field embeddings,
                      residual via W_res, relu; stacked; logit from flatten.
  * "cross"           (DCN-v2): x_{l+1} = x0 * (W x_l + b) + x_l cross tower
                      in parallel with a deep MLP tower; stacked combine.
  * "transformer-seq" (BST): [history || target] through a transformer block,
                      concat with profile features, MLP tower -> CTR.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.configs.base import RecConfig
from repro.core.pifs import PIFSEmbeddingEngine, engine_for_tables
from repro.models.layers import mlp_apply, mlp_specs
from repro.models.params import Spec


# ---------------------------------------------------------------------------
# Engine construction
# ---------------------------------------------------------------------------


def build_engine(cfg: RecConfig, mesh: Mesh, hot_fraction: float = 0.05,
                 dtype=jnp.float32, storage: str = "fp32",
                 dedup: str = "off",
                 ) -> Tuple[PIFSEmbeddingEngine, np.ndarray]:
    """``storage='int8'`` selects the quantized cold tier (serving-only);
    ``dedup`` sets the engine default for gather-once duplicate coalescing.

    The returned offsets are int64; lookups add them and downcast to int32
    on device, which is safe because engine_for_tables validates the whole
    padded address space fits int32 at construction (a silent-truncation
    regression is pinned in tests/test_pifs_engine.py).
    """
    return engine_for_tables(list(cfg.vocab_sizes), cfg.embed_dim, mesh,
                             hot_fraction=hot_fraction, dtype=dtype,
                             storage=storage, dedup=dedup)


def _constrain_full_batch(x: jax.Array, engine) -> jax.Array:
    """Re-shard a batch-leading tensor over (dp + tp) for the dense towers.

    The engine's lookup shards the batch over dp only (the tp axis holds the
    table shards); leaving the dense interaction/MLP compute in that layout
    makes every tp replica redundantly compute the same batch slice — a
    16x waste measured on dcn-v2 train_batch (EXPERIMENTS.md §Perf).  One
    cheap resharding here lets the dense towers use the full mesh.
    """
    axes, mesh = engine.axes, engine.mesh
    full = tuple(axes.dp) + (axes.tp,)
    spec = P(full, *([None] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(mesh, spec))


def _seq_lookup(engine, state, ids: jax.Array, offset: int, mode: str,
                dp_shard: bool = True, impl: str = "jnp",
                block_l: int = 8, dedup: Optional[str] = None) -> jax.Array:
    """(B, S) ids in table `offset` -> (B, S, D) per-position embeddings."""
    idx = (ids + offset)[..., None]          # (B, S, 1): one bag per position
    return engine.lookup(state, idx.astype(jnp.int32), mode=mode,
                         dp_shard=dp_shard, impl=impl, block_l=block_l,
                         dedup=dedup)


def _field_lookup(engine, state, ids: jax.Array, offsets: np.ndarray,
                  mode: str, dp_shard: bool = True, impl: str = "jnp",
                  block_l: int = 8, dedup: Optional[str] = None) -> jax.Array:
    """(B, F) per-field ids -> (B, F, D)."""
    idx = (ids + jnp.asarray(offsets, jnp.int32)[None, :])[..., None]
    return engine.lookup(state, idx.astype(jnp.int32), mode=mode,
                         dp_shard=dp_shard, impl=impl, block_l=block_l,
                         dedup=dedup)


# ---------------------------------------------------------------------------
# Tiny dense attention (seqs are 20-50 tokens; scores fit easily)
# ---------------------------------------------------------------------------


def _mha(p: dict, x: jax.Array, n_heads: int, causal: bool,
         kv: Optional[jax.Array] = None) -> jax.Array:
    b, s, d = x.shape
    kv = x if kv is None else kv
    sk = kv.shape[1]
    dh = p["wq"].shape[1] // n_heads
    q = (x @ p["wq"]).reshape(b, s, n_heads, dh)
    k = (kv @ p["wk"]).reshape(b, sk, n_heads, dh)
    v = (kv @ p["wv"]).reshape(b, sk, n_heads, dh)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(dh).astype(x.dtype)
    if causal:
        mask = jnp.tril(jnp.ones((s, sk), bool))
        sc = jnp.where(mask, sc, -1e30)
    a = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", a, v).reshape(b, s, n_heads * dh)
    return o @ p["wo"]


def _mha_specs(d_in: int, d_attn: int, d_out: int, dtype) -> dict:
    return {
        "wq": Spec((d_in, d_attn), dtype),
        "wk": Spec((d_in, d_attn), dtype),
        "wv": Spec((d_in, d_attn), dtype),
        "wo": Spec((d_attn, d_out), dtype),
    }


def _ln(x: jax.Array, g: jax.Array, b: jax.Array, eps: float = 1e-6):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


# ---------------------------------------------------------------------------
# Param specs per model
# ---------------------------------------------------------------------------


def model_specs(cfg: RecConfig, mesh: Mesh, dtype=jnp.float32) -> dict:
    d = cfg.embed_dim
    it = cfg.interaction
    if it == "self-attn-seq":        # SASRec
        blocks = []
        for _ in range(cfg.n_blocks):
            blocks.append({
                "attn": _mha_specs(d, d, d, dtype),
                "ln1_g": Spec((d,), dtype, init="ones"),
                "ln1_b": Spec((d,), dtype, init="zeros"),
                "ln2_g": Spec((d,), dtype, init="ones"),
                "ln2_b": Spec((d,), dtype, init="zeros"),
                "ffn_w1": Spec((d, d), dtype),
                "ffn_b1": Spec((d,), dtype, init="zeros"),
                "ffn_w2": Spec((d, d), dtype),
                "ffn_b2": Spec((d,), dtype, init="zeros"),
            })
        return {
            "pos_emb": Spec((cfg.seq_len, d), dtype, scale=0.02),
            "blocks": blocks,
            "ln_f_g": Spec((d,), dtype, init="ones"),
            "ln_f_b": Spec((d,), dtype, init="zeros"),
        }
    if it == "self-attn":            # AutoInt
        layers = []
        for _ in range(cfg.n_attn_layers):
            layers.append({
                "attn": _mha_specs(d, cfg.d_attn * cfg.n_heads, d, dtype),
                "w_res": Spec((d, d), dtype),
            })
        F = cfg.n_sparse
        return {"layers": layers,
                "head_w": Spec((F * d, 1), dtype),
                "head_b": Spec((1,), dtype, init="zeros")}
    if it == "cross":                # DCN-v2
        x0_dim = cfg.n_dense + cfg.n_sparse * d
        cross = []
        for _ in range(cfg.n_cross_layers):
            cross.append({"w": Spec((x0_dim, x0_dim), dtype,
                                    scale=1.0 / np.sqrt(x0_dim)),
                          "b": Spec((x0_dim,), dtype, init="zeros")})
        deep = mlp_specs((x0_dim,) + cfg.mlp_dims, dtype=dtype)
        head_in = x0_dim + cfg.mlp_dims[-1]
        return {"cross": cross, "deep": deep,
                "head_w": Spec((head_in, 1), dtype),
                "head_b": Spec((1,), dtype, init="zeros")}
    if it == "transformer-seq":      # BST
        S = cfg.seq_len + 1          # history + target
        block = {
            "attn": _mha_specs(d, d, d, dtype),
            "ln1_g": Spec((d,), dtype, init="ones"),
            "ln1_b": Spec((d,), dtype, init="zeros"),
            "ln2_g": Spec((d,), dtype, init="ones"),
            "ln2_b": Spec((d,), dtype, init="zeros"),
            "ffn_w1": Spec((d, 4 * d), dtype),
            "ffn_b1": Spec((4 * d,), dtype, init="zeros"),
            "ffn_w2": Spec((4 * d, d), dtype),
            "ffn_b2": Spec((d,), dtype, init="zeros"),
        }
        mlp_in = S * d + cfg.n_dense
        return {
            "pos_emb": Spec((S, d), dtype, scale=0.02),
            "blocks": [block] * cfg.n_blocks if cfg.n_blocks > 1 else [block],
            "mlp": mlp_specs((mlp_in,) + cfg.mlp_dims + (1,), dtype=dtype),
        }
    raise ValueError(f"unknown interaction {it!r}")


# ---------------------------------------------------------------------------
# Forward passes
# ---------------------------------------------------------------------------


def _sasrec_block(bp: dict, x: jax.Array) -> jax.Array:
    h = _ln(x, bp["ln1_g"], bp["ln1_b"])
    x = x + _mha(bp["attn"], h, n_heads=1, causal=True)
    h = _ln(x, bp["ln2_g"], bp["ln2_b"])
    f = jax.nn.relu(h @ bp["ffn_w1"] + bp["ffn_b1"]) @ bp["ffn_w2"] + bp["ffn_b2"]
    return x + f


def sasrec_encode(params, engine, state, seq_ids: jax.Array, cfg: RecConfig,
                  mode: str = "pifs", dp_shard: bool = True,
                  impl: str = "jnp", block_l: int = 8,
                  dedup: Optional[str] = None) -> jax.Array:
    """(B, S) history -> (B, S, D) causal representations."""
    x = _seq_lookup(engine, state, seq_ids, 0, mode, dp_shard,
                    impl=impl, block_l=block_l, dedup=dedup)  # (B, S, D)
    if dp_shard:
        x = _constrain_full_batch(x, engine)
    x = x * jnp.sqrt(cfg.embed_dim).astype(x.dtype) + params["pos_emb"]
    for bp in params["blocks"]:
        x = _sasrec_block(bp, x)
    return _ln(x, params["ln_f_g"], params["ln_f_b"])


def bst_forward(params, engine, state, batch, cfg: RecConfig,
                mode: str = "pifs", impl: str = "jnp",
                block_l: int = 8, dedup: Optional[str] = None) -> jax.Array:
    """batch: seq (B, S), target (B,), dense (B, n_dense) -> CTR logit (B,)."""
    seq, target = batch["seq"], batch["target"]
    B, S = seq.shape
    tokens = jnp.concatenate([seq, target[:, None]], axis=1)  # (B, S+1)
    x = _seq_lookup(engine, state, tokens, 0, mode, impl=impl,
                    block_l=block_l, dedup=dedup)
    x = _constrain_full_batch(x, engine)
    x = x + params["pos_emb"]
    for bp in params["blocks"]:
        h = _ln(x, bp["ln1_g"], bp["ln1_b"])
        x = x + _mha(bp["attn"], h, n_heads=cfg.n_heads, causal=False)
        h = _ln(x, bp["ln2_g"], bp["ln2_b"])
        f = (jax.nn.leaky_relu(h @ bp["ffn_w1"] + bp["ffn_b1"])
             @ bp["ffn_w2"] + bp["ffn_b2"])
        x = x + f
    flat = x.reshape(B, -1)
    z = jnp.concatenate([flat, batch["dense"]], axis=-1)
    n_mlp = len(cfg.mlp_dims) + 1
    return mlp_apply(params["mlp"], z, n_mlp, act="relu")[:, 0]


def autoint_forward(params, engine, state, batch, cfg: RecConfig,
                    offsets: np.ndarray, mode: str = "pifs",
                    impl: str = "jnp", block_l: int = 8,
                    dedup: Optional[str] = None) -> jax.Array:
    x = _field_lookup(engine, state, batch["fields"], offsets, mode,
                      impl=impl, block_l=block_l, dedup=dedup)  # (B,F,D)
    x = _constrain_full_batch(x, engine)
    for lp in params["layers"]:
        x = jax.nn.relu(_mha(lp["attn"], x, cfg.n_heads, causal=False)
                        + x @ lp["w_res"])
    B = x.shape[0]
    return (x.reshape(B, -1) @ params["head_w"] + params["head_b"])[:, 0]


def dcnv2_forward(params, engine, state, batch, cfg: RecConfig,
                  offsets: np.ndarray, mode: str = "pifs",
                  impl: str = "jnp", block_l: int = 8,
                  dedup: Optional[str] = None) -> jax.Array:
    emb = _field_lookup(engine, state, batch["fields"], offsets, mode,
                        impl=impl, block_l=block_l, dedup=dedup)
    emb = _constrain_full_batch(emb, engine)
    B = emb.shape[0]
    x0 = jnp.concatenate([batch["dense"], emb.reshape(B, -1)], axis=-1)
    x = x0
    for cp in params["cross"]:
        x = x0 * (x @ cp["w"] + cp["b"]) + x
    deep = mlp_apply(params["deep"], x0, len(cfg.mlp_dims), final_act=True)
    z = jnp.concatenate([x, deep], axis=-1)
    return (z @ params["head_w"] + params["head_b"])[:, 0]


# ---------------------------------------------------------------------------
# Losses
# ---------------------------------------------------------------------------


def _bce(logits: jax.Array, labels: jax.Array) -> jax.Array:
    y = labels.astype(jnp.float32)
    lg = logits.astype(jnp.float32)
    return jnp.mean(jnp.maximum(lg, 0) - lg * y + jnp.log1p(jnp.exp(-jnp.abs(lg))))


def sasrec_loss(params, engine, state, batch, cfg, mode="pifs") -> jax.Array:
    """Sampled next-item BCE (paper's objective): positive = actual next item,
    negative = uniform sample, scored by dot with the item embedding."""
    h = sasrec_encode(params, engine, state, batch["seq"], cfg, mode)  # (B,S,D)
    pos_e = _seq_lookup(engine, state, batch["pos"], 0, mode)
    neg_e = _seq_lookup(engine, state, batch["neg"], 0, mode)
    pos_s = jnp.sum(h * pos_e, axis=-1)
    neg_s = jnp.sum(h * neg_e, axis=-1)
    valid = (batch["seq"] > 0).astype(jnp.float32)
    ls = (jax.nn.softplus(-pos_s) + jax.nn.softplus(neg_s)) * valid
    return ls.sum() / jnp.maximum(valid.sum(), 1.0)


def forward(params, engine, state, batch, cfg: RecConfig,
            offsets: np.ndarray, mode: str = "pifs", impl: str = "jnp",
            block_l: int = 8, dedup: Optional[str] = None) -> jax.Array:
    it = cfg.interaction
    if it == "self-attn":
        return autoint_forward(params, engine, state, batch, cfg, offsets,
                               mode, impl=impl, block_l=block_l, dedup=dedup)
    if it == "cross":
        return dcnv2_forward(params, engine, state, batch, cfg, offsets,
                             mode, impl=impl, block_l=block_l, dedup=dedup)
    if it == "transformer-seq":
        return bst_forward(params, engine, state, batch, cfg, mode,
                           impl=impl, block_l=block_l, dedup=dedup)
    if it == "self-attn-seq":
        # CTR-style scoring of a target against the sequence representation
        h = sasrec_encode(params, engine, state, batch["seq"], cfg, mode,
                          impl=impl, block_l=block_l, dedup=dedup)
        t = _seq_lookup(engine, state, batch["target"][:, None], 0, mode,
                        impl=impl, block_l=block_l, dedup=dedup)[:, 0]
        return jnp.sum(h[:, -1] * t, axis=-1)
    raise ValueError(it)


def loss_fn(params, engine, state, batch, cfg, offsets, mode="pifs"):
    if cfg.interaction == "self-attn-seq":
        return sasrec_loss(params, engine, state, batch, cfg, mode)
    logits = forward(params, engine, state, batch, cfg, offsets, mode)
    return _bce(logits, batch["labels"])


# ---------------------------------------------------------------------------
# Retrieval: score a query against n_candidates explicit item ids
# ---------------------------------------------------------------------------


def retrieval_scores(params, engine, state, batch, cfg: RecConfig,
                     offsets: np.ndarray, mode: str = "pifs") -> jax.Array:
    """batch: model query inputs (B=1 semantics) + cand_ids (n_cand,) sharded
    over dp.  Sequential models score <user_repr, cand_emb>; CTR models tile
    the query and run a full forward per candidate."""
    cand = batch["cand_ids"]                      # (n_cand,)
    n_cand = cand.shape[0]
    it = cfg.interaction
    if it in ("self-attn-seq",):
        h = sasrec_encode(params, engine, state, batch["seq"], cfg, mode,
                          dp_shard=False)
        u = h[:, -1]                              # (1, D)
        # candidates shard over dp: (dp, n_cand/dp, 1) bags
        ce = _seq_lookup(engine, state, cand[:, None], 0, mode)[:, 0]
        return ce @ u[0]
    # CTR models: tile query features across candidates
    if it == "transformer-seq":
        tiled = {
            "seq": jnp.broadcast_to(batch["seq"], (n_cand,) + batch["seq"].shape[1:]),
            "target": cand,
            "dense": jnp.broadcast_to(batch["dense"],
                                      (n_cand,) + batch["dense"].shape[1:]),
        }
        return bst_forward(params, engine, state, tiled, cfg, mode)
    fields = jnp.broadcast_to(batch["fields"],
                              (n_cand,) + batch["fields"].shape[1:])
    # candidate id replaces field 0 (the item/ad field)
    fields = fields.at[:, 0].set(cand % cfg.vocab_sizes[0])
    tiled = {"fields": fields}
    if "dense" in batch:
        tiled["dense"] = jnp.broadcast_to(
            batch["dense"], (n_cand,) + batch["dense"].shape[1:])
    return forward(params, engine, state, tiled, cfg, offsets, mode)


# ---------------------------------------------------------------------------
# Step factories
# ---------------------------------------------------------------------------


def make_train_step(cfg: RecConfig, engine: PIFSEmbeddingEngine,
                    offsets: np.ndarray, mesh: Mesh, optimizer, emb_optimizer,
                    mode: str = "pifs"):
    def step(params, emb_state, opt_state, emb_opt_state, batch):
        def full_loss(p, cold, hot):
            st = dataclasses.replace(emb_state, cold=cold, hot=hot)
            return loss_fn(p, engine, st, batch, cfg, offsets, mode=mode)

        loss, grads = jax.value_and_grad(full_loss, argnums=(0, 1, 2))(
            params, emb_state.cold, emb_state.hot)
        gp, gcold, ghot = grads
        new_params, new_opt = optimizer.update(gp, opt_state, params)
        new_state, new_emb_opt = engine.optimizer_step(
            emb_optimizer, emb_state, gcold, ghot, emb_opt_state)
        return new_params, new_state, new_opt, new_emb_opt, {"loss": loss}
    return step


def make_serve_step(cfg: RecConfig, engine: PIFSEmbeddingEngine,
                    offsets: np.ndarray, mesh: Mesh, mode: str = "pifs",
                    impl: str = "jnp", block_l: int = 8,
                    dedup: Optional[str] = None):
    def step(params, emb_state, batch):
        return jax.nn.sigmoid(
            forward(params, engine, emb_state, batch, cfg, offsets,
                    mode=mode, impl=impl, block_l=block_l, dedup=dedup))
    return step


def make_retrieval_step(cfg: RecConfig, engine: PIFSEmbeddingEngine,
                        offsets: np.ndarray, mesh: Mesh, mode: str = "pifs"):
    def step(params, emb_state, batch):
        return retrieval_scores(params, engine, emb_state, batch, cfg,
                                offsets, mode=mode)
    return step


# ---------------------------------------------------------------------------
# Input specs (dry-run stand-ins)
# ---------------------------------------------------------------------------


def input_specs(cfg: RecConfig, shape_kind: str, batch: int,
                n_candidates: int = 0, with_labels: bool = False
                ) -> Dict[str, jax.ShapeDtypeStruct]:
    i32, f32 = jnp.int32, jnp.float32
    it = cfg.interaction
    out: Dict[str, jax.ShapeDtypeStruct] = {}
    if it == "self-attn-seq":
        out["seq"] = jax.ShapeDtypeStruct((batch, cfg.seq_len), i32)
        if shape_kind == "train":
            out["pos"] = jax.ShapeDtypeStruct((batch, cfg.seq_len), i32)
            out["neg"] = jax.ShapeDtypeStruct((batch, cfg.seq_len), i32)
        elif shape_kind == "retrieval":
            out["cand_ids"] = jax.ShapeDtypeStruct((n_candidates,), i32)
        else:
            out["target"] = jax.ShapeDtypeStruct((batch,), i32)
    elif it == "transformer-seq":
        out["seq"] = jax.ShapeDtypeStruct((batch, cfg.seq_len), i32)
        out["dense"] = jax.ShapeDtypeStruct((batch, cfg.n_dense), f32)
        if shape_kind == "retrieval":
            out["cand_ids"] = jax.ShapeDtypeStruct((n_candidates,), i32)
        else:
            out["target"] = jax.ShapeDtypeStruct((batch,), i32)
    else:
        out["fields"] = jax.ShapeDtypeStruct((batch, cfg.n_sparse), i32)
        if cfg.n_dense:
            out["dense"] = jax.ShapeDtypeStruct((batch, cfg.n_dense), f32)
        if shape_kind == "retrieval":
            out["cand_ids"] = jax.ShapeDtypeStruct((n_candidates,), i32)
    if with_labels and shape_kind == "train" and it != "self-attn-seq":
        out["labels"] = jax.ShapeDtypeStruct((batch,), i32)
    return out


def input_pspecs(cfg: RecConfig, shape_kind: str, mesh: Mesh,
                 with_labels: bool = False) -> Dict[str, P]:
    dp = ("pod", "data") if "pod" in mesh.axis_names else (
        ("data",) if "data" in mesh.axis_names else None)
    specs = input_specs(cfg, shape_kind, batch=2, n_candidates=2,
                        with_labels=with_labels)
    out: Dict[str, P] = {}
    for k, s in specs.items():
        if shape_kind == "retrieval":
            # the query replicates; the candidate list shards over dp
            out[k] = P(dp) if k == "cand_ids" else P(*((None,) * len(s.shape)))
        else:
            out[k] = P(*((dp,) + (None,) * (len(s.shape) - 1)))
    return out
