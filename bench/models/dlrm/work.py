"""The work a served DLRM batch requires, whatever the implementation does.

Counted from the configuration's shapes and the batch's real requests only,
so padding rows, lane-padded table rows, duplicate gathers or a different
datapath all read against the same work:

* FLOPs: bottom MLP (and the projection to ``D``), the pooling adds
  (``L - 1`` per bag and lane), the pairwise dots of the interaction, and the
  top MLP; two per multiply-add.
* Bytes: each distinct (table, row) touched, ``D`` float32 values once; every
  MLP weight and bias once; the dense inputs and ids in, the scores out.
"""
from __future__ import annotations

import numpy as np


def _layers(model: dict):
    d, T = model["emb_dim"], model["n_tables"]
    F = T + 1
    bot = [model["n_dense"]] + list(model["bottom_mlp"])
    top = [F * (F - 1) // 2 + d] + list(model["top_mlp"])
    mats = list(zip(bot[:-1], bot[1:])) + list(zip(top[:-1], top[1:]))
    biases = sum(bot[1:]) + sum(top[1:])
    if bot[-1] != d:
        mats.append((bot[-1], d))
    return mats, biases


def batch_work(model: dict, ids: np.ndarray) -> tuple:
    """(flops, bytes) of serving requests with table-local ``ids`` (n, T, L)."""
    n, T, L = ids.shape
    d = model["emb_dim"]
    F = T + 1
    mats, biases = _layers(model)
    macs = sum(a * b for a, b in mats)
    flops = n * (2 * macs + T * (L - 1) * d + 2 * d * F * (F - 1) // 2)
    keyed = ids.astype(np.int64) + (np.arange(T, dtype=np.int64)
                                    * model["emb_num"])[None, :, None]
    unique_rows = np.unique(keyed).size
    weight_bytes = 4 * (macs + biases)
    io_bytes = n * 4 * (model["n_dense"] + T * L + 1)
    return float(flops), float(unique_rows * d * 4 + weight_bytes + io_bytes)
