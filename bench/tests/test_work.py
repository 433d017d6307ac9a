"""The work count behind ``mfu`` and the table of peaks."""
import types

import numpy as np
import pytest

from bench import peaks
from bench.metrics import _read
from bench.models.dlrm import work

# D=4, 2 tables, 3 dense features, bottom 3-5-4 (ends at D: no projection),
# F=3 features so 3 pairwise dots, top (3+4)-6-1
TINY = {"emb_num": 10, "emb_dim": 4, "n_tables": 2, "n_dense": 3,
        "bottom_mlp": [5, 4], "top_mlp": [6, 1]}
# multiply-adds 3*5 + 5*4 + 7*6 + 6*1 = 83; biases 5 + 4 + 6 + 1 = 16
IO_PER_REQUEST = 4 * (3 + 2 * 2 + 1)         # dense, ids (T*L), score


def test_flops_and_bytes_by_hand():
    ids = np.array([[[0, 1], [5, 5]], [[1, 2], [5, 6]]])     # (2, T=2, L=2)
    flops, nbytes = work.batch_work(TINY, ids)
    # per request: 2*83 MLP, T*(L-1)*D = 8 pooling adds, 2*D*3 = 24 dots
    assert flops == 2 * (166 + 8 + 24)
    # distinct rows: table 0 {0,1,2}, table 1 {5,6}
    assert nbytes == 5 * 4 * 4 + 4 * (83 + 16) + 2 * IO_PER_REQUEST


def test_projection_counted_where_bottom_mlp_is_wider():
    wide = dict(TINY, bottom_mlp=[5, 6])
    ids = np.zeros((1, 2, 2), np.int64)
    flops, nbytes = work.batch_work(wide, ids)
    macs = 3 * 5 + 5 * 6 + 7 * 6 + 6 * 1 + 6 * 4
    assert flops == 2 * macs + 8 + 24
    assert nbytes == 2 * 16 + 4 * (macs + 5 + 6 + 6 + 1) + IO_PER_REQUEST


def test_duplicate_ids_count_each_row_once():
    ids = np.full((64, 2, 2), 7)
    flops, nbytes = work.batch_work(TINY, ids)
    assert nbytes == 2 * 4 * 4 + 4 * (83 + 16) + 64 * IO_PER_REQUEST


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peak rates"):
        peaks.peaks_for("TPU v99")
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_least_time_takes_the_binding_bound():
    p = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert peaks.least_time_s(1000, 50, p) == 10.0       # compute bound
    assert peaks.least_time_s(100, 50, p) == 5.0         # memory bound
    assert peaks.least_time_s(100, 50, p, chips=4) == 1.25


def test_mfu_of_duplicate_batch_on_an_ideal_device_is_100():
    """A device that takes exactly the least time for a batch full of
    duplicate ids reads 100%, never more: repeated gathers are not work."""
    p = peaks.peaks_for("TPU v5 lite")
    ids = np.full((512, 2, 2), 3)
    least = peaks.least_time_s(*work.batch_work(TINY, ids), p)
    ctx = types.SimpleNamespace(
        trace=types.SimpleNamespace(steps=[least],
                                    step_seconds=lambda: least),
        peaks=p, traced_batches=lambda: [None],
        least_time_s=lambda b: least)
    assert _read.mfu_pct(ctx) == pytest.approx(100.0)
