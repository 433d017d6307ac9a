"""Whole runs with the timed path broken underneath: ``correct`` has to come
out false.  The faults a serving cell can have: an answer altered where it
is produced, and, on four chips, the exchange between chips left out."""
from unittest import mock

import numpy as np
import pytest

from bench.tests.conftest import mix, tiny_cell
from bench.tests.test_rehearsal import cpu_run


def altered_scores(make):
    program = make()
    execute = program.execute

    def wrong(batch):
        out = np.array(execute(batch))
        out[0] += 0.01
        return out

    program.execute = wrong
    return program


def no_exchange(make):
    """The program built and warmed with every psum left out: each chip's
    pooled partial goes on as if it were the whole sum."""
    with mock.patch("jax.lax.psum", lambda x, axis_name, **kw: x):
        return make()


def test_an_altered_score_is_caught(tiny_rmc3):
    cell = tiny_cell(tiny_rmc3, mix("zipf-poisson-14400", rate_per_s=400))
    result, _ = cpu_run(cell, build=altered_scores)
    assert result["correct"] is False
    assert result["checks"]["score_gap"]["value"] > 0.009


@pytest.mark.parametrize("fault,correct", [(None, True),
                                           (no_exchange, False)])
def test_the_exchange_between_chips_left_out_is_caught(tiny_rmc4_t32, fault,
                                                       correct):
    traffic = mix("zipf-poisson-14400", rate_per_s=200)
    traffic["buckets"] = [32]
    result, _ = cpu_run(tiny_cell(tiny_rmc4_t32, traffic, chips=4),
                        build=fault)
    assert result["device"]["count"] == 4
    assert result["correct"] is correct
