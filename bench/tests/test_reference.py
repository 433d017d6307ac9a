"""The plain reference against the program on the CPU (where float32
matmuls are exact, so both compute the same function to rounding), and the
control: the reference in bfloat16 has to fail the configuration's limit."""
import jax
import numpy as np
import pytest

from bench import traffic
from bench.models.dlrm import reference as ref
from bench.tests.conftest import mix

SEED = 2**31 + 3


def program_scores(model, reqs, chips, replan):
    from bench.models.dlrm.program import Program
    from repro.serving.batcher import Bucket
    from repro.serving.request import Request
    m = mix("zipf-poisson-14400")
    m["buckets"] = [32]
    p = Program(model, m, SEED, jax.devices()[:chips])
    dense, ids = p.features(reqs)
    batch = p.pad([Request(rid=i, arrival_s=0.0, deadline_s=1.0,
                           features={"dense": dense[i], "indices": ids[i]},
                           pooling=ids.shape[-1]) for i in range(32)],
                  Bucket(32, ids.shape[-1]))
    with p.mesh:
        if replan:
            p.observe(batch)
            p.replan()
        return p.execute(batch)


@pytest.mark.parametrize("chips,replan", [(1, False), (1, True), (4, True)])
def test_reference_matches_the_program(tiny_rmc3, tiny_rmc4_t32, chips,
                                       replan):
    model = tiny_rmc3 if chips == 1 else tiny_rmc4_t32
    reqs = traffic.generate(mix("zipf-poisson-14400", rate_per_s=200), model,
                            5, 1.0)
    got = program_scores(model, reqs, chips, replan)
    want = ref.scores(model, SEED, reqs.dense[:32], reqs.ids[:32],
                      jax.devices()[:chips], precision="default")
    assert np.max(np.abs(got - want)) < 1e-6


@pytest.mark.parametrize("config", ["tiny_rmc3", "tiny_rmc4_t32"])
def test_the_bfloat16_control_fails_the_limit(config, request):
    model = request.getfixturevalue(config)
    chips = model["deployment"]["chips"]
    reqs = traffic.generate(mix("zipf-poisson-14400", rate_per_s=1000),
                            model, 9, 1.0)
    n = 512
    devices = jax.devices()[:chips]
    want = ref.scores(model, SEED, reqs.dense[:n], reqs.ids[:n], devices,
                      precision="default")
    control = ref.scores(model, SEED, reqs.dense[:n], reqs.ids[:n], devices,
                         precision="bfloat16")
    gap = ref.compared(control, want)["score_gap"]
    assert gap > model["limits"]["score_gap"]
