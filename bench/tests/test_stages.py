"""The stage split of the serve step's device time (``bench/stages.py``):
each instant counted once, stages and idle adding up to the step, and the
step's scopes found in its compiled text (times in nanoseconds)."""
import pytest

from bench import run as bench_run
from bench import stages
from bench.tests.conftest import BENCH, mix
from bench.tests.test_trace import ALL_REDUCE, FUSION, STEP, xspace_text

INNER = "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"


def test_each_instant_goes_to_the_op_that_started_last():
    ops = [(0, 10, "a"), (2, 4, "b"), (3, 6, "c"), (12, 13, "a")]
    assert stages.exclusive(ops) == {"a": 7, "b": 1, "c": 3}


def test_stages_and_idle_add_up_to_the_step():
    from jax.profiler import ProfileData
    planes = {"/device:TPU:0": {
        "XLA Modules": [(STEP, 1000, 3000), (STEP, 6000, 8000),
                        ("jit_block(12)", 9000, 9500)],
        "XLA Ops": [(FUSION, 1000, 2000), (INNER, 1200, 1500),
                    (ALL_REDUCE, 2000, 2600), (FUSION, 6000, 7000),
                    (FUSION, 9000, 9500)]}}
    profile = ProfileData.from_text_proto(xspace_text(planes))
    names = {"fusion.1": "embed", "fusion.2": "interaction",
             "all-reduce.1": "combine"}
    (chip,) = stages.stage_ms(profile, {"jit_step"}, 1, names).values()
    us = {k: pytest.approx(v * 1e3) for k, v in chip.items()
          if k != "steps"}
    # per step: embed (700 + 1000) / 2, the nested fusion 300 / 2, the
    # all-reduce 600 / 2; idle (4000 - 2600) / 2
    assert chip["steps"] == 2
    assert us == {"step_ms": 2.0, "embed": 0.85, "interaction": 0.15,
                  "combine": 0.3, "idle": 0.7}


@pytest.mark.parametrize("model, traffic, chips", [
    ("tiny_rmc3", ("random-closed-2048",
                   {"outstanding": 1024, "pool": 4096}), 1),
    ("tiny_rmc4_t32", ("zipf-poisson-14400", {"rate_per_s": 400}), 4)])
def test_the_compiled_step_names_its_stages(model, traffic, chips, request):
    import jax
    model = request.getfixturevalue(model)
    program_mod = bench_run._import(BENCH / "models" / "dlrm" / "program.py",
                                    "bench_program")
    program = program_mod.Program(model, mix(traffic[0], **traffic[1]),
                                   2**31 + 17, jax.devices()[:chips])
    try:
        with program.mesh:
            found = set(stages.stage_map(stages.step_texts(program)).values())
    finally:
        program.close()
    want = set(stages.STAGES) - ({"combine"} if chips == 1 else set())
    assert want <= found
    assert "unknown" not in found
