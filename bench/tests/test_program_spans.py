"""The program's own spans beside the harness: the trace reduction ignores
them, and each reader of them gives a number on a CPU driver run with
recording on and ``None`` with it off."""
import pytest

from bench import run as bench_run
from bench import trace, traffic
from bench.driver import Driver
from bench.tests.conftest import BENCH, mix
from bench.tests.test_trace import PLANES, xspace_text

BOTH = ("stage_ms", "dispatch_ms", "block_ms", "fetch_ms", "batcher_ms")
READERS = {"online": [f"{m}.online" for m in BOTH],
           "bulk": [f"{m}.bulk" for m in BOTH] + ["probe_pct.bulk"]}
MIXES = {"online": ("zipf-poisson-14400", {"rate_per_s": 400}),
         "bulk": ("random-closed-2048", {"outstanding": 1024, "pool": 4096})}
SEED = 2**31 + 17


def _reduce(planes):
    from jax.profiler import ProfileData
    return trace.reduce_profile(ProfileData.from_text_proto(
        xspace_text(planes)), {"jit_step"}, chips=2)


def test_program_marks_do_not_enter_the_reduction():
    host = PLANES["/host:CPU"]["python3"]
    nested = host + [("repro.serve.execute", 1100, 3400),
                     ("repro.serve.stage", 1100, 1300),
                     ("repro.serve.dispatch", 1300, 1500),
                     ("repro.serve.block", 1500, 3400),
                     ("repro.observe.count", 8850, 9000),
                     ("repro.observe.probe", 9000, 9900)]
    planes = dict(PLANES, **{"/host:CPU": {"python3": nested}})
    a, b = _reduce(PLANES), _reduce(planes)
    assert b.idle_by_mark == a.idle_by_mark
    assert b.gaps == a.gaps
    assert (b.window_s, b.busy_s, b.steps) == (a.window_s, a.busy_s, a.steps)


def served_window(model, kind, record):
    """One second of the driver over the program, with the program's
    recorder on or off; returns what a metric reader may read."""
    import jax
    from repro.core.spans import SPANS
    name, load = MIXES[kind]
    m = mix(name, **load)
    program_mod = bench_run._import(BENCH / "models" / "dlrm" / "program.py",
                                    "bench_program")
    reqs = traffic.generate(m, model, SEED, 1.0)
    program = program_mod.Program(model, m, SEED, jax.devices()[:1])
    SPANS.clear()
    SPANS.enable(record)
    try:
        with program.mesh:
            served = Driver(program, reqs, m).run(1.0)
    finally:
        SPANS.enable(False)
        program.close()
    return bench_run.Context(model=model, served=served, trace=None,
                             reqs=reqs, chips=1)


def read(ctx, name):
    reader = bench_run._import(BENCH / "metrics" / f"{name}.py",
                               f"bench_metric_{name}")
    return reader.read(ctx)


@pytest.mark.parametrize("kind", ["online", "bulk"])
def test_readers_read_a_recorded_window(tiny_rmc3, kind):
    from repro.core.spans import SPANS
    ctx = served_window(tiny_rmc3, kind, record=True)
    try:
        values = {n: read(ctx, n) for n in READERS[kind]}
        assert all(v is not None and v > 0 for v in values.values()), values
        # the four parts of the harness's execute span add up to it
        parts = sum(values[f"{m}.{kind}"] for m in BOTH[:4])
        execute = 1e3 * sum(b - a for a, b in ctx.spans("execute")
                            if a >= ctx.served.t0) / sum(
            1 for a, _ in ctx.spans("execute") if a >= ctx.served.t0)
        assert parts == pytest.approx(execute, rel=0.05)
        # a drop inside the window voids every reader
        SPANS.dropped, SPANS.dropped_since = 1, ctx.served.t_end - 1e-3
        assert all(read(ctx, n) is None for n in READERS[kind])
    finally:
        SPANS.clear()


def test_readers_give_none_with_recording_off(tiny_rmc3):
    ctx = served_window(tiny_rmc3, "bulk", record=False)
    for name in READERS["online"] + READERS["bulk"]:
        assert read(ctx, name) is None, name
