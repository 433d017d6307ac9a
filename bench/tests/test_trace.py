"""The trace reduction on a small recorded profile whose numbers are worked
out by hand (times in nanoseconds)."""
import pytest

from bench import trace

STEP = "jit_step(11)"
FUSION = "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
FUSION7 = "%fusion.7 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
ALL_REDUCE = "%all-reduce.1 = f32[8]{0} all-reduce(f32[8]{0} %f)"
ALL_GATHER = "%all-gather-start = (f32[8]{0}) all-gather-start(f32[2]{0} %g)"
COPY = "%copy.2 = f32[2]{0} copy(f32[2]{0} %c)"

PLANES = {
    "/device:TPU:0": {
        "XLA Modules": [(STEP, 1000, 3000), (STEP, 6000, 8000),
                        ("jit_block(12)", 9000, 9500)],
        "XLA Ops": [(FUSION, 1000, 2000), (ALL_REDUCE, 2000, 3000),
                    (FUSION, 6000, 7000), (ALL_REDUCE, 6500, 8000),
                    (COPY, 9000, 9500)],
        "Async XLA Ops": [(ALL_GATHER, 8000, 8200)],
    },
    "/device:TPU:1": {
        "XLA Modules": [(STEP, 1000, 3500), (STEP, 6000, 7500)],
        "XLA Ops": [(FUSION7, 1000, 3500), (FUSION7, 6000, 7500)],
    },
    "/host:CPU": {
        "python3": [("bench.wait", 0, 900), ("bench.pad", 900, 1000),
                    ("bench.execute", 1000, 3600), ("bench.wait", 3600, 5900),
                    ("PjitFunction(step)", 1000, 1200),
                    ("bench.execute", 5900, 8300),
                    ("bench.observe", 8800, 10000)],
    },
}


def xspace_text(planes: dict) -> str:
    """An XSpace text proto holding ``planes``: {plane: {line: [(name,
    start_ns, end_ns)]}}."""
    out = []
    for pid, (pname, lines) in enumerate(planes.items(), 1):
        names = sorted({n for evs in lines.values() for n, _, _ in evs})
        ids = {n: i for i, n in enumerate(names, 1)}
        body = [f'  id: {pid}', f'  name: "{pname}"']
        for lid, (lname, evs) in enumerate(lines.items(), 1):
            ev = "".join(
                f" events {{ metadata_id: {ids[n]} offset_ps: {a * 1000}"
                f" duration_ps: {(b - a) * 1000} }}" for n, a, b in evs)
            body.append(f'  lines {{ id: {lid} name: "{lname}" '
                        f'timestamp_ns: 0{ev} }}')
        for n, i in ids.items():
            body.append(f'  event_metadata {{ key: {i} value {{ id: {i} '
                        f'name: "{n}" }} }}')
        out.append("planes {\n" + "\n".join(body) + "\n}")
    return "\n".join(out)


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData
    profile = ProfileData.from_text_proto(xspace_text(PLANES))
    return trace.reduce_profile(profile, {"jit_step"}, chips=2)


def ns(x):
    return pytest.approx(x * 1e-9, rel=1e-9, abs=1e-15)


def test_window_and_busy(reduced):
    # marks span [0, 10000); chip 0 ops cover 2000 + 2000 + 500, chip 1
    # 2500 + 1500: busy is their mean
    assert reduced.window_s == ns(10000)
    assert reduced.busy_s == ns(4250)


def test_steps_take_the_slowest_chip(reduced):
    # step 1: chip 0 2000, chip 1 2500; step 2: 2000 and 1500
    assert reduced.steps == [ns(2500), ns(2000)]
    assert reduced.step_seconds() == ns(4500)


def test_collectives_and_their_exposed_part(reduced):
    # chip 0: all-reduce [2000,3000) and [6500,8000), async all-gather
    # [8000,8200): union 2700; no compute under [2000,3000) or [7000,8200)
    assert reduced.collective_s == ns(2700)
    assert reduced.exposed_collective_s == ns(2200)
    assert reduced.exposed_collective_ms_per_step() == pytest.approx(
        1e3 * 2200e-9 / 2)


def test_idle_gaps_divided_among_host_marks(reduced):
    # chip 0 idle: [0,1000) wait 900 + pad 100; [3000,6000) execute 600 +
    # wait 2300 + execute 100; [8000,9000) execute 300 + observe 200, 500
    # under no mark; [9500,10000) observe 500
    assert [(k, v) for k, v in reduced.gaps] == [
        ("wait", ns(1000)), ("wait", ns(3000)), ("other", ns(1000)),
        ("observe", ns(500))]
    b = reduced.breakdown()
    assert b["idle_gaps"] == [["wait", ns(3200)], ["execute", ns(1000)],
                              ["observe", ns(700)], ["other", ns(500)],
                              ["pad", ns(100)]]
    assert b["device_ops"] == [["fusion.7", ns(4000)],
                               ["all-reduce.1", ns(2500)],
                               ["fusion.1", ns(2000)], ["copy.2", ns(500)]]
    assert reduced.longest_gaps(2) == [["wait", ns(3000)],
                                       ["wait", ns(1000)]]
    assert sum(v for _, v in b["idle_gaps"]) == ns(10000 - 4500)


def test_missing_device_planes_raise():
    from jax.profiler import ProfileData
    host_only = {"/host:CPU": PLANES["/host:CPU"]}
    with pytest.raises(ValueError, match="device planes"):
        trace.reduce_profile(
            ProfileData.from_text_proto(xspace_text(host_only)),
            {"jit_step"}, chips=1)


@pytest.mark.parametrize("keep,remove,want", [
    ([(0, 10), (20, 30)], [(1, 2), (5, 12), (25, 40), (-5, 0.5)],
     [(0.5, 1), (2, 5), (20, 25)]),
    ([(0, 10)], [], [(0, 10)]),
    ([(0, 10)], [(0, 10)], []),
])
def test_subtract(keep, remove, want):
    assert trace.subtract(keep, remove) == want
