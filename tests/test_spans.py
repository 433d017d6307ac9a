"""The serving path's spans and counters (``repro.core.spans``): off by
default, the spans of one execute nested inside it, counter snapshots, the
bounded buffer, and the marks a profile holds."""
import dataclasses
import tempfile
from pathlib import Path

import jax
import pytest

from repro.configs.rmc import RMC3
from repro.core.spans import NULL, PREFIX, SPANS, Recorder
from repro.distributed.sharding import make_mesh
from repro.serving import (AdmissionQueue, BatcherConfig, Bucket,
                           DynamicBatcher, FixedServiceModel, bind_model,
                           dummy_request_factory, make_padder)

BUCKET = Bucket(8, 8)
SVC = FixedServiceModel(base_s=4e-3, per_row_s=2.5e-4)


@pytest.fixture(scope="module")
def served():
    """A tiny RMC3 binding (RMC3's widths, 4,096 rows per table), its
    padder and a padded batch of five requests; every bucket compiled."""
    cfg = dataclasses.replace(RMC3, emb_num=4096)
    mesh = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    with mesh:
        binding = bind_model(cfg, mesh, seed=3)
        pad = make_padder(cfg)
        make = dummy_request_factory(cfg)
        reqs = [make(i, BUCKET.pooling) for i in range(5)]
        batch = pad(reqs, BUCKET)
        binding.execute(batch)
        binding.observe(batch)
        yield binding, pad, reqs, batch


@pytest.fixture
def recording():
    SPANS.clear()
    SPANS.enable()
    try:
        yield SPANS
    finally:
        SPANS.enable(False)
        SPANS.clear()


def by_name(recs, name):
    return [r for r in recs if r.name == name]


def test_off_by_default_records_nothing(served):
    binding, pad, reqs, batch = served
    SPANS.clear()
    assert not SPANS.refresh()
    q = AdmissionQueue(16)
    for r in reqs:
        q.offer(r)
    DynamicBatcher(BatcherConfig(batch_sizes=(8,), poolings=(8,))).decide(
        0.0, q.view(), None, SVC)
    pad(q.pop_n(5), BUCKET)
    binding.execute(batch)
    binding.observe(batch)
    assert SPANS.records == [] and SPANS.counters == {}
    assert not SPANS.on


def inside(recs, outer):
    return [r for r in recs if r is not outer
            and outer.t0 <= r.t0 and r.t1 <= outer.t1]


def test_execute_nests_stage_dispatch_block(served, recording):
    binding, _, _, batch = served
    binding.execute(batch)
    (ex,) = by_name(recording.records, "serve.execute")
    kids = inside(recording.records, ex)
    assert [r.name for r in kids] == ["serve.stage", "serve.dispatch",
                                      "serve.block"]
    for a, b in zip(kids, kids[1:]):
        assert a.t0 <= a.t1 <= b.t0
    assert ex.counters == {}
    assert all(r.counters is None for r in kids)


def test_observe_counts_then_probes(served, recording):
    binding, _, _, batch = served
    binding.execute(batch)
    binding.observe(batch)
    (ex,) = by_name(recording.records, "serve.execute")
    names = [r.name for r in recording.records if r.t0 >= ex.t1]
    assert names == ["observe.count", "observe.probe"]
    count, probe = recording.records[-2:]
    assert ex.t1 <= count.t0 <= count.t1 <= probe.t0 <= probe.t1


def test_off_hands_out_one_shared_context():
    rec = Recorder()
    assert rec.span("serve.execute") is NULL
    assert rec.tally("queue.view") is NULL
    with rec.span("a"), rec.tally("b"):
        pass
    assert rec.records == [] and rec.counters == {}


def test_a_batch_reads_the_state_when_it_starts(served):
    binding, pad, reqs, _ = served
    SPANS.clear()
    SPANS.enable()
    q = AdmissionQueue(16)
    for r in reqs:
        q.offer(r)
    SPANS.forced = False            # as when a profile stops between batches
    b = pad(q.pop_n(5), BUCKET)
    assert not SPANS.on
    binding.observe(b)
    assert SPANS.records == []
    SPANS.clear()


def test_counter_snapshots_difference(served, recording):
    binding, _, reqs, batch = served
    q = AdmissionQueue(16)
    for r in reqs:
        q.offer(r)
    batcher = DynamicBatcher(BatcherConfig(batch_sizes=(8,), poolings=(8,)))
    binding.execute(batch)
    for _ in range(3):
        batcher.decide(0.0, q.view(), None, SVC)
    q.view()
    binding.execute(batch)
    first, last = by_name(recording.records, "serve.execute")
    assert first.counters == {}
    assert last.counters["queue.view"][0] == 4
    assert last.counters["batcher.decide"][0] == 3
    assert last.counters["queue.view"][1] > 0
    assert recording.counters["queue.view"][0] == 4


def test_the_buffer_is_bounded_and_counts_drops():
    rec = Recorder(capacity=2)
    rec.enable()
    for name in ("a", "b", "c", "d"):
        with rec.span(name):
            pass
    assert [r.name for r in rec.records] == ["a", "b"]
    assert rec.dropped == 2
    assert rec.dropped_since is not None
    assert rec.dropped_since >= rec.records[-1].t1


def test_a_profile_turns_recording_on_and_holds_the_marks(served):
    from jax.profiler import ProfileData
    binding, pad, reqs, _ = served
    SPANS.clear()
    out = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=opts)
    try:
        binding.execute(pad(reqs, BUCKET))        # reads the state: on
        q = AdmissionQueue(16)
        for r in reqs:
            q.offer(r)
        b = pad(q.pop_n(5), BUCKET)
        binding.execute(b)
        binding.observe(b)
    finally:
        jax.profiler.stop_trace()
    assert not SPANS.refresh()
    assert len(by_name(SPANS.records, "serve.execute")) == 2
    SPANS.clear()
    (path,) = Path(out).rglob("*.xplane.pb")
    names = {e.name for plane in ProfileData.from_file(str(path)).planes
             if plane.name == "/host:CPU"
             for line in plane.lines for e in line.events}
    want = {"serve.execute", "serve.stage", "serve.dispatch", "serve.block",
            "queue.pop", "observe.count", "observe.probe"}
    assert {PREFIX + n for n in want} <= names
    assert not any(n.startswith("bench.") for n in names)
