"""The served host path's spans on a jax.profiler trace (DESIGN.md §15).

Under a CPU profiler trace, a ``SimService`` serving three heat1d requests
writes one span per boundary of its host path, nested under
``service.pump`` or ``service.submit``, each carrying the request ids it
handles: ``service.stack`` once per membership change of the bucket, with
its reason, and ``service.unstack`` once per departure. Its results are
bit-identical with the profiler on and off."""

import numpy as np
import pytest

import repro.obs as obs
from repro.service import ServiceConfig, SimRequest, SimService, scaled_state0

SPANS = {
    "service.submit", "service.resolve", "service.pump", "service.fill",
    "service.join", "service.stack", "service.chunk", "service.dispatch",
    "service.sync", "service.unstack", "service.snapshot", "service.finalize",
}
STEPS, EVERY = 40, 20


@pytest.fixture(autouse=True)
def _obs_off():
    obs.disable()
    yield
    obs.disable()


def _serve():
    """Two requests, one pump, then a third that joins mid-flight."""
    svc = SimService(ServiceConfig())

    def submit(scale):
        return svc.submit(SimRequest(
            "heat1d", steps=STEPS, overrides={"nx": 48}, precision="r2f2_16",
            snapshot_every=EVERY, state0=scaled_state0("heat1d", scale, {"nx": 48}),
        ))

    handles = [submit(0.5), submit(1.0)]
    svc.pump()
    handles.append(submit(1.5))
    svc.run_until_idle()
    return handles


def _inside(child, parents):
    return any(p.start_ns <= child.start_ns and child.end_ns <= p.end_ns for p in parents)


def test_served_path_spans(profiled):
    handles, spans = profiled(_serve)
    spans = [s for s in spans if s.name.startswith("service.")]
    by = {name: [s for s in spans if s.name == name] for name in SPANS}
    assert set(s.name for s in spans) == SPANS

    for name in ("service.dispatch", "service.sync"):
        assert all(_inside(s, by["service.chunk"]) for s in by[name])
    # A and B alone queue their second chunk behind their first; C's join
    # drops it, so no chunk ran ahead
    ahead = [s for s in by["service.dispatch"] if s.stats["ahead"]]
    assert [s.stats["steps"] for s in ahead] == [EVERY]
    assert not any(s.stats["ahead"] for s in by["service.chunk"])
    assert all(_inside(s, by["service.pump"]) for s in by["service.chunk"])
    roots = by["service.pump"] + by["service.submit"]
    for s in spans:
        if s.name not in ("service.pump", "service.submit"):
            assert _inside(s, roots), s.name

    ids = [h.id for h in handles]
    assert [s.stats["request"] for s in by["service.submit"]] == ids
    assert sorted(s.stats["request"] for s in by["service.join"]) == ids
    assert sorted(s.stats["request"] for s in by["service.finalize"]) == ids
    assert sorted(s.stats["request"] for s in by["service.snapshot"]) == sorted(
        ids * (STEPS // EVERY))
    for s in by["service.chunk"]:
        members = [int(m) for m in str(s.stats["members"]).split()]
        assert set(members) <= set(ids)
        assert s.stats["steps"] > 0

    # A and B start together, C joins after one chunk, A and B drain after
    # the second and C after the third: three packings, three chunks
    def members(s):
        return [int(m) for m in str(s.stats["members"]).split()]

    a, b, c = ids
    assert [(s.stats["reason"], members(s)) for s in by["service.stack"]] == [
        ("first", [a, b]), ("join", [a, b, c]), ("drain", [c])]
    assert [(s.stats["reason"], members(s)) for s in by["service.unstack"]] == [
        ("drain", [a, b]), ("drain", [c])]
    assert len(by["service.chunk"]) == 3

    assert all(s.stats["wait_us"] >= 0 for s in by["service.join"])
    for h in handles:
        rec = h._record
        assert h.status == "done"
        assert h.queue_s >= 0 and h.service_s > 0
        assert h.queue_s + h.service_s == pytest.approx(rec.done_at - rec.submitted_at)
        (fin,) = [s for s in by["service.finalize"] if s.stats["request"] == h.id]
        assert fin.stats["queue_us"] == pytest.approx(h.queue_s * 1e6)
        assert fin.stats["service_us"] == pytest.approx(h.service_s * 1e6)


def test_results_bit_identical_with_profiler_on_and_off(profiled):
    on, _ = profiled(_serve)
    off = _serve()
    for a, b in zip(on, off):
        ra, rb = a.result(), b.result()
        np.testing.assert_array_equal(
            np.asarray(ra.state).view(np.uint32), np.asarray(rb.state).view(np.uint32))
        assert ra.snapshot_steps == rb.snapshot_steps
        for sa, sb in zip(ra.snapshots, rb.snapshots):
            np.testing.assert_array_equal(
                np.asarray(sa).view(np.uint32), np.asarray(sb).view(np.uint32))


def test_lone_request_runs_its_chunks_ahead(profiled):
    """A lone request's chunks after its first are dispatched ahead, each
    inside the span of the chunk before, ahead of that chunk's sync, and
    its pumps still stream one frame each."""

    def serve():
        svc = SimService(ServiceConfig())
        svc.submit(SimRequest("heat1d", steps=STEPS, overrides={"nx": 48},
                              precision="r2f2_16", snapshot_every=EVERY // 2))
        svc.run_until_idle()
        return svc.metrics

    metrics, spans = profiled(serve)
    by = {name: [s for s in spans if s.name == name]
          for name in ("service.pump", "service.chunk", "service.dispatch",
                       "service.snapshot")}
    chunks = STEPS // (EVERY // 2)
    assert [bool(s.stats["ahead"]) for s in by["service.chunk"]] == [False] + [True] * (
        chunks - 1)
    ahead = [s for s in by["service.dispatch"] if s.stats["ahead"]]
    assert len(ahead) == metrics.chunks_ahead == chunks - 1
    syncs = [s for s in spans if s.name == "service.sync"]
    for d, c, sync in zip(ahead, by["service.chunk"], syncs):
        assert _inside(d, [c]) and d.end_ns <= sync.start_ns
    pumps = [p for p in by["service.pump"] if any(_inside(c, [p]) for c in by["service.chunk"])]
    assert [sum(_inside(s, [p]) for s in by["service.snapshot"]) for p in pumps] == [1] * chunks
