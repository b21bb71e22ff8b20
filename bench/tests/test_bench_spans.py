"""bench.spans and the service readers on a small trace fixture, written
as the ``.xplane.pb`` of a run under a stand-in ``harness.TRACE_DIR``."""

import types
from pathlib import Path

import pytest

from bench import harness, reduce, spans

FIXTURE = Path(__file__).parent / "fixtures" / "served_spans.textproto"
READERS = ("service.host_ms_per_pump", "service.submit_ms_p50",
           "service.queue_ms_p50", "service.device_ops_per_pump")


def _run(tmp_path, monkeypatch, text, window_s=None):
    """Write ``text`` as this run's trace; returns the readers' ``ctx``."""
    from jax.profiler import ProfileData

    trace_dir = tmp_path / ".bench_trace"
    path = trace_dir / "heat1d.served" / "plugins" / "profile" / "run" / "host.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    monkeypatch.setattr(harness, "TRACE_DIR", trace_dir)
    trace = reduce.reduce(reduce.load(str(trace_dir)))
    if window_s is not None:
        trace.window_s = window_s
    return types.SimpleNamespace(trace=trace, counts={})


def _read(ctx):
    return {name: harness.load_module("metrics", name).read(ctx) for name in READERS}


def test_readers_exact(tmp_path, monkeypatch):
    ctx = _run(tmp_path, monkeypatch, FIXTURE.read_text())
    assert ctx.trace.window_s == pytest.approx(100e-6)
    assert _read(ctx) == {
        "service.host_ms_per_pump": pytest.approx(0.012),
        "service.submit_ms_p50": pytest.approx(0.005),
        "service.queue_ms_p50": pytest.approx(0.004),
        "service.device_ops_per_pump": pytest.approx(2.0),
    }


def test_window_holds_only_spans_inside_it(tmp_path, monkeypatch):
    win = spans.window(_run(tmp_path, monkeypatch, FIXTURE.read_text()))
    assert [s.stats["request"] for s in win.named("service.submit")] == [2, 3]
    assert len(win.named("service.pump")) == 3  # the fourth ends past the window
    first = win.named("service.pump")[0]
    assert [s.ms for s in win.children(first, "service.sync")] == [pytest.approx(0.017)]
    assert win.children(win.named("service.pump")[2], "service.sync") == []


def test_no_service_spans_in_the_window_reads_none(tmp_path, monkeypatch):
    text = FIXTURE.read_text().replace('name: "service.', 'name: "other.')
    ctx = _run(tmp_path, monkeypatch, text)
    assert _read(ctx) == dict.fromkeys(READERS)


def test_trace_of_another_window_reads_none(tmp_path, monkeypatch):
    ctx = _run(tmp_path, monkeypatch, FIXTURE.read_text(), window_s=30.0)
    assert _read(ctx) == dict.fromkeys(READERS)


def test_no_trace_file_reads_none(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path / "absent")
    ctx = types.SimpleNamespace(trace=reduce.Trace(1.0, 0.0, 1, {}, {}), counts={})
    assert _read(ctx) == dict.fromkeys(READERS)
