"""bench.reduce and the trace readers on a small trace fixture."""

import types
from pathlib import Path

import pytest

from bench import harness, reduce

FIXTURE = Path(__file__).parent / "fixtures" / "two_devices.textproto"
RECORDED = Path(__file__).parent / "fixtures" / "tpu_swe2d_ens51.textproto"


@pytest.fixture(scope="module")
def trace():
    from jax.profiler import ProfileData

    return reduce.reduce(ProfileData.from_text_proto(FIXTURE.read_text()))


def test_window_and_busy_union(trace):
    assert trace.n_devices == 2
    assert trace.window_s == pytest.approx(100e-6)
    # device 0: overlapping ops count once (50us); device 1: 70us
    assert trace.busy_s == pytest.approx(60e-6)
    assert trace.idle_share == pytest.approx(0.4)


def test_ops_summed_over_devices(trace):
    mega = "%vmap_jit_swe2d_mega__.1"
    assert trace.ops == {mega: pytest.approx(110e-6), "%fusion.1": pytest.approx(20e-6)}
    assert trace.top_ops(1) == [(mega, pytest.approx(110e-6))]


def test_idle_split_over_host_spans(trace):
    # per device, then averaged: device 0 idles 50us, device 1 30us
    assert trace.idle["bench.window"] == pytest.approx((25e-6 + 15e-6) / 2)
    assert trace.idle["bench.dispatch"] == pytest.approx((10e-6 + 5e-6) / 2)
    assert trace.idle["bench.wait"] == pytest.approx((15e-6 + 10e-6) / 2)
    assert sum(trace.idle.values()) == pytest.approx(trace.window_s - trace.busy_s)
    assert [name for name, _ in trace.top_idle()][0] == "bench.window"


def test_trace_without_window_span_is_refused():
    from jax.profiler import ProfileData

    text = FIXTURE.read_text().replace('name: "bench.window"', 'name: "other"')
    with pytest.raises(ValueError, match="bench.window"):
        reduce.reduce(ProfileData.from_text_proto(text))


def _ctx(trace, counts, config="swe2d_128"):
    from bench.peaks import peaks

    return types.SimpleNamespace(
        trace=trace, counts=counts, config=harness.load_json("configs", config),
        work=harness.load_module("work", config), peaks=peaks("TPU v5 lite"),
    )


@pytest.mark.parametrize("op,mega", [
    ("%vmap_jit_swe2d_mega__.1", True), ("%jit_heat1d_mega.3", True),
    ("%vmap_jit_heat1d_mega__.1", True), ("%fusion.1", False),
    ("%slice_bitcast_fusion", False), ("%vmap_jit_horizon", False),
])
def test_megakernel_is_found_by_its_wrapper_name(op, mega):
    from bench.metrics import is_megakernel

    assert is_megakernel(op) == mega


def test_kernel_readers(trace):
    counts = {"member_steps": 2 * 51 * 400, "member_horizons": 2 * 51}
    ctx = _ctx(trace, counts)
    us = harness.load_module("metrics", "mega.us_per_member_step").read(ctx)
    assert us == pytest.approx(110e-6 * 1e6 / counts["member_steps"])
    share = harness.load_module("metrics", "mega_roofline").read(ctx)
    work = ctx.work
    flops = counts["member_horizons"] * work.flops(ctx.config)
    nbytes = counts["member_horizons"] * work.hbm_bytes(ctx.config)
    least = max(flops / 197e12, nbytes / 819e9)
    assert share == pytest.approx(100 * least / 110e-6)
    idle = harness.load_module("metrics", "device.idle_share.ens").read(ctx)
    assert idle == pytest.approx(40.0)


def test_readers_find_nothing_return_none(trace):
    ctx = _ctx(trace, {})
    for name in ("mega.us_per_member_step", "mega_roofline", "service.chunk_ms_p50",
                 "service.compiles_in_window"):
        assert harness.load_module("metrics", name).read(ctx) is None
    ctx.trace = reduce.Trace(window_s=1.0, busy_s=1.0, n_devices=1, ops={"%fusion": 1.0}, idle={})
    ctx.counts = {"member_steps": 10, "member_horizons": 1}
    assert harness.load_module("metrics", "mega_roofline").read(ctx) is None


def test_recorded_tpu_trace():
    """A trace recorded on the chip reduces to what its run reported."""
    from jax.profiler import ProfileData

    trace = reduce.reduce(ProfileData.from_text_proto(RECORDED.read_text()))
    assert trace.n_devices == 1
    assert trace.window_s == pytest.approx(1.028632551)
    assert trace.busy_s == pytest.approx(0.999145861)
    assert sum(trace.idle.values()) == pytest.approx(trace.window_s - trace.busy_s)
    assert [name for name, _ in trace.top_idle()] == ["bench.wait", "bench.dispatch", "bench.window"]
    mega, seconds = trace.top_ops(1)[0]
    assert mega == "%vmap_jit_swe2d_mega__.1"
    assert seconds == pytest.approx(0.998670931)
    ctx = _ctx(trace, {"member_steps": 20 * 51 * 400, "member_horizons": 20 * 51})
    us = harness.load_module("metrics", "mega.us_per_member_step").read(ctx)
    assert us == pytest.approx(0.998670931e6 / (20 * 51 * 400))
    assert 0 < harness.load_module("metrics", "mega_roofline").read(ctx) < 100
