"""repro.obs — the unified observability subsystem (DESIGN.md §15).

Covers the ISSUE-9 contract: span nesting + deterministic sampling,
Prometheus export round-trip through the strict parser, registry
label-cardinality bound, ServiceMetrics NaN guards and the
compile-vs-execute split, PASSIVITY (instrumented runs bit-identical to
uninstrumented ones on heat1d and swe2d across all three execution planes),
and precision telemetry whose k series equals the tracker's carried k at
every chunk boundary."""

import dataclasses
import json
import math

import jax
import numpy as np
import pytest

import repro.obs as obs
from repro.core.policy import PRESETS
from repro.obs.metrics import MetricsRegistry, parse_prometheus
from repro.obs.precision import PrecisionTelemetry, load_telemetry
from repro.obs.timing import measure
from repro.obs.trace import Tracer, load_trace
from repro.pde import Simulation
from repro.pde.heat1d import HeatConfig
from repro.pde.swe2d import SWEConfig
from repro.precision import site_tracker_init
from repro.service import ServiceConfig, SimRequest, SimService
from repro.service.metrics import ServiceMetrics

TRACKED = dataclasses.replace(PRESETS["r2f2_16"], mode="rr_tracked")

SMALL = {
    "heat1d": HeatConfig(nx=64),
    "swe2d": SWEConfig(nx=32, ny=32),
}


@pytest.fixture(autouse=True)
def _obs_off():
    """Every test starts and ends with observability disabled."""
    obs.disable()
    yield
    obs.disable()


def assert_bits_equal(a, b):
    a, b = np.asarray(a), np.asarray(b)
    assert a.dtype == b.dtype
    if a.dtype == np.float32:
        np.testing.assert_array_equal(a.view(np.uint32), b.view(np.uint32))
    else:
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# tracing: nesting, sampling determinism, bounds, export
# ---------------------------------------------------------------------------


class TestProfilerSink:
    """``obs.span`` / ``obs.instant`` always reach a running jax.profiler
    trace, on its host plane with their args as stats; the Tracer records
    them only when obs is enabled."""

    @pytest.mark.parametrize("enabled", [False, True], ids=["obs_off", "obs_on"])
    def test_spans_land_on_host_plane_with_args_and_nest(self, profiled, enabled):
        if enabled:
            obs.enable()

        def body():
            with obs.span("unit.outer", request=7, members="3 4") as sp:
                with obs.span("unit.inner", wait_us=12.5):
                    pass
                sp.set_metadata(late=2)
            obs.instant("unit.mark", request=7)

        _, spans = profiled(body)
        by = {s.name: s for s in spans if s.name.startswith("unit.")}
        assert set(by) == {"unit.outer", "unit.inner", "unit.mark"}
        outer, inner, mark = by["unit.outer"], by["unit.inner"], by["unit.mark"]
        assert outer.stats == {"request": 7, "members": "3 4", "late": 2}
        assert inner.stats == {"wait_us": 12.5}
        assert mark.stats == {"request": 7}
        assert outer.start_ns <= inner.start_ns <= inner.end_ns <= outer.end_ns
        assert outer.end_ns <= mark.start_ns
        if enabled:
            recorded = {s.name: s for s in obs.active().tracer.spans}
            assert set(recorded) == set(by)
            assert recorded["unit.outer"].args == {"request": 7, "members": "3 4", "late": 2}
            assert recorded["unit.inner"].depth == 1
            assert recorded["unit.mark"].dur_us is None



class TestTracer:
    def test_span_nesting_depths(self):
        tr = Tracer()
        with tr.span("outer"):
            with tr.span("mid"):
                with tr.span("inner"):
                    pass
            tr.instant("event")
        by_name = {s.name: s for s in tr.spans}
        assert by_name["outer"].depth == 0
        assert by_name["mid"].depth == 1
        assert by_name["inner"].depth == 2
        assert by_name["event"].depth == 1  # recorded inside "outer"
        # children complete before parents, so inner durations are smaller
        assert by_name["inner"].dur_us <= by_name["outer"].dur_us

    def test_span_args_mutable_late_attach(self):
        tr = Tracer()
        with tr.span("chunk", a=1) as args:
            args["computed"] = 42
        assert tr.spans[0].args == {"a": 1, "computed": 42}

    def test_sampling_is_deterministic_and_proportional(self):
        def record(n):
            tr = Tracer(sample=0.5)
            for i in range(n):
                with tr.span(f"s{i}"):
                    pass
            return [s.name for s in tr.spans]

        a, b = record(10), record(10)
        assert a == b  # no RNG: identical runs record identical span sets
        assert len(a) == 5  # exactly the sampled fraction
        # the analytic keep rule, spelled out
        kept = [
            f"s{n}"
            for n in range(10)
            if math.floor((n + 1) * 0.5) > math.floor(n * 0.5)
        ]
        assert a == kept

    def test_nested_spans_inherit_sampling_decision(self):
        tr = Tracer(sample=0.5)
        for i in range(4):
            with tr.span("top"):
                with tr.span("child"):
                    pass
                tr.instant("ev")
        # 2 of 4 tops kept, each with exactly its own child + instant
        names = [s.name for s in tr.spans]
        assert names.count("top") == 2
        assert names.count("child") == 2
        assert names.count("ev") == 2

    def test_sample_zero_keeps_nothing_but_bare_instants(self):
        tr = Tracer(sample=0.0)
        with tr.span("never"):
            tr.instant("inherits-drop")
        tr.instant("lifecycle")  # outside any span: always kept
        assert [s.name for s in tr.spans] == ["lifecycle"]

    def test_capacity_bound_and_dropped_counter(self):
        tr = Tracer(capacity=3)
        for i in range(7):
            with tr.span(f"s{i}"):
                pass
        assert len(tr.spans) == 3
        assert tr.dropped == 4

    def test_chrome_trace_export_and_load(self, tmp_path):
        tr = Tracer()
        with tr.span("work", kind="test"):
            tr.instant("mark")
        path = tr.save(str(tmp_path / "trace.json"))
        doc = load_trace(path)
        events = doc["traceEvents"]
        assert {e["ph"] for e in events} == {"X", "i"}
        x = next(e for e in events if e["ph"] == "X")
        assert x["name"] == "work" and x["dur"] >= 0
        assert x["args"]["kind"] == "test"
        i = next(e for e in events if e["ph"] == "i")
        assert "dur" not in i and i["s"] == "t"

    def test_load_trace_rejects_garbage(self, tmp_path):
        p = tmp_path / "bad.json"
        p.write_text(json.dumps({"not": "a trace"}))
        with pytest.raises(ValueError):
            load_trace(str(p))

    def test_self_time_is_accounted(self):
        tr = Tracer()
        for _ in range(50):
            with tr.span("s"):
                pass
        assert tr.self_seconds > 0.0


# ---------------------------------------------------------------------------
# metrics registry: counters/gauges/histograms, export, strict parsing
# ---------------------------------------------------------------------------


class TestMetricsRegistry:
    def test_counter_labels_and_totals(self):
        reg = MetricsRegistry()
        c = reg.counter("jobs_total", "jobs")
        c.inc(ok="true")
        c.inc(2, ok="false")
        assert c.value(ok="true") == 1
        assert c.value(ok="false") == 2
        assert c.total() == 3
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_redeclare_same_type_ok_different_type_raises(self):
        reg = MetricsRegistry()
        reg.counter("x_total")
        assert reg.counter("x_total") is reg.counter("x_total")
        with pytest.raises(TypeError):
            reg.gauge("x_total")

    def test_histogram_cumulative_buckets(self):
        reg = MetricsRegistry()
        h = reg.histogram("lat_seconds", buckets=(0.1, 1.0))
        for v in (0.05, 0.5, 0.7, 5.0):
            h.observe(v)
        snap = h.snapshot()
        assert snap["count"] == 4
        assert snap["sum"] == pytest.approx(6.25)
        assert dict(snap["buckets"]) == {0.1: 1, 1.0: 3}  # cumulative

    def test_prometheus_round_trip(self):
        reg = MetricsRegistry()
        reg.counter("c_total", "a counter").inc(3, stage="x")
        reg.gauge("g", "a gauge").set(1.5)
        h = reg.histogram("h_seconds", "a histogram", buckets=(0.01, 0.1))
        h.observe(0.05, op="mul")
        h.observe(0.2, op="mul")
        families = parse_prometheus(reg.export_prometheus())
        assert families["c_total"]["type"] == "counter"
        assert ("c_total", {"stage": "x"}, 3.0) in families["c_total"]["samples"]
        assert ("g", {}, 1.5) in families["g"]["samples"]
        hs = {
            (name, labels.get("le")): v
            for name, labels, v in families["h_seconds"]["samples"]
        }
        assert hs[("h_seconds_bucket", "0.01")] == 0
        assert hs[("h_seconds_bucket", "0.1")] == 1
        assert hs[("h_seconds_bucket", "+Inf")] == 2
        assert hs[("h_seconds_count", None)] == 2
        assert hs[("h_seconds_sum", None)] == pytest.approx(0.25)

    @pytest.mark.parametrize(
        "text",
        [
            "no_type_decl 1.0\n",  # sample without a TYPE header
            "# TYPE h histogram\nh_bucket{le=\"+Inf\"} 2\nh_count 2\n",  # no _sum
            "# TYPE h histogram\nh_bucket{le=\"0.1\"} 1\nh_sum 1\nh_count 1\n",  # no +Inf
            "# TYPE h histogram\n"
            "h_bucket{le=\"0.1\"} 3\nh_bucket{le=\"+Inf\"} 2\n"
            "h_sum 1\nh_count 2\n",  # non-cumulative buckets
            "# TYPE h histogram\n"
            "h_bucket{le=\"+Inf\"} 2\nh_sum 1\nh_count 5\n",  # count != +Inf
            "# TYPE c counter\nc not-a-number\n",
            "# TYPE c counter\nc{bad-label=\"x\"} 1\n",
        ],
    )
    def test_strict_parser_rejects_malformed(self, text):
        with pytest.raises(ValueError):
            parse_prometheus(text)

    def test_label_cardinality_bound(self):
        reg = MetricsRegistry(max_series=4)
        c = reg.counter("wide_total")
        for i in range(10):
            c.inc(member=str(i))
        assert len(c.samples()) == 4
        assert reg.dropped_series == 6
        # export stays parseable after drops
        parse_prometheus(reg.export_prometheus())

    def test_export_json_schema(self):
        reg = MetricsRegistry()
        reg.counter("c_total").inc()
        doc = reg.export_json()
        assert doc["schema"] == "repro.obs/metrics@1"
        assert doc["metrics"]["c_total"]["samples"][0]["value"] == 1.0


# ---------------------------------------------------------------------------
# ServiceMetrics: NaN guards + compile/execute split
# ---------------------------------------------------------------------------


class TestServiceMetricsGuards:
    def test_zero_denominators_return_nan(self):
        m = ServiceMetrics()
        assert math.isnan(m.throughput())
        assert math.isnan(m.throughput("nokey"))
        assert math.isnan(m.latency_us(50))
        mean, mx = m.occupancy()
        assert math.isnan(mean) and mx == 0
        # summary never raises on the empty service
        s = m.summary()
        assert math.isnan(s["throughput_steps_per_s"])
        assert math.isnan(s["chunk_latency_p50_us"])
        assert "nan" in m.report()

    def test_only_compile_samples_still_nan_throughput(self):
        m = ServiceMetrics()
        m.observe_chunk("k", 2, 8, 1.0, compiled=True)
        assert math.isnan(m.throughput())
        assert math.isnan(m.latency_us(99))
        assert m.occupancy() == (2.0, 2)  # occupancy counts compile calls

    def test_compile_split_excluded_from_percentiles(self):
        m = ServiceMetrics()
        m.observe_chunk("k", 2, 8, 10.0, compiled=True)  # one huge compile
        for _ in range(9):
            m.observe_chunk("k", 2, 8, 0.001)
        s = m.summary()
        assert s["chunks"] == 10 and s["compiles"] == 1
        assert s["compile_seconds"] == pytest.approx(10.0)
        assert s["busy_seconds"] == pytest.approx(0.009)
        # the compile no longer pollutes the tail
        assert s["chunk_latency_p99_us"] < 2_000
        assert m.throughput() == pytest.approx(9 * 16 / 0.009)

    def test_attribute_increment_api_preserved(self):
        m = ServiceMetrics()
        m.submitted += 1
        m.evicted += 2
        assert m.submitted == 1 and m.evicted == 2
        assert m.registry.counter("repro_service_submitted_total").total() == 1

    def test_reports_into_active_obs_registry(self):
        scope = obs.enable()
        m = ServiceMetrics()
        m.submitted += 1
        assert scope.registry.counter("repro_service_submitted_total").total() == 1


class TestServiceCompileSplit:
    def test_first_call_per_program_books_as_compile(self):
        svc = SimService(ServiceConfig(max_queue=64))
        for _ in range(2):  # identical requests: 2nd rides the cached program
            svc.submit(SimRequest("heat1d", steps=32, precision="f32",
                                  overrides={"nx": 32}, snapshot_every=8))
        svc.run_until_idle()
        m = svc.metrics
        assert m.compiles >= 1
        assert m.compile_seconds > 0.0
        assert m.chunks > m.compiles  # warm calls exist
        assert np.isfinite(m.latency_us(50))
        compiled_flags = [c for *_, c in m.chunk_samples]
        assert any(compiled_flags) and not all(compiled_flags)
        # warm (execute) samples are all much faster than the compile call
        warm = [s for *_, s, c in m.chunk_samples if not c]
        cold = [s for *_, s, c in m.chunk_samples if c]
        assert max(warm) < max(cold)


# ---------------------------------------------------------------------------
# passivity: instrumented == uninstrumented, bit for bit, on every plane
# ---------------------------------------------------------------------------


def _run(name, prec, execution, steps=20, every=6):
    sim = Simulation(name, SMALL[name], prec)
    return sim.run(steps, snapshot_every=every, execution=execution)


class TestPassivity:
    @pytest.mark.parametrize("name", ["heat1d", "swe2d"])
    @pytest.mark.parametrize("execution", ["reference", "fused", "megakernel"])
    def test_tracked_run_bit_identical_under_obs(self, name, execution):
        base = _run(name, TRACKED, execution)
        obs.enable(sample=1.0)
        inst = _run(name, TRACKED, execution)
        o = obs.active()
        assert len(o.tracer.spans) > 0  # it really was instrumented
        assert len(o.telemetry) > 0  # and the tracker really was drained
        obs.disable()
        jax.tree_util.tree_map(assert_bits_equal, base.state, inst.state)
        jax.tree_util.tree_map(assert_bits_equal, base.snapshots, inst.snapshots)
        np.testing.assert_array_equal(
            np.asarray(base.tracker.state.k), np.asarray(inst.tracker.state.k)
        )
        np.testing.assert_array_equal(
            np.asarray(base.tracker.state.overflow_steps),
            np.asarray(inst.tracker.state.overflow_steps),
        )

    @pytest.mark.parametrize("name", ["heat1d", "swe2d"])
    def test_untracked_f32_bit_identical_under_obs(self, name):
        base = _run(name, PRESETS["f32"], "reference")
        obs.enable()
        inst = _run(name, PRESETS["f32"], "reference")
        obs.disable()
        jax.tree_util.tree_map(assert_bits_equal, base.state, inst.state)

    def test_record_tracker_refuses_jax_tracers(self):
        obs.enable()
        tracker = site_tracker_init(("a", "b"), TRACKED.fmt)

        @jax.jit
        def traced(tr):
            obs.record_tracker("inside-jit", tr, 0)
            return tr.state.k

        traced(tracker)
        assert len(obs.active().telemetry) == 0  # drain skipped under trace
        obs.record_tracker("outside", tracker, 0)
        assert len(obs.active().telemetry) == 2


# ---------------------------------------------------------------------------
# precision telemetry: k series == carried tracker at every chunk boundary
# ---------------------------------------------------------------------------


def _ground_truth_boundary_k(name, prec, execution, steps, every):
    """Thread (state, tracker) through per-chunk solo runs — the carried
    tracker at each chunk boundary, observed directly."""
    sim = Simulation(name, SMALL[name], prec)
    state, tracker = None, None
    out = []
    done = 0
    while done < steps:
        n = min(every, steps - done)
        res = sim.run(
            n, snapshot_every=n, state0=state, tracker=tracker,
            execution=execution,
        )
        state, tracker = res.state, res.tracker
        done += n
        out.append((done, np.asarray(tracker.state.k).copy()))
    return out


class TestTelemetrySeries:
    @pytest.mark.parametrize("name", ["heat1d", "swe2d"])
    @pytest.mark.parametrize("execution", ["reference", "fused"])
    def test_replayed_series_equals_carried_k(self, name, execution):
        """A captured instrumented run's telemetry series must equal the
        carried tracker's k at every chunk boundary (steps=20, every=6:
        includes the remainder chunk)."""
        steps, every = 20, 6
        truth = _ground_truth_boundary_k(name, TRACKED, execution, steps, every)
        obs.enable(sample=1.0)
        sim = Simulation(name, SMALL[name], TRACKED)
        res = sim.run(steps, snapshot_every=every, execution=execution,
                      capture=True)
        tel = obs.active().telemetry
        sites = sim.stepper.sites
        for j, site in enumerate(sites):
            t_steps, t_k = tel.k_series(f"sim:{name}", site)
            assert list(t_steps) == [s for s, _ in truth]
            assert list(t_k) == [int(k[j]) for _, k in truth]
        # and the last sample is the run's final carried tracker
        np.testing.assert_array_equal(
            np.asarray(res.tracker.state.k), truth[-1][1]
        )

    def test_coverage_fraction_attached(self):
        obs.enable()
        sim = Simulation("heat1d", SMALL["heat1d"], TRACKED)
        sim.run(12, snapshot_every=6, capture=True)
        for s in obs.active().telemetry.all_series():
            assert s.coverage is not None and 0.0 <= s.coverage <= 1.0

    def test_uncaptured_run_records_final_tracker(self):
        obs.enable()
        sim = Simulation("heat1d", SMALL["heat1d"], TRACKED)
        res = sim.run(12, snapshot_every=6)
        tel = obs.active().telemetry
        assert tel.final_k("sim:heat1d") == {
            n: int(res.tracker.state.k[i])
            for i, n in enumerate(res.tracker.names)
        }

    def test_service_chunk_boundary_drain_matches_result(self):
        obs.enable()
        svc = SimService(ServiceConfig(max_queue=16))
        h = svc.submit(SimRequest("heat1d", steps=24, precision=TRACKED,
                                  overrides={"nx": 32}, snapshot_every=8))
        svc.run_until_idle()
        res = h.result()
        tel = obs.active().telemetry
        scopes = [sc for sc in tel.scopes() if sc.endswith(":heat1d")]
        assert scopes, f"no service telemetry scopes in {tel.scopes()}"
        assert any(tel.final_k(sc) == res.final_k for sc in scopes)
        # one sample per chunk the request rode, stamped at its elapsed steps
        steps, _ = tel.k_series(scopes[0], res.tracker.names[0])
        assert len(steps) == res.chunks
        assert int(steps[-1]) == res.elapsed

    def test_telemetry_save_load_round_trip(self, tmp_path):
        t = PrecisionTelemetry()
        t.record_series(
            "s", ["a"], [6, 12], np.array([[3], [4]]), np.array([[1], [1]]),
            np.array([[0], [0]]), coverage={"a": 0.97},
        )
        p = t.save(str(tmp_path / "telemetry.json"))
        back = load_telemetry(p)
        s = back.all_series()[0]
        assert s.k == [3, 4] and s.grew == [1, 1] and s.coverage == 0.97
        with pytest.raises(ValueError):
            bad = tmp_path / "bad.json"
            bad.write_text(json.dumps({"schema": "other"}))
            load_telemetry(str(bad))


# ---------------------------------------------------------------------------
# shared timing helper + end-to-end export/reporter
# ---------------------------------------------------------------------------


class TestTimingAndReporter:
    def test_measure_splits_compile_from_steady_state(self):
        import jax.numpy as jnp

        @jax.jit
        def f(x):
            return x * 2.0

        t = measure(f, jnp.ones((64,)), iters=3)
        assert t.iters == 3
        assert t.compile_us > t.us_per_call  # first call paid the trace
        np.testing.assert_array_equal(np.asarray(t.result), np.full((64,), 2.0))

    def test_enable_export_disable_round_trip(self, tmp_path):
        obs.enable()
        with obs.span("unit", n=1):
            obs.inc("repro_test_events_total", kind="unit")
        paths = obs.export(str(tmp_path))
        obs.disable()
        doc = load_trace(paths["trace"])
        assert any(e["name"] == "unit" for e in doc["traceEvents"])
        with open(paths["prometheus"]) as f:
            fams = parse_prometheus(f.read())
        assert "repro_test_events_total" in fams
        with pytest.raises(RuntimeError):
            obs.export(str(tmp_path))  # disabled: must refuse

    def test_reporter_smoke_gate_passes(self, tmp_path):
        from repro.obs.__main__ import main

        assert main(["--smoke", "--out", str(tmp_path / "obs")]) == 0
        # and the report mode reads back what the smoke exported
        assert main(["--dir", str(tmp_path / "obs"), "--top", "3"]) == 0
