"""repro.service — the batched simulation-serving plane (ISSUE 5).

The acceptance contract: packing is *semantically invisible*. For every
registered stepper, a request served through a multi-request bucket —
including one that joins mid-flight via continuous batching, with a
deliberately misaligned snapshot cadence so the bucket's chunking differs
from either solo run — yields bit-identical snapshots/state to a solo
``Simulation.run`` for f32/bf16/fixed/rr_tile/deploy, and identical final
split ``k`` + §5.3 adjustment counters for ``rr_tracked``. Around that:
eviction→resume bit-exactness through ``repro.ckpt``, admission control and
backpressure, bucketing rules, the unified policy-artifact resolution,
streaming, metrics, and the solver's new ``tracker0_batch`` repacking entry.
The packing matrix and the resident batch are in ``test_service_packing.py``
and ``test_service_resident.py``, so that a worker per file spreads them.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.flexformat import FlexFormat
from repro.core.policy import PRESETS, PrecisionConfig
from repro.pde import BurgersConfig, HeatConfig, Simulation
from repro.profile import PrecisionPolicy
from repro.service import (
    ServiceConfig,
    ServiceOverloaded,
    SimRequest,
    SimService,
    resolve_request,
)

from _service_cases import (
    MODES,
    SMALL_CFGS,
    TRACKED,
    _assert_trackers_equal,
    _assert_tree_equal,
    _fail_second_chunk,
    _scaled,
    _solo,
)


# ---------------------------------------------------------------------------
# solver: the repacking entry the service builds on
# ---------------------------------------------------------------------------


class TestTrackerBatchRepacking:
    def test_run_ensemble_tracker0_batch_resumes(self):
        """Chunked ensemble advance with tracker stacks handed back in ==
        one uninterrupted ensemble, bit for bit (state AND adjust state)."""
        cfg = BurgersConfig(nx=48)
        sim = Simulation("burgers1d", cfg, TRACKED)
        u0 = sim.stepper.init_state(cfg)
        u0b = jnp.stack([u0, 0.5 * u0, 2.0 * u0])

        full = sim.run_ensemble(u0b, 20, snapshot_every=10)
        first = sim.run_ensemble(u0b, 10, snapshot_every=10)
        second = sim.run_ensemble(
            first.state, 10, snapshot_every=10, tracker0_batch=first.tracker
        )
        np.testing.assert_array_equal(np.asarray(second.state), np.asarray(full.state))
        _assert_trackers_equal(second.tracker, full.tracker)


# ---------------------------------------------------------------------------
# scheduler: bucketing rules, admission control, backpressure
# ---------------------------------------------------------------------------


class TestScheduling:
    def test_compatible_requests_share_a_bucket(self):
        svc = SimService(ServiceConfig())
        cfg = HeatConfig(nx=48)
        for _ in range(3):
            svc.submit(SimRequest("heat1d", steps=8, precision="f32", cfg=cfg))
        svc._fill()
        assert len(svc._live_buckets()) == 1
        assert len(svc._live_buckets()[0]) == 3

    def test_incompatible_requests_get_sibling_buckets(self):
        svc = SimService(ServiceConfig())
        cfg = HeatConfig(nx=48)
        svc.submit(SimRequest("heat1d", steps=8, precision="f32", cfg=cfg))
        svc.submit(SimRequest("heat1d", steps=8, precision="bf16", cfg=cfg))  # mode
        svc.submit(SimRequest("heat1d", steps=8, precision="f32", cfg=HeatConfig(nx=32)))  # cfg
        svc.submit(SimRequest("heat2d", steps=8, precision="f32"))  # stepper
        svc._fill()
        assert len(svc._live_buckets()) == 4

    def test_max_bucket_caps_vmap_width(self):
        svc = SimService(ServiceConfig(max_bucket=2))
        cfg = HeatConfig(nx=48)
        for _ in range(5):
            svc.submit(SimRequest("heat1d", steps=8, precision="f32", cfg=cfg))
        svc._fill()
        widths = sorted(len(b) for b in svc._live_buckets())
        assert widths == [1, 2, 2]

    def test_backpressure_raises_and_counts(self):
        svc = SimService(ServiceConfig(max_queue=2))
        svc.submit(SimRequest("heat1d", steps=8))
        svc.submit(SimRequest("heat1d", steps=8))
        with pytest.raises(ServiceOverloaded):
            svc.submit(SimRequest("heat1d", steps=8))
        assert svc.metrics.rejected == 1
        assert svc.metrics.submitted == 2

    def test_bad_requests_rejected_at_admission(self):
        svc = SimService(ServiceConfig())
        with pytest.raises(KeyError, match="no PDE stepper"):
            svc.submit(SimRequest("not-a-stepper", steps=8))
        with pytest.raises(ValueError, match="horizon"):
            svc.submit(SimRequest("heat1d", steps=0))
        with pytest.raises(ValueError, match="snapshot_every"):
            svc.submit(SimRequest("heat1d", steps=8, snapshot_every=-5))
        assert svc.metrics.rejected == 3

    def test_explicit_fused_ineligible_rejected_at_submit(self):
        """execution='fused' on a stepper without a fused body fails at
        admission, not mid-flight."""
        from repro.pde import Stepper, register_stepper
        from repro.pde.registry import _STEPPERS

        class NoFused(Stepper):
            sites = ("nf.mul",)

            def default_config(self):
                return None

            def init_state(self, cfg):
                return jnp.ones((8,), jnp.float32)

            def step(self, u, cfg, ops):
                return ops.mul(jnp.float32(0.5), u, "nf.mul")

        try:
            register_stepper("test_nofused", NoFused)
            svc = SimService(ServiceConfig())
            with pytest.raises(ValueError, match="not fused-eligible"):
                svc.submit(SimRequest("test_nofused", steps=4, precision="f32",
                                      execution="fused"))
            assert svc.metrics.rejected == 1
        finally:
            _STEPPERS.pop("test_nofused", None)

    def test_max_active_members_bounds_occupancy(self):
        svc = SimService(ServiceConfig(max_active_members=2))
        cfg = HeatConfig(nx=48)
        for _ in range(4):
            svc.submit(SimRequest("heat1d", steps=8, precision="f32", cfg=cfg,
                                  snapshot_every=4))
        svc.run_until_idle()
        assert svc.metrics.completed == 4
        assert svc.metrics.occupancy()[1] <= 2


# ---------------------------------------------------------------------------
# eviction / resume (satellite: bit-exact round trip through repro.ckpt)
# ---------------------------------------------------------------------------


class TestEvictionResume:
    def test_evicted_and_resumed_is_bit_identical(self, tmp_path):
        """A tracked request checkpointed out mid-run and resumed produces
        bit-identical snapshots AND identical final tracker k / §5.3
        counters to an uninterrupted run."""
        cfg = BurgersConfig(nx=48)
        svc = SimService(ServiceConfig(ckpt_dir=str(tmp_path), auto_resume=False))
        hA = svc.submit(SimRequest("burgers1d", steps=30, precision=TRACKED,
                                   cfg=cfg, snapshot_every=10, execution="reference"))
        hB = svc.submit(SimRequest("burgers1d", steps=30, precision=TRACKED,
                                   cfg=cfg, snapshot_every=10, execution="reference"))
        svc.pump()  # both at elapsed=10
        path = svc.evict(hA.id)
        assert hA.status == "evicted"
        assert os.path.isdir(path)
        assert svc.evicted_ids == [hA.id]

        svc.run_until_idle()  # B completes alone; A stays evicted
        assert hB.status == "done" and hA.status == "evicted"

        svc.resume(hA.id)
        svc.run_until_idle()
        assert hA.status == "done"

        solo = Simulation("burgers1d", cfg, TRACKED).run(30, snapshot_every=10)
        np.testing.assert_array_equal(np.stack(hA.snapshots), np.asarray(solo.snapshots))
        np.testing.assert_array_equal(np.asarray(hA.result().state), np.asarray(solo.state))
        _assert_trackers_equal(hA.result().tracker, solo.tracker)

        kinds = [e.kind for e in hA.stream.drain()]
        assert kinds == ["snapshot", "evicted", "resumed", "snapshot", "snapshot", "done"]
        assert svc.metrics.evicted == 1 and svc.metrics.resumed == 1

    def test_auto_evict_spills_long_horizon_under_pressure(self, tmp_path):
        """With one slot, a long-horizon member is spilled for shorter
        queued work and transparently restored after — both complete,
        bit-identically to solo."""
        cfg = HeatConfig(nx=48)
        svc = SimService(ServiceConfig(
            ckpt_dir=str(tmp_path), max_active_members=1,
            auto_evict=True, evict_min_remaining=0,
        ))
        hLong = svc.submit(SimRequest("heat1d", steps=40, precision="r2f2_16",
                                      cfg=cfg, snapshot_every=10))
        svc.pump()  # long runs its first chunk
        hShort = svc.submit(SimRequest("heat1d", steps=8, precision="r2f2_16",
                                       cfg=cfg, snapshot_every=4))
        svc.run_until_idle()
        assert hLong.status == "done" and hShort.status == "done"
        assert svc.metrics.evicted >= 1 and svc.metrics.resumed >= 1

        soloL = Simulation("heat1d", cfg, PRESETS["r2f2_16"]).run(40, snapshot_every=10)
        np.testing.assert_array_equal(
            np.stack(hLong.snapshots), np.asarray(soloL.snapshots)
        )


def _assert_bits_equal(a, b):
    def same(x, y):
        x, y = np.asarray(x), np.asarray(y)
        assert (x.dtype, x.shape) == (y.dtype, y.shape)
        assert x.tobytes() == y.tobytes()

    jax.tree_util.tree_map(same, a, b)


#: (stepper, mode, execution, storage, steps) at a cadence of 2 steps:
#: every stepper x mode on the served plane ("auto": the megakernel), one
#: quantized-storage case, one remainder horizon, the fused and reference
#: planes
AHEAD_CASES = [
    (stepper, mode, "auto", "f32", 16)
    for stepper in sorted(SMALL_CFGS)
    for mode in ("rr_tracked", "deploy", "f32")
] + [
    ("heat1d", "rr_tracked", "auto", "quantized", 16),
    ("burgers1d", "rr_tracked", "auto", "f32", 17),
    ("heat2d", "rr_tracked", "fused", "f32", 16),
    ("advection1d", "rr_tracked", "reference", "f32", 16),
]


class TestChunksAhead:
    @pytest.mark.parametrize("stepper,mode,execution,storage,steps", AHEAD_CASES)
    def test_lone_request_runs_ahead_bit_identically(
        self, stepper, mode, execution, storage, steps
    ):
        """A request alone in the service runs every chunk after its first
        ahead, one snapshot interval a chunk as before, and its state,
        frames, frame steps, tracker, final k and §5.3 counters equal its
        solo run and those of a twin served beside a sibling bucket, where
        no chunk runs ahead, bit for bit."""
        every = 2
        prec = dict((m[0], m[1]) for m in MODES)[mode]
        cfg = SMALL_CFGS[stepper]

        def serve(twins, **config):
            svc = SimService(ServiceConfig(**config))
            hs = [svc.submit(SimRequest(stepper, steps=steps, precision=prec, cfg=cfg,
                                        snapshot_every=every, execution=execution,
                                        storage=storage))
                  for _ in range(twins)]
            svc.run_until_idle()
            assert all(h.status == "done" for h in hs)
            return hs[0].result(), svc.metrics

        chunks = -(-steps // every)
        lone, m = serve(1)
        assert (lone.chunks, m.chunks, m.chunks_ahead) == (chunks, chunks, chunks - 1)
        assert m.registry.counter("repro_service_chunks_ahead_total").total() == chunks - 1
        assert m.summary()["chunks_ahead"] == chunks - 1
        assert f"ahead={chunks - 1}" in m.report()
        beside, m = serve(2, max_bucket=1)  # two live buckets take turns
        assert (beside.chunks, m.chunks_ahead) == (chunks, 0)
        solo = Simulation(stepper, cfg, prec).run(
            steps, snapshot_every=every, execution=execution, storage=storage)

        assert lone.snapshot_steps == beside.snapshot_steps == list(
            range(every, steps + 1, every))
        for other in (beside, solo):
            _assert_bits_equal(lone.state, other.state)
            _assert_bits_equal(lone.snapshots, list(other.snapshots))
            assert (lone.tracker is None) == (other.tracker is None)
            if lone.tracker is not None:
                _assert_bits_equal(lone.tracker.state, other.tracker.state)
        assert (lone.final_k, lone.adjustments) == (beside.final_k, beside.adjustments)
        if mode == "rr_tracked":
            assert lone.final_k is not None

    @pytest.mark.parametrize("condition", ["two_buckets", "queued"])
    def test_no_chunk_runs_ahead_beside_other_work(self, condition):
        """Another live bucket or a queued request: no chunk runs ahead
        while it is there. With one slot, B waits in the queue through A's
        8 chunks and then runs 7 of its own 8 ahead, alone."""
        cfg = HeatConfig(nx=48)
        config = {"two_buckets": {"max_bucket": 1},
                  "queued": {"max_active_members": 1}}[condition]
        svc = SimService(ServiceConfig(**config))
        scales = (None, 0.5)
        hs = [svc.submit(SimRequest(
            "heat1d", steps=16, precision=TRACKED, cfg=cfg, snapshot_every=2,
            state0=None if s is None else _scaled(
                Simulation("heat1d", cfg, TRACKED).stepper.init_state(cfg), s)))
            for s in scales]
        svc.run_until_idle()
        m = svc.metrics
        assert m.chunks == 16
        assert m.chunks_ahead == {"two_buckets": 0, "queued": 7}[condition]
        for h, scale in zip(hs, scales):
            solo = _solo("heat1d", "rr_tracked", 16, 2, scale)
            np.testing.assert_array_equal(np.stack(h.snapshots), np.asarray(solo.snapshots))
            _assert_tree_equal(h.result().state, solo.state)
            _assert_trackers_equal(h.result().tracker, solo.tracker)

    def test_frames_stream_one_a_pump(self):
        """Running ahead leaves streaming as it was: a lone request's frames
        reach its stream one a pump, as each interval ends."""
        svc = SimService(ServiceConfig())
        h = svc.submit(SimRequest("heat1d", steps=16, precision=TRACKED,
                                  cfg=HeatConfig(nx=48), snapshot_every=2))
        per_pump = []
        while svc.pump():
            per_pump.append([(e.kind, e.step) for e in h.stream.drain()])
        assert per_pump == [[("snapshot", s)] for s in range(2, 16, 2)] + [
            [("snapshot", 16), ("done", 16)]]
        assert svc.metrics.chunks_ahead == 7

    @pytest.mark.parametrize("change", ["join", "evict"])
    def test_membership_change_drops_the_chunk_run_ahead(self, change, tmp_path):
        """A join or an eviction after a lone bucket's pump drops the chunk
        it ran ahead: the next chunk runs anew from the batch of the last
        one, and every member matches its solo run."""
        cfg = BurgersConfig(nx=48)
        svc = SimService(ServiceConfig(ckpt_dir=str(tmp_path)))

        def req(scale):
            init = Simulation("burgers1d", cfg, TRACKED).stepper.init_state(cfg)
            return SimRequest("burgers1d", steps=30, precision=TRACKED, cfg=cfg,
                              snapshot_every=10, state0=_scaled(init, scale),
                              execution="reference")

        hA = svc.submit(req(1.0))
        hB = svc.submit(req(0.5))
        svc.pump()  # A and B at 10, their second chunk dispatched ahead
        (bucket,) = svc._live_buckets()
        assert bucket._ahead is not None
        if change == "join":
            hC = svc.submit(req(1.5))
            svc.pump()  # C joins: A and B's chunk from 10 runs anew, with C
            assert hA._record.elapsed == hB._record.elapsed == 20
            assert hC._record.elapsed == 10
            served = ((hA, 1.0), (hB, 0.5), (hC, 1.5))
        else:
            svc.evict(hA.id)
            assert hA._record.elapsed == 10 and bucket._ahead is None
            served = ((hA, 1.0), (hB, 0.5))
        svc.run_until_idle()
        for h, scale in served:
            assert h.status == "done"
            solo = Simulation("burgers1d", cfg, TRACKED).run(
                30, snapshot_every=10, state0=_scaled(
                    Simulation("burgers1d", cfg, TRACKED).stepper.init_state(cfg), scale))
            np.testing.assert_array_equal(np.stack(h.snapshots), np.asarray(solo.snapshots))
            _assert_tree_equal(h.result().state, solo.state)
            _assert_trackers_equal(h.result().tracker, solo.tracker)

    def test_failure_of_a_chunk_run_ahead_surfaces_at_its_chunk(self):
        """A chunk whose dispatch ahead raises fails its members in the pump
        that would have run it: the chunk before is streamed first, and
        the members leave with its state."""
        cfg = SMALL_CFGS["heat1d"]
        svc = SimService(ServiceConfig())
        h = svc.submit(SimRequest("heat1d", steps=12, precision="f32", cfg=cfg,
                                  snapshot_every=4, execution="reference"))
        _fail_second_chunk(svc, h._record.key)
        assert svc.pump()
        assert [(e.kind, e.step) for e in h.stream.drain()] == [("snapshot", 4)]
        with pytest.raises(RuntimeError, match="injected chunk failure"):
            svc.pump()
        rec = h._record
        assert h.status == "failed" and rec.elapsed == 4
        _assert_tree_equal(rec.state, _solo("heat1d", "f32", 4, 4).state)
        assert [e.kind for e in h.stream.drain()] == ["failed"]
        assert svc.active_members == 0


# ---------------------------------------------------------------------------
# per-request precision policies (unified artifact resolution)
# ---------------------------------------------------------------------------


def _accepted_policy():
    return PrecisionPolicy(
        stepper="heat1d",
        fmt=FlexFormat(3, 9, 3),
        sites={
            "heat.flux": {"k": 1, "k_lo": 0, "k_hi": 2},
            "heat.update": {"k": 2, "k_lo": 1, "k_hi": 3},
        },
        validation={"accepted": True, "rel_l2_deploy": 0.0},
    )


class TestPerRequestPolicies:
    def test_policy_seeds_tracker_and_bounds(self):
        rec = resolve_request(
            1, SimRequest("heat1d", steps=8, precision=TRACKED, policy=_accepted_policy())
        )
        np.testing.assert_array_equal(np.asarray(rec.tracker.state.k), [1, 2])
        assert rec.key.prec.k_bounds == ((0, 2), (1, 3))

    def test_unaccepted_policy_refused(self):
        pol = _accepted_policy()
        pol.validation = None
        svc = SimService(ServiceConfig())
        with pytest.raises(ValueError, match="never accepted"):
            svc.submit(SimRequest("heat1d", steps=8, precision=TRACKED, policy=pol))
        assert svc.metrics.rejected == 1

    def test_foreign_stepper_policy_refused(self):
        with pytest.raises(ValueError, match="do not transfer"):
            resolve_request(
                1, SimRequest("burgers1d", steps=8, precision=TRACKED,
                              policy=_accepted_policy())
            )

    def test_policy_fmt_rebases_request_precision(self):
        """The artifact's format wins (shared resolve_policy gate), so a
        request submitted with a different fmt still buckets on the
        artifact's <EB,MB,FX>."""
        other = dataclasses.replace(TRACKED, fmt=FlexFormat(3, 8, 4))
        rec = resolve_request(
            1, SimRequest("heat1d", steps=8, precision=other, policy=_accepted_policy())
        )
        assert rec.key.prec.fmt == FlexFormat(3, 9, 3)

    def test_different_policies_same_bounds_pack_by_prec(self):
        """Bucket compatibility is the *effective* config: two requests with
        the same artifact share a bucket; different k_bounds split."""
        polA = _accepted_policy()
        recA = resolve_request(1, SimRequest("heat1d", steps=8, precision=TRACKED, policy=polA))
        recB = resolve_request(2, SimRequest("heat1d", steps=8, precision=TRACKED, policy=polA))
        assert recA.key == recB.key
        polC = _accepted_policy()
        polC.sites["heat.flux"]["k_hi"] = 3
        recC = resolve_request(3, SimRequest("heat1d", steps=8, precision=TRACKED, policy=polC))
        assert recC.key != recA.key

    def test_service_run_with_policy_matches_solo_policy_run(self):
        pol = _accepted_policy()
        svc = SimService(ServiceConfig())
        h = svc.submit(SimRequest("heat1d", steps=16, precision=TRACKED,
                                  policy=pol, snapshot_every=8))
        svc.run_until_idle()
        solo = Simulation("heat1d", None, TRACKED).run(16, snapshot_every=8, policy=pol)
        np.testing.assert_array_equal(np.stack(h.snapshots), np.asarray(solo.snapshots))
        _assert_trackers_equal(h.result().tracker, solo.tracker)

    def test_serve_shim_delegates_to_artifact_impl(self):
        """serve.decode.resolve_policy is a thin shim over the single
        implementation in repro.profile.artifact."""
        from repro.profile.artifact import resolve_policy as impl
        from repro.serve import resolve_policy as shim

        pol = _accepted_policy()
        prec = PrecisionConfig(mode="deploy", fmt=FlexFormat(3, 8, 4))
        got_prec, got_pol = shim(prec, pol)
        exp_prec, exp_pol = impl(prec, pol)
        assert got_prec == exp_prec and got_pol is exp_pol is pol
        pol.validation = None
        with pytest.raises(ValueError, match="never accepted"):
            shim(prec, pol)
        # opting out mirrors the shared impl too
        assert shim(prec, pol, require_accepted=False)[0].fmt == pol.fmt


# ---------------------------------------------------------------------------
# streaming + metrics
# ---------------------------------------------------------------------------


class TestStreamingAndMetrics:
    def test_stream_events_arrive_in_order(self):
        svc = SimService(ServiceConfig())
        h = svc.submit(SimRequest("heat1d", steps=12, precision="f32",
                                  cfg=HeatConfig(nx=48), snapshot_every=4))
        seen = []
        while svc.pump():
            seen += h.stream.drain()
        kinds = [e.kind for e in seen]
        assert kinds == ["snapshot", "snapshot", "snapshot", "done"]
        assert [e.step for e in seen] == [4, 8, 12, 12]
        assert h.stream.closed
        snap0 = seen[0].payload
        assert isinstance(snap0, np.ndarray) and snap0.shape == (48,)

    def test_metrics_surface(self):
        svc = SimService(ServiceConfig())
        cfg = BurgersConfig(nx=48)
        for s in (1.0, 0.5):
            svc.submit(SimRequest("burgers1d", steps=12, precision=TRACKED, cfg=cfg,
                                  snapshot_every=4,
                                  state0=s * Simulation("burgers1d", cfg, TRACKED).stepper.init_state(cfg)))
        svc.run_until_idle()
        s = svc.metrics.summary()
        assert s["submitted"] == s["completed"] == 2
        assert s["chunks"] == 3  # both members aligned: 3 shared chunks
        assert s["member_steps"] == 24
        assert s["occupancy_mean"] == 2.0 and s["occupancy_max"] == 2
        assert s["throughput_steps_per_s"] > 0
        assert np.isfinite(s["chunk_latency_p50_us"])
        assert s["chunk_latency_p99_us"] >= s["chunk_latency_p50_us"]
        assert set(s["site_adjustments"]) == {"burgers.uu", "burgers.flux"}
        assert "throughput" in svc.metrics.report()

    def test_compiled_chunk_cache_reused_across_repacks(self):
        """Steady-state traffic re-uses jitted chunk programs: serving two
        identical sequential requests compiles no more programs than the
        distinct (chunk, width) shapes seen."""
        svc = SimService(ServiceConfig())
        cfg = HeatConfig(nx=48)
        svc.submit(SimRequest("heat1d", steps=12, precision="f32", cfg=cfg,
                              snapshot_every=4))
        svc.run_until_idle()
        n_first = len(svc._compiler)
        svc.submit(SimRequest("heat1d", steps=12, precision="f32", cfg=cfg,
                              snapshot_every=4))
        svc.run_until_idle()
        assert len(svc._compiler) == n_first  # same (key, chunk, width): no retrace


# ---------------------------------------------------------------------------
# sharding: bucket members ride the logical batch axis
# ---------------------------------------------------------------------------


class TestShardedService:
    def test_service_under_mesh_context(self):
        from jax.sharding import Mesh

        from repro.dist.sharding import axis_rules

        cfg = BurgersConfig(nx=48)
        mesh = Mesh(np.array(jax.devices()[:1]).reshape(1), ("data",))
        svc = SimService(ServiceConfig())  # sharded=None -> auto-detect
        with mesh, axis_rules(mesh):
            hs = [
                svc.submit(SimRequest("burgers1d", steps=12, precision="r2f2_16",
                                      cfg=cfg, snapshot_every=4))
                for _ in range(2)
            ]
            svc.run_until_idle()
        assert all(h.status == "done" for h in hs)
        solo = Simulation("burgers1d", cfg, PRESETS["r2f2_16"]).run(12, snapshot_every=4)
        np.testing.assert_array_equal(np.stack(hs[0].snapshots), np.asarray(solo.snapshots))
