"""repro.service: the resident bucket batch, restacked only on a membership
change.

Members joining, draining, evicted and resumed, and a sibling bucket whose
chunk raises: every member matches its solo run. Shared cases live in
``_service_cases.py``.
"""

import jax
import numpy as np
import pytest

import repro.obs as obs
from repro.core.policy import PRESETS
from repro.pde import HeatConfig, Simulation
from repro.service import ServiceConfig, SimRequest, SimService

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
# the resident batch: restacked only on a membership change
# ---------------------------------------------------------------------------


class TestResidentBatch:
    @pytest.mark.parametrize("stepper", sorted(SMALL_CFGS))
    @pytest.mark.parametrize("mode", [m[0] for m in MODES])
    def test_schedule_bit_identical_to_solo(self, stepper, mode, tmp_path):
        """Members joining and draining mid-run, one evicted and resumed, and
        a sibling bucket whose second chunk raises: every member that
        finishes matches its solo run, and the failed one leaves with its
        state as of its last good chunk."""
        prec = dict((m[0], m[1]) for m in MODES)[mode]
        other = "bf16" if mode == "f32" else "f32"
        cfg = SMALL_CFGS[stepper]
        init = Simulation(stepper, cfg, prec).stepper.init_state(cfg)

        def req(steps, every, scale=None, precision=prec):
            return SimRequest(stepper, steps=steps, precision=precision, cfg=cfg,
                              snapshot_every=every,
                              state0=None if scale is None else _scaled(init, scale),
                              execution="reference")

        svc = SimService(ServiceConfig(ckpt_dir=str(tmp_path), auto_resume=False))
        hA = svc.submit(req(24, 8))
        hB = svc.submit(req(18, 6, 0.5))
        svc.pump()  # A and B at 6
        hC = svc.submit(req(12, 4, 1.5))
        svc.pump()  # C joins: all advance 2
        svc.evict(hB.id)  # B leaves mid-run at 8 (not one of its events)
        hD = svc.submit(req(12, 4, precision=PRESETS[other]))  # a sibling bucket
        _fail_second_chunk(svc, hD._record.key)
        svc.pump()
        svc.pump()
        svc.resume(hB.id)
        failures = 0
        for _ in range(100):
            try:
                if not svc.pump():
                    break
            except RuntimeError:
                failures += 1
        assert failures == 1

        for h, run in ((hA, (24, 8)), (hB, (18, 6, 0.5)), (hC, (12, 4, 1.5))):
            assert h.status == "done"
            solo = _solo(stepper, mode, *run)
            np.testing.assert_array_equal(np.stack(h.snapshots), np.asarray(solo.snapshots))
            _assert_tree_equal(h.result().state, solo.state)
            _assert_trackers_equal(h.result().tracker, solo.tracker)
        assert svc.metrics.evicted == 1 and svc.metrics.resumed == 1

        recD = hD._record
        assert hD.status == "failed" and recD.elapsed == 4
        _assert_tree_equal(recD.state, _solo(stepper, other, 4, 4).state)
        assert recD.resident_in is None
        assert svc.active_members == 0

    @pytest.mark.parametrize("mode", [m[0] for m in MODES])
    def test_lone_request_restacks_once(self, mode):
        """A request alone in its bucket for 8 chunks builds its batch once
        and reuses it for the other 7."""
        prec = dict((m[0], m[1]) for m in MODES)[mode]
        svc = SimService(ServiceConfig())
        h = svc.submit(SimRequest("heat1d", steps=16, precision=prec,
                                  cfg=HeatConfig(nx=48), snapshot_every=2))
        svc.run_until_idle()
        assert h.status == "done" and h.result().chunks == 8
        m = svc.metrics
        assert (m.restacks, m.resident_chunks) == (1, 7)
        assert m.registry.counter("repro_service_restacks_total").total() == 1
        assert m.registry.counter("repro_service_resident_chunks_total").total() == 7

    @pytest.mark.parametrize("stepper", sorted(SMALL_CFGS))
    def test_tracker_telemetry_matches_chunk_outputs(self, stepper, monkeypatch):
        """With telemetry on, each request's per-chunk tracker series is its
        row of every chunk's stacked output tracker, entry for entry."""
        cfg = SMALL_CFGS[stepper]
        s0b = _scaled(Simulation(stepper, cfg, TRACKED).stepper.init_state(cfg), 0.5)
        svc = SimService(ServiceConfig())
        rows = {}  # request id -> [(step, k, grew, shrank)] sliced from the outputs
        calls = {}  # id of a call's outputs -> (outputs, members, chunk steps)
        get = svc._compiler.get

        def spy(sim, key, chunk, *args, **kw):
            fn, fresh = get(sim, key, chunk, *args, **kw)

            def run(*xs):
                out = fn(*xs)
                (bucket,) = svc._live_buckets()
                calls[id(out)] = (out, list(bucket.members), chunk)
                return out

            return run, fresh

        sync = jax.block_until_ready

        def synced(x):
            # a chunk's rows as the bucket syncs it: a chunk run ahead and
            # dropped at a join is never synced, so it is no member's chunk
            if id(x) in calls:
                out, members, chunk = calls.pop(id(x))
                st = out[2].state
                for i, m in enumerate(members):
                    rows.setdefault(m.id, []).append((
                        m.elapsed + chunk, np.asarray(st.k[i]),
                        np.asarray(st.overflow_steps[i]), np.asarray(st.shrink_steps[i]),
                    ))
            return sync(x)

        svc._compiler.get = spy
        monkeypatch.setattr(jax, "block_until_ready", synced)
        obs.enable()
        try:
            hA = svc.submit(SimRequest(stepper, steps=24, precision=TRACKED, cfg=cfg,
                                       snapshot_every=8, execution="reference"))
            svc.pump()
            hB = svc.submit(SimRequest(stepper, steps=18, precision=TRACKED, cfg=cfg,
                                       snapshot_every=6, state0=s0b,
                                       execution="reference"))
            svc.run_until_idle()
            tel = obs.active().telemetry
            names = hA.result().tracker.names
            series = {
                h.id: [tel.series(f"req{h.id}:{stepper}", n) for n in names]
                for h in (hA, hB)
            }
        finally:
            obs.disable()

        for h in (hA, hB):
            expected = rows[h.id]
            assert len(expected) == h.result().chunks
            for i, s in enumerate(series[h.id]):
                assert s.steps == [step for step, *_ in expected]
                assert s.k == [int(k[i]) for _, k, _, _ in expected]
                assert s.grew == [int(g[i]) for _, _, g, _ in expected]
                assert s.shrank == [int(r[i]) for _, _, _, r in expected]
