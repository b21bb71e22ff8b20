"""repro.service: packing is semantically invisible, per stepper x mode.

A request served through a multi-request bucket, including one that joins
mid-flight with a misaligned snapshot cadence, matches its solo
``Simulation.run`` bit for bit (final split ``k`` and the §5.3 adjustment
counters for ``rr_tracked``). Shared cases live in ``_service_cases.py``.
"""

import numpy as np
import pytest

from repro.core.policy import PRESETS
from repro.pde import HeatConfig, Heat2DConfig, Simulation
from repro.service import ServiceConfig, SimRequest, SimService

from _service_cases import (
    MODES,
    SMALL_CFGS,
    TRACKED,
    _assert_trackers_equal,
    _scaled,
    _solo,
)


# ---------------------------------------------------------------------------
# the acceptance matrix: packing invisibility per stepper x mode
# ---------------------------------------------------------------------------


class TestPackingInvisibility:
    @pytest.mark.parametrize("stepper", sorted(SMALL_CFGS))
    @pytest.mark.parametrize("mode", [m[0] for m in MODES])
    def test_bucketed_equals_solo(self, stepper, mode):
        """Two requests share a bucket; the second joins mid-flight with a
        misaligned cadence (forcing chunk subdivision); both must reproduce
        their solo runs."""
        prec = dict((m[0], m[1]) for m in MODES)[mode]
        bit_exact = dict((m[0], m[2]) for m in MODES)[mode]
        cfg = SMALL_CFGS[stepper]
        sim = Simulation(stepper, cfg, prec)
        s0b = _scaled(sim.stepper.init_state(cfg), 0.5)

        svc = SimService(ServiceConfig())
        hA = svc.submit(
            SimRequest(stepper, steps=24, precision=prec, cfg=cfg,
                       snapshot_every=8, execution="reference")
        )
        assert svc.pump()  # A runs its first chunk alone...
        hB = svc.submit(  # ...then B joins the running bucket mid-flight
            SimRequest(stepper, steps=18, precision=prec, cfg=cfg,
                       snapshot_every=6, state0=s0b, execution="reference")
        )
        svc.run_until_idle()
        assert hA.status == "done" and hB.status == "done"
        # they really shared one bucket (continuous batching, not siblings)
        assert svc.metrics.occupancy()[1] == 2

        soloA = _solo(stepper, mode, 24, 8)
        soloB = _solo(stepper, mode, 18, 6, 0.5)
        for h, solo in ((hA, soloA), (hB, soloB)):
            if bit_exact:
                np.testing.assert_array_equal(
                    np.stack(h.snapshots), np.asarray(solo.snapshots)
                )
                np.testing.assert_array_equal(
                    np.asarray(h.result().state), np.asarray(solo.state)
                )
            _assert_trackers_equal(h.result().tracker, solo.tracker)

    def test_fused_bucket_parity(self):
        """The fused plane: deploy rides bf16 kernels bit-exactly through a
        shared bucket with a mid-flight joiner; rr_tracked converges to the
        identical final split + §5.3 counters."""
        cfg = Heat2DConfig(nx=16, ny=16)
        for prec, bit_exact in ((PRESETS["deploy"], True), (TRACKED, False)):
            sim = Simulation("heat2d", cfg, prec)
            if not sim.fused_eligible():
                pytest.skip("heat2d not fused-eligible in this build")
            svc = SimService(ServiceConfig())
            hA = svc.submit(
                SimRequest("heat2d", steps=12, precision=prec, cfg=cfg,
                           snapshot_every=4, execution="fused")
            )
            assert svc.pump()
            hB = svc.submit(
                SimRequest("heat2d", steps=12, precision=prec, cfg=cfg,
                           snapshot_every=4,
                           state0=_scaled(sim.stepper.init_state(cfg), 0.5),
                           execution="fused")
            )
            svc.run_until_idle()
            assert svc.metrics.occupancy()[1] == 2
            soloA = sim.run(12, snapshot_every=4, execution="fused")
            soloB = sim.run(
                12, snapshot_every=4, execution="fused",
                state0=_scaled(sim.stepper.init_state(cfg), 0.5),
            )
            for h, solo in ((hA, soloA), (hB, soloB)):
                _assert_trackers_equal(h.result().tracker, solo.tracker)
                if bit_exact:
                    np.testing.assert_array_equal(
                        np.stack(h.snapshots), np.asarray(solo.snapshots)
                    )
                else:
                    np.testing.assert_allclose(
                        np.stack(h.snapshots), np.asarray(solo.snapshots),
                        rtol=2e-2, atol=1e-5,
                    )

    def test_remainder_horizon(self):
        """A horizon that is not a multiple of the cadence drains with the
        same snapshots + final state as solo (remainder steps run, no
        trailing snapshot)."""
        cfg = HeatConfig(nx=48)
        svc = SimService(ServiceConfig())
        h = svc.submit(
            SimRequest("heat1d", steps=23, precision="r2f2_16", cfg=cfg,
                       snapshot_every=8, execution="reference")
        )
        svc.run_until_idle()
        solo = Simulation("heat1d", cfg, PRESETS["r2f2_16"]).run(23, snapshot_every=8)
        assert h.snapshot_steps == [8, 16]
        np.testing.assert_array_equal(np.stack(h.snapshots), np.asarray(solo.snapshots))
        np.testing.assert_array_equal(np.asarray(h.result().state), np.asarray(solo.state))
