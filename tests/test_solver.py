"""The repro.pde.solver framework: stepper registry, scan/snapshot driver,
tracker threading (the ISSUE 2 regression: rr_tracked PDE runs genuinely
carry k across steps), vmapped + sharded ensembles, and shim parity."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.policy import PRESETS, PrecisionConfig
from repro.pde import (
    BurgersConfig,
    HeatConfig,
    SimResult,
    Simulation,
    StepOps,
    Stepper,
    get_stepper,
    initial_wave,
    known_steppers,
    register_stepper,
    scenarios,
    simulate_heat,
    simulate_swe,
    SWEConfig,
)
from repro.precision import SiteTracker, get_engine

TRACKED = dataclasses.replace(PRESETS["r2f2_16"], mode="rr_tracked")
BUILTINS = ("advection1d", "burgers1d", "heat1d", "heat2d", "swe2d")


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


class TestStepperRegistry:
    def test_builtins_registered(self):
        assert set(BUILTINS) <= set(known_steppers())

    def test_get_stepper_resolves(self):
        for name in BUILTINS:
            st = get_stepper(name)
            assert isinstance(st, Stepper)
            assert st.name == name
            assert st.sites, name  # every workload declares its sites
            assert st.failure_mode in ("underflow", "overflow", "nonlinear-drift")

    def test_every_stepper_has_a_scenario(self):
        """The paper-pattern table (chip_smoke.py, examples/pde_zoo.py) judges
        every registered stepper at its own configuration type."""
        table = scenarios()
        assert sorted(table) == list(known_steppers())
        for name, sc in table.items():
            assert type(sc.cfg) is type(get_stepper(name).default_config()), name
            assert sc.judge in ("rel", "corr"), name

    def test_unknown_stepper_raises(self):
        with pytest.raises(KeyError, match="no PDE stepper"):
            get_stepper("not-a-stepper")

    def test_custom_stepper_is_drop_in(self):
        """A registered stepper immediately drives through Simulation."""

        class DecayStepper(Stepper):
            sites = ("decay.mul",)

            def default_config(self):
                return None

            def init_state(self, cfg):
                return jnp.ones((16,), jnp.float32)

            def step(self, u, cfg, ops):
                return ops.mul(jnp.float32(0.5), u, "decay.mul")

        from repro.pde.registry import _STEPPERS

        try:
            register_stepper("test_decay", DecayStepper)
            res = Simulation("test_decay", None, PRESETS["f32"]).run(3)
            np.testing.assert_allclose(np.asarray(res.state), 0.125)
        finally:
            _STEPPERS.pop("test_decay", None)


# ---------------------------------------------------------------------------
# shim parity: the old per-workload simulate() == the framework, bit for bit
# ---------------------------------------------------------------------------


class TestShimParity:
    @pytest.mark.parametrize("prec", ["f32", "r2f2_16", "e5m10"])
    def test_heat_shim_is_framework(self, prec):
        cfg = HeatConfig(nx=64)
        out, snaps = simulate_heat(cfg, PRESETS[prec], 120)
        res = Simulation("heat1d", cfg, PRESETS[prec]).run(120)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(res.state))
        np.testing.assert_array_equal(np.asarray(snaps), np.asarray(res.snapshots))

    def test_swe_shim_is_framework(self):
        cfg = SWEConfig(nx=32, ny=32)
        out, snaps = simulate_swe(cfg, PRESETS["r2f2_16"], 40)
        res = Simulation("swe2d", cfg, PRESETS["r2f2_16"]).run(40)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(res.state))
        np.testing.assert_array_equal(np.asarray(snaps), np.asarray(res.snapshots))
        assert snaps.shape[0] == 4  # swe snapshots h only, 4 by default

    def test_snapshot_every_and_remainder(self):
        cfg = HeatConfig(nx=64)
        res = Simulation("heat1d", cfg, PRESETS["f32"]).run(103, snapshot_every=25)
        assert res.snapshots.shape == (4, 64)  # 103 = 4*25 + 3 remainder steps
        # remainder steps really ran: state != last snapshot
        assert not np.array_equal(np.asarray(res.state), np.asarray(res.snapshots[-1]))


# ---------------------------------------------------------------------------
# tracker threading — the regression this refactor exists for
# ---------------------------------------------------------------------------


class TestTrackerThreading:
    def test_untracked_modes_get_no_tracker(self):
        for prec in ("f32", "e5m10", "r2f2_16", "bf16"):
            sim = Simulation("heat1d", HeatConfig(nx=32), PRESETS[prec])
            assert sim.init_tracker() is None
            assert sim.run(5).tracker is None

    def test_tracked_mode_auto_tracker_covers_sites(self):
        sim = Simulation("heat1d", HeatConfig(nx=32), TRACKED)
        tr = sim.init_tracker()
        assert isinstance(tr, SiteTracker)
        assert tr.names == get_stepper("heat1d").sites

    def test_rr_tracked_k_grows_during_run(self):
        """From a narrow start, the carried split must grow to cover the
        heat workload's alpha~1e-5 underflow pressure — stateless selection
        cannot leave this trace."""
        sim = Simulation("heat1d", HeatConfig(nx=64), TRACKED)
        tr0 = sim.init_tracker(k0=0)
        res = sim.run(50, tracker=tr0)
        assert isinstance(res.tracker, SiteTracker)
        k0 = np.asarray(tr0.state.k)
        k1 = np.asarray(res.tracker.state.k)
        assert (k1 != k0).any(), "tracker state did not evolve during the run"
        assert int(res.tracker.k("heat.flux")) == TRACKED.fmt.fx

    def test_rr_tracked_k_shrinks_on_range_drift(self):
        """Burgers: u*u needs the full split at t=0, then post-shock decay
        collapses the range — the carried k must shrink back (the paper's
        §4.2 redundancy rule exercised across steps)."""
        sim = Simulation("burgers1d", BurgersConfig(nx=128), TRACKED)
        res = sim.run(1200)
        k_init = TRACKED.fmt.fx  # default tracker starts wide
        k_fin = int(res.tracker.k("burgers.uu"))
        assert k_fin < k_init
        assert int(np.asarray(res.tracker.state.shrink_steps).sum()) >= 1

    def test_deploy_mode_tracks_too(self):
        res = Simulation("burgers1d", BurgersConfig(nx=128), PRESETS["deploy"]).run(600)
        assert isinstance(res.tracker, SiteTracker)
        assert int(res.tracker.k("burgers.uu")) < PRESETS["deploy"].fmt.fx

    def test_rr_tracked_heat_matches_f32(self):
        """Accuracy: the tracked engine (k carried across steps) reproduces
        the f32 run like the stateless rr engine does."""
        cfg = HeatConfig(nx=128)
        ref, _ = simulate_heat(cfg, PRESETS["f32"], 1000)
        res = Simulation("heat1d", cfg, TRACKED).run(1000)
        err = np.linalg.norm(np.asarray(res.state) - np.asarray(ref)) / np.linalg.norm(
            np.asarray(ref)
        )
        assert err < 0.05

    def test_rr_tracked_swe_survives_range_ramp(self):
        """SWE from rest: hu ramps ~2 exponents/step at first, so a stale
        carried k would inf the momentum flux. The engine's Fig.-5 semantics
        (grow-and-retry within the step, shrink only via EMA evidence) must
        keep the tracked run finite and on the f32 solution."""
        cfg = SWEConfig(nx=64, ny=64)
        ref = np.asarray(Simulation("swe2d", cfg, PRESETS["f32"]).run(150).state)
        res = Simulation("swe2d", cfg, TRACKED).run(150)
        out = np.asarray(res.state)
        assert np.isfinite(out).all()
        w, wr = out[0] - cfg.depth, ref[0] - cfg.depth
        corr = np.corrcoef(w.reshape(-1), wr.reshape(-1))[0, 1]
        assert corr > 0.98

    def test_explicit_tracker_resumes(self):
        """Two chained runs == one long run (tracker is resumable state)."""
        sim = Simulation("burgers1d", BurgersConfig(nx=128), TRACKED)
        a = sim.run(200)
        b = sim.run(200, state0=a.state, tracker=a.tracker)
        long = sim.run(400)
        np.testing.assert_array_equal(np.asarray(b.state), np.asarray(long.state))
        np.testing.assert_array_equal(
            np.asarray(b.tracker.state.k), np.asarray(long.tracker.state.k)
        )


# ---------------------------------------------------------------------------
# ensembles
# ---------------------------------------------------------------------------


class TestEnsembles:
    def _batch(self, cfg, scales):
        return jnp.asarray(scales, jnp.float32)[:, None] * initial_wave(cfg)[None, :]

    def test_vmapped_ensemble_matches_single_runs(self):
        cfg = BurgersConfig(nx=64)
        sim = Simulation("burgers1d", cfg, PRESETS["r2f2_16"])
        u0b = self._batch(cfg, [0.5, 1.0, 2.0])
        ens = sim.run_ensemble(u0b, 100)
        assert ens.state.shape == (3, 64)
        assert ens.snapshots.shape[0] == 3
        for i in range(3):
            single = sim.run(100, state0=u0b[i])
            np.testing.assert_array_equal(
                np.asarray(ens.state[i]), np.asarray(single.state)
            )

    def test_tracked_ensemble_has_per_member_trackers(self):
        """Each member carries its own adjust-unit state: a small-amplitude
        member must settle on a smaller split than a large one."""
        cfg = BurgersConfig(nx=64)
        sim = Simulation("burgers1d", cfg, TRACKED)
        ens = sim.run_ensemble(self._batch(cfg, [0.001, 1.0]), 30)
        k = np.asarray(ens.tracker.state.k)
        assert k.shape[0] == 2  # leading member dim
        i_uu = ens.tracker.names.index("burgers.uu")
        assert k[0, i_uu] < k[1, i_uu]

    def test_sharded_ensemble_runs_under_mesh(self):
        from jax.sharding import Mesh

        from repro.dist.sharding import axis_rules

        cfg = BurgersConfig(nx=64)
        sim = Simulation("burgers1d", cfg, PRESETS["r2f2_16"])
        u0b = self._batch(cfg, [1.0] * 4)
        mesh = Mesh(np.array(jax.devices()[:1]).reshape(1), ("data",))
        with mesh, axis_rules(mesh):
            ens = sim.run_ensemble(u0b, 20, sharded=True)
        assert ens.state.shape == (4, 64)
        assert np.isfinite(np.asarray(ens.state)).all()


# ---------------------------------------------------------------------------
# StepOps + engine `tracks` contract
# ---------------------------------------------------------------------------


class TestStepOps:
    def test_tracks_attribute(self):
        assert get_engine("rr_tracked").tracks
        assert get_engine("deploy").tracks
        for mode in ("f32", "bf16", "fixed", "rr_tile"):
            assert not get_engine(mode).tracks

    def test_stepops_untracked_matches_module_multiply(self):
        from repro.precision import multiply

        a = jnp.asarray(np.random.default_rng(0).normal(0, 30, (64,)), jnp.float32)
        for prec in ("f32", "e5m10", "r2f2_16", "bf16"):
            cfg = PRESETS[prec]
            ops = StepOps(cfg)
            np.testing.assert_array_equal(
                np.asarray(ops.mul(a, a, "x.y")),
                np.asarray(multiply(a, a, cfg, site="x.y")),
            )
            assert ops.tracker is None

    def test_stepops_div_store(self):
        cfg = PRESETS["e5m10"]
        ops = StepOps(cfg)
        a = jnp.asarray([1.5, 2.5, 3.75], jnp.float32)
        from repro.precision import divide, store

        np.testing.assert_array_equal(
            np.asarray(ops.div(a, a + 1)), np.asarray(divide(a, a + 1, cfg))
        )
        np.testing.assert_array_equal(
            np.asarray(ops.store(a)), np.asarray(store(a, cfg))
        )

    def test_simresult_fields(self):
        res = Simulation("heat1d", HeatConfig(nx=32), PRESETS["f32"]).run(4)
        assert isinstance(res, SimResult)
        assert res.tracker is None
        assert res.state.shape == (32,)
