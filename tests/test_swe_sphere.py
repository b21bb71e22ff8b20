"""Shallow water on the sphere (``swe_sphere``, Williamson et al. 1992 cases
2 and 5): the program against the plain float32 reference, the balance of
case 2, mass conservation, the precision ladder on case 5's ranges, the
megakernel through the normal path, and the sites' telemetry."""

import dataclasses
import math

import jax.numpy as jnp
import numpy as np
import pytest

import repro.obs as obs
from bench import harness
from bench.reference import swe_sphere as reference
from repro.core.flexformat import FlexFormat
from repro.core.policy import PRESETS
from repro.pde import Simulation
from repro.pde import swe_sphere as S

#: 32 x 16 cells of 11.25 degrees; dt = 120 s is Courant 0.24 on the polar
#: row, so 200 steps are 6.7 model hours
SMALL = S.SphereConfig(nlon=32, nlat=16, dt=120.0)


def _case2(nlon, nlat, dt):
    """Williamson case 2 (global steady state, alpha = 0): no mountain, g h0 =
    2.94e4 m^2/s^2 and u0 = 2 pi a / 12 days."""
    a, g = S.SphereConfig.radius, S.SphereConfig.g
    return S.SphereConfig(nlon=nlon, nlat=nlat, dt=dt, h0=2.94e4 / g,
                          u0=2.0 * math.pi * a / (12.0 * 86400.0), mountain=0.0)


def _tracked(fmt):
    return dataclasses.replace(PRESETS["r2f2_16_384"], mode="rr_tracked", fmt=FlexFormat(*fmt))


def _fields(cfg):
    config = harness.load_json("configs", "williamson5_t42")
    return dict(config["fields"], nlon=cfg.nlon, nlat=cfg.nlat, dt=cfg.dt)


def _state0(cfg, scale=1.0):
    return reference.initial_state(_fields(cfg), np.array([scale]))[0]


def _gaps(program, ref, cfg):
    """Per-field relative L2 gaps of (h, hu, hv), h about its resting depth."""
    p, r = np.asarray(program, np.float64), np.asarray(ref, np.float64)
    return [np.linalg.norm(p[i] - r[i]) / np.linalg.norm(r[i] - o)
            for i, o in enumerate((cfg.h0, 0.0, 0.0))]


def test_reference_plane_matches_plain_reference():
    """The two implementations round differently (the program's flux is
    hu*hu/h + (g/2)(h*h), the reference's hu*hu/h + ((g/2) h) h; the
    program's grid fields fold 1/(a cos) into one float32 factor). h and hu
    read about 5e-6; hv, which starts at zero and after 200 steps has 3% of
    hu's norm, reads 1.1e-4; 1e-3 is nine times that."""
    U0 = _state0(SMALL)
    prog = Simulation("swe_sphere", SMALL, PRESETS["f32"]).run(200, snapshot_every=50, state0=U0)
    ref_final, ref_snaps = reference.run(_fields(SMALL), U0, 200, 50)
    assert max(_gaps(prog.state, ref_final, SMALL)) < 1e-3
    for p, r in zip(prog.snapshots, ref_snaps):
        p, r = np.asarray(p, np.float64), np.asarray(r, np.float64)
        assert np.linalg.norm(p - r) / np.linalg.norm(r - SMALL.h0) < 1e-3


def test_initial_state_is_the_references():
    """The program's case 5 state and the reference's agree to float32
    rounding of the 5,960 m depth (one ulp is 4.9e-4 m; the two evaluate
    the balance in float64 and float32)."""
    mine = np.asarray(S.initial_state(SMALL, 1.5))
    theirs = np.asarray(reference.initial_state(_fields(SMALL), np.array([1.5]))[0])
    np.testing.assert_allclose(mine, theirs, rtol=1e-6, atol=1e-2)


def test_case2_holds_its_balance_for_a_day():
    """Case 2 is a steady geostrophic state. At 64 x 32 over one model day h
    departs from its start by 0.53% of its largest value (the scheme's
    diffusion erodes the balance a little): a wrong sign of the curvature
    term reads 1.74%, of the Coriolis term 34%, in the program and the
    reference alike."""
    cfg = _case2(64, 32, 60.0)
    sim = Simulation("swe_sphere", cfg, PRESETS["f32"])
    U0 = sim.stepper.init_state(cfg)
    res = sim.run(1440, snapshot_every=1440, state0=U0)
    h0, h1 = np.asarray(U0[0], np.float64), np.asarray(res.state[0], np.float64)
    assert np.abs(h1 - h0).max() / h0.max() < 0.01


def test_mass_is_conserved():
    """No flux crosses a pole face (cos = 0 there) and the longitude wraps,
    so sum(h cos) changes only by float32 rounding: each row's divergence
    is weighted by 1/(a cos) rounded to float32 (6e-8 of it), which leaves
    5.0e-7 of the mass unbalanced over 200 steps; 2e-6 is four times that."""
    U0 = _state0(SMALL)
    res = Simulation("swe_sphere", SMALL, PRESETS["f32"]).run(200, snapshot_every=50, state0=U0)
    cos = np.cos(S._latitudes(SMALL))[:, None]
    before = (np.asarray(U0[0], np.float64) * cos).sum()
    after = (np.asarray(res.state[0], np.float64) * cos).sum()
    assert abs(after / before - 1.0) < 2e-6


def test_e5m10_overflows():
    """hu is about 1.2e5, beyond E5M10's 65,504 as an operand."""
    res = Simulation("swe_sphere", SMALL, PRESETS["e5m10"]).run(10, snapshot_every=10, state0=_state0(SMALL))
    assert not np.isfinite(np.asarray(res.state)).all()


def test_r2f2_16_384_stays_near_f32():
    """R2F2-16 <3,8,4> widens sph.q1q1 and sph.div to k = 4 (E7M8) and keeps
    the state finite. Its error is the pressure term's: g h^2/2 is about
    1.7e8 and keeps 9 mantissa bits at k = 3 (an ulp of 2.6e5), about its
    own cell-to-cell difference in longitude, so the meridional momentum
    hv, a small field, takes most of it. After 200 steps at 32 x 16 the
    gaps read 0.0028 (h), 0.0054 (hu) and 0.126 (hv)."""
    U0 = _state0(SMALL)
    f32 = Simulation("swe_sphere", SMALL, PRESETS["f32"]).run(200, snapshot_every=50, state0=U0)
    r2f2 = Simulation("swe_sphere", SMALL, _tracked((3, 8, 4))).run(200, snapshot_every=50, state0=U0)
    gaps = _gaps(r2f2.state, f32.state, SMALL)
    assert gaps[0] < 0.01 and gaps[1] < 0.02 and gaps[2] < 0.25
    assert r2f2.tracker.k("sph.q1q1") == 4 and r2f2.tracker.k("sph.div") == 4


def test_r2f2_16_393_saturates_and_overflows():
    """<3,9,3>'s widest split is E6M9 (largest value about 4.3e9); hu*hu is
    about 1.4e10, so sph.q1q1 sits at k = 3 and the state overflows."""
    res = Simulation("swe_sphere", SMALL, _tracked((3, 9, 3))).run(200, snapshot_every=50, state0=_state0(SMALL))
    assert res.tracker.k("sph.q1q1") == 3
    assert not np.isfinite(np.asarray(res.state)).all()


def test_ghost_rows_are_the_polar_rows_half_a_turn_round():
    a = jnp.arange(2 * 8, dtype=jnp.float32).reshape(2, 8)
    framed = np.asarray(S._across_poles(a, -1.0))
    np.testing.assert_array_equal(framed[0], -np.roll(np.asarray(a[0]), 4))
    np.testing.assert_array_equal(framed[-1], -np.roll(np.asarray(a[-1]), 4))
    np.testing.assert_array_equal(framed[1:-1], np.asarray(a))
    grid = S.sphere_grid(SMALL)
    assert (grid.cos_f[0] == 0).all() and (grid.cos_f[-1] == 0).all()


def test_auto_runs_the_ensemble_on_the_megakernel():
    """execution="auto" resolves to the megakernel, and a vmapped ensemble
    equals its members run one at a time: trackers exactly, states to
    float32 rounding (XLA's CPU backend contracts multiply-adds into fused
    ones differently in the batched and the single kernel; 1.8e-7 apart)."""
    prec = _tracked((3, 8, 4))
    sim = Simulation("swe_sphere", SMALL, prec)
    assert sim._resolve_execution("auto") == "megakernel"
    states = reference.initial_state(_fields(SMALL), np.array([0.5, 1.5]))
    ens = sim.run_ensemble(states, 12, snapshot_every=6, execution="auto")
    for i in range(2):
        one = sim.run(12, snapshot_every=6, state0=states[i], execution="megakernel")
        np.testing.assert_allclose(np.asarray(ens.state[i]), np.asarray(one.state), rtol=1e-5, atol=0)
        np.testing.assert_array_equal(np.asarray(ens.tracker.state.k[i]), np.asarray(one.tracker.state.k))


@pytest.fixture
def telemetry():
    obs.enable()
    try:
        yield lambda: obs.active().telemetry
    finally:
        obs.disable()


def test_sites_reach_precision_telemetry(telemetry):
    """After an eager megakernel run and ensemble, each sph.* site's final k
    and adjust counters are in repro.obs.precision's telemetry."""
    prec = _tracked((3, 8, 4))
    sim = Simulation("swe_sphere", SMALL, prec)
    res = sim.run(12, snapshot_every=6, state0=_state0(SMALL), execution="megakernel")
    tel = telemetry()
    assert tel.final_k("sim:swe_sphere") == {
        n: int(res.tracker.state.k[i]) for i, n in enumerate(res.tracker.names)
    }
    assert set(tel.final_k("sim:swe_sphere")) == set(S.SITES)
    states = reference.initial_state(_fields(SMALL), np.array([0.5, 1.5]))
    ens = sim.run_ensemble(states, 12, snapshot_every=6, execution="auto")
    for m in range(2):
        for j, site in enumerate(S.SITES):
            series = tel.series(f"ens:swe_sphere/m{m}", site)
            assert series.k[-1] == int(ens.tracker.state.k[m, j])
            assert series.grew[-1] == int(ens.tracker.state.overflow_steps[m, j])
            assert series.shrank[-1] == int(ens.tracker.state.shrink_steps[m, j])
