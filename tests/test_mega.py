"""The megakernel execution plane (DESIGN.md §14): whole-horizon runs in
ONE ``pallas_call`` with the adjust unit evolving on-chip. Per-stepper
bit-parity against the chunked fused plane across the mode ladder (overflow
workloads produce NaNs, so parity is checked on raw f32 BIT patterns),
tracked-mode final splits and §5.3 counters, capture-stream parity, packed
carried storage, single-launch program structure, dispatch/fallback, and
the scalar adjust-unit law itself."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.policy import PRESETS, adjust_step, tracker_init, tracker_observe
from repro.pack import unpack_state
from repro.pde import Simulation, Stepper, get_stepper
from repro.pde.advection1d import AdvectionConfig
from repro.pde.burgers1d import BurgersConfig, initial_wave
from repro.pde.heat1d import HeatConfig
from repro.pde.heat2d import Heat2DConfig
from repro.pde.swe2d import SWEConfig
from repro.pde.swe_sphere import SphereConfig
from repro.precision import mega_eligible

TRACKED = dataclasses.replace(PRESETS["r2f2_16"], mode="rr_tracked")
BUILTINS = ("advection1d", "burgers1d", "heat1d", "heat2d", "swe2d", "swe_sphere")

SMALL = {
    "heat1d": HeatConfig(nx=64),
    "heat2d": Heat2DConfig(nx=24, ny=24),
    "advection1d": AdvectionConfig(nx=128),
    "burgers1d": BurgersConfig(nx=128),
    "swe2d": SWEConfig(nx=32, ny=32),
    "swe_sphere": SphereConfig(nlon=32, nlat=16, dt=120.0),
}


def assert_bits_equal(a, b):
    """Bit-pattern equality for f32 arrays. Overflow-mode workloads (e5m10
    on a 2.5e5 field) legitimately produce NaNs on BOTH planes; ``==``
    compares NaN as unequal, so parity is asserted on the raw bits."""
    a, b = np.asarray(a), np.asarray(b)
    assert a.dtype == b.dtype == np.float32
    np.testing.assert_array_equal(a.view(np.uint32), b.view(np.uint32))


#: XLA's CPU backend contracts ``a * b + c`` into one fused multiply-add
#: inside a fusion, so where the two planes fuse an update differently their
#: float32 results part in the last bits. swe_sphere's update does (at 32x16
#: over 20 steps: f32 3.2e-7, bf16 and deploy 2.9e-6 of the value, a few
#: elements of 1,536); its states are held to float32 rounding instead of
#: bits, its trackers still exactly.
FMA_CLOSE = ("swe_sphere",)


def assert_planes_agree(name, a, b):
    if name in FMA_CLOSE:
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=0)
    else:
        assert_bits_equal(a, b)


def _pair(name, prec, steps=20, every=6, **kw):
    """(chunked fused, megakernel) runs of the same horizon — steps=20,
    every=6 exercises the remainder interval (two trailing substeps)."""
    cfg = SMALL[name]
    fus = Simulation(name, cfg, prec).run(
        steps, snapshot_every=every, execution="fused", **kw
    )
    meg = Simulation(name, cfg, prec).run(
        steps, snapshot_every=every, execution="megakernel", **kw
    )
    return fus, meg


# ---------------------------------------------------------------------------
# parity: megakernel == chunked fused, per stepper, across the mode ladder
# ---------------------------------------------------------------------------


class TestMegaParity:
    @pytest.mark.parametrize("name", BUILTINS)
    @pytest.mark.parametrize("preset", ["r2f2_16", "e5m10", "bf16", "f32"])
    def test_untracked_modes_bit_exact(self, name, preset):
        """The in-kernel substep uses the same FusedOps arithmetic and the
        same boundary storage rounding as the chunked plane, so states and
        snapshots must agree bit for bit — NaN patterns included."""
        fus, meg = _pair(name, PRESETS[preset])
        assert_planes_agree(name, fus.state, meg.state)
        assert_planes_agree(name, fus.snapshots, meg.snapshots)
        assert meg.tracker is None

    @pytest.mark.parametrize("name", BUILTINS)
    def test_rr_tracked_bit_exact_with_identical_counters(self, name):
        """The tentpole's parity contract: the on-chip adjust unit ticks
        every substep but the datapath floor latches only at snapshot
        boundaries (the chunked plane's fold cadence), so rr_tracked is
        bit-exact AND the final per-site splits, EMAs, and §5.3 counters
        are identical — not merely close."""
        fus, meg = _pair(name, TRACKED)
        assert_planes_agree(name, fus.state, meg.state)
        assert_planes_agree(name, fus.snapshots, meg.snapshots)
        for field in ("k", "hi_ema", "lo_ema", "overflow_steps", "shrink_steps"):
            np.testing.assert_array_equal(
                np.asarray(getattr(fus.tracker.state, field)),
                np.asarray(getattr(meg.tracker.state, field)),
                err_msg=f"{name}: tracker.{field} diverged",
            )

    @pytest.mark.parametrize("name", BUILTINS)
    def test_deploy_bit_exact_including_tracker(self, name):
        """deploy (bf16 datapath, shadow tracker) evolves its tracker
        on-chip too; arithmetic is split-independent so everything matches."""
        fus, meg = _pair(name, PRESETS["deploy"])
        assert_planes_agree(name, fus.state, meg.state)
        np.testing.assert_array_equal(
            np.asarray(fus.tracker.state.k), np.asarray(meg.tracker.state.k)
        )

    def test_tracked_mega_resumes(self):
        """Two chained megakernel runs == one long one: the tracker rows
        streamed out of the kernel are the same resumable adjust-unit state."""
        sim = Simulation("burgers1d", SMALL["burgers1d"], TRACKED)
        a = sim.run(60, snapshot_every=15, execution="megakernel")
        b = sim.run(
            60, snapshot_every=15, state0=a.state, tracker=a.tracker,
            execution="megakernel",
        )
        long = sim.run(120, snapshot_every=15, execution="megakernel")
        assert_bits_equal(b.state, long.state)
        np.testing.assert_array_equal(
            np.asarray(b.tracker.state.k), np.asarray(long.tracker.state.k)
        )

    def test_snapshot_shapes_with_remainder(self):
        fus, meg = _pair("heat1d", PRESETS["r2f2_16"], steps=20, every=6)
        assert meg.snapshots.shape == (3, SMALL["heat1d"].nx)
        assert fus.snapshots.shape == meg.snapshots.shape


# ---------------------------------------------------------------------------
# capture: the in-kernel evidence/histogram stream matches the chunked one
# ---------------------------------------------------------------------------


class TestMegaCapture:
    def test_capture_parity_with_chunked(self):
        """With ``capture=True`` the megakernel streams the same per-substep
        site evidence and exponent histograms the chunked kernels emit."""
        fus, meg = _pair("burgers1d", TRACKED, steps=18, every=6, capture=True)
        assert meg.profile is not None
        np.testing.assert_array_equal(
            np.asarray(fus.profile.evidence), np.asarray(meg.profile.evidence)
        )
        np.testing.assert_array_equal(
            np.asarray(fus.profile.exp_time), np.asarray(meg.profile.exp_time)
        )
        np.testing.assert_array_equal(
            np.asarray(fus.profile.exp_total), np.asarray(meg.profile.exp_total)
        )

    def test_capture_evidence_shape(self):
        sim = Simulation("burgers1d", SMALL["burgers1d"], TRACKED)
        res = sim.run(12, snapshot_every=4, execution="megakernel", capture=True)
        n_sites = len(get_stepper("burgers1d").sites)
        assert res.profile.evidence.shape == (12, n_sites, 2)
        assert res.profile.exp_time.shape[0] == 3


# ---------------------------------------------------------------------------
# carried storage: quantized and packed ride the megakernel too
# ---------------------------------------------------------------------------


class TestMegaStorage:
    @pytest.mark.parametrize("storage", ["quantized", "packed"])
    def test_storage_parity_with_chunked(self, storage):
        """Boundary storage rounding happens INSIDE the kernel at each
        snapshot boundary; the carried payloads must match the chunked
        plane's pack/unpack bits exactly (heat1d exercises the packed-io
        kernel path, swe2d the host-pack path)."""
        for name in ("heat1d", "swe2d"):
            fus, meg = _pair(name, PRESETS["r2f2_16"], storage=storage)
            ffl, _ = jax.tree_util.tree_flatten(fus.state)
            mfl, tdef = jax.tree_util.tree_flatten(meg.state)
            assert len(ffl) == len(mfl)
            for fa, ma in zip(ffl, mfl):
                np.testing.assert_array_equal(np.asarray(fa), np.asarray(ma))

    def test_packed_equals_quantized_shadow(self):
        """Unpacking the packed megakernel's carried state reproduces the
        quantized run bit for bit — packing is a lossless re-encode of the
        storage-rounded field."""
        sim = Simulation("heat1d", SMALL["heat1d"], PRESETS["r2f2_16"])
        qz = sim.run(20, snapshot_every=6, execution="megakernel", storage="quantized")
        pk = sim.run(20, snapshot_every=6, execution="megakernel", storage="packed")
        assert_bits_equal(qz.state, unpack_state(pk.state))


# ---------------------------------------------------------------------------
# dispatch: eligibility, strict "megakernel", auto preference + fallback
# ---------------------------------------------------------------------------


class _NoMegaStepper(Stepper):
    sites = ("nm.mul",)

    def default_config(self):
        return None

    def init_state(self, cfg):
        return jnp.ones((16,), jnp.float32)

    def step(self, u, cfg, ops):
        return ops.mul(jnp.float32(0.5), u, "nm.mul")


class TestMegaDispatch:
    def test_shape_gate_swe(self):
        """SWE megakernel parity needs the flux grid whole-in-block; a basin
        wider than the kernel block is fused-eligible but mega-ineligible."""
        big = SWEConfig(nx=200, ny=200)
        sim = Simulation("swe2d", big, PRESETS["r2f2_16"])
        assert sim.fused_eligible() and not sim.mega_eligible()
        with pytest.raises(ValueError, match="not megakernel-eligible"):
            sim.run(4, execution="megakernel")

    def test_auto_falls_back_to_fused_on_ineligible_shape(self):
        big = SWEConfig(nx=144, ny=144)
        sim = Simulation("swe2d", big, PRESETS["r2f2_16"])
        auto = sim.run(6, snapshot_every=3, execution="auto")
        fus = sim.run(6, snapshot_every=3, execution="fused")
        assert_bits_equal(auto.state, fus.state)

    def test_no_mega_step_hook_is_ineligible(self):
        from repro.pde.registry import _STEPPERS, register_stepper

        register_stepper("test_nomega", _NoMegaStepper)
        try:
            sim = Simulation("test_nomega", None, PRESETS["r2f2_16"])
            assert not sim.mega_eligible()
            assert not mega_eligible(PRESETS["r2f2_16"], get_stepper("test_nomega"))
            with pytest.raises(ValueError, match="not megakernel-eligible"):
                sim.run(4, execution="megakernel")
        finally:
            _STEPPERS.pop("test_nomega", None)

    def test_auto_prefers_megakernel_when_eligible(self):
        sim = Simulation("heat1d", SMALL["heat1d"], PRESETS["r2f2_16"])
        assert sim.mega_eligible()
        auto = sim.run(20, snapshot_every=6, execution="auto")
        meg = sim.run(20, snapshot_every=6, execution="megakernel")
        assert_bits_equal(auto.state, meg.state)
        assert_bits_equal(auto.snapshots, meg.snapshots)


# ---------------------------------------------------------------------------
# program structure: the whole horizon really is ONE pallas_call
# ---------------------------------------------------------------------------


def _count_pallas_weighted(jaxpr) -> int:
    """Scan-weighted pallas_call count — kernel LAUNCHES at runtime, not
    call sites in the jaxpr text."""
    n = 0
    for eqn in jaxpr.eqns:
        w = eqn.params.get("length", 1) if eqn.primitive.name == "scan" else 1
        if eqn.primitive.name == "pallas_call":
            n += 1
        for v in eqn.params.values():
            vals = v if isinstance(v, (list, tuple)) else (v,)
            for wv in vals:
                inner = getattr(wv, "jaxpr", wv)
                if hasattr(inner, "eqns"):
                    n += w * _count_pallas_weighted(inner)
    return n


def _horizon_launches(sim, steps, every, execution):
    state0 = sim.stepper.init_state(sim.cfg)

    def fn(s0):
        return sim.run(
            steps, snapshot_every=every, state0=s0, execution=execution
        ).state

    return _count_pallas_weighted(jax.jit(fn).trace(state0).jaxpr.jaxpr)


class TestMegaLaunches:
    def test_single_launch_per_horizon(self):
        """The tentpole claim, asserted on the traced program: 24 steps at
        every=6 is 4 launches chunked, exactly 1 on the megakernel."""
        sim = Simulation("heat1d", SMALL["heat1d"], PRESETS["r2f2_16"])
        assert _horizon_launches(sim, 24, 6, "megakernel") == 1
        assert _horizon_launches(sim, 24, 6, "fused") == 4

    def test_single_launch_with_remainder_and_tracker(self):
        sim = Simulation("burgers1d", SMALL["burgers1d"], TRACKED)
        assert _horizon_launches(sim, 20, 6, "megakernel") == 1


# ---------------------------------------------------------------------------
# ensembles
# ---------------------------------------------------------------------------


class TestMegaEnsembles:
    def test_vmapped_mega_ensemble_matches_single_runs(self):
        cfg = SMALL["burgers1d"]
        sim = Simulation("burgers1d", cfg, PRESETS["r2f2_16"])
        u0b = jnp.asarray([0.5, 1.0, 2.0], jnp.float32)[:, None] * initial_wave(cfg)[None, :]
        ens = sim.run_ensemble(u0b, 24, execution="megakernel")
        assert ens.state.shape == (3, cfg.nx)
        for i in range(3):
            single = sim.run(24, state0=u0b[i], execution="megakernel")
            assert_bits_equal(ens.state[i], single.state)


# ---------------------------------------------------------------------------
# the scalar adjust-unit law: adjust_step IS tracker_observe's kernel
# ---------------------------------------------------------------------------


class TestAdjustLaw:
    def test_adjust_step_equals_tracker_observe(self):
        """Evolving one site's scalar state through adjust_step (the form
        the megakernel runs on-chip) matches gather/scatter tracker_observe
        tick for tick — same splits, EMAs, and §5.3 counters."""
        cfg = PRESETS["r2f2_16"]
        rng = np.random.default_rng(7)
        evidence = rng.uniform(-20, 30, size=(40, 2)).astype(np.float32)

        tr = tracker_init(3, cfg.fmt)
        site = 1
        k = tr.k[site]
        hi, lo = tr.hi_ema[site], tr.lo_ema[site]
        ov, sh = tr.overflow_steps[site], tr.shrink_steps[site]
        for ae, be in evidence:
            tr = tracker_observe(tr, site, jnp.float32(ae), jnp.float32(be), cfg)
            k, hi, lo, ov, sh = adjust_step(
                k, hi, lo, ov, sh, jnp.float32(ae), jnp.float32(be), cfg
            )
        assert int(tr.k[site]) == int(k)
        np.testing.assert_allclose(float(tr.hi_ema[site]), float(hi), rtol=0, atol=0)
        np.testing.assert_allclose(float(tr.lo_ema[site]), float(lo), rtol=0, atol=0)
        assert int(tr.overflow_steps[site]) == int(ov)
        assert int(tr.shrink_steps[site]) == int(sh)

    def test_adjust_step_respects_k_bounds(self):
        cfg = PRESETS["r2f2_16"]
        fx = cfg.fmt.fx
        k, *_ = adjust_step(
            jnp.int32(0),
            jnp.float32(-100.0),
            jnp.float32(100.0),
            jnp.int32(0),
            jnp.int32(0),
            jnp.float32(30.0),  # huge demand: wants k -> fx
            jnp.float32(30.0),
            cfg,
            k_bounds=(0, 2),
        )
        assert 0 <= int(k) <= 2 < fx
