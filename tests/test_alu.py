"""Bit-level suites for the flexible ALU ops (repro.alu) + paper gates.

Property tests (hypothesis) pin the op law against f64 oracles:
quantize-operands -> substrate op -> quantize-result at the effective
``E(EB+k)M(MB+FX-k)`` format, with NO tail truncation (only the multiplier
models dropped partial products). Bounds of ``width=32`` strategies are
float32 values: hypothesis refuses a bound float32 cannot represent.
Covered operand regimes: Sterbenz cancellation (exact subtraction), the
subnormal floor, and the near-overflow edge.

Paper-pattern gates mirror §5's per-workload story for the ops the SWE
momentum flux now routes through the engine: fixed E5M10 add/divide blow up
on SWE-ramp magnitudes while the 16-bit flexible ops stay finite and
f32-close, and the tracked divide shows up as a live policy site in a real
swe2d run.
"""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.alu import flex_add, flex_div, flex_rsqrt, flex_sub
from repro.core import FlexFormat, max_normal, quantize_em
from repro.core.flexformat import min_subnormal
from repro.core.policy import PrecisionConfig
from repro.precision import PRESETS, add, divide, multiply, rsqrt

FMT = FlexFormat(3, 9, 3)


def _q(x, k):
    """Quantize to the effective format at split k, as f64."""
    e, m = FMT.eb + k, FMT.mb + FMT.fx - k
    return float(np.asarray(quantize_em(np.float32(x), e, m), np.float64))


def _fmt_bits(k):
    return FMT.eb + k, FMT.mb + FMT.fx - k


def _assert_oracle(res, exact, k, *, ulps=1.0):
    """res must be exact's format-rounding: within ``ulps`` ULPs of the
    effective format, inf past the overflow edge, 0 under the subnormal
    floor (each edge with a half-ULP tolerance band where either outcome is
    a legal rounding)."""
    e, m = _fmt_bits(k)
    top = float(max_normal(e, m))
    sub_floor = float(min_subnormal(e, m))
    if abs(exact) > top * (1.0 + 2.0**-m):
        assert np.isinf(res) and np.sign(res) == np.sign(exact), (res, exact)
        return
    if exact == 0.0:
        assert res == 0.0
        return
    if abs(exact) < sub_floor / 2.0:
        assert res == 0.0 or abs(res) == sub_floor, (res, exact)
        return
    if np.isinf(res):  # inside the band: rounding up to inf is legal
        assert abs(exact) >= top, (res, exact)
        return
    # ULP at exact's magnitude, floored at the subnormal spacing
    ulp = max(2.0 ** (np.floor(np.log2(abs(exact))) - m), sub_floor)
    assert abs(res - exact) <= ulps * ulp + 1e-300, (res, exact, ulp)


def _flex_scalar(fn, *args, k):
    out, _ = fn(*[np.float32([x]) for x in args], FMT, k=k)
    return float(np.asarray(out, np.float64)[0])


@settings(max_examples=120, deadline=None)
@given(
    a=st.floats(min_value=-1e6, max_value=1e6, allow_nan=False, allow_infinity=False, width=32),
    b=st.floats(min_value=-1e6, max_value=1e6, allow_nan=False, allow_infinity=False, width=32),
    k=st.integers(0, 3),
)
def test_prop_add_matches_f64_oracle(a, b, k):
    qa, qb = _q(a, k), _q(b, k)
    if not (np.isfinite(qa) and np.isfinite(qb)):
        return  # operand already past the format edge; covered by edge gate
    res = _flex_scalar(flex_add, a, b, k=k)
    # f32 substrate + format rounding: allow one extra ULP for the double
    # rounding against the f64 sum
    _assert_oracle(res, qa + qb, k, ulps=2.0)


@settings(max_examples=120, deadline=None)
@given(
    a=st.floats(min_value=-256.0, max_value=256.0, allow_nan=False, allow_infinity=False, width=32),
    b=st.floats(min_value=-256.0, max_value=256.0, allow_nan=False, allow_infinity=False, width=32),
    k=st.integers(0, 3),
)
def test_prop_div_matches_f64_oracle(a, b, k):
    qa, qb = _q(a, k), _q(b, k)
    if qb == 0.0 or not (np.isfinite(qa) and np.isfinite(qb)):
        return
    res = _flex_scalar(flex_div, a, b, k=k)
    _assert_oracle(res, qa / qb, k, ulps=2.0)


@settings(max_examples=120, deadline=None)
@given(
    x=st.floats(min_value=float(np.float32(1e-6)), max_value=1e6, allow_nan=False, allow_infinity=False, width=32),
    k=st.integers(0, 3),
)
def test_prop_rsqrt_matches_f64_oracle(x, k):
    qx = _q(x, k)
    if qx <= 0.0 or not np.isfinite(qx):
        return
    res = _flex_scalar(flex_rsqrt, x, k=k)
    # substrate rsqrt is itself a correctly-rounded-ish f32 approx: 3 ULPs
    _assert_oracle(res, 1.0 / np.sqrt(qx), k, ulps=3.0)


@settings(max_examples=120, deadline=None)
@given(
    a=st.floats(min_value=float(np.float32(1e-3)), max_value=1000.0, allow_nan=False, allow_infinity=False, width=32),
    r=st.floats(min_value=0.5, max_value=2.0, allow_nan=False, allow_infinity=False, width=32),
    k=st.integers(0, 3),
)
def test_prop_sterbenz_subtraction_exact(a, r, k):
    """qb in [qa/2, 2qa] -> qa - qb is representable: flex_sub is EXACT.

    The classic alignment-cancellation case — any tail truncation in the
    add path (the multiplier's shortcut) would break this identity."""
    qa = _q(a, k)
    if not np.isfinite(qa):
        return  # operand past the split's overflow edge (small-k + big a)
    qb = _q(qa * r, k)
    if qa <= 0.0 or qb <= 0.0 or not (qa / 2.0 <= qb <= 2.0 * qa):
        return  # rounding pushed qb outside the Sterbenz band
    res = _flex_scalar(flex_sub, qa, qb, k=k)
    assert res == qa - qb, (qa, qb, res)


class TestEdges:
    def test_subnormal_operands_survive_add(self):
        # E3M12 at k=0: min normal 2^-2, subnormal grid down to 2^-14
        tiny = 2.0**-13
        res = _flex_scalar(flex_add, tiny, tiny, k=0)
        assert res == 2.0**-12

    def test_near_overflow_add_rounds_to_inf(self):
        e, m = _fmt_bits(0)  # E3M12: max normal just under 8
        top = float(max_normal(e, m))
        res = _flex_scalar(flex_add, top, top, k=0)
        assert np.isinf(res)

    def test_wide_split_rescues_the_same_add(self):
        top0 = float(max_normal(*_fmt_bits(0)))
        res = _flex_scalar(flex_add, top0, top0, k=3)  # E6M9 spans it
        assert np.isfinite(res) and res == pytest.approx(2 * top0, rel=2**-9)

    def test_auto_k_picks_covering_split(self):
        # 12+12=24 > E3's max normal (~16): evidence-selected k must widen
        out, stats = flex_add(np.float32([12.0]), np.float32([12.0]), FMT)
        assert np.isfinite(np.asarray(out)).all()
        assert int(np.asarray(stats.k).max()) >= 1


class TestSwePaperGates:
    """§5's SWE ramp, per op: E5M10 fails, 16-bit flexible matches f32."""

    # momentum-flux magnitudes from the SWE basin: h ~ 500 -> q1*q1 ~ 2.5e5
    T1, Q3 = 2.5e5, 500.0

    def test_e5m10_divide_overflows_on_momentum_flux(self):
        q = quantize_em(np.float32([self.T1]), 5, 10)  # 2.5e5 > 65504
        assert np.isinf(np.asarray(q)).all()
        out = np.asarray(quantize_em(np.asarray(q) / self.Q3, 5, 10))
        assert np.isinf(out).all()  # the ramp poisons the divide

    def test_flexible_divide_survives_momentum_flux(self):
        out, _ = flex_div(np.float32([self.T1]), np.float32([self.Q3]), FMT)
        out = float(np.asarray(out)[0])
        assert np.isfinite(out)
        assert out == pytest.approx(self.T1 / self.Q3, rel=2**-8)

    def test_e5m10_add_overflows_on_ramp_sums(self):
        out = np.asarray(
            quantize_em(np.float32(4.0e4) + np.float32(4.0e4), 5, 10)
        )
        assert np.isinf(out).all()
        fx, _ = flex_add(np.float32([4.0e4]), np.float32([4.0e4]), FMT)
        assert np.isfinite(np.asarray(fx)).all()

    def test_swe2d_tracked_divide_is_a_live_site(self):
        """Integration: the momentum-flux divide rides the policy engine —
        swe2d declares the div site/op and a tracked run carries a split
        for it while staying f32-correlated."""
        from repro.pde import Simulation, get_stepper

        stepper = get_stepper("swe2d")
        assert "swe.div" in stepper.sites
        assert stepper.site_ops[stepper.sites.index("swe.div")] == "div"

        cfg = dataclasses.replace(stepper.default_config(), nx=32, ny=32)
        steps = 40
        ref = Simulation("swe2d", cfg, PRESETS["f32"]).run(steps)
        prec = dataclasses.replace(PRESETS["r2f2_16"], mode="rr_tracked")
        sim = Simulation("swe2d", cfg, prec)
        res = sim.run(steps)
        obs = np.asarray(stepper.observables(res.state, cfg), np.float64)
        refo = np.asarray(stepper.observables(ref.state, cfg), np.float64)
        assert np.isfinite(obs).all()
        corr = np.corrcoef(
            (obs - cfg.depth).ravel(), (refo - cfg.depth).ravel()
        )[0, 1]
        assert corr > 0.98
        i = res.tracker.names.index("swe.div")
        k_div = int(np.asarray(res.tracker.state.k)[i])
        assert 0 <= k_div <= FMT.fx

    def test_e5m10_swe2d_destroyed_flexible_survives(self):
        """The full §5 verdict on a reduced basin: fixed E5M10 goes
        non-finite on the ramp; the same run under 16-bit flexible doesn't."""
        from repro.pde import Simulation, get_stepper

        stepper = get_stepper("swe2d")
        cfg = dataclasses.replace(stepper.default_config(), nx=32, ny=32)
        steps = 60
        fixed = Simulation("swe2d", cfg, PRESETS["e5m10"]).run(steps)
        obs_fixed = np.asarray(stepper.observables(fixed.state, cfg))
        assert not np.isfinite(obs_fixed).all()

        flex = Simulation("swe2d", cfg, PRESETS["r2f2_16"]).run(steps)
        obs_flex = np.asarray(stepper.observables(flex.state, cfg))
        assert np.isfinite(obs_flex).all()


@pytest.mark.parametrize("mode", ["f32", "bf16", "fixed", "rr_tile", "rr_tracked", "deploy"])
def test_engine_alu_protocol_coverage(mode):
    """Every registered engine implements the extended ALU protocol and
    returns finite, close-to-f32 results for in-range operands."""
    cfg = PrecisionConfig(mode=mode, fmt=FMT, fixed_em=(5, 10))
    rng = np.random.default_rng(7)
    a = rng.uniform(0.5, 4.0, 256).astype(np.float32)
    b = rng.uniform(0.5, 4.0, 256).astype(np.float32)
    for fn, exact in (
        (lambda: add(a, b, cfg), a.astype(np.float64) + b),
        (lambda: divide(a, b, cfg), a.astype(np.float64) / b),
        (lambda: rsqrt(jnp.abs(a), cfg), 1.0 / np.sqrt(a.astype(np.float64))),
        (lambda: multiply(a, b, cfg), a.astype(np.float64) * b),
    ):
        out = np.asarray(fn(), np.float64)
        assert np.isfinite(out).all()
        rel = np.abs(out - exact) / np.abs(exact)
        assert rel.max() < 2**-6  # every 16-bit mode keeps >= 7 mantissa bits
