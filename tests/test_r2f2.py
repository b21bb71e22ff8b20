"""Tests for the R2F2 multiplier: split selection, tile/sequential modes."""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (
    FlexFormat,
    max_normal,
    min_normal,
    quantize_em,
    r2f2_mul_sequential,
    r2f2_multiply,
    select_k,
    select_k_operand,
)

FMT = FlexFormat(3, 9, 3)


class TestSelectK:
    def test_covers_product_overflow(self):
        # h*h with h ~ 1000: product exp ~ 19 -> needs E6 (k=3 for EB=3)
        k = int(select_k(jnp.int32(9), jnp.int32(9), FMT))
        e = FMT.eb + k
        assert float(max_normal(e, FMT.mb + FMT.fx - k)) > 1e6

    def test_covers_small_products(self):
        # paper §3.1: operands < 1e-4 need E6M9, not E5M10
        k = int(select_k(jnp.int32(-17), jnp.int32(-3), FMT))  # alpha=1e-5 * lap
        assert FMT.eb + k == 6

    def test_minimal_for_unit_range(self):
        k = int(select_k(jnp.int32(0), jnp.int32(0), FMT))
        assert k == 0  # E3M12 suffices around 1.0

    def test_operand_only(self):
        assert int(select_k_operand(jnp.int32(0), FMT)) == 0
        assert int(select_k_operand(jnp.int32(40), FMT)) == 3  # needs E6
        assert int(select_k_operand(jnp.int32(-25), FMT)) == 3


class TestTileMultiply:
    # the paper's three widths (Fig. 6): k-bit R2F2 against the fixed format
    # of the same width; it reports 70.2 / 70.6 / 70.7% lower mean error
    @pytest.mark.parametrize(
        "fmt, fixed",
        [
            (FlexFormat(3, 9, 3), (5, 10)),
            (FlexFormat(3, 8, 3), (5, 9)),
            (FlexFormat(3, 7, 3), (5, 8)),
        ],
        ids=["r2f2_16-E5M10", "r2f2_15-E5M9", "r2f2_14-E5M8"],
    )
    def test_more_accurate_than_fixed_half(self, fmt, fixed):
        e, m = fixed
        rng = np.random.default_rng(0)
        a = (10.0 ** rng.uniform(-4, 4, 50000)).astype(np.float32)
        b = (10.0 ** rng.uniform(-4, 4, 50000)).astype(np.float32)
        exact = a.astype(np.float64) * b.astype(np.float64)
        p_rr, _ = r2f2_multiply(a, b, fmt, tile_shape=(100,))
        p_fx = np.asarray(
            quantize_em(
                np.asarray(quantize_em(a, e, m)) * np.asarray(quantize_em(b, e, m)),
                e,
                m,
            ),
            np.float64,
        )
        err_rr = np.abs(np.asarray(p_rr, np.float64) - exact) / np.abs(exact)
        ovf = ~np.isfinite(p_fx)
        err_fx = np.where(ovf, 1.0, np.abs(np.nan_to_num(p_fx) - exact) / np.abs(exact))
        # paper: ~70% avg error reduction
        assert err_rr.mean() < 0.5 * err_fx.mean()

    def test_no_overflow_in_sweep_range(self):
        rng = np.random.default_rng(1)
        a = (10.0 ** rng.uniform(-4, 4, 20000)).astype(np.float32)
        b = (10.0 ** rng.uniform(-4, 4, 20000)).astype(np.float32)
        p, stats = r2f2_multiply(a, b, FMT, tile_shape=(100,))
        assert np.isfinite(np.asarray(p)).all()
        assert int(stats.overflow_count) == 0

    def test_tail_approx_small_and_rare(self):
        """Paper §4.1: approximation errors < 0.1% in < 0.04%... of products.
        (we assert the same order of magnitude)"""
        rng = np.random.default_rng(2)
        a = rng.uniform(0.5, 2.0, 200000).astype(np.float32)
        b = rng.uniform(0.5, 2.0, 200000).astype(np.float32)
        p_t, _ = r2f2_multiply(a, b, FMT, tail_approx=True)
        p_e, _ = r2f2_multiply(a, b, FMT, tail_approx=False)
        p_t, p_e = np.asarray(p_t, np.float64), np.asarray(p_e, np.float64)
        diff = p_t != p_e
        assert diff.mean() < 0.01  # rare
        if diff.any():
            rel = np.abs(p_t[diff] - p_e[diff]) / np.abs(p_e[diff])
            assert rel.max() < 1.5e-3  # small


class TestSequential:
    def test_adapts_to_drifting_range(self):
        # stream drifts large -> small; k must grow for overflow then shrink
        t = np.linspace(0, 1, 3000).astype(np.float32)
        a = (3e4 * np.exp(-10 * t)).astype(np.float32) + 1e-6
        b = a.copy()
        prods, st_ = r2f2_mul_sequential(a, b, FMT)
        assert int(st_.overflow_adjusts) >= 1  # a*a ~ 9e8 needs E6+ early
        assert int(st_.redundancy_adjusts) >= 1  # late values ~1e-6 shrink back
        exact = a.astype(np.float64) ** 2
        rel = np.abs(np.asarray(prods, np.float64) - exact) / exact
        assert np.median(rel) < 2e-3

    def test_matches_tile_mode_in_steady_state(self):
        rng = np.random.default_rng(3)
        a = rng.uniform(1.0, 2.0, 500).astype(np.float32)
        b = rng.uniform(1.0, 2.0, 500).astype(np.float32)
        p_seq, st_ = r2f2_mul_sequential(a, b, FMT)
        p_tile, _ = r2f2_multiply(a, b, FMT, k=0)
        # steady state k=0 (range ~1): sequential settles immediately
        np.testing.assert_array_equal(np.asarray(p_seq)[10:], np.asarray(p_tile)[10:])


@settings(max_examples=150, deadline=None)
@given(
    ea=st.integers(-20, 20),
    eb=st.integers(-20, 20),
)
def test_prop_selected_k_covers_cluster_when_possible(ea, eb):
    """For any operand cluster tops, the chosen split represents both
    operands' tops and the product top without overflow or flush-to-zero —
    whenever the format family can (otherwise the hardware saturates at
    k=FX and overflows, like any 16-bit unit would)."""
    k = int(select_k(jnp.int32(ea), jnp.int32(eb), FMT))
    e = FMT.eb + k
    m = FMT.mb + FMT.fx - k
    emax_family = 2 ** (FMT.eb + FMT.fx - 1) - 1  # 31 for <3,9,3>
    need_hi = max(ea, eb, ea + eb + 1)
    a = np.float32(1.5 * 2.0**ea)
    b = np.float32(1.5 * 2.0**eb)
    for v, top in ((a, ea), (b, eb), (np.float32(a * b), ea + eb + 1)):
        q = float(quantize_em(v, e, m))
        if top <= emax_family and need_hi <= emax_family:
            assert np.isfinite(q), (k, v)
            assert q != 0.0, (k, v)
        elif top > emax_family:
            assert np.isinf(q)  # saturated family: hardware overflow
