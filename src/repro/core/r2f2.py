"""R2F2 — the paper's Runtime-ReconFigurable Floating-point multiplier (§4).

Two execution models of the same semantics:

1. ``r2f2_multiply`` — **tile-wise, TPU-native** (DESIGN.md §2): a vector
   machine can scan operand tiles before multiplying, so the hardware's
   "overflow -> grow exponent -> retry" feedback loop collapses into a
   single pre-pass that picks, per tile, the minimal exponent width
   ``k in [0, FX]`` that represents the operands and their products. The
   minimal-k choice subsumes the paper's redundancy rule (a redundant
   exponent is exactly a non-minimal one).

2. ``r2f2_mul_sequential`` — **hardware-faithful state machine**: a
   ``lax.scan`` over a multiplication stream carrying the current split
   ``k``, reproducing the paper's precision adjustment unit (Fig. 5)
   bit-for-bit: on overflow/underflow grow the exponent by one bit and
   *retry* the multiply; when operands and result all show exponent
   redundancy (§4.2's two-bits-after-MSB rule) shrink by one bit. Used to
   reproduce the paper's adjustment-count observations (§5.3).

Both models round products with the paper's flexible-region approximation
(Fig. 4b): only ``FX`` extra bits of the flexible partial products are kept,
which for split ``k`` leaves ``MB + 1 + k`` guard bits below the target
mantissa LSB before the final round-to-nearest-even (see guard-bit derivation
in the docstring of :func:`product_guard_bits`).
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from .flexformat import (
    FlexFormat,
    exponent_redundant,
    max_exponent,
    quantize_em_with_flags,
)

__all__ = [
    "R2F2Stats",
    "product_guard_bits",
    "OPS",
    "op_bounds",
    "select_k",
    "select_k_op",
    "select_k_operand",
    "r2f2_multiply",
    "r2f2_mul_sequential",
    "SequentialState",
]

#: Operations the adjust unit knows an exponent envelope for. ``"mul"`` is
#: the paper's op; the rest generalize the Fig.-5 law to the remaining
#: solver arithmetic (repro.alu): alignment-shift evidence for add/sub,
#: quotient-range evidence for divide, and the halved-exponent envelope for
#: rsqrt.
OPS = ("mul", "add", "div", "rsqrt")


class R2F2Stats(NamedTuple):
    """Diagnostics returned by the tile-wise multiplier."""

    k: jnp.ndarray  # per-tile chosen flexible split
    overflow_count: jnp.ndarray  # elements that still overflow at k (saturated at FX)
    underflow_count: jnp.ndarray  # elements quantized into the subnormal range


def product_guard_bits(fmt: FlexFormat, k) -> jnp.ndarray:
    """Guard bits kept below the result-mantissa LSB under the paper's
    approximation.

    Fig. 4b: the fixed partial product keeps ``2*(MB+1)`` bits and the
    flexible region keeps only ``FX`` extra bits, so the assembled product
    significand has ``2*(MB+1) + FX`` bits. The result mantissa needs
    ``m + 1 = MB + FX - k + 1`` bits, leaving

        guard = (2*MB + 2 + FX) - (MB + FX - k + 1) = MB + 1 + k

    bits before RNE. When ``k = FX`` the full product fits and the
    approximation is exact.
    """
    return fmt.mb + 1 + jnp.asarray(k, jnp.int32)


def _bit_length(n):
    """Bits needed to write the non-negative integer ``n`` (0 for 0), counted
    up to 8 — enough for every exponent width a FlexFormat can reach. Pure
    integer compares: exact on every backend, where ``ceil(log2(.))`` is
    only as exact as the device's logarithm."""
    return sum((n >= (1 << i)).astype(jnp.int32) for i in range(8))


def _needed_e_bits(max_exp, eb: int, fx: int):
    """Smallest e_bits in [eb, eb+fx] whose emax covers ``max_exp``
    (emax(e) = 2**(e-1) - 1). Saturates at eb+fx like the hardware does
    after exhausting its flexible bits."""
    need = jnp.maximum(jnp.asarray(max_exp), 0).astype(jnp.int32)
    # e such that 2**(e-1) - 1 >= need  <=>  e - 1 >= bit_length(need)
    return jnp.clip(_bit_length(need) + 1, eb, eb + fx)


def _needed_e_bits_lo(min_exp, eb: int, fx: int):
    """Smallest e_bits in [eb, eb+fx] whose emin reaches DOWN to ``min_exp``
    (emin(e) = 2 - 2**(e-1) <= min_exp), so the value-cluster top stays
    normal instead of flushing — the paper's underflow-adjust trigger."""
    # 2**(e-1) >= 2 - min_exp  <=>  e - 1 >= bit_length(1 - min_exp)
    t = jnp.maximum(1 - jnp.asarray(min_exp).astype(jnp.int32), 0)
    return jnp.clip(_bit_length(t) + 1, eb, eb + fx)


def select_k(a_max_exp, b_max_exp, fmt: FlexFormat):
    """Minimal flexible split ``k`` such that the operand clusters AND their
    product neither overflow nor underflow in ``E(EB+k)``.

    ``a_max_exp``/``b_max_exp`` are per-tile ``floor(log2(max|.|))`` values
    (int32). Upper bound: the product of values with exponents ea, eb is
    < 2**(ea+eb+2), so covering ``ea+eb+1`` suffices. Lower bound: the
    *cluster tops* (max magnitudes) of both operands and of the product
    (>= 2**(ea+eb)) must stay normal — this reproduces the paper's §3.1
    observation that multiplications with operands < 1e-4 need E6M9 rather
    than E5M10: small operands push the LOW coverage, not the high one.
    Values far below their tile's top are distribution tails (e.g. zero
    crossings) and may flush gradually, as in the hardware.
    """
    hi = jnp.maximum(jnp.maximum(a_max_exp, b_max_exp), a_max_exp + b_max_exp + 1)
    lo = jnp.minimum(jnp.minimum(a_max_exp, b_max_exp), a_max_exp + b_max_exp)
    e = jnp.maximum(
        _needed_e_bits(hi, fmt.eb, fmt.fx), _needed_e_bits_lo(lo, fmt.eb, fmt.fx)
    )
    return e - fmt.eb


def op_bounds(ae, be, op: str = "mul"):
    """Exponent envelope ``(hi, lo)`` an operation on value clusters topped
    at exponents ``(ae, be)`` must cover — the per-op generalization of
    :func:`select_k`'s product bound. All arithmetic is f32 (exact for
    exponent-sized integers), so int32 and f32 evidence agree bit-for-bit.

    mul:   product of tops is < 2**(ae+be+2) and >= 2**(ae+be).
    add:   alignment-shift evidence — the sum's top can carry out one bit
           above the larger operand; cancellation tails flush gradually like
           any distribution tail, so the low side is the smaller operand top.
    div:   quotient-range evidence — |a/b| for cluster tops lies within
           2**(ae-be-1) .. 2**(ae-be+1), and both operands must stay normal.
    rsqrt: unary (callers pass ``be = ae``) — the result exponent is
           ~ -ae/2, so the envelope spans the operand top and the halved,
           negated top on both sides.
    """
    if op not in OPS:
        raise ValueError(f"unknown alu op {op!r}; known: {OPS}")
    ae = jnp.asarray(ae, jnp.float32)
    be = jnp.asarray(be, jnp.float32)
    if op == "mul":
        hi = jnp.maximum(jnp.maximum(ae, be), ae + be + 1)
        lo = jnp.minimum(jnp.minimum(ae, be), ae + be)
    elif op == "add":
        hi = jnp.maximum(ae, be) + 1
        lo = jnp.minimum(ae, be)
    elif op == "div":
        hi = jnp.maximum(jnp.maximum(ae, be), ae - be + 1)
        lo = jnp.minimum(jnp.minimum(ae, be), ae - be - 1)
    else:  # rsqrt
        r_hi = jnp.ceil(-ae / 2.0)
        r_lo = jnp.floor(-(ae + 1.0) / 2.0)
        hi = jnp.maximum(ae, r_hi)
        lo = jnp.minimum(ae, r_lo)
    return hi, lo


def select_k_op(a_max_exp, b_max_exp, fmt: FlexFormat, op: str = "mul"):
    """Minimal flexible split covering one operation's exponent envelope —
    :func:`select_k` generalized over :data:`OPS` via :func:`op_bounds`.
    ``select_k_op(ae, be, fmt, "mul")`` equals ``select_k(ae, be, fmt)``."""
    hi, lo = op_bounds(a_max_exp, b_max_exp, op)
    e = jnp.maximum(
        _needed_e_bits(hi, fmt.eb, fmt.fx), _needed_e_bits_lo(lo, fmt.eb, fmt.fx)
    )
    return e - fmt.eb


def select_k_operand(max_exp, fmt: FlexFormat):
    """Minimal split for a single operand tile: its cluster top must be
    representable as a normal (neither overflow nor flush)."""
    e = jnp.maximum(
        _needed_e_bits(max_exp, fmt.eb, fmt.fx),
        _needed_e_bits_lo(max_exp, fmt.eb, fmt.fx),
    )
    return e - fmt.eb


def _tile_max_exp(x, tile_shape: Optional[Tuple[int, ...]]):
    """Per-tile max unbiased exponent; returns (max_exp_tiles, broadcast_fn).

    ``tile_shape`` of None means one format for the whole array (per-tensor).
    Otherwise x is viewed as tiles of ``tile_shape`` (must divide x.shape)
    and the reduction is per tile; the broadcast_fn expands a per-tile value
    back to elementwise shape.
    """
    if tile_shape is None:
        return max_exponent(x).reshape(()), (lambda t: t)

    if len(tile_shape) != x.ndim:
        raise ValueError(f"tile_shape rank {len(tile_shape)} != operand rank {x.ndim}")
    for d, t in zip(x.shape, tile_shape):
        if d % t != 0:
            raise ValueError(f"tile {tile_shape} does not divide shape {x.shape}")
    # reshape (d0, d1, ...) -> (d0//t0, t0, d1//t1, t1, ...), reduce tile dims
    split = []
    for d, t in zip(x.shape, tile_shape):
        split += [d // t, t]
    red_axes = tuple(range(1, 2 * x.ndim, 2))
    me = jnp.squeeze(max_exponent(x.reshape(split), axis=red_axes), axis=red_axes)

    def broadcast(t):
        t = jnp.asarray(t)
        expand = t.reshape(tuple(s for pair in zip(t.shape, (1,) * x.ndim) for s in pair))
        return jnp.broadcast_to(
            expand, tuple(s for pair in zip(t.shape, tile_shape) for s in pair)
        ).reshape(x.shape)

    return me, broadcast


def r2f2_multiply(
    a,
    b,
    fmt: FlexFormat,
    *,
    k=None,
    tile_shape: Optional[Tuple[int, ...]] = None,
    tail_approx: bool = True,
):
    """Tile-wise R2F2 elementwise product emulation.

    a, b: f32 arrays (same shape). ``k``: fixed split, or None to select the
    minimal split per tile (``tile_shape``; None = per-tensor). Returns
    ``(product, R2F2Stats)``. The product is rounded to the runtime format
    with the paper's flexible-region tail approximation when ``tail_approx``.
    """
    a = jnp.asarray(a, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    if k is None:
        ae, bcast_a = _tile_max_exp(a, tile_shape)
        be, _ = _tile_max_exp(b, tile_shape)
        k_tile = select_k(ae, be, fmt)
        k_full = bcast_a(k_tile)
    else:
        k_tile = jnp.asarray(k, jnp.int32)
        k_full = jnp.broadcast_to(k_tile, a.shape) if k_tile.ndim == 0 else k_tile

    e_bits = fmt.eb + k_full
    m_bits = fmt.mb + fmt.fx - k_full

    qa, oa, ua = quantize_em_with_flags(a, e_bits, m_bits)
    qb, ob, ub = quantize_em_with_flags(b, e_bits, m_bits)
    # Products of <=13-bit significands are exact in f32 (24-bit significand).
    p = qa * qb
    guard = product_guard_bits(fmt, k_full) if tail_approx else None
    qp, op, up = quantize_em_with_flags(p, e_bits, m_bits, tail_trunc_bits=guard)

    stats = R2F2Stats(
        k=k_tile,
        overflow_count=jnp.sum(oa | ob | op),
        underflow_count=jnp.sum(ua | ub | up),
    )
    return qp, stats


# ---------------------------------------------------------------------------
# Hardware-faithful sequential mode (paper Fig. 5 state machine).
# ---------------------------------------------------------------------------


class SequentialState(NamedTuple):
    k: jnp.ndarray  # current flexible split (int32 scalar)
    overflow_adjusts: jnp.ndarray  # times precision was increased (paper §5.3)
    redundancy_adjusts: jnp.ndarray  # times precision was decreased


def sequential_init(fmt: FlexFormat, k0: int = 0) -> SequentialState:
    del fmt
    return SequentialState(
        k=jnp.asarray(k0, jnp.int32),
        overflow_adjusts=jnp.asarray(0, jnp.int32),
        redundancy_adjusts=jnp.asarray(0, jnp.int32),
    )


def _mul_at_k(a, b, fmt: FlexFormat, k, tail_approx: bool):
    e_bits = fmt.eb + k
    m_bits = fmt.mb + fmt.fx - k
    qa, oa, ua = quantize_em_with_flags(a, e_bits, m_bits)
    qb, ob, ub = quantize_em_with_flags(b, e_bits, m_bits)
    p = qa * qb
    guard = product_guard_bits(fmt, k) if tail_approx else None
    qp, op, up = quantize_em_with_flags(p, e_bits, m_bits, tail_trunc_bits=guard)
    fault = oa | ob | op | ua | ub | up
    return qp, fault


def r2f2_mul_sequential(
    a_stream,
    b_stream,
    fmt: FlexFormat,
    *,
    k0: int = 0,
    tail_approx: bool = True,
):
    """Run a stream of scalar multiplications through the paper's adjustment
    unit. Semantics per element (Fig. 5):

      1. multiply at the current split ``k``;
      2. if overflow/underflow occurred: grow the exponent (``k += 1``) and
         retry, up to the FX budget (a ``fori_loop`` over FX retries — the
         hardware re-issues the multiply with the updated mask);
      3. else if BOTH operands and the result show exponent redundancy
         (two-bits-after-MSB rule): shrink the exponent (``k -= 1``) for
         subsequent operations (no retry -- the current result is exact
         enough by construction).

    Returns ``(products, SequentialState)`` with the adjustment counters the
    paper reports (e.g. heat eq: 5 overflow / 23 redundancy in 1.5M muls).
    """
    a_stream = jnp.asarray(a_stream, jnp.float32).reshape(-1)
    b_stream = jnp.asarray(b_stream, jnp.float32).reshape(-1)

    def step(state: SequentialState, ab):
        a, b = ab

        def retry_body(_, carry):
            k, n_up, done = carry
            _, fault = _mul_at_k(a, b, fmt, k, tail_approx)
            grow = fault & (k < fmt.fx) & ~done
            return (
                k + grow.astype(jnp.int32),
                n_up + grow.astype(jnp.int32),
                done | ~fault,
            )

        k, n_up, _ = jax.lax.fori_loop(
            0, fmt.fx + 1, retry_body, (state.k, jnp.asarray(0, jnp.int32), jnp.asarray(False))
        )
        p, _ = _mul_at_k(a, b, fmt, k, tail_approx)

        e_bits = fmt.eb + k
        red = (
            exponent_redundant(a, e_bits)
            & exponent_redundant(b, e_bits)
            & exponent_redundant(p, e_bits)
            & (k > 0)
            & (n_up == 0)
        )
        new_state = SequentialState(
            k=k - red.astype(jnp.int32),
            overflow_adjusts=state.overflow_adjusts + n_up,
            redundancy_adjusts=state.redundancy_adjusts + red.astype(jnp.int32),
        )
        return new_state, p

    init = sequential_init(fmt)
    final_state, products = jax.lax.scan(step, init, (a_stream, b_stream))
    return products, final_state
