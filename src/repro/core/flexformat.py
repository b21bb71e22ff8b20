"""Flexible floating-point formats (paper §4.1, Fig. 4a).

A FlexFormat ``<EB, MB, FX>`` is a fixed-total-bitwidth floating point layout:

    [ 1 sign | EB fixed exponent | MB fixed mantissa | FX flexible bits ]

At runtime, ``k`` of the FX flexible bits are allocated to the exponent and
``FX - k`` to the mantissa (mask bits in hardware), yielding an effective
IEEE-style binary format ``E(EB+k) M(MB+FX-k)`` with

    bias       = 2**(e-1) - 1
    emax       = 2**(e-1) - 1          (all-ones biased exponent reserved)
    emin       = 2 - 2**(e-1)          (minimum normal exponent)
    subnormals supported, signed zero, overflow -> +-inf.

These conventions exactly reproduce the paper's examples: E5M10's largest
value is 65504 = 2**15 * (2 - 2**-10), and <3,8,4> with all flexible bits on
the exponent (k=4 -> E7M8) represents up to 2**63 * (1 + 255/256) ~= 1.84e19.

Everything in this module is pure-jnp, bit-exact (round-to-nearest-even via
integer arithmetic on the f32 encoding), and fully vectorized, so it can be
used inside jit/pjit/Pallas and is the ground-truth oracle for the kernels.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "FlexFormat",
    "quantize_em",
    "quantize_em_with_flags",
    "quantize_product",
    "max_normal",
    "min_normal",
    "min_subnormal",
    "exponent_bias",
    "unbiased_exponent",
    "max_exponent",
    "exponent_redundant",
    "pack_r2f2",
    "unpack_r2f2",
    "E5M10",
    "E5M9",
    "E5M8",
    "E8M23",
]

_F32_MANT_BITS = 23
_F32_EXP_BITS = 8
_F32_BIAS = 127
_U32_ABS_MASK = np.uint32(0x7FFFFFFF)
_U32_SIGN_MASK = np.uint32(0x80000000)


@dataclasses.dataclass(frozen=True)
class FlexFormat:
    """The paper's ``<EB, MB, FX>`` flexible format descriptor."""

    eb: int  # fixed exponent bits
    mb: int  # fixed mantissa bits
    fx: int  # flexible bits (runtime-assignable to exponent or mantissa)

    def __post_init__(self):
        if self.eb < 2:
            raise ValueError("need >=2 fixed exponent bits")
        if self.mb < 1:
            raise ValueError("need >=1 fixed mantissa bits")
        if self.fx < 0:
            raise ValueError("FX must be >= 0")
        if self.eb + self.fx > _F32_EXP_BITS:
            raise ValueError("exponent cannot exceed f32's 8 bits (emulation substrate)")
        if self.mb + self.fx > _F32_MANT_BITS:
            raise ValueError("mantissa cannot exceed f32's 23 bits (emulation substrate)")

    @property
    def total_bits(self) -> int:
        return 1 + self.eb + self.mb + self.fx

    def em(self, k) -> Tuple[int, int]:
        """Effective (exponent_bits, mantissa_bits) when ``k`` flex bits go to exponent."""
        return self.eb + k, self.mb + self.fx - k

    def k_range(self):
        return 0, self.fx

    def __str__(self) -> str:  # paper notation
        return f"<{self.eb},{self.mb},{self.fx}>"


# Fixed IEEE-style formats used as baselines in the paper (FX = 0).
E5M10 = FlexFormat(5, 10, 0)  # standard half
E5M9 = FlexFormat(5, 9, 0)  # 15-bit fixed
E5M8 = FlexFormat(5, 8, 0)  # 14-bit fixed
E8M23 = FlexFormat(8, 23, 0)  # f32 itself (identity quantization)


def _is_static(v) -> bool:
    return isinstance(v, (int, np.integer, np.ndarray))


def _int(v):
    """Integer format parameter: Python/NumPy integers stay NumPy, so formats
    known at trace time fold into constants; anything else is a jnp int32
    array (a runtime split). Inside a Pallas TPU kernel this is what keeps
    static formats from ever reaching the chip as scalar bit casts."""
    return np.asarray(v, np.int32) if _is_static(v) else jnp.asarray(v, jnp.int32)


def exponent_bias(e_bits) -> jnp.ndarray:
    return (1 << (_int(e_bits) - 1)) - 1


def _emax(e_bits):
    # All-ones biased exponent reserved for inf/nan (IEEE convention; matches
    # the paper's 65504 / 1.84e19 examples).
    return (1 << (_int(e_bits) - 1)) - 1


def _emin(e_bits):
    return 2 - (1 << (_int(e_bits) - 1))


def max_normal(e_bits, m_bits) -> jnp.ndarray:
    """Largest finite value of E(e)M(m), as f32."""
    return jnp.asarray(_scale_pow2(2.0 - _pow2(-_int(m_bits)), _emax(e_bits)), jnp.float32)


def min_normal(e_bits) -> jnp.ndarray:
    return jnp.asarray(_pow2(_emin(e_bits)), jnp.float32)


def min_subnormal(e_bits, m_bits) -> jnp.ndarray:
    return jnp.asarray(_pow2(_emin(e_bits) - _int(m_bits)), jnp.float32)


def _bits(x):
    return jax.lax.bitcast_convert_type(jnp.asarray(x, jnp.float32), jnp.uint32)


def _from_bits(u):
    return jax.lax.bitcast_convert_type(jnp.asarray(u, jnp.uint32), jnp.float32)


def _pow2(n):
    """Exact 2**n as f32 for integer n in [-149, 127], via bit construction.

    (XLA lowers jnp.exp2 to exp(x*ln2) on CPU which is NOT exact for integer
    powers -- exactness here is load-bearing for bit-exact quantization.)
    A static ``n`` is evaluated in NumPy (a constant); a traced ``n`` keeps
    its shape, so kernels pass runtime splits as ``(1, 1)`` vectors.
    """
    if _is_static(n):
        return np.ldexp(np.float32(1.0), np.clip(n, -149, 127)).astype(np.float32)
    n = jnp.asarray(n, jnp.int32)
    normal = _from_bits((jnp.clip(n, -126, 127) + 127).astype(jnp.uint32) << _F32_MANT_BITS)
    sub_shift = jnp.clip(n + 149, 0, _F32_MANT_BITS).astype(jnp.uint32)
    sub = _from_bits(jnp.uint32(1) << sub_shift)
    return jnp.where(n >= -126, normal, sub)


def _scale_pow2(x, n):
    """Exact x * 2**n in (up to) two exact power-of-two multiplies, valid for
    |n| <= 254 as long as the final result is representable."""
    n = _int(n)
    h1 = np.clip(n, -126, 127) if _is_static(n) else jnp.clip(n, -126, 127)
    return x * _pow2(h1) * _pow2(n - h1)


def unbiased_exponent(x) -> jnp.ndarray:
    """floor(log2(|x|)) for normal f32 inputs, via bit extraction (int32)."""
    u = _bits(x) & _U32_ABS_MASK
    return (u >> _F32_MANT_BITS).astype(jnp.int32) - _F32_BIAS


def max_exponent(x, axis=None) -> jnp.ndarray:
    """Unbiased exponent of the largest finite magnitude in ``x``, reduced
    over ``axis`` (default: all axes) with the reduced axes kept. Zeros,
    f32 subnormals and non-finite values count as exponent -127, so the
    result is the same whether or not the device flushes subnormals.

    The reduced axes stay as size-1 axes so that a kernel's per-block
    result remains a vector: a Pallas TPU kernel can bit-cast vectors but
    not scalars. A whole-array reduction of rank > 2 folds the leading axes
    first, then the trailing two, which is the order the TPU kernel
    compiler can lay out."""
    mag = jnp.where(jnp.isfinite(x), jnp.abs(jnp.asarray(x, jnp.float32)), 0.0)
    if axis is None and mag.ndim > 2:
        lead = jnp.max(mag, axis=tuple(range(mag.ndim - 2)))
        top = jnp.max(lead, keepdims=True).reshape((1,) * mag.ndim)
        return unbiased_exponent(top)
    return unbiased_exponent(jnp.max(mag, axis=axis, keepdims=True))


def _round_mantissa_rne(u_abs, m_bits):
    """RNE-round the f32 encoding ``u_abs`` (sign stripped) to ``m_bits`` of
    mantissa. Integer trick: the carry out of the mantissa propagates into the
    exponent field automatically, which is exactly IEEE behaviour."""
    shift = _F32_MANT_BITS - jnp.asarray(m_bits, jnp.uint32)
    one = jnp.uint32(1)
    half = (one << shift) >> 1  # 2**(shift-1); 0 when shift == 0
    lsb = (u_abs >> shift) & one
    rounded = u_abs + jnp.where(shift > 0, half - one + lsb, jnp.uint32(0))
    return rounded & ~((one << shift) - one)


def quantize_em_with_flags(x, e_bits, m_bits, tail_trunc_bits=None):
    """Bit-exact RNE quantization of f32 ``x`` to E(e)M(m).

    ``e_bits``/``m_bits`` may be scalars or arrays broadcastable against ``x``
    (per-tile formats). Returns ``(y, overflow, underflow)`` where

      overflow : |x| rounds above max_normal  -> y = +-inf  (hardware raises
                 the adjust-up signal, paper Fig. 5)
      underflow: x != 0 but |x| lands in the subnormal/zero range of the
                 target format (gradual precision loss; also an adjust-up
                 trigger in the paper's unit).

    ``tail_trunc_bits``: if set to ``t``, the mantissa is first truncated
    (toward zero) to ``m_bits + t`` fractional bits before the final RNE
    rounding. This models the paper's flexible-region product approximation
    ("only keep FX extra bits and eliminate the computation after that",
    §4.1): partial products below the FX guard region are dropped.

    Note: XLA CPU runs with DAZ/FTZ for f32 subnormals, so inputs with
    |x| < 2**-126 are explicitly treated as (signed) zero here for
    self-consistency. This is invisible for every format with e_bits <= 8
    whose own subnormals are f32-normal (all the paper's <=16-bit formats).
    """
    x = jnp.asarray(x, jnp.float32)
    e_bits = _int(e_bits)
    m_bits = _int(m_bits)

    u = _bits(x)
    sign = u & _U32_SIGN_MASK
    u_abs = u & _U32_ABS_MASK
    # Explicit DAZ (see docstring): zero the magnitude of f32 subnormals.
    u_abs = jnp.where((u_abs >> _F32_MANT_BITS) == 0, jnp.uint32(0), u_abs)

    is_nan = jnp.isnan(x)
    is_inf = jnp.isinf(x)

    if tail_trunc_bits is not None:
        # Drop everything below m+t fractional mantissa bits (truncate toward
        # zero on the magnitude) -- the hardware never computes those partial
        # products. Only affects normals; the subnormal path re-derives from
        # the truncated value as the hardware rounds from its res register.
        t = jnp.asarray(tail_trunc_bits, jnp.int32)
        keep = jnp.clip(m_bits + t, 1, _F32_MANT_BITS)
        tshift = (_F32_MANT_BITS - keep).astype(jnp.uint32)
        u_abs = u_abs & ~((jnp.uint32(1) << tshift) - jnp.uint32(1))

    # --- normal path: RNE mantissa rounding with natural exponent carry.
    r = _round_mantissa_rne(u_abs, m_bits)
    r_exp = (r >> _F32_MANT_BITS).astype(jnp.int32) - _F32_BIAS

    emax = _emax(e_bits)
    emin = _emin(e_bits)

    overflow = (r_exp > emax) & ~is_nan
    y_norm = _from_bits(sign | r)

    # --- subnormal path: single-rounding from the (possibly tail-truncated)
    # original magnitude. |x| < 2**emin  =>  x / 2**(emin-m) < 2**m <= 2**23,
    # so the scaled value is exactly representable and jnp.round (RNE) gives
    # the correctly-rounded subnormal.
    x_mag = _from_bits(u_abs)
    sub_ulp_exp = emin - m_bits
    scaled = _scale_pow2(x_mag, -sub_ulp_exp)
    y_sub_mag = _scale_pow2(jnp.round(scaled), sub_ulp_exp)
    y_sub = jnp.where(sign != 0, -y_sub_mag, y_sub_mag)

    x_exp = (u_abs >> _F32_MANT_BITS).astype(jnp.int32) - _F32_BIAS
    in_sub_range = (x_exp < emin) & (u_abs != 0)
    # After RNE the subnormal may round up to min_normal; that is fine (it is
    # representable) but it is no longer an underflow event.
    rounded_to_normal = jnp.abs(y_sub) >= _pow2(emin)

    y = jnp.where(in_sub_range, y_sub, y_norm)
    inf = _from_bits(sign | jnp.uint32(0x7F800000))
    y = jnp.where(overflow | is_inf, inf, y)
    y = jnp.where(is_nan, x, y)
    y = jnp.where(u_abs == 0, _from_bits(sign), y)  # signed zero passthrough

    underflow = in_sub_range & ~rounded_to_normal & ~is_nan
    overflow = overflow | (is_inf & ~is_nan)
    return y, overflow, underflow


def quantize_em(x, e_bits, m_bits, tail_trunc_bits=None):
    """Value-only variant of :func:`quantize_em_with_flags`."""
    return quantize_em_with_flags(x, e_bits, m_bits, tail_trunc_bits)[0]


def quantize_product(p, e_bits, m_bits, fx_guard_bits):
    """Round an exact f32 product to E(e)M(m) with the paper's FX-tail
    truncation approximation (§4.1, Fig. 4b)."""
    return quantize_em_with_flags(p, e_bits, m_bits, tail_trunc_bits=fx_guard_bits)


def exponent_redundant(x, e_bits):
    """The paper's redundancy detector (§4.2): in the biased exponent of
    ``x`` under an ``e_bits``-wide exponent, the two bits following the MSB
    both being the complement of the MSB indicates the exponent field is
    wider than needed and one flexible bit can be returned to the mantissa.

    Example (paper): 8-bit biased exponent 10000111 (2**8) has MSB=1 followed
    by 00 -> redundant; representable in 5 bits as 10111.
    """
    x = jnp.asarray(x, jnp.float32)
    e_bits = jnp.asarray(e_bits, jnp.int32)
    ue = unbiased_exponent(x)
    biased = ue + exponent_bias(e_bits)  # value in [0, 2**e) for in-range x
    msb = (biased >> (e_bits - 1)) & 1
    b1 = (biased >> (e_bits - 2)) & 1
    b2 = jnp.where(e_bits >= 3, (biased >> (e_bits - 3)) & 1, 1 - msb)
    nz = jnp.abs(x) > 0
    return nz & (b1 == 1 - msb) & (b2 == 1 - msb)


# ---------------------------------------------------------------------------
# Bit-level packing of the storage layout (Fig. 4a): sign | exp | mantissa in
# ``1 + EB + MB + FX`` bits, plus the k (mask) metadata kept out-of-band.
# Used by property tests to prove the emulation matches the storage format.
# ---------------------------------------------------------------------------


def pack_r2f2(x, fmt: FlexFormat, k):
    """Encode quantized f32 values into the ``total_bits``-wide integer
    payload for format ``fmt`` at flex split ``k``. Assumes ``x`` is already
    representable (i.e. output of quantize_em for the same (e, m))."""
    e_bits = fmt.eb + _int(k)
    m_bits = fmt.mb + fmt.fx - _int(k)
    x = jnp.asarray(x, jnp.float32)
    u = _bits(x)
    sign = (u >> 31).astype(jnp.uint32)
    f32_exp = ((u & _U32_ABS_MASK) >> _F32_MANT_BITS).astype(jnp.int32)
    mant32 = (u & jnp.uint32((1 << _F32_MANT_BITS) - 1)).astype(jnp.uint32)

    bias = exponent_bias(e_bits)
    emin = _emin(e_bits)
    unb = f32_exp - _F32_BIAS

    is_zero = (u & _U32_ABS_MASK) == 0
    is_inf = jnp.isinf(x)
    is_nan = jnp.isnan(x)
    is_sub = (~is_zero) & (unb < emin)

    mshift = (_F32_MANT_BITS - m_bits).astype(jnp.uint32)
    mant_norm = (mant32 >> mshift).astype(jnp.uint32)
    # subnormal: value = 0.mant * 2**emin -> mantissa field = round(|x| / 2**(emin-m))
    sub_field = jnp.round(_scale_pow2(jnp.abs(x), -(emin - m_bits)))
    # via int32: the TPU kernel compiler converts float <-> signed only
    # (the field is below 2**23 either way)
    mant_sub = sub_field.astype(jnp.int32).astype(jnp.uint32)

    exp_field = jnp.where(is_sub | is_zero, 0, unb + bias).astype(jnp.uint32)
    exp_field = jnp.where(is_inf | is_nan, ((1 << e_bits) - 1).astype(jnp.uint32), exp_field)
    mant_field = jnp.where(is_sub, mant_sub, jnp.where(is_zero | is_inf, 0, mant_norm))
    mant_field = jnp.where(is_nan, jnp.uint32(1) << (m_bits - 1).astype(jnp.uint32), mant_field)

    payload = (
        (sign << (e_bits + m_bits).astype(jnp.uint32))
        | (exp_field << m_bits.astype(jnp.uint32))
        | mant_field
    )
    return payload.astype(jnp.uint32)


def unpack_r2f2(payload, fmt: FlexFormat, k):
    """Decode :func:`pack_r2f2` payloads back to f32."""
    e_bits = fmt.eb + _int(k)
    m_bits = fmt.mb + fmt.fx - _int(k)
    payload = jnp.asarray(payload, jnp.uint32)

    one = jnp.uint32(1)
    m_mask = (one << m_bits.astype(jnp.uint32)) - one
    e_mask = (one << e_bits.astype(jnp.uint32)) - one
    mant = (payload & m_mask).astype(jnp.int32).astype(jnp.float32)
    expf = ((payload >> m_bits.astype(jnp.uint32)) & e_mask).astype(jnp.int32)
    sign = (payload >> (e_bits + m_bits).astype(jnp.uint32)) & one

    bias = exponent_bias(e_bits)
    emin = _emin(e_bits)
    m_f = m_bits.astype(jnp.float32)

    is_sub = expf == 0
    is_special = expf == ((one << e_bits.astype(jnp.uint32)) - one).astype(jnp.int32)
    del m_f

    mag_norm = _scale_pow2(1.0 + mant * _pow2(-m_bits), expf - bias)
    mag_sub = _scale_pow2(mant, emin - m_bits)
    mag = jnp.where(is_sub, mag_sub, mag_norm)
    mag = jnp.where(is_special, jnp.where(mant == 0, jnp.inf, jnp.nan), mag)
    return jnp.where(sign == 1, -mag, mag).astype(jnp.float32)
