"""Shared per-block R2F2 primitives for Pallas kernel bodies.

Every stencil kernel needs the same in-VMEM building block: a shared-split
R2F2 product of two blocks (the paper's same-format rule, §4.1 — one runtime
``k`` per block pair, covering both operands and the product bound). It used
to be copy-pasted verbatim into each kernel module; it lives here once now,
and any new stencil kernel composes it.

Pure ``jnp`` on purpose: inside a ``pallas_call`` the ops trace onto VMEM
block refs; outside they run as plain XLA — which is what the bit-parity
tests rely on. The oracles in :mod:`repro.kernels.ref` deliberately do NOT
import this module: they re-derive the same math independently so a bug
here cannot hide from the kernel-vs-oracle tests.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.flexformat import max_exponent, quantize_em
from repro.core.r2f2 import product_guard_bits, select_k, select_k_op

__all__ = [
    "block_max_exp",
    "rr_mul_block",
    "rr_add_block",
    "rr_div_block",
    "rr_rsqrt_block",
]


def block_max_exp(t):
    """Max unbiased exponent over one VMEM block (finite values only), as a
    ``(1, ..., 1)`` vector of the block's rank — everything derived from it
    (the split ``k``, the format widths) stays a vector, which is what the
    TPU kernel compiler can bit-cast."""
    return max_exponent(t)


def rr_mul_block(a, b, fmt, tail_approx, *, exps=None, k_min=None, k_fixed=None):
    """Shared-split R2F2 product of two blocks (same-format rule, §4.1).

    ``exps`` lets a caller that already reduced the operands (the fused
    plane computes the exponents once for both split selection and tracker
    evidence) pass ``(a_max_exp, b_max_exp)`` instead of re-reducing;
    ``k_min`` floors the selected split at a carried tracker value — the
    adjust unit's persistent k under which a tracked fused chunk runs;
    ``k_fixed`` bypasses selection entirely and multiplies at exactly that
    split (the pinned static-deployment emulation — no live widen). All
    default to the original pre-fused-plane behaviour bit-for-bit.
    """
    if k_fixed is not None:
        k = jnp.asarray(k_fixed, jnp.int32)
    else:
        ae, be = exps if exps is not None else (block_max_exp(a), block_max_exp(b))
        k = select_k(ae, be, fmt)
        if k_min is not None:
            k = jnp.maximum(k, jnp.asarray(k_min, jnp.int32))
    e_b, m_b = fmt.eb + k, fmt.mb + fmt.fx - k
    aq = quantize_em(a, e_b, m_b)
    bq = quantize_em(b, e_b, m_b)
    guard = product_guard_bits(fmt, k) if tail_approx else None
    return quantize_em(aq * bq, e_b, m_b, tail_trunc_bits=guard)


def _rr_alu_block(a, b, fmt, op, substrate, *, exps=None, k_min=None, k_fixed=None):
    """Shared-split flexible ALU op on blocks — ``rr_mul_block``'s shape for
    the repro.alu ops, with the split picked under the op's own exponent
    envelope (:func:`repro.core.r2f2.select_k_op`). No tail truncation: the
    flexible-region approximation models dropped partial *products* and has
    no analogue in adder/divider datapaths (see ``repro.alu.flexops``)."""
    if k_fixed is not None:
        k = jnp.asarray(k_fixed, jnp.int32)
    else:
        ae, be = exps if exps is not None else (block_max_exp(a), block_max_exp(b))
        k = select_k_op(ae, be, fmt, op)
        if k_min is not None:
            k = jnp.maximum(k, jnp.asarray(k_min, jnp.int32))
    e_b, m_b = fmt.eb + k, fmt.mb + fmt.fx - k
    aq = quantize_em(a, e_b, m_b)
    bq = quantize_em(b, e_b, m_b)
    return quantize_em(substrate(aq, bq), e_b, m_b)


def rr_add_block(a, b, fmt, *, exps=None, k_min=None, k_fixed=None):
    """Shared-split flexible sum (alignment-shift envelope)."""
    return _rr_alu_block(a, b, fmt, "add", lambda x, y: x + y, exps=exps, k_min=k_min, k_fixed=k_fixed)


def rr_div_block(a, b, fmt, *, exps=None, k_min=None, k_fixed=None):
    """Shared-split flexible quotient (quotient-range envelope)."""
    return _rr_alu_block(a, b, fmt, "div", lambda x, y: x / y, exps=exps, k_min=k_min, k_fixed=k_fixed)


def rr_rsqrt_block(x, fmt, *, exps=None, k_min=None, k_fixed=None):
    """Shared-split flexible reciprocal square root (unary envelope);
    ``exps`` is the operand exponent doubled up, ``(ex, ex)``."""
    if exps is None:
        ex = block_max_exp(x)
        exps = (ex, ex)
    return _rr_alu_block(
        x, x, fmt, "rsqrt", lambda v, _w: jax.lax.rsqrt(v), exps=exps, k_min=k_min, k_fixed=k_fixed
    )
