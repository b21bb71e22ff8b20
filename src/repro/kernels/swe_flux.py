"""Fused Pallas kernel: SWE momentum-flux equation with R2F2 multiplies —
built on the shared :mod:`repro.kernels.fused` sweep machinery.

The paper's substituted sub-equation (§5.3) is the SWE hot spot:

    Ux_mx = q1*q1/q3 + 0.5*g*q3*q3

This kernel fuses, per VMEM block: the three policy multiplications (q1*q1,
q3*q3 and g/2*(q3*q3), each with a block-shared runtime split), the policy
division (the ``repro.alu`` flexible divider — its split picked under the
quotient-range envelope at the ``swe.div`` site), and the add — one HBM
round trip for the whole flux field instead of five. The body is purely
elementwise, so both axes tile freely; non-divisible shapes are padded (q3
with 1.0 so the padded divisor stays finite and can't dominate a mixed
block's range reduction) and cropped.

Blocks are (bm, bn) tiles over the 2D field, (8, 128)-aligned.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.core.policy import PrecisionConfig
from repro.kernels import fused
from repro.kernels.blockops import rr_mul_block  # noqa: F401 — shared block math

G_GRAV = 9.81
DEFAULT_BLOCK = (64, 128)

SWE_SITES = ("swe.q1q1", "swe.q3q3", "swe.gq3", "swe.div")
#: per-site ops aligned with SWE_SITES — the division is a first-class
#: policy op now (repro.alu), no longer a raw-f32 bystander
SWE_OPS = ("mul", "mul", "mul", "div")


def _swe_flux_body(sites, g=G_GRAV):
    q1q1_site, q3q3_site, gq3_site, div_site = sites

    def body(state, ops):
        q1, q3 = state
        t1 = ops.mul(q1, q1, q1q1_site)  # multiplier 1
        t2 = ops.div(t1, q3, div_site)  # flexible divider (quotient envelope)
        t3 = ops.mul(q3, q3, q3q3_site)  # multiplier 2
        t4 = ops.mul(jnp.full_like(t3, 0.5 * g), t3, gq3_site)  # mult 3
        return (t2 + t4,)

    return body


def swe_flux_fused(
    q1,
    q3,
    *,
    prec,
    block=None,
    sites=SWE_SITES,
    site_ops=SWE_OPS,
    k_floor=None,
    collect_evidence=False,
    capture=None,
    interpret=None,
    g=G_GRAV,
):
    """Fused-plane entry: momentum flux + per-site evidence over 2D fields.

    ``block`` defaults to the policy's ``kernel_blocks[:2]``; ``g`` is the
    gravity of the flux's pressure term. Returns
    ``(flux, evidence)`` with evidence shaped ``(1, n_sites, 2)`` (the flux
    is one substep of a fused chunk), plus a ``(n_sites, 2, n_bins)``
    exponent-count array when a ``capture`` spec is given.
    """
    block = tuple(prec.kernel_blocks[:2]) if block is None else block
    res = fused.fused_sweep(
        _swe_flux_body(sites, g),
        (q1, q3),
        prec=prec,
        sites=sites,
        site_ops=site_ops,
        steps=1,
        block=block,
        n_out=1,
        pad_values=(0.0, 1.0),  # q3 is a divisor: pad finite, range-neutral
        k_floor=k_floor,
        collect_evidence=collect_evidence,
        capture=capture,
        interpret=interpret,
    )
    if capture is not None:
        (out,), ev, counts = res
        return out, ev, counts
    (out,), ev = res
    return out, ev


def swe_flux_pallas(q1, q3, *, fmt, block=DEFAULT_BLOCK, tail_approx=True, interpret=None):
    """Momentum flux over 2D fields q1=(hu), q3=h. Returns same-shape f32.

    Historical fmt-keyed surface over :func:`swe_flux_fused` (rr_tile
    semantics, no evidence); ``interpret=None`` auto-detects the backend."""
    prec = PrecisionConfig(mode="rr_tile", fmt=fmt, tail_approx=tail_approx)
    out, _ = swe_flux_fused(q1, q3, prec=prec, block=block, interpret=interpret)
    return out
