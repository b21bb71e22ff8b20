"""Range-distribution capture primitives — the profile subsystem's in-loop
half (DESIGN.md §11).

The paper's first contribution is "a thorough analysis of data range
distributions during scientific simulations" (Figs. 3–4); the tracker only
keeps an EMA of each site's *max* exponent, which is enough to drive the
adjust unit but not to reproduce those figures or to tune a static policy
offline. Capture widens the evidence stream to **binned counts**: every
policy multiplication bins the unbiased exponents of both (broadcast)
operands into width-1 exponent bins, per named site, alongside the existing
site-level max-exponent evidence.

Everything here is pure ``jnp`` over ``repro.core`` — deliberately free of
solver/kernel imports — so the SAME binning functions run in three places
and can never disagree:

* inside :class:`repro.pde.solver.StepOps` (reference execution),
* inside :class:`repro.kernels.fused.FusedOps` (Pallas kernel bodies, where
  the counts ride out as an extra kernel output, summed across blocks),
* offline, when tests replay operands through the binning directly.

Counting convention: exact zeros and non-finite values are excluded (they
carry no exponent; zero padding in fused kernels therefore cannot
contaminate the counts), and exponents outside ``[e_lo, e_hi]`` clamp into
the edge bins. Counts are int32 (exact far beyond f32's 2**24 integer
ceiling). With width-1 bins the per-site max exponent is exactly the
highest occupied bin, which is what makes the histogram a strict widening
of the max-exponent evidence.
"""

from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.flexformat import unbiased_exponent
from repro.core.policy import _site_max_exp

__all__ = [
    "CaptureSpec",
    "CaptureResult",
    "exp_hist",
    "pair_exp_hist",
    "block_pair_exp_hist",
    "site_evidence",
]


@dataclasses.dataclass(frozen=True)
class CaptureSpec:
    """Static (hashable — safe as a jit/pallas static arg) binning layout.

    One bin per unbiased exponent value in ``[e_lo, e_hi]`` inclusive. The
    defaults cover every workload in the repo with wide margins (operand
    exponents observed so far span roughly [-40, 35]); out-of-range
    exponents clamp into the edge bins rather than being dropped, so a
    saturated edge bin is visible in the report instead of silent.
    """

    e_lo: int = -64
    e_hi: int = 63

    def __post_init__(self):
        if self.e_hi <= self.e_lo:
            raise ValueError(f"empty exponent range [{self.e_lo}, {self.e_hi}]")

    @property
    def n_bins(self) -> int:
        return self.e_hi - self.e_lo + 1

    def edges(self):
        """Bin exponents as a host-side range (analysis axis labels)."""
        return range(self.e_lo, self.e_hi + 1)


class CaptureResult(NamedTuple):
    """What a captured run hands to the offline layer (arrays only — a plain
    pytree, so it rides through jit/scan/vmap like any other result leaf).

    ``evidence``  (steps, n_sites, 2) f32 — per-step site-level operand
                  max exponents, the same stream the adjust unit consumes
                  (:func:`repro.core.policy.tracker_observe`); the
                  autotuner replays it verbatim.
    ``exp_time``  (n_snapshots, n_sites, 2, n_bins) int32 — per-snapshot-
                  interval elementwise operand exponent counts (the paper's
                  range-over-simulation-time view).
    ``exp_total`` (n_sites, 2, n_bins) int32 — whole-run counts, remainder
                  steps included (``exp_time`` covers only whole intervals).
    """

    evidence: Any
    exp_time: Any
    exp_total: Any


def exp_hist(x, spec: CaptureSpec, mask=None) -> jnp.ndarray:
    """Bin one (broadcast) operand's elementwise unbiased exponents.

    Returns ``(n_bins,) int32``. Zeros and non-finite values are excluded;
    out-of-range exponents clamp into the edge bins. ``mask`` (same shape,
    bool) restricts counting to True lanes — the fused kernels use it to
    keep non-zero pad lanes out of the counts.
    """
    x = jnp.asarray(x, jnp.float32).reshape(-1)
    keep = jnp.isfinite(x) & (x != 0.0)
    if mask is not None:
        keep = keep & jnp.asarray(mask).reshape(-1)
    idx = jnp.clip(unbiased_exponent(x) - spec.e_lo, 0, spec.n_bins - 1)
    hit = (idx[:, None] == jnp.arange(spec.n_bins, dtype=jnp.int32)[None, :]) & keep[:, None]
    return jnp.sum(hit, axis=0, dtype=jnp.int32)


def pair_exp_hist(a, b, spec: CaptureSpec, mask=None) -> jnp.ndarray:
    """Bin both operands of one multiplication (already broadcast to a
    common shape by the caller). Returns ``(2, n_bins) int32``."""
    return jnp.stack([exp_hist(a, spec, mask), exp_hist(b, spec, mask)])


def _block_hist_row(x, spec: CaptureSpec, mask=None) -> jnp.ndarray:
    """:func:`exp_hist` of one 2-D kernel block as a ``(1, n_bins)`` row:
    one masked count per bin. The TPU kernel compiler lowers this form,
    where it refuses :func:`exp_hist`'s flatten-and-one-hot; the counts are
    the same (the capture parity tests hold the kernel planes to the
    reference plane's :func:`exp_hist`)."""
    keep = jnp.isfinite(x) & (x != 0.0)
    if mask is not None:
        keep = keep & mask
    idx = jnp.clip(unbiased_exponent(x) - spec.e_lo, 0, spec.n_bins - 1)
    bins = jax.lax.broadcasted_iota(jnp.int32, (1, spec.n_bins), 1)
    out = jnp.zeros((1, spec.n_bins), jnp.int32)
    for b in range(spec.n_bins):
        count = jnp.sum(jnp.where((idx == b) & keep, 1, 0), keepdims=True)
        out = jnp.where(bins == b, count, out)
    return out


def block_pair_exp_hist(a, b, spec: CaptureSpec, mask=None) -> jnp.ndarray:
    """:func:`pair_exp_hist` inside a kernel: both operands of one
    multiplication (2-D blocks of one shape), ``(2, n_bins) int32``."""
    return jnp.concatenate(
        [_block_hist_row(a, spec, mask), _block_hist_row(b, spec, mask)], axis=0
    )


def site_evidence(a, b) -> jnp.ndarray:
    """One multiplication's site-level evidence ``(a_max_exp, b_max_exp)``
    as a ``(2,) f32`` — byte-for-byte what the tracker consumes
    (:func:`repro.core.policy.tracker_update`'s reduction) and what the
    fused kernels emit per substep."""
    return jnp.stack([_site_max_exp(a), _site_max_exp(b)])
