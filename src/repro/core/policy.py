"""Precision policy: how rr-precision plugs into models and solvers.

The paper's precision adjustment unit is *stateful in time* because hardware
sees one multiplication at a time. A vector machine sees whole tiles, so two
complementary mechanisms cover the same behaviour (DESIGN.md §2):

* **stateless tile selection** (``mode="rr_tile"``): every operand tile gets
  the minimal safe exponent split ``k`` from a max-|x| pre-pass — the
  runtime reconfiguration happens per tile per step, no carried state;
* **tracked selection** (``mode="rr_tracked"``): a :class:`RangeTracker`
  carries an EMA of each site's max exponent across steps (the moral
  equivalent of the hardware unit's persistence, and of AMP loss-scaling
  state), so the split is available *before* the data is seen — this is the
  deployment story, where the format choice must precede the MXU issue.

``mode="deploy"`` runs the arithmetic in bf16 (the MXU-rate proxy for 16-bit
flexible operands — same operand bytes, same issue rate) while still driving
the tracker, so dry-run/roofline numbers reflect what R2F2 silicon would
execute; ``emulate`` modes are bit-exact but slow (numerics studies).
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from .flexformat import FlexFormat, max_exponent
from .r2f2 import (  # noqa: F401
    _needed_e_bits,
    _needed_e_bits_lo,
    _tile_max_exp,
    op_bounds,
    select_k,
)

__all__ = [
    "PrecisionConfig",
    "KNOWN_MODES",
    "RangeTracker",
    "adjust_step",
    "tracker_init",
    "tracker_observe",
    "tracker_update",
    "tracker_k",
    "evidence_bounds",
    "evidence_k_need",
    "PRESETS",
]

# Modes a PrecisionConfig may carry. The six builtins are listed statically;
# repro.precision.register_engine() extends this set at registration time, so
# third-party engines (fp8, stochastic rounding, ...) become valid modes
# without touching this module.
KNOWN_MODES = {"f32", "bf16", "fixed", "rr_tile", "rr_tracked", "deploy"}


@dataclasses.dataclass(frozen=True)
class PrecisionConfig:
    """Static (hashable — safe as a jit static arg) precision policy.

    mode (each is a registered repro.precision engine):
      "f32"        — reference arithmetic
      "bf16"       — plain mixed precision baseline
      "fixed"      — fixed E(e)M(m) emulation (e.g. E5M10: the paper's
                     failing baseline), ``fixed_em`` below
      "rr_tile"    — R2F2 emulation, per-tile runtime k selection
      "rr_tracked" — R2F2 emulation, k from a (Site)Tracker site
      "deploy"     — bf16 arithmetic + tracker-driven k bookkeeping

    use_kernels: let rr engines dispatch eligible 2-D contractions to the
    Pallas ``r2f2_matmul`` fast path (forward-only; see DESIGN.md §7). The
    policy — not the call site — picks the fast path.
    """

    mode: str = "deploy"
    fmt: FlexFormat = FlexFormat(3, 9, 3)  # the paper's favourite 16-bit config
    fixed_em: Tuple[int, int] = (5, 10)
    tile: int = 128  # tile edge used for per-tile k selection
    tail_approx: bool = True  # paper's flexible-region product approximation
    ema: float = 0.95  # RangeTracker decay
    headroom: int = 1  # extra exponent slack (in powers of 2) for tracked mode
    use_kernels: bool = False  # Pallas fast path for eligible contractions
    #: Freeze the carried split: tracked engines (``rr_tracked``/``deploy``)
    #: neither update the tracker nor widen the live k past it — the run
    #: executes at exactly the per-site k the tracker was initialised with.
    #: This is the *profiled static deployment* emulation (a silicon build
    #: without the adjust unit, configured from a ``repro.profile``
    #: PrecisionPolicy artifact); it also makes policy replays bit-stable.
    pinned: bool = False
    #: Per-site ``(k_lo, k_hi)`` clamps applied by ``tracker_observe`` when
    #: re-picking a site's split — the autotuner's floor/ceiling hints for
    #: ``rr_tracked`` (ordered like the tracker's site rows, normally set via
    #: ``repro.profile.PrecisionPolicy.apply``). None: unconstrained.
    k_bounds: Optional[Tuple[Tuple[int, int], ...]] = None
    #: Pallas kernel block shapes, (bm, bn, bk): the matmul fast path tiles
    #: (bm, bk) x (bk, bn), and elementwise fused kernels (the SWE flux)
    #: tile 2-D fields with (bm, bn) — the policy, not the kernel module,
    #: owns that tiling, so dispatch eligibility and the kernels can never
    #: disagree about blocks. Stencil sweep kernels are exempt: they keep
    #: the coupled extent whole in-block by construction and only ever
    #: block the independent row axis. Shapes that don't divide are padded
    #: and cropped, never rejected.
    kernel_blocks: Tuple[int, int, int] = (128, 128, 128)

    def __post_init__(self):
        if self.mode not in KNOWN_MODES:
            raise ValueError(
                f"unknown precision mode {self.mode!r}; known: {sorted(KNOWN_MODES)} "
                "(register new modes via repro.precision.register_engine)"
            )

    @property
    def is_emulated(self) -> bool:
        from repro.precision.registry import get_engine  # lazy: no import cycle

        return get_engine(self).emulated


PRESETS = {
    "f32": PrecisionConfig(mode="f32"),
    "bf16": PrecisionConfig(mode="bf16"),
    "e5m10": PrecisionConfig(mode="fixed", fixed_em=(5, 10)),
    "e5m9": PrecisionConfig(mode="fixed", fixed_em=(5, 9)),
    "e5m8": PrecisionConfig(mode="fixed", fixed_em=(5, 8)),
    "r2f2_16": PrecisionConfig(mode="rr_tile", fmt=FlexFormat(3, 9, 3)),
    "r2f2_16_384": PrecisionConfig(mode="rr_tile", fmt=FlexFormat(3, 8, 4)),
    "r2f2_15": PrecisionConfig(mode="rr_tile", fmt=FlexFormat(3, 8, 3)),
    "r2f2_14": PrecisionConfig(mode="rr_tile", fmt=FlexFormat(3, 7, 3)),
    "deploy": PrecisionConfig(mode="deploy"),
}


class RangeTracker(NamedTuple):
    """Per-site numeric state (a pytree; thread it like RNG state).

    Arrays are [n_sites]-shaped; model layers under ``scan`` hold their own
    stacked copies (leading layer dim) like any other carried state.
    """

    hi_ema: jnp.ndarray  # f32 — EMA of per-step max needed exponent
    lo_ema: jnp.ndarray  # f32 — EMA of per-step min needed exponent (underflow side)
    k: jnp.ndarray  # int32 — current flexible split per site
    overflow_steps: jnp.ndarray  # int32 — cumulative adjust-up events
    shrink_steps: jnp.ndarray  # int32 — cumulative adjust-down events


def tracker_init(n_sites: int, fmt: FlexFormat, k0=None) -> RangeTracker:
    """Fresh tracker. ``k0`` may be a scalar or an ``(n_sites,)`` array of
    per-site starting splits (e.g. a ``repro.profile`` policy's tuned k);
    default: start wide (safe), shrink via redundancy."""
    k0 = fmt.fx if k0 is None else k0
    return RangeTracker(
        hi_ema=jnp.zeros((n_sites,), jnp.float32),
        lo_ema=jnp.zeros((n_sites,), jnp.float32),
        k=jnp.broadcast_to(jnp.asarray(k0, jnp.int32), (n_sites,)),
        overflow_steps=jnp.zeros((n_sites,), jnp.int32),
        shrink_steps=jnp.zeros((n_sites,), jnp.int32),
    )


def _site_max_exp(x) -> jnp.ndarray:
    return max_exponent(x).reshape(()).astype(jnp.float32)


def _k_for(hi, lo, fmt: FlexFormat):
    """Split whose format covers the exponent envelope ``[lo, hi]``."""
    e = jnp.maximum(
        _needed_e_bits(hi.astype(jnp.int32), fmt.eb, fmt.fx),
        _needed_e_bits_lo(lo.astype(jnp.int32), fmt.eb, fmt.fx),
    )
    return e - fmt.eb


def evidence_bounds(ae, be, op: str = "mul"):
    """One observation's exponent envelope ``(step_hi, step_lo)``: operand
    cluster tops plus the op's result bound (same derivation as
    :func:`repro.core.r2f2.select_k`, generalized per op by
    :func:`repro.core.r2f2.op_bounds`). Vectorized over evidence arrays."""
    return op_bounds(ae, be, op)


def evidence_k_need(ae, be, cfg: PrecisionConfig, op: str = "mul") -> jnp.ndarray:
    """Instantaneous split one site-level observation ``(ae, be)`` demands
    (headroom included) — the per-issue statistic the tracker grows toward
    and ``repro.profile``'s autotuner derives its floor/ceiling hints from.
    Vectorized: feed the whole captured evidence stream at once."""
    step_hi, step_lo = evidence_bounds(ae, be, op)
    return _k_for(step_hi + cfg.headroom, step_lo - cfg.headroom, cfg.fmt)


def adjust_step(
    k,
    hi_ema,
    lo_ema,
    overflow_steps,
    shrink_steps,
    ae,
    be,
    cfg: PrecisionConfig,
    op: str = "mul",
    k_bounds: Optional[Tuple[int, int]] = None,
):
    """One tick of the paper's adjust unit, in jax-pure scalar-state form:
    fold one operation's operand max-exponent evidence ``(ae, be)`` into a
    single site's carried state and re-pick its split. Grow immediately on
    demand (overflow semantics); shrink only when the EMA shows persistent
    redundancy; count both events (the §5.3 adjustment counters).

    All five state values are scalars (or broadcastable arrays) — no
    ``RangeTracker`` gather/scatter — so the law runs unchanged inside a
    Pallas kernel body where the tracker lives in registers/SMEM and
    evolves on-chip each substep (``repro.kernels.mega``), exactly like
    the hardware unit sitting next to the multiplier. ``k_bounds`` is this
    site's static ``(k_lo, k_hi)`` clamp, or None for unconstrained.

    Returns ``(k, hi_ema, lo_ema, overflow_steps, shrink_steps)`` updated.
    """
    fmt = cfg.fmt
    step_hi, step_lo = evidence_bounds(ae, be, op)

    hi = cfg.ema * hi_ema + (1.0 - cfg.ema) * step_hi
    hi = jnp.maximum(hi, step_hi)  # never smooth away a spike
    lo = cfg.ema * lo_ema + (1.0 - cfg.ema) * step_lo
    lo = jnp.minimum(lo, step_lo)

    k_need_now = _k_for(step_hi + cfg.headroom, step_lo - cfg.headroom, fmt)
    k_need_ema = _k_for(hi + cfg.headroom, lo - cfg.headroom, fmt)
    # grow immediately on demand; shrink only toward the persistent-need EMA
    k_new = jnp.maximum(k_need_now, jnp.minimum(k, k_need_ema))
    if k_bounds is not None:
        # the autotuner's floor/ceiling hints for this site
        k_new = jnp.clip(k_new, k_bounds[0], k_bounds[1])
    grew = (k_new > k).astype(jnp.int32)
    shrank = (k_new < k).astype(jnp.int32)
    return k_new, hi, lo, overflow_steps + grew, shrink_steps + shrank


def tracker_observe(
    state: RangeTracker, site: int, ae, be, cfg: PrecisionConfig, op: str = "mul"
) -> RangeTracker:
    """Fold one operation's operand max-exponent evidence ``(ae, be)``
    into the tracker and re-pick the site's split: gather the site's
    scalar state, apply :func:`adjust_step` (the jax-pure adjust-unit
    law), scatter back. ``op`` picks the envelope law — alignment-shift
    for add, quotient-range for div (see :data:`repro.core.r2f2.OPS`);
    the default keeps the paper's multiply semantics.

    The evidence is exactly what the fused Pallas kernels emit per substep
    (per-site max-exponent reductions, cross-block maxed), so the fused
    execution plane's chunk fold-in, the megakernel's on-chip per-substep
    adjust, and the stepwise ``tracker_update`` apply identical
    adjust-unit math.
    """
    kb = None if cfg.k_bounds is None else cfg.k_bounds[site]
    k_new, hi_ema, lo_ema, ov, sh = adjust_step(
        state.k[site],
        state.hi_ema[site],
        state.lo_ema[site],
        state.overflow_steps[site],
        state.shrink_steps[site],
        ae,
        be,
        cfg,
        op,
        k_bounds=kb,
    )
    return RangeTracker(
        hi_ema=state.hi_ema.at[site].set(hi_ema),
        lo_ema=state.lo_ema.at[site].set(lo_ema),
        k=state.k.at[site].set(k_new),
        overflow_steps=state.overflow_steps.at[site].set(ov),
        shrink_steps=state.shrink_steps.at[site].set(sh),
    )


def tracker_update(
    state: RangeTracker, site: int, a, b, cfg: PrecisionConfig, op: str = "mul"
) -> RangeTracker:
    """Fold the live ranges of an arithmetic site into the tracker
    (reduce the operands to max-exponent evidence, then
    :func:`tracker_observe`)."""
    return tracker_observe(state, site, _site_max_exp(a), _site_max_exp(b), cfg, op)


def tracker_k(state: RangeTracker, site: int) -> jnp.ndarray:
    return state.k[site]
