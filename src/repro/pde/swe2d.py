"""2D shallow-water equations, Lax-Wendroff (Richtmyer two-step) — paper §2
and Fig. 8.

State U = (h, hu, hv) on a square ocean basin. Fluxes

    F(U) = (hu,  hu^2/h + g h^2/2,  huv/h)
    G(U) = (hv,  huv/h,             hv^2/h + g h^2/2)

As in the paper's experiment, ONLY the momentum-flux equation
``Ux_mx = q1_mx*q1_mx/q3_mx + 0.5*g*q3_mx*q3_mx`` is routed through the
precision policy (they substituted exactly one of the 24 sub-equations) —
its three multiplications on the R2F2 multiplier and, since the
``repro.alu`` extension, its division on the tracked flexible divider;
everything else stays f32. With a realistic resting depth
(h0 = 500 m, the ``SWEConfig.depth`` default) the term ``h*h = 2.5e5``
overflows E5M10's 65504 ceiling, so standard half corrupts the simulation
while R2F2 widens the exponent at runtime (k -> FX) and matches the
full-precision run — the paper's Fig. 8.

The workload is a thin :class:`repro.pde.solver.Stepper` registered as
``"swe2d"``; ``simulate``/``swe_step`` remain as shims with unchanged
numerics over the shared :class:`~repro.pde.solver.Simulation` driver.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.precision import PrecisionConfig

from .registry import register_stepper
from .solver import Simulation, StepOps, Stepper

__all__ = ["SWEConfig", "SWE2DStepper", "initial_state", "swe_step", "simulate"]

G = 9.81


@dataclasses.dataclass(frozen=True)
class SWEConfig:
    nx: int = 128
    ny: int = 128
    length: float = 1.0e6  # 1000 km basin
    depth: float = 500.0  # resting depth (m) — h*h = 2.5e5 overflows E5M10
    bump: float = 100.0  # initial gaussian surface displacement (m)
    bump_sigma: float = 0.05  # as a fraction of the basin
    cfl: float = 0.4

    @property
    def dx(self) -> float:
        return self.length / self.nx

    @property
    def dy(self) -> float:
        return self.length / self.ny

    @property
    def dt(self) -> float:
        c = (G * (self.depth + self.bump)) ** 0.5
        return self.cfl * min(self.dx, self.dy) / (c * 2.0**0.5)


def initial_state(cfg: SWEConfig):
    x = jnp.linspace(0, 1, cfg.nx, dtype=jnp.float32)
    y = jnp.linspace(0, 1, cfg.ny, dtype=jnp.float32)
    xx, yy = jnp.meshgrid(x, y, indexing="ij")
    r2 = (xx - 0.5) ** 2 + (yy - 0.5) ** 2
    h = cfg.depth + cfg.bump * jnp.exp(-r2 / (2 * cfg.bump_sigma**2))
    hu = jnp.zeros_like(h)
    hv = jnp.zeros_like(h)
    return jnp.stack([h, hu, hv])


SITES = ("swe.q1q1", "swe.q3q3", "swe.gq3", "swe.div")


def _momentum_flux(q1, q3, ops: StepOps, g: float = G, sites=SITES):
    """The paper's substituted equation: q1*q1/q3 + 0.5*g*q3*q3, with its
    multiplications on the policy's multiplier AND its division on the
    policy's flexible divider (``repro.alu`` — the tracked ``swe.div``
    site, split picked under the quotient-range envelope). Every other
    division in this solver stays on the f32 divider. ``sites`` names the
    (q1*q1, q3*q3, g/2*q3*q3, division) sites; the spherical solver
    (:mod:`repro.pde.swe_sphere`) reuses the equation under its own names
    and its own ``g``."""
    q1q1, q3q3, gq3, div = sites
    t1 = ops.mul(q1, q1, q1q1)
    t2 = ops.div(t1, q3, div)
    t3 = ops.mul(q3, q3, q3q3)
    t4 = ops.mul(jnp.float32(0.5 * g), t3, gq3)
    return t2 + t4


def _momentum_flux_x(q1, q3, prec: PrecisionConfig):
    """Untracked shim kept for the kernel parity tests."""
    return _momentum_flux(q1, q3, StepOps(prec))


def _flux_F(U, mom):
    """F(U) with the substituted momentum flux computed by ``mom(q1, q3)``."""
    h, hu, hv = U[0], U[1], U[2]
    return jnp.stack([hu, mom(hu, h), hu * hv / h])


def _flux_G(U, mom):
    h, hu, hv = U[0], U[1], U[2]
    # G's momentum-y flux is the same algebraic form in (hv, h)
    return jnp.stack([hv, hu * hv / h, mom(hv, h)])


def _edge_rows(a, negate=False):
    """Frame ``a`` with a copy of its first and last rows (negated for a
    reflected normal momentum)."""
    top, bottom = a[:1], a[-1:]
    if negate:
        top, bottom = -top, -bottom
    return jnp.concatenate([top, a, bottom], axis=0)


def _edge_cols(a, negate=False):
    """Frame ``a`` with a copy of its first and last columns."""
    left, right = a[:, :1], a[:, -1:]
    if negate:
        left, right = -left, -right
    return jnp.concatenate([left, a, right], axis=1)


def _reflect(interior):
    """Reflective walls around the updated interior ``(3, nx-2, ny-2)``:
    zero normal momentum at the boundaries, mirrored h. Every boundary
    value is a copy (or negation) of an interior neighbour, so the walls
    are built by concatenation (the TPU kernel compiler has no scatter)."""
    h, hu, hv = interior[0], interior[1], interior[2]
    return jnp.stack([
        _edge_cols(_edge_rows(h)),
        _edge_cols(_edge_rows(hu, negate=True)),
        _edge_rows(_edge_cols(hv, negate=True)),
    ])


_F32 = PrecisionConfig(mode="f32")


def _lw_step(U, cfg: SWEConfig, mom):
    """One Richtmyer two-step Lax-Wendroff update. ``mom(q1, q3)`` computes
    the paper's substituted x-midpoint momentum flux (the only policy-routed
    sub-equation); every other sub-equation stays f32."""
    dt, dx, dy = cfg.dt, cfg.dx, cfg.dy
    f32 = StepOps(_F32)

    def f32_mom(q1, q3):
        return _momentum_flux(q1, q3, f32)

    F = _flux_F(U, f32_mom)
    Gf = _flux_G(U, f32_mom)

    # half-step states at x- and y-midpoints (interior staggered grids)
    Ux = 0.5 * (U[:, 1:, :] + U[:, :-1, :]) - (dt / (2 * dx)) * (F[:, 1:, :] - F[:, :-1, :])
    Uy = 0.5 * (U[:, :, 1:] + U[:, :, :-1]) - (dt / (2 * dy)) * (Gf[:, :, 1:] - Gf[:, :, :-1])

    Fx = _flux_F(Ux, mom)  # fluxes at x-midpoints — the paper's Ux_mx eq
    Gy = _flux_G(Uy, f32_mom)

    interior = (
        U[:, 1:-1, 1:-1]
        - (dt / dx) * (Fx[:, 1:, 1:-1] - Fx[:, :-1, 1:-1])
        - (dt / dy) * (Gy[:, 1:-1, 1:] - Gy[:, 1:-1, :-1])
    )
    return _reflect(interior)


@register_stepper("swe2d")
class SWE2DStepper(Stepper):
    """One Richtmyer two-step Lax-Wendroff update.

    Faithful to the paper's experiment (§5.3): of the ~24 sub-equations, ONLY
    the x-midpoint momentum-flux equation ``Ux_mx = q1_mx^2/q3_mx +
    0.5*g*q3_mx^2`` is routed through the precision policy (inside
    ``_flux_F(Ux, ops)``) — three multiplier sites plus the ``swe.div``
    flexible-divider site; every other sub-equation stays in the baseline
    precision.
    """

    sites = SITES
    site_ops = ("mul", "mul", "mul", "div")
    failure_mode = "overflow"
    story = "h*h = 2.5e5 at a realistic basin depth overflows E5M10's 65504"
    snapshots_default = 4

    def default_config(self) -> SWEConfig:
        return SWEConfig()

    def init_state(self, cfg: SWEConfig):
        return initial_state(cfg)

    def gravity(self, cfg) -> float:
        """The ``g`` of the substituted equation's pressure term."""
        return G

    def update(self, U, cfg, mom):
        """One whole update, the substituted flux computed by ``mom(q1, q3)``
        — the one method a solver sharing this stepper's planes replaces."""
        return _lw_step(U, cfg, mom)

    def step(self, U, cfg: SWEConfig, ops: StepOps):
        g, sites = self.gravity(cfg), self.sites
        return self.update(U, cfg, lambda q1, q3: _momentum_flux(q1, q3, ops, g, sites))

    def fused_step(
        self,
        U,
        cfg: SWEConfig,
        prec,
        steps: int,
        *,
        k_floor=None,
        collect_evidence: bool = False,
        capture=None,
        interpret=None,
    ):
        """Fused-plane chunk: the substituted momentum-flux equation runs in
        the Pallas :func:`repro.kernels.swe_flux.swe_flux_fused` kernel (its
        three policy multiplications + division + add in one VMEM pass);
        the rest of the Lax-Wendroff step is f32 XLA, and the substep loop
        is a scan around the kernel call — the fusion boundary is the
        paper's §5.3 substitution boundary."""
        from repro.kernels.swe_flux import swe_flux_fused  # lazy: pallas off cold paths

        def mom(q1, q3):
            res = swe_flux_fused(
                q1,
                q3,
                prec=prec,
                sites=self.sites,
                site_ops=self.site_ops,
                k_floor=k_floor,
                collect_evidence=collect_evidence,
                capture=capture,
                interpret=interpret,
                g=self.gravity(cfg),
            )
            if capture is not None:
                flux, mom.evidence, mom.counts = res
            else:
                flux, mom.evidence = res
            return flux

        def substep(U, _):
            U = self.update(U, cfg, mom)
            if capture is not None:
                return U, (mom.evidence, mom.counts)
            return U, mom.evidence  # (1, n_sites, 2) per substep, or None

        U, ys = jax.lax.scan(substep, U, None, length=steps)
        if capture is not None:
            ev_steps, counts = ys
            return U, ev_steps[:, 0], jnp.sum(counts, axis=0, dtype=jnp.int32)
        return U, None if ys is None else ys[:, 0]

    def mega_supported(self, cfg: SWEConfig, prec) -> bool:
        """Megakernel parity needs the chunked flux kernel's grid to be a
        single block: the momentum-flux midpoint arrays are ``(nx-1, ny)``,
        so both extents must fit one ``prec.kernel_blocks`` tile — otherwise
        the chunked plane picks per-tile splits the whole-field megakernel
        cannot reproduce."""
        return (cfg.nx - 1) <= prec.kernel_blocks[0] and cfg.ny <= prec.kernel_blocks[1]

    def mega_step(
        self,
        U,
        cfg: SWEConfig,
        prec,
        steps: int,
        every: int,
        *,
        tracker=None,
        collect_evidence: bool = False,
        capture=None,
        interpret=None,
        storage: str = "f32",
    ):
        """Whole-horizon run: the ENTIRE Lax-Wendroff update — the
        substituted momentum-flux equation on the policy datapath, every
        other sub-equation in f32 — plus snapshots and the adjust unit, in
        one ``pallas_call`` (:func:`repro.kernels.mega.swe2d_mega`)."""
        from repro.kernels.mega import swe2d_mega  # lazy: pallas off cold paths

        return swe2d_mega(
            U,
            cfg=cfg,
            prec=prec,
            steps=steps,
            every=every,
            sites=self.sites,
            site_ops=self.site_ops,
            tracker=tracker,
            collect_evidence=collect_evidence,
            capture=capture,
            interpret=interpret,
            storage=storage,
        )

    def observables(self, U, cfg: SWEConfig):
        return U[0]  # snapshot h only

    def metric_offset(self, cfg: SWEConfig) -> float:
        return cfg.depth  # rel-L2 judges the wave, not the resting basin


_STEPPER = SWE2DStepper()


def swe_step(U, cfg: SWEConfig, prec: PrecisionConfig):
    """One Lax-Wendroff update (untracked shim over the registered stepper)."""
    return _STEPPER.step(U, cfg, StepOps(prec))


def simulate(
    cfg: SWEConfig,
    prec: PrecisionConfig,
    steps: int,
    snapshot_every: Optional[int] = None,
    U0: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    res = Simulation("swe2d", cfg, prec).run(
        steps,
        snapshot_every=snapshot_every,
        state0=None if U0 is None else jnp.asarray(U0, jnp.float32),
    )
    return res.state, res.snapshots
