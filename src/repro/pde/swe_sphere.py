"""Shallow-water equations on the sphere: Williamson et al. (1992) test
cases 2 and 5 on a longitude-latitude grid.

State ``U = (h, hu, hv)``, shape ``(3, nlat, nlon)`` with longitude on the
last (lane) axis. ``h`` is the fluid depth (Williamson's ``h*``), ``h_s``
the mountain, ``(λ, φ)`` longitude and latitude, ``a`` the radius, ``f =
2Ω sinφ``::

    ∂h/∂t    + 1/(a cosφ) [∂(hu)/∂λ + ∂(hv cosφ)/∂φ]                             = 0
    ∂(hu)/∂t + 1/(a cosφ) [∂(hu·u + g h²/2)/∂λ + ∂(hu·v cosφ)/∂φ]                = (f + u tanφ/a) hv − g h/(a cosφ) ∂h_s/∂λ
    ∂(hv)/∂t + 1/(a cosφ) [∂(hv·u)/∂λ + ∂(hv·v cosφ)/∂φ] + (1/a) ∂(g h²/2)/∂φ  = −(f + u tanφ/a) hu − (g h/a) ∂h_s/∂φ

Grid: cell centres at ``λ_i = i Δλ`` and ``φ_j = −π/2 + (j + ½) Δφ``, with
``Δλ = 2π/nlon`` and ``Δφ = π/nlat``. Longitude wraps around. Across each
pole the ghost row is the polar row at ``λ + π`` with ``hu`` and ``hv``
negated (the local east and north point the other way there).

Scheme: a Richtmyer two-step Lax-Wendroff update, split in its predictor as
:mod:`repro.pde.swe2d`'s is.

* λ predictor, at the faces ``i + ½`` of each row::

      Ux = (U_i + U_{i+1})/2 − dt/(2 a cosφ Δλ) (F_{i+1} − F_i),
      F(U) = (hu, hu·hu/h + g h²/2, hu·hv/h)

* φ predictor, at the ``nlat + 1`` faces ``j + ½`` of the row-extended
  state (ghost rows included)::

      Uy = (U_j + U_{j+1})/2 − dt/(2 a c̄ Δφ) (G_{j+1} c_{j+1} − G_j c_j)
           [hv: − dt/(2 a Δφ) (P_{j+1} − P_j)],
      G(U) = (hv, hu·hv/h, hv·hv/h),  P = g h²/2,

  where ``c_j = cos φ_j`` (a ghost row takes its polar row's) and ``c̄ =
  (c_j + c_{j+1})/2``, so the pole faces divide by no zero.
* corrector, at the cells::

      U⁺ = U − dt/(a cosφ Δλ) (F(Ux)_{i+½} − F(Ux)_{i−½})
             − dt/(a cosφ Δφ) (G(Uy)_{j+½} cos φ_{j+½} − G(Uy)_{j−½} cos φ_{j−½})
             [hv: − dt/(a Δφ) (P(Uy)_{j+½} − P(Uy)_{j−½})]
             + dt S(U + dt/2 S(U)) + dt K ∇²U,

  with ``cos φ`` exactly 0 at the two pole faces, so nothing flows through a
  pole and ``Σ h cosφ`` is conserved. The source ``S = (0, (f + u tanφ/a)
  hv − g h/(a cosφ) ∂h_s/∂λ, −(f + u tanφ/a) hu − (g h/a) ∂h_s/∂φ)`` is
  taken at the explicit midpoint of itself; the mountain's gradients are
  centred differences of ``h_s`` on the grid. ``∇²q = (q_{i+1} − 2q_i +
  q_{i−1}) / (a cosφ Δλ)² + (cosφ_{j+½} (q_{j+1} − q_j) − cosφ_{j−½} (q_j −
  q_{j−1})) / (a² cosφ Δφ²)`` acts on each field with ``K = 4e5`` m²/s:
  without it a slow instability grows at the polar rows and the run blows
  up before day 15, in float32 as in any precision.

As in swe2d (the paper's §5.3), ONLY the λ-face zonal momentum flux
``hu·hu/h + g h²/2`` of the corrector runs on the policy datapath (the
``sph.*`` sites: three multiplications and the tracked divide); every other
sub-equation stays f32. At case 5's depth (about 5,960 m) and jet (20 m/s)
the operand ``hu`` is about 1.2e5, beyond E5M10's 65,504, and ``hu·hu``
about 1.4e10, beyond R2F2-16 ``<3,9,3>``'s widest split E6M9; ``<3,8,4>``
holds it at k = 4 (E7M8).

Initial state (case 5, ``u0 = 20`` m/s, ``h0 = 5960`` m): ``u = u0 cosφ``,
``v = 0``, ``g (h + h_s) = g h0 − (a Ω u0 + u0²/2) sin²φ``, and a cone
``h_s = 2000 (1 − r/R)`` with ``R = π/9``, ``r² = min(R², (λ − 3π/2)² + (φ −
π/6)²)``. Case 2 (steady geostrophic flow) is the same state with no
mountain, ``g h0 = 2.94e4`` and ``u0 = 2πa / 12 days``.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

from .registry import register_stepper
from .solver import StepOps
from .swe2d import _F32, SWE2DStepper, _momentum_flux

__all__ = ["SphereConfig", "SphereGrid", "SWESphereStepper", "sphere_grid", "initial_state"]

SITES = ("sph.q1q1", "sph.q3q3", "sph.gq3", "sph.div")


@dataclasses.dataclass(frozen=True)
class SphereConfig:
    """Williamson case 5 at T42-class resolution by default."""

    nlon: int = 128
    nlat: int = 64
    dt: float = 8.0  # s: Courant 0.25 on the polar row at 30 m/s
    h0: float = 5960.0  # m
    u0: float = 20.0  # m/s
    mountain: float = 2000.0  # m, the cone's height; 0 gives case 2's globe
    mountain_radius: float = math.pi / 9
    mountain_lon: float = 1.5 * math.pi
    mountain_lat: float = math.pi / 6
    radius: float = 6.37122e6  # m
    omega: float = 7.292e-5  # 1/s
    g: float = 9.80616  # m/s^2
    diffusion: float = 4.0e5  # m^2/s, K of the scheme's Laplacian

    @property
    def dlam(self) -> float:
        return 2.0 * math.pi / self.nlon

    @property
    def dphi(self) -> float:
        return math.pi / self.nlat


def _latitudes(cfg) -> np.ndarray:
    return -0.5 * math.pi + (np.arange(cfg.nlat) + 0.5) * cfg.dphi


def _mountain(cfg) -> np.ndarray:
    """``h_s`` (nlat, nlon) in float64."""
    lam = np.arange(cfg.nlon) * cfg.dlam
    phi = _latitudes(cfg)
    R = cfg.mountain_radius
    r2 = (lam[None, :] - cfg.mountain_lon) ** 2 + (phi[:, None] - cfg.mountain_lat) ** 2
    return cfg.mountain * (1.0 - np.sqrt(np.minimum(R * R, r2)) / R)


class SphereGrid(NamedTuple):
    """The grid's read-only float32 fields, each ``(rows, nlon)``."""

    inv_acos: np.ndarray  # (nlat,) 1/(a cosφ) at the cells
    cos_e: np.ndarray  # (nlat+2,) cosφ of the row-extended cells (ghosts: their polar row's)
    inv_acbar: np.ndarray  # (nlat+1,) 1/(a c̄) at the φ faces
    cos_f: np.ndarray  # (nlat+1,) cosφ at the φ faces, exactly 0 at the poles
    f: np.ndarray  # (nlat,) 2Ω sinφ
    tan_a: np.ndarray  # (nlat,) tanφ/a
    mx: np.ndarray  # (nlat,) g/(a cosφ) ∂h_s/∂λ
    my: np.ndarray  # (nlat,) (g/a) ∂h_s/∂φ


@functools.lru_cache(maxsize=None)
def sphere_grid(cfg: SphereConfig) -> SphereGrid:
    a, n = cfg.radius, cfg.nlon
    phi = _latitudes(cfg)
    cos = np.cos(phi)
    cos_e = np.concatenate([cos[:1], cos, cos[-1:]])
    faces = -0.5 * math.pi + np.arange(cfg.nlat + 1) * cfg.dphi
    cos_f = np.cos(faces)
    cos_f[0] = cos_f[-1] = 0.0
    hs = _mountain(cfg)
    dhs_dlam = (np.roll(hs, -1, axis=1) - np.roll(hs, 1, axis=1)) / (2.0 * cfg.dlam)
    hs_e = np.concatenate(
        [np.roll(hs[:1], n // 2, axis=1), hs, np.roll(hs[-1:], n // 2, axis=1)]
    )
    dhs_dphi = (hs_e[2:] - hs_e[:-2]) / (2.0 * cfg.dphi)

    def rows(v):
        return np.ascontiguousarray(np.broadcast_to(np.asarray(v, np.float64)[:, None], (len(v), n)))

    fields = SphereGrid(
        inv_acos=rows(1.0 / (a * cos)),
        cos_e=rows(cos_e),
        inv_acbar=rows(1.0 / (a * 0.5 * (cos_e[1:] + cos_e[:-1]))),
        cos_f=rows(cos_f),
        f=rows(2.0 * cfg.omega * np.sin(phi)),
        tan_a=rows(np.tan(phi) / a),
        mx=cfg.g / (a * cos[:, None]) * dhs_dlam,
        my=(cfg.g / a) * dhs_dphi,
    )
    return SphereGrid(*(np.asarray(x, np.float32) for x in fields))


def initial_state(cfg: SphereConfig, u_scale: float = 1.0):
    """Case 5 (case 2 without a mountain) with the jet ``u0 * u_scale``."""
    u0 = cfg.u0 * u_scale
    phi = _latitudes(cfg)[:, None]
    surface = cfg.h0 - (cfg.radius * cfg.omega * u0 + 0.5 * u0 * u0) * np.sin(phi) ** 2 / cfg.g
    h = (surface - _mountain(cfg)).astype(np.float32)
    u = np.broadcast_to(u0 * np.cos(phi), h.shape).astype(np.float32)
    hu = h * u
    return jnp.asarray(np.stack([h, hu, np.zeros_like(h)]))


def _east(a):
    """The value at the next cell east (λ + Δλ), wrapping around."""
    return jnp.roll(a, -1, axis=-1)


def _west(a):
    return jnp.roll(a, 1, axis=-1)


def _across_poles(a, sign: float):
    """Frame ``a`` (nlat, nlon) with its ghost rows: each polar row at λ + π,
    times ``sign`` (-1 for a momentum)."""
    half = a.shape[-1] // 2
    south = jnp.roll(a[:1], half, axis=-1)
    north = jnp.roll(a[-1:], half, axis=-1)
    if sign < 0:
        south, north = -south, -north
    return jnp.concatenate([south, a, north], axis=0)


def _source(h, hu, hv, grid):
    """The momenta's source: Coriolis, curvature and the mountain."""
    coef = grid.f + (hu / h) * grid.tan_a
    return coef * hv - h * grid.mx, -(coef * hu) - h * grid.my


def _sphere_step(U, cfg: SphereConfig, grid, mom):
    """One update (the module docstring's scheme). ``mom(q1, q3)`` computes
    the corrector's λ-face zonal momentum flux (the only policy-routed
    sub-equation); ``grid`` holds the :class:`SphereGrid` fields as arrays."""
    g, dt = cfg.g, cfg.dt
    cl, cp, ca = dt / cfg.dlam, dt / cfg.dphi, dt / (cfg.radius * cfg.dphi)
    f32 = StepOps(_F32)

    def f32_mom(q1, q3):
        return _momentum_flux(q1, q3, f32, g, SITES)

    h, hu, hv = U[0], U[1], U[2]

    # λ predictor at the faces i + 1/2
    def half_lam(q, fq):
        return 0.5 * (q + _east(q)) - (0.5 * cl) * grid.inv_acos * (_east(fq) - fq)

    hx = half_lam(h, hu)
    hux = half_lam(hu, f32_mom(hu, h))
    hvx = half_lam(hv, hu * hv / h)

    # φ predictor at the faces j + 1/2 of the row-extended state
    he, hue, hve = _across_poles(h, 1.0), _across_poles(hu, -1.0), _across_poles(hv, -1.0)
    pe = (0.5 * g) * he * he

    def half_phi(q, gq):
        gq = gq * grid.cos_e
        return 0.5 * (q[1:] + q[:-1]) - (0.5 * cp) * grid.inv_acbar * (gq[1:] - gq[:-1])

    hy = half_phi(he, hve)
    huy = half_phi(hue, hue * hve / he)
    hvy = half_phi(hve, hve * hve / he) - (0.5 * ca) * (pe[1:] - pe[:-1])

    # corrector
    def laplacian(q, qe):
        zonal = (_east(q) - 2.0 * q + _west(q)) * grid.inv_acos * grid.inv_acos
        dq = (qe[1:] - qe[:-1]) * grid.cos_f
        return kz * zonal + km * grid.inv_acos * (dq[1:] - dq[:-1])

    kz = dt * cfg.diffusion / (cfg.dlam * cfg.dlam)
    km = dt * cfg.diffusion / (cfg.radius * cfg.dphi * cfg.dphi)

    def div(fq, gq):
        gq = gq * grid.cos_f
        return cl * grid.inv_acos * (fq - _west(fq)) + cp * grid.inv_acos * (gq[1:] - gq[:-1])

    su, sv = _source(h, hu, hv, grid)
    su, sv = _source(h, hu + (0.5 * dt) * su, hv + (0.5 * dt) * sv, grid)
    py = (0.5 * g) * hy * hy
    h1 = h - div(hux, hvy) + laplacian(h, he)
    hu1 = hu - div(mom(hux, hx), huy * hvy / hy) + dt * su + laplacian(hu, hue)
    hv1 = (
        hv - div(hux * hvx / hx, hvy * hvy / hy) - ca * (py[1:] - py[:-1]) + dt * sv
        + laplacian(hv, hve)
    )
    return jnp.stack([h1, hu1, hv1])


def _grid_arrays(cfg: SphereConfig) -> SphereGrid:
    return SphereGrid(*(jnp.asarray(x) for x in sphere_grid(cfg)))


@register_stepper("swe_sphere")
class SWESphereStepper(SWE2DStepper):
    """Williamson case 5 on the sphere. The λ-face zonal momentum flux of the
    corrector is the paper's substituted equation (``sph.*`` sites); the
    chunked fused plane runs it in swe2d's flux kernel at Williamson's
    ``g``, the megakernel runs the whole update with the grid's fields as
    read-only kernel inputs (:func:`repro.kernels.mega.swe_sphere_mega`)."""

    sites = SITES
    story = "hu ~ 1.2e5 overflows E5M10 as an operand; hu*hu ~ 1.4e10 needs <3,8,4>'s E7M8"

    def default_config(self) -> SphereConfig:
        return SphereConfig()

    def init_state(self, cfg: SphereConfig):
        return initial_state(cfg)

    def gravity(self, cfg: SphereConfig) -> float:
        return cfg.g

    def update(self, U, cfg: SphereConfig, mom):
        return _sphere_step(U, cfg, _grid_arrays(cfg), mom)

    def mega_supported(self, cfg: SphereConfig, prec) -> bool:
        """The momentum flux's λ-face grid is ``(nlat, nlon)``: it must be one
        ``prec.kernel_blocks`` tile for the chunked plane's split to be the
        whole field's."""
        return cfg.nlat <= prec.kernel_blocks[0] and cfg.nlon <= prec.kernel_blocks[1]

    def mega_step(self, U, cfg: SphereConfig, prec, steps: int, every: int, **kw):
        """The whole horizon in one ``pallas_call``
        (:func:`repro.kernels.mega.swe_sphere_mega`)."""
        from repro.kernels.mega import swe_sphere_mega  # lazy: pallas off cold paths

        return swe_sphere_mega(
            U, cfg=cfg, prec=prec, steps=steps, every=every, sites=self.sites,
            site_ops=self.site_ops, **kw,
        )

    def metric_offset(self, cfg: SphereConfig) -> float:
        return cfg.h0
