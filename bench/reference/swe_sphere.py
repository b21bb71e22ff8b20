"""Shallow-water equations on the sphere (Williamson et al. 1992, test case
5: zonal flow over an isolated mountain), Richtmyer two-step Lax-Wendroff,
float32.

State ``U = (h, hu, hv)``, shape ``(3, nlat, nlon)``, longitude on the last
axis; ``h`` is the depth, ``h_s`` the mountain, ``a`` the radius, ``f = 2Ω
sinφ``::

    ∂h/∂t    + 1/(a cosφ) [∂(hu)/∂λ + ∂(hv cosφ)/∂φ]                             = 0
    ∂(hu)/∂t + 1/(a cosφ) [∂(hu·u + g h²/2)/∂λ + ∂(hu·v cosφ)/∂φ]                = (f + u tanφ/a) hv − g h/(a cosφ) ∂h_s/∂λ
    ∂(hv)/∂t + 1/(a cosφ) [∂(hv·u)/∂λ + ∂(hv·v cosφ)/∂φ] + (1/a) ∂(g h²/2)/∂φ  = −(f + u tanφ/a) hu − (g h/a) ∂h_s/∂φ

Cells at ``λ_i = i Δλ``, ``φ_j = −π/2 + (j + ½) Δφ``; longitude wraps; the
ghost row beyond each pole is the polar row at ``λ + π`` with the momenta
negated. With ``F = (hu, hu²/h + g h²/2, hu hv/h)``, ``G = (hv, hu hv/h,
hv²/h)`` and ``P = g h²/2``:

    Ux = (U_i + U_{i+1})/2 − dt/(2 a cosφ Δλ) (F_{i+1} − F_i)
    Uy = (U_j + U_{j+1})/2 − dt/(2 a c̄ Δφ) (G_{j+1} cosφ_{j+1} − G_j cosφ_j)
         [hv: − dt/(2 a Δφ) (P_{j+1} − P_j)]        (ghost rows: their polar row's cosφ)
    U⁺ = U − dt/(a cosφ Δλ) (F(Ux)_{i+½} − F(Ux)_{i−½})
           − dt/(a cosφ Δφ) (G(Uy) cosφ_f |_{j+½} − G(Uy) cosφ_f |_{j−½})
           [hv: − dt/(a Δφ) (P(Uy)_{j+½} − P(Uy)_{j−½})]
           + dt S(U + dt/2 S(U)) + dt K ∇²U

where ``c̄`` is the mean of the two rows' cosφ, ``cosφ_f`` the faces' (0 at
the poles), ``S`` the right-hand side above, the mountain's gradients are
centred differences on the grid, and ``∇²`` the spherical Laplacian of each
field (``∂²/∂λ²/(a cosφ)² + ∂(cosφ ∂/∂φ)/∂φ/(a² cosφ)``, second
differences with the faces' cosφ) at the configured ``K`` (``diffusion``).

Initial state: ``u = u0 cosφ``, ``v = 0``, ``g (h + h_s) = g h0 − (a Ω u0 +
u0²/2) sin²φ``, ``h_s = h_s0 (1 − r/R)`` with ``r² = min(R², (λ − λc)² + (φ
− φc)²)``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def _lat(cfg) -> np.ndarray:
    return -0.5 * math.pi + (np.arange(cfg["nlat"]) + 0.5) * math.pi / cfg["nlat"]


def _topography(cfg) -> np.ndarray:
    lam = np.arange(cfg["nlon"]) * 2.0 * math.pi / cfg["nlon"]
    phi = _lat(cfg)
    R = cfg["mountain_radius"]
    dist2 = (lam[None, :] - cfg["mountain_lon"]) ** 2 + (phi[:, None] - cfg["mountain_lat"]) ** 2
    return cfg["mountain"] * (1.0 - np.sqrt(np.minimum(R * R, dist2)) / R)


def _col(v):
    return jnp.asarray(np.asarray(v, np.float64)[:, None], jnp.float32)


def _coefficients(cfg) -> dict:
    """Per-row metric terms (column vectors) and the mountain's gradients."""
    a, nlon, nlat = cfg["radius"], cfg["nlon"], cfg["nlat"]
    dlam, dphi = 2.0 * math.pi / nlon, math.pi / nlat
    phi = _lat(cfg)
    cos = np.cos(phi)
    cos_ext = np.concatenate([cos[:1], cos, cos[-1:]])
    cos_face = np.cos(-0.5 * math.pi + np.arange(nlat + 1) * dphi)
    cos_face[[0, -1]] = 0.0
    hs = _topography(cfg)
    dhs_dlam = (np.roll(hs, -1, 1) - np.roll(hs, 1, 1)) / (2.0 * dlam)
    beyond = np.concatenate([np.roll(hs[:1], nlon // 2, 1), hs, np.roll(hs[-1:], nlon // 2, 1)])
    dhs_dphi = (beyond[2:] - beyond[:-2]) / (2.0 * dphi)
    return dict(
        acos=_col(a * cos), cos_ext=_col(cos_ext), acbar=_col(a * 0.5 * (cos_ext[1:] + cos_ext[:-1])),
        cos_face=_col(cos_face), f=_col(2.0 * cfg["omega"] * np.sin(phi)), tan=_col(np.tan(phi)),
        dhs_dlam=jnp.asarray(dhs_dlam, jnp.float32), dhs_dphi=jnp.asarray(dhs_dphi, jnp.float32),
    )


def initial_state(cfg, scales):
    """One member per entry of ``scales``: the jet ``u0`` times the scale.
    Shape ``(members, 3, nlat, nlon)``."""
    u0 = jnp.float32(cfg["u0"]) * jnp.asarray(scales, jnp.float32)[:, None, None]
    phi = jnp.asarray(_lat(cfg), jnp.float32)[None, :, None]
    hs = jnp.asarray(_topography(cfg), jnp.float32)[None]
    a, omega, g = jnp.float32(cfg["radius"]), jnp.float32(cfg["omega"]), jnp.float32(cfg["g"])
    h = cfg["h0"] - (a * omega * u0 + 0.5 * u0 * u0) * jnp.sin(phi) ** 2 / g - hs
    hu = h * (u0 * jnp.cos(phi))
    return jnp.stack([h, hu, jnp.zeros_like(h)], axis=1)


def _F(U, g):
    h, hu, hv = U[0], U[1], U[2]
    return jnp.stack([hu, hu * hu / h + 0.5 * g * h * h, hu * hv / h])


def _G(U):
    h, hu, hv = U[0], U[1], U[2]
    return jnp.stack([hv, hu * hv / h, hv * hv / h])


def _with_poles(U):
    """Rows -1 and nlat: the polar rows half a turn round, momenta negated."""
    half = U.shape[-1] // 2
    sign = jnp.asarray([1.0, -1.0, -1.0], jnp.float32)[:, None, None]
    south = sign * jnp.roll(U[:, :1], half, axis=2)
    north = sign * jnp.roll(U[:, -1:], half, axis=2)
    return jnp.concatenate([south, U, north], axis=1)


def _rhs(U, c, g, a):
    h, hu, hv = U[0], U[1], U[2]
    rot = c["f"] + hu / h * c["tan"] / a
    return jnp.stack([
        jnp.zeros_like(h),
        rot * hv - g * h * c["dhs_dlam"] / c["acos"],
        -rot * hu - g * h * c["dhs_dphi"] / a,
    ])


def _laplacian(U, E, c, cfg):
    """Each field's spherical Laplacian; ``E`` is ``U`` with its ghost rows."""
    a, dlam, dphi = cfg["radius"], 2.0 * math.pi / cfg["nlon"], math.pi / cfg["nlat"]
    zonal = (jnp.roll(U, -1, axis=2) - 2.0 * U + jnp.roll(U, 1, axis=2)) / (c["acos"] * dlam) ** 2
    flux = (E[:, 1:] - E[:, :-1]) * c["cos_face"]
    return zonal + (flux[:, 1:] - flux[:, :-1]) / (c["acos"] * a * dphi * dphi)


def step(U, cfg):
    g, a, dt = cfg["g"], cfg["radius"], cfg["dt"]
    dlam, dphi = 2.0 * math.pi / cfg["nlon"], math.pi / cfg["nlat"]
    c = _coefficients(cfg)
    east = jnp.roll(U, -1, axis=2)
    F = _F(U, g)
    Ux = 0.5 * (U + east) - dt / (2.0 * dlam) * (jnp.roll(F, -1, axis=2) - F) / c["acos"]
    E = _with_poles(U)
    Gc = _G(E) * c["cos_ext"]
    P = 0.5 * g * E[0] * E[0]
    Uy = 0.5 * (E[:, 1:] + E[:, :-1]) - dt / (2.0 * dphi) * (Gc[:, 1:] - Gc[:, :-1]) / c["acbar"]
    Uy = Uy.at[2].add(-dt / (2.0 * a * dphi) * (P[1:] - P[:-1]))
    Fx = _F(Ux, g)
    Gy = _G(Uy) * c["cos_face"]
    Py = 0.5 * g * Uy[0] * Uy[0]
    mid = U + 0.5 * dt * _rhs(U, c, g, a)
    out = (
        U
        - dt / dlam * (Fx - jnp.roll(Fx, 1, axis=2)) / c["acos"]
        - dt / dphi * (Gy[:, 1:] - Gy[:, :-1]) / c["acos"]
        + dt * _rhs(mid, c, g, a)
        + dt * cfg["diffusion"] * _laplacian(U, E, c, cfg)
    )
    return out.at[2].add(-dt / (a * dphi) * (Py[1:] - Py[:-1]))


def offsets(cfg):
    """The resting background of each field, removed before a gap is taken."""
    return [cfg["h0"], 0.0, 0.0]


def observable(U):
    return U[0]


def run(cfg, state0, steps: int, every: int):
    """``steps`` updates of one member; returns ``(final, snapshots)`` with a
    snapshot of ``h`` after every ``every`` steps."""

    def inner(U, _):
        return step(U, cfg), None

    def outer(U, _):
        U, _ = jax.lax.scan(inner, U, None, length=every)
        return U, observable(U)

    n_out = steps // every
    U, snaps = jax.lax.scan(outer, jnp.asarray(state0, jnp.float32), None, length=n_out)
    rem = steps - n_out * every
    if rem:
        U, _ = jax.lax.scan(inner, U, None, length=rem)
    return U, snaps
