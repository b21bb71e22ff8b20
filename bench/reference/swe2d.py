"""2D shallow-water equations, Richtmyer two-step Lax-Wendroff, float32.

State ``U = (h, hu, hv)`` on an ``nx x ny`` basin with reflective walls.

    F(U) = (hu, hu^2/h + g h^2/2, hu hv/h)
    G(U) = (hv, hu hv/h, hv^2/h + g h^2/2)

Half steps at the x- and y-midpoints,

    Ux = (U[i+1] + U[i]) / 2 - dt/(2dx) (F[i+1] - F[i])
    Uy = (U[j+1] + U[j]) / 2 - dt/(2dy) (G[j+1] - G[j])

then the interior update ``U -= dt/dx (F(Ux)[i] - F(Ux)[i-1]) + dt/dy
(G(Uy)[j] - G(Uy)[j-1])``. The walls copy the nearest interior value, with
the normal momentum negated. ``dt = cfl min(dx, dy) / (sqrt(g (depth +
bump)) sqrt 2)`` from the configured bump. The initial state is a resting
basin of the configured depth with a Gaussian bump of height ``bump`` and
width ``bump_sigma`` (as a fraction of the basin) at its centre.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

G = 9.81


def _dt(cfg) -> float:
    dx, dy = cfg["length"] / cfg["nx"], cfg["length"] / cfg["ny"]
    c = math.sqrt(G * (cfg["depth"] + cfg["bump"]))
    return cfg["cfl"] * min(dx, dy) / (c * math.sqrt(2.0))


def initial_state(cfg, scales):
    """One member per entry of ``scales``: the bump's height is the configured
    one times the scale. Shape ``(members, 3, nx, ny)``."""
    x = jnp.linspace(0.0, 1.0, cfg["nx"], dtype=jnp.float32)
    y = jnp.linspace(0.0, 1.0, cfg["ny"], dtype=jnp.float32)
    xx, yy = jnp.meshgrid(x, y, indexing="ij")
    bump = jnp.exp(-((xx - 0.5) ** 2 + (yy - 0.5) ** 2) / (2.0 * cfg["bump_sigma"] ** 2))
    heights = jnp.float32(cfg["bump"]) * jnp.asarray(scales, jnp.float32)
    h = cfg["depth"] + heights[:, None, None] * bump[None]
    zero = jnp.zeros_like(h)
    return jnp.stack([h, zero, zero], axis=1)


def _flux_F(U):
    h, hu, hv = U[0], U[1], U[2]
    return jnp.stack([hu, hu * hu / h + 0.5 * G * h * h, hu * hv / h])


def _flux_G(U):
    h, hu, hv = U[0], U[1], U[2]
    return jnp.stack([hv, hu * hv / h, hv * hv / h + 0.5 * G * h * h])


def _walls(interior):
    """Frame the interior with walls: each wall value copies its interior
    neighbour; x-walls (first and last rows) negate hu, y-walls (first and
    last columns) negate hv."""
    h, hu, hv = interior[0], interior[1], interior[2]

    def rows(a, negate=False):
        top, bottom = (-a[:1], -a[-1:]) if negate else (a[:1], a[-1:])
        return jnp.concatenate([top, a, bottom], axis=0)

    def cols(a, negate=False):
        left, right = (-a[:, :1], -a[:, -1:]) if negate else (a[:, :1], a[:, -1:])
        return jnp.concatenate([left, a, right], axis=1)

    return jnp.stack([cols(rows(h)), cols(rows(hu, negate=True)), rows(cols(hv, negate=True))])


def step(U, cfg):
    dt = _dt(cfg)
    dx, dy = cfg["length"] / cfg["nx"], cfg["length"] / cfg["ny"]
    F, Gf = _flux_F(U), _flux_G(U)
    Ux = 0.5 * (U[:, 1:, :] + U[:, :-1, :]) - (dt / (2 * dx)) * (F[:, 1:, :] - F[:, :-1, :])
    Uy = 0.5 * (U[:, :, 1:] + U[:, :, :-1]) - (dt / (2 * dy)) * (Gf[:, :, 1:] - Gf[:, :, :-1])
    Fx, Gy = _flux_F(Ux), _flux_G(Uy)
    interior = (
        U[:, 1:-1, 1:-1]
        - (dt / dx) * (Fx[:, 1:, 1:-1] - Fx[:, :-1, 1:-1])
        - (dt / dy) * (Gy[:, 1:-1, 1:] - Gy[:, 1:-1, :-1])
    )
    return _walls(interior)


def offsets(cfg):
    """The resting background of each field, removed before a gap is taken."""
    return [cfg["depth"], 0.0, 0.0]


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
