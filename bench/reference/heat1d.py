"""1D heat equation, explicit finite differences, float32.

    u'[i] = u[i] + ((alpha * lap[i]) * (dt / dx^2)),   lap = u[i-1] - 2 u[i] + u[i+1]

with ``dx = length / nx`` and ``dt = cfl dx^2 / alpha`` and both end points
held fixed. The initial condition is ``amplitude * sin(modes pi x / length)``
on ``nx`` points spaced evenly over ``[0, length]``, zero at both ends.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _dtodx2(cfg) -> float:
    dx = cfg["length"] / cfg["nx"]
    dt = cfg["cfl"] * dx * dx / cfg["alpha"]
    return dt / (dx * dx)


def initial_state(cfg, scales):
    """One member per entry of ``scales``: the configured sine profile with
    its amplitude multiplied by the scale. Shape ``(members, nx)``."""
    x = jnp.linspace(0.0, cfg["length"], cfg["nx"], dtype=jnp.float32)
    u = cfg["amplitude"] * jnp.sin(cfg["modes"] * jnp.pi * x / cfg["length"])
    u = jnp.concatenate([jnp.zeros((1,)), u[1:-1], jnp.zeros((1,))])
    return jnp.asarray(scales, jnp.float32)[:, None] * u[None, :]


def step(u, cfg):
    lap = u[:-2] - 2.0 * u[1:-1] + u[2:]
    flux = jnp.float32(cfg["alpha"]) * lap
    upd = flux * jnp.float32(_dtodx2(cfg))
    return jnp.concatenate([u[:1], u[1:-1] + upd, u[-1:]])


def offsets(cfg):
    """The resting background of each field, removed before a gap is taken."""
    return [0.0]


def observable(u):
    return u


def run(cfg, state0, steps: int, every: int):
    """``steps`` updates of one member; returns ``(final, snapshots)`` with a
    snapshot of the observable after every ``every`` steps."""

    def inner(u, _):
        return step(u, cfg), None

    def outer(u, _):
        u, _ = jax.lax.scan(inner, u, None, length=every)
        return u, observable(u)

    n_out = steps // every
    u, snaps = jax.lax.scan(outer, jnp.asarray(state0, jnp.float32), None, length=n_out)
    rem = steps - n_out * every
    if rem:
        u, _ = jax.lax.scan(inner, u, None, length=rem)
    return u, snaps
