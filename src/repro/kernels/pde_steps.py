"""Fused whole-step Pallas kernels for the beyond-paper PDE workloads
(heat2d / advection1d / burgers1d), on the shared
:mod:`repro.kernels.fused` sweep machinery.

Each kernel advances the workload a whole multi-substep chunk inside one
``pallas_call`` — the same two-phase shape as ``heat_stencil``: state loads
once into VMEM, every policy multiplication runs on a per-block runtime
split, and the per-site range evidence comes back for the adjust unit. The
bodies are line-for-line the registered steppers' ``step`` methods (same op
order, same f32 adds), which is what makes the fused and reference paths
bit-identical whenever a block covers the whole field.

Layout notes: the 1-D periodic workloads keep the whole rod in-block (the
rolls are in-register); the 2-D heat field is one ``(nx, ny)`` block — the
coupled extent never crosses a block boundary, so there is no inter-block
halo to exchange.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import fused

HEAT2D_SITES = ("heat2d.flux", "heat2d.update")
ADVECTION1D_SITES = ("adv.flux", "adv.update")
BURGERS1D_SITES = ("burgers.uu", "burgers.flux")


# ---------------------------------------------------------------------------
# 2D heat: explicit 5-point stencil, two-multiplier split
# ---------------------------------------------------------------------------


def _heat2d_body(alpha, dtodx2, sites):
    flux_site, update_site = sites

    def body(state, ops):
        (u,) = state
        lap = (  # 5-point interior laplacian, adds in f32
            u[:-2, 1:-1]
            + u[2:, 1:-1]
            + u[1:-1, :-2]
            + u[1:-1, 2:]
            - 4.0 * u[1:-1, 1:-1]
        )
        flux = ops.mul(jnp.float32(alpha), lap, flux_site)
        upd = ops.mul(flux, jnp.float32(dtodx2), update_site)
        # the interior update, framed by the Dirichlet edges (concatenation:
        # the TPU kernel compiler has no scatter for ``.at[].add``)
        mid = jnp.concatenate([u[1:-1, :1], u[1:-1, 1:-1] + upd, u[1:-1, -1:]], axis=1)
        return (jnp.concatenate([u[:1], mid, u[-1:]], axis=0),)

    return body


@functools.partial(
    jax.jit,
    static_argnames=(
        "alpha", "dtodx2", "prec", "steps", "sites", "collect_evidence", "capture",
        "interpret", "storage",
    ),
)
def heat2d_sweep(
    u0,
    *,
    alpha,
    dtodx2,
    prec,
    steps=1,
    sites=HEAT2D_SITES,
    k_floor=None,
    collect_evidence=False,
    capture=None,
    interpret=None,
    storage="f32",
):
    """Advance a (nx, ny) field ``steps`` 5-point explicit-FD substeps.

    Returns ``(u, evidence)`` (+ exponent counts when ``capture`` is set).
    ``storage="packed"`` takes and returns the field as a single-block
    :class:`repro.pack.PackedArray`.
    """
    nx, ny = u0.shape
    res = fused.fused_sweep(
        _heat2d_body(float(alpha), float(dtodx2), sites),
        (u0,),
        prec=prec,
        sites=sites,
        steps=steps,
        block=(nx, ny),
        k_floor=k_floor,
        collect_evidence=collect_evidence,
        capture=capture,
        interpret=interpret,
        storage=storage,
    )
    if capture is not None:
        (out,), ev, counts = res
        return out, ev, counts
    (out,), ev = res
    return out, ev


# ---------------------------------------------------------------------------
# 1D advection: flux-form upwind, periodic
# ---------------------------------------------------------------------------


def _advection1d_body(speed, dtodx, sites):
    flux_site, update_site = sites

    def body(state, ops):
        (u,) = state
        f = ops.mul(jnp.float32(speed), u, flux_site)
        df = f - jnp.roll(f, 1, axis=1)  # upwind difference, adds in f32
        upd = ops.mul(jnp.float32(dtodx), df, update_site)
        return (u - upd,)

    return body


@functools.partial(
    jax.jit,
    static_argnames=(
        "speed", "dtodx", "prec", "steps", "sites", "collect_evidence", "capture",
        "interpret", "storage",
    ),
)
def advection1d_sweep(
    u0,
    *,
    speed,
    dtodx,
    prec,
    steps=1,
    sites=ADVECTION1D_SITES,
    k_floor=None,
    collect_evidence=False,
    capture=None,
    interpret=None,
    storage="f32",
):
    """Advance a (nx,) periodic profile ``steps`` upwind substeps.

    Returns ``(u, evidence)`` (+ exponent counts when ``capture`` is set).
    ``storage="packed"`` takes/returns a single-block PackedArray profile.
    """
    packed = storage == "packed"
    n = u0.shape[0]
    lead = u0.with_view((1, n)) if packed else u0[None, :]
    res = fused.fused_sweep(
        _advection1d_body(float(speed), float(dtodx), sites),
        (lead,),
        prec=prec,
        sites=sites,
        steps=steps,
        block=(1, n),
        k_floor=k_floor,
        collect_evidence=collect_evidence,
        capture=capture,
        interpret=interpret,
        storage=storage,
    )
    if capture is not None:
        (out,), ev, counts = res
        return (out.with_view((n,)) if packed else out[0]), ev, counts
    (out,), ev = res
    return (out.with_view((n,)) if packed else out[0]), ev


# ---------------------------------------------------------------------------
# 1D Burgers: conservative Lax-Friedrichs, periodic
# ---------------------------------------------------------------------------


def _burgers1d_body(dt, dx, sites):
    uu_site, flux_site = sites

    def body(state, ops):
        (u,) = state
        uu = ops.mul(u, u, uu_site)  # the nonlinear flux product
        f = ops.mul(jnp.float32(0.5), uu, flux_site)  # f = u^2/2
        u_avg = 0.5 * (jnp.roll(u, -1, axis=1) + jnp.roll(u, 1, axis=1))
        df = jnp.roll(f, -1, axis=1) - jnp.roll(f, 1, axis=1)
        return (u_avg - (dt / (2.0 * dx)) * df,)

    return body


@functools.partial(
    jax.jit,
    static_argnames=(
        "dt", "dx", "prec", "steps", "sites", "collect_evidence", "capture",
        "interpret", "storage",
    ),
)
def burgers1d_sweep(
    u0,
    *,
    dt,
    dx,
    prec,
    steps=1,
    sites=BURGERS1D_SITES,
    k_floor=None,
    collect_evidence=False,
    capture=None,
    interpret=None,
    storage="f32",
):
    """Advance a (nx,) periodic wave ``steps`` Lax-Friedrichs substeps.

    Returns ``(u, evidence)`` (+ exponent counts when ``capture`` is set).
    ``storage="packed"`` takes/returns a single-block PackedArray wave.
    """
    packed = storage == "packed"
    n = u0.shape[0]
    lead = u0.with_view((1, n)) if packed else u0[None, :]
    res = fused.fused_sweep(
        _burgers1d_body(float(dt), float(dx), sites),
        (lead,),
        prec=prec,
        sites=sites,
        steps=steps,
        block=(1, n),
        k_floor=k_floor,
        collect_evidence=collect_evidence,
        capture=capture,
        interpret=interpret,
        storage=storage,
    )
    if capture is not None:
        (out,), ev, counts = res
        return (out.with_view((n,)) if packed else out[0]), ev, counts
    (out,), ev = res
    return (out.with_view((n,)) if packed else out[0]), ev
