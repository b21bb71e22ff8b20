"""Ahead-of-time compiles of the Pallas kernels for a TPU v5e, without a chip.

The TPU compiler is installed with JAX and compiles for a described topology,
so these tests catch what interpret mode cannot: layouts, blocks and ops
that Mosaic refuses (scalar bit casts, scatters, unaligned blocks). Each
kernel is compiled at its configuration's size with ``interpret=False`` and
must contain a Pallas TPU kernel (``tpu_custom_call``).

The topology is described inside a module fixture (never at import time):
only the worker that runs this file loads the TPU library.
"""

from __future__ import annotations

import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs import burgers1d, heat1d, heat2d, swe2d, williamson5
from repro.core.flexformat import E8M23
from repro.core.policy import PRESETS, tracker_init
from repro.kernels import mega
from repro.kernels.heat_stencil import HEAT1D_SITES, heat1d_sweep
from repro.kernels.pde_steps import BURGERS1D_SITES, HEAT2D_SITES, heat2d_sweep
from repro.kernels.r2f2_matmul import r2f2_matmul_pallas
from repro.kernels.r2f2_quantize import r2f2_quantize_pallas
from repro.kernels.swe_flux import SWE_OPS, SWE_SITES, swe_flux_fused
from repro.pack import PackedArray, payload_dtype
from repro.pde.swe_sphere import SITES as SPHERE_SITES
from repro.profile.capture import CaptureSpec


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


MODES = ("f32", "e5m10", "r2f2_16", "rr_tracked")


def _prec(mode):
    if mode == "rr_tracked":
        return dataclasses.replace(PRESETS["r2f2_16"], mode="rr_tracked")
    return PRESETS[mode]


def _tracked(mode):
    return mode == "rr_tracked"


def _heat1d_sweep(mode):
    c, p = heat1d.CONFIG, _prec(mode)
    kw = dict(k_floor=jnp.zeros((2,), jnp.int32), collect_evidence=True) if _tracked(mode) else {}

    def fn(u):
        return heat1d_sweep(
            u, alpha=c.alpha, dtodx2=c.dtodx2, prec=p, steps=8, block_rows=1,
            interpret=False, **kw,
        )

    return fn, [((1, c.nx), jnp.float32)]


def _heat2d_sweep(mode):
    c, p = heat2d.CONFIG, _prec(mode)
    kw = dict(k_floor=jnp.zeros((2,), jnp.int32), collect_evidence=True) if _tracked(mode) else {}

    def fn(u):
        return heat2d_sweep(
            u, alpha=c.alpha, dtodx2=c.dtodx2, prec=p, steps=8, interpret=False, **kw
        )

    return fn, [((c.nx, c.ny), jnp.float32)]


def _swe_flux(mode):
    c, p = swe2d.CONFIG, _prec(mode)
    kw = dict(k_floor=jnp.zeros((4,), jnp.int32), collect_evidence=True) if _tracked(mode) else {}

    def fn(q1, q3):
        return swe_flux_fused(q1, q3, prec=p, interpret=False, **kw)

    shape = (c.nx - 1, c.ny)  # the x-midpoint grid the solver hands the kernel
    return fn, [(shape, jnp.float32), (shape, jnp.float32)]


def _heat1d_mega(mode):
    c, p = heat1d.CONFIG, _prec(mode)
    tr = tracker_init(2, p.fmt) if _tracked(mode) else None

    def fn(u):
        return mega.heat1d_mega(
            u, alpha=c.alpha, dtodx2=c.dtodx2, prec=p, steps=64, every=16,
            sites=HEAT1D_SITES, tracker=tr, interpret=False,
        )

    return fn, [((c.nx,), jnp.float32)]


def _burgers1d_mega(mode):
    c, p = burgers1d.CONFIG, _prec(mode)
    tr = tracker_init(2, p.fmt) if _tracked(mode) else None

    def fn(u):
        return mega.burgers1d_mega(
            u, dt=c.dt, dx=c.dx, prec=p, steps=64, every=16, sites=BURGERS1D_SITES,
            tracker=tr, collect_evidence=True, interpret=False,
        )

    return fn, [((c.nx,), jnp.float32)]


def _swe2d_mega(mode):
    c, p = swe2d.CONFIG, _prec(mode)
    tr = tracker_init(4, p.fmt) if _tracked(mode) else None

    def fn(u):
        return mega.swe2d_mega(
            u, cfg=c, prec=p, steps=8, every=4, sites=SWE_SITES, site_ops=SWE_OPS,
            tracker=tr, interpret=False,
        )

    return fn, [((3, c.nx, c.ny), jnp.float32)]


def _heat2d_mega(mode):
    c, p = heat2d.CONFIG, _prec(mode)
    tr = tracker_init(2, p.fmt) if _tracked(mode) else None

    def fn(u):
        return mega.heat2d_mega(
            u, alpha=c.alpha, dtodx2=c.dtodx2, prec=p, steps=64, every=16,
            sites=HEAT2D_SITES, tracker=tr, interpret=False,
        )

    return fn, [((c.nx, c.ny), jnp.float32)]


def _heat1d_sweep_capture(mode):
    c, p = heat1d.CONFIG, _prec(mode)

    def fn(u):
        return heat1d_sweep(
            u, alpha=c.alpha, dtodx2=c.dtodx2, prec=p, steps=8, block_rows=1,
            capture=CaptureSpec(), interpret=False,
        )

    return fn, [((1, c.nx), jnp.float32)]


def _heat1d_sweep_packed(mode):
    """In-kernel packed storage: the payload is decoded in the prologue and
    re-encoded in the epilogue."""
    c, p = heat1d.CONFIG, _prec(mode)

    def fn(payload, k):
        u = PackedArray(payload, k, p.fmt, (1, c.nx), (1, c.nx))
        return heat1d_sweep(
            u, alpha=c.alpha, dtodx2=c.dtodx2, prec=p, steps=8, block_rows=1,
            storage="packed", interpret=False,
        )

    return fn, [((1, c.nx), payload_dtype(p.fmt)), ((1, 1), jnp.int32)]


def _swe2d_mega_capture(mode):
    c, p = swe2d.CONFIG, _prec(mode)
    tr = tracker_init(4, p.fmt) if _tracked(mode) else None

    def fn(u):
        return mega.swe2d_mega(
            u, cfg=c, prec=p, steps=8, every=4, sites=SWE_SITES, site_ops=SWE_OPS,
            tracker=tr, capture=CaptureSpec(), interpret=False,
        )

    return fn, [((3, c.nx, c.ny), jnp.float32)]


def _swe2d_mega_packed(mode):
    """Boundary storage rounding of a rank-3 leaf inside the megakernel."""
    c, p = swe2d.CONFIG, _prec(mode)

    def fn(u):
        return mega.swe2d_mega(
            u, cfg=c, prec=p, steps=8, every=4, sites=SWE_SITES, site_ops=SWE_OPS,
            storage="packed", interpret=False,
        )

    return fn, [((3, c.nx, c.ny), jnp.float32)]


def _sphere_prec(mode):
    """The cell's R2F2-16 split ``<3,8,4>`` for the R2F2 modes."""
    p = _prec(mode)
    if p.mode.startswith("rr"):
        p = dataclasses.replace(p, fmt=PRESETS["r2f2_16_384"].fmt)
    return p


def _swe_sphere_mega(mode, capture=None, steps=williamson5.STEPS_PER_DAY, every=2700):
    """The ``williamson5.ens51`` cell's program: 51 members under ``vmap``,
    (3, 64, 128) each, one model day, the grid's fields as shared read-only
    kernel inputs; the longitude wrap and the half-turn pole rows are lane
    rolls inside the kernel."""
    c, p = williamson5.CONFIG, _sphere_prec(mode)
    tr = tracker_init(4, p.fmt) if _tracked(mode) else None

    def member(u):
        return mega.swe_sphere_mega(
            u, cfg=c, prec=p, steps=steps, every=every,
            sites=SPHERE_SITES, site_ops=SWE_OPS, tracker=tr, capture=capture,
            interpret=False,
        )

    return jax.vmap(member), [((51, 3, c.nlat, c.nlon), jnp.float32)]


def _swe_sphere_mega_capture(mode):
    """Capture streams every substep's evidence out of VMEM as one block, so
    it compiles for a short horizon (a day's would need 44 MB of VMEM)."""
    return _swe_sphere_mega(mode, capture=CaptureSpec(), steps=8, every=4)


PDE_KERNELS = {
    "heat1d_sweep": _heat1d_sweep,
    "heat2d_sweep": _heat2d_sweep,
    "swe_flux_fused": _swe_flux,
    "heat1d_mega": _heat1d_mega,
    "heat2d_mega": _heat2d_mega,
    "burgers1d_mega": _burgers1d_mega,
    "swe2d_mega": _swe2d_mega,
    "heat1d_sweep+capture": _heat1d_sweep_capture,
    "heat1d_sweep+packed": _heat1d_sweep_packed,
    "swe2d_mega+capture": _swe2d_mega_capture,
    "swe2d_mega+packed": _swe2d_mega_packed,
    "swe_sphere_mega": _swe_sphere_mega,
    "swe_sphere_mega+capture": _swe_sphere_mega_capture,
}


def _compiled_text(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("kernel", sorted(PDE_KERNELS))
def test_pde_kernel_compiles_for_v5e(kernel, mode, one_chip):
    fn, shapes = PDE_KERNELS[kernel](mode)
    assert "tpu_custom_call" in _compiled_text(fn, shapes, one_chip)


# the f32 case of the standalone kernels: E8M23 is f32 itself (identity format)
MATMUL_FORMATS = {"f32": E8M23, "r2f2_16": PRESETS["r2f2_16"].fmt}


@pytest.mark.parametrize("mode", sorted(MATMUL_FORMATS))
def test_r2f2_matmul_compiles_for_v5e(mode, one_chip):
    fmt = MATMUL_FORMATS[mode]

    def fn(a, b):
        return r2f2_matmul_pallas(a, b, fmt=fmt, interpret=False)

    shapes = [((512, 256), jnp.float32), ((256, 384), jnp.float32)]
    assert "tpu_custom_call" in _compiled_text(fn, shapes, one_chip)


@pytest.mark.parametrize("mode", sorted(MATMUL_FORMATS))
def test_r2f2_quantize_compiles_for_v5e(mode, one_chip):
    fmt = MATMUL_FORMATS[mode]

    def fn(x):
        return r2f2_quantize_pallas(x, fmt=fmt, interpret=False)

    assert "tpu_custom_call" in _compiled_text(fn, [((512, 768), jnp.float32)], one_chip)
