"""Pallas kernel: per-tile R2F2 quantization (the "precision adjustment
unit" as a TPU vector-unit pass).

Each grid cell owns one (bm, bn) VMEM tile. The kernel body scans the tile's
max magnitude, picks the minimal flexible split ``k`` (DESIGN.md §2 — the
hardware's overflow-retry loop collapsed into a pre-pass), quantizes the tile
to ``E(EB+k) M(MB+FX-k)`` with bit-exact RNE, and writes both the quantized
tile and the per-tile ``k`` metadata (the mask bits of Fig. 4a, stored
out-of-band like any block-scaled format's scale).

TPU notes: everything is elementwise u32 bit-twiddling + an 8x128-lane max
reduction — pure VPU work, no MXU. Block shape defaults to (256, 256) f32 =
256 KiB in VMEM (in+out), well under the ~16 MiB/core budget, and is a
multiple of the (8, 128) f32 tiling.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.flexformat import quantize_em
from repro.core.r2f2 import select_k_operand
from repro.kernels.blockops import block_max_exp
from repro.kernels.fused import resolve_interpret

DEFAULT_BLOCK = (256, 256)


def _quantize_kernel(x_ref, y_ref, k_ref, *, fmt):
    x = x_ref[...]
    # operand-only need: product bound handled by the consumer's shared-k
    k = select_k_operand(block_max_exp(x), fmt)  # (1, 1)
    e_bits = fmt.eb + k
    m_bits = fmt.mb + fmt.fx - k
    y_ref[...] = quantize_em(x, e_bits, m_bits)
    k_ref[...] = k[None, None]


@functools.partial(jax.jit, static_argnames=("fmt", "block", "interpret"))
def r2f2_quantize_pallas(x, *, fmt, block=DEFAULT_BLOCK, interpret=None):
    """Quantize a 2D f32 array tile-by-tile. Returns (y, k_tiles).

    Each tile's split is written as its own ``(1, 1)`` trailing block of a
    ``(gm, gn, 1, 1)`` array — a block the TPU compiler accepts, where a
    ``(1, 1)`` block of a ``(gm, gn)`` array is not (8, 128)-aligned."""
    m, n = x.shape
    bm = min(block[0], m)
    bn = min(block[1], n)
    if m % bm or n % bn:
        raise ValueError(f"shape {x.shape} not divisible by block ({bm},{bn})")
    grid = (m // bm, n // bn)
    call = pl.pallas_call(
        functools.partial(_quantize_kernel, fmt=fmt),
        grid=grid,
        in_specs=[pl.BlockSpec((bm, bn), lambda i, j: (i, j))],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((1, 1, 1, 1), lambda i, j: (i, j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), jnp.float32),
            jax.ShapeDtypeStruct(grid + (1, 1), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
    )
    y, k = call(x.astype(jnp.float32))
    return y, k[:, :, 0, 0]
