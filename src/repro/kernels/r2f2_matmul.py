"""Pallas kernel: blocked matmul through R2F2 multipliers.

Faithful mapping of the paper's multiplier into an MXU pipeline:

* each (bm, bk) x (bk, bn) block pair shares ONE flexible split ``k`` —
  the paper's same-format-operands rule (§4.1) at block granularity;
* ``k`` is the minimal split covering both operand tiles AND their product
  bound — the overflow-retry loop collapsed into a pre-pass (DESIGN.md §2);
* operands are quantized to ``E(EB+k) M(MB+FX-k)`` bit-exactly (RNE);
* products accumulate in f32. Two product-rounding semantics:
    - ``round_products=False`` (deployment): products stay exact into the
      accumulator — how an R2F2-fed MXU would behave (bf16-MXU-style);
    - ``round_products=True`` (scalar-faithful): every scalar product is
      rounded to the runtime format (incl. the paper's FX-tail truncation)
      before summation — the paper's discrete multiplier feeding an adder.
      Materializes (bm, bk, bn) intermediates; use small blocks.

Grid: (M/bm, N/bn, K/bk), K innermost ("arbitrary" semantics — sequential
accumulation into the same output block; m, n are "parallel"). Default
blocks (128, 128, 128): A+B+O tiles = 3 * 64 KiB f32 in VMEM, MXU-aligned.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.flexformat import quantize_em
from repro.core.r2f2 import product_guard_bits, select_k
from repro.kernels.blockops import block_max_exp
from repro.kernels.fused import resolve_interpret

DEFAULT_BLOCKS = (128, 128, 128)


def _matmul_kernel(a_ref, b_ref, o_ref, *, fmt, round_products, tail_approx):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    a = a_ref[...]
    b = b_ref[...]
    k = select_k(block_max_exp(a), block_max_exp(b), fmt)  # (1, 1)
    e_bits = fmt.eb + k
    m_bits = fmt.mb + fmt.fx - k
    aq = quantize_em(a, e_bits, m_bits)
    bq = quantize_em(b, e_bits, m_bits)

    if round_products:
        # scalar-faithful: round each product to the runtime format before
        # the adds (paper Fig. 4b, incl. the FX-tail truncation).
        guard = product_guard_bits(fmt, k) if tail_approx else None
        prods = aq[:, :, None] * bq[None, :, :]  # (bm, bk, bn), exact in f32
        prods = quantize_em(prods, e_bits, m_bits, tail_trunc_bits=guard)
        partial = jnp.sum(prods, axis=1)
    else:
        partial = jnp.dot(aq, bq, preferred_element_type=jnp.float32)

    o_ref[...] += partial


@functools.partial(
    jax.jit,
    static_argnames=("fmt", "blocks", "round_products", "tail_approx", "interpret"),
)
def r2f2_matmul_pallas(
    a,
    b,
    *,
    fmt,
    blocks=DEFAULT_BLOCKS,
    round_products=False,
    tail_approx=True,
    interpret=None,
):
    """C = A @ B with R2F2 block semantics. A: (M, K) f32, B: (K, N) f32.

    Non-divisible shapes are zero-padded up to block multiples and the
    output cropped back: padded zeros contribute nothing to the products
    and never raise a block's max exponent, so the real region's split
    selection and quantization are unchanged.
    """
    m, kdim = a.shape
    k2, n = b.shape
    if kdim != k2:
        raise ValueError(f"contraction mismatch {a.shape} @ {b.shape}")
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    bm = min(blocks[0], m)
    bn = min(blocks[1], n)
    bk = min(blocks[2], kdim)
    pm, pn, pk = -m % bm, -n % bn, -kdim % bk
    if pm or pk:
        a = jnp.pad(a, ((0, pm), (0, pk)))
    if pk or pn:
        b = jnp.pad(b, ((0, pk), (0, pn)))
    mp, np_, kp = m + pm, n + pn, kdim + pk

    grid = (mp // bm, np_ // bn, kp // bk)
    call = pl.pallas_call(
        functools.partial(
            _matmul_kernel,
            fmt=fmt,
            round_products=round_products,
            tail_approx=tail_approx,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        interpret=resolve_interpret(interpret),
    )
    out = call(a, b)
    return out[:m, :n] if (pm or pn) else out
