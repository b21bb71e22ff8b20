"""Pure-jnp oracles for every Pallas kernel in this package.

Each function reproduces its kernel's semantics exactly (same per-tile split
selection, same quantization, same accumulation order *modulo* f32-add
reassociation, which is exact here because tests compare allclose with tight
tolerances and the emulated formats have few mantissa bits).
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.core.flexformat import quantize_em, unbiased_exponent
from repro.core.r2f2 import product_guard_bits, select_k, select_k_op, select_k_operand


def _max_exp(t):
    mag = jnp.where(jnp.isfinite(t), jnp.abs(t), 0.0)
    return unbiased_exponent(jnp.max(mag))


def _operand_k(t, fmt):
    return select_k_operand(_max_exp(t), fmt)


def r2f2_quantize_ref(x, *, fmt, block=(256, 256)):
    """Oracle for r2f2_quantize_pallas: per-(bm,bn)-tile minimal-k quantize."""
    x = jnp.asarray(x, jnp.float32)
    m, n = x.shape
    bm = min(block[0], m)
    bn = min(block[1], n)
    gm, gn = m // bm, n // bn
    xt = x.reshape(gm, bm, gn, bn)
    mag = jnp.where(jnp.isfinite(xt), jnp.abs(xt), 0.0)
    me = unbiased_exponent(jnp.max(mag, axis=(1, 3)))
    k = select_k_operand(me, fmt)
    kb = k[:, None, :, None]
    y = quantize_em(xt, fmt.eb + kb, fmt.mb + fmt.fx - kb)
    return y.reshape(m, n), k


def r2f2_matmul_ref(a, b, *, fmt, blocks=(128, 128, 128), round_products=False, tail_approx=True):
    """Oracle for r2f2_matmul_pallas: loop over block pairs in the same
    (i, j, k) order, shared split per pair, f32 accumulation."""
    a = jnp.asarray(a, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    m, kd = a.shape
    _, n = b.shape
    bm = min(blocks[0], m)
    bn = min(blocks[1], n)
    bk = min(blocks[2], kd)
    out = jnp.zeros((m, n), jnp.float32)
    for i in range(m // bm):
        for j in range(n // bn):
            acc = jnp.zeros((bm, bn), jnp.float32)
            for kk in range(kd // bk):
                at = a[i * bm:(i + 1) * bm, kk * bk:(kk + 1) * bk]
                bt = b[kk * bk:(kk + 1) * bk, j * bn:(j + 1) * bn]
                k = select_k(_max_exp(at), _max_exp(bt), fmt)
                e_bits, m_bits = fmt.eb + k, fmt.mb + fmt.fx - k
                aq = quantize_em(at, e_bits, m_bits)
                bq = quantize_em(bt, e_bits, m_bits)
                if round_products:
                    guard = product_guard_bits(fmt, k) if tail_approx else None
                    prods = aq[:, :, None] * bq[None, :, :]
                    prods = quantize_em(prods, e_bits, m_bits, tail_trunc_bits=guard)
                    acc = acc + jnp.sum(prods, axis=1)
                else:
                    acc = acc + jnp.dot(aq, bq, preferred_element_type=jnp.float32)
            out = out.at[i * bm:(i + 1) * bm, j * bn:(j + 1) * bn].set(acc)
    return out


def heat_stencil_ref(u0, alpha, dtodx2, *, fmt, steps=1, block_rows=8, tail_approx=True):
    """Oracle for heat_stencil_pallas: identical math per row-block."""
    u0 = jnp.asarray(u0, jnp.float32)
    rows, nx = u0.shape
    br = min(block_rows, rows)
    alpha = jnp.asarray(alpha, jnp.float32)
    dtodx2 = jnp.asarray(dtodx2, jnp.float32)

    def rr_mul(a, b):
        k = select_k(_max_exp(a), _max_exp(b), fmt)
        e_b, m_b = fmt.eb + k, fmt.mb + fmt.fx - k
        guard = product_guard_bits(fmt, k) if tail_approx else None
        return quantize_em(
            quantize_em(a, e_b, m_b) * quantize_em(b, e_b, m_b),
            e_b,
            m_b,
            tail_trunc_bits=guard,
        )

    def block_step(u):
        lap = u[:, :-2] - 2.0 * u[:, 1:-1] + u[:, 2:]
        flux = rr_mul(jnp.broadcast_to(alpha, lap.shape), lap)
        upd = rr_mul(flux, jnp.broadcast_to(dtodx2, lap.shape))
        interior = u[:, 1:-1] + upd
        return jnp.concatenate([u[:, :1], interior, u[:, -1:]], axis=1)

    blocks = []
    for i in range(rows // br):
        u = u0[i * br:(i + 1) * br]
        for _ in range(steps):
            u = block_step(u)
        blocks.append(u)
    return jnp.concatenate(blocks, axis=0)


def swe_flux_ref(q1, q3, *, fmt, block=(64, 128), tail_approx=True):
    """Oracle for swe_flux_pallas: per-block momentum flux with R2F2 muls
    and the flexible divide (shared split under the quotient-range envelope,
    no tail truncation — dividers have no partial-product tail to drop)."""
    q1 = jnp.asarray(q1, jnp.float32)
    q3 = jnp.asarray(q3, jnp.float32)
    m, n = q1.shape
    bm, bn = min(block[0], m), min(block[1], n)

    def rr_mul(a, b):
        k = select_k(_max_exp(a), _max_exp(b), fmt)
        e_b, m_b = fmt.eb + k, fmt.mb + fmt.fx - k
        guard = product_guard_bits(fmt, k) if tail_approx else None
        return quantize_em(
            quantize_em(a, e_b, m_b) * quantize_em(b, e_b, m_b),
            e_b, m_b, tail_trunc_bits=guard,
        )

    def rr_div(a, b):
        k = select_k_op(_max_exp(a), _max_exp(b), fmt, "div")
        e_b, m_b = fmt.eb + k, fmt.mb + fmt.fx - k
        return quantize_em(
            quantize_em(a, e_b, m_b) / quantize_em(b, e_b, m_b), e_b, m_b
        )

    out = jnp.zeros((m, n), jnp.float32)
    for i in range(m // bm):
        for j in range(n // bn):
            a = q1[i * bm:(i + 1) * bm, j * bn:(j + 1) * bn]
            h = q3[i * bm:(i + 1) * bm, j * bn:(j + 1) * bn]
            t2 = rr_div(rr_mul(a, a), h)
            t3 = rr_mul(h, h)
            t4 = rr_mul(jnp.full_like(t3, 0.5 * 9.81), t3)
            out = out.at[i * bm:(i + 1) * bm, j * bn:(j + 1) * bn].set(t2 + t4)
    return out
