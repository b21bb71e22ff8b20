"""The packed R2F2 storage format (DESIGN.md §13).

A :class:`PackedArray` is a registered pytree node carrying one array's
R2F2 storage representation:

* ``payload`` — the 2-D bit payload (``pack_r2f2`` fields: sign | exp |
  mantissa), ``uint16`` whenever the format fits 16 bits (every format the
  paper studies), ``uint32`` otherwise;
* ``k`` — the per-block flexible split, one int32 per storage block;
* static aux data — the :class:`~repro.core.flexformat.FlexFormat`, the
  logical array shape, the 2-D view dims, and the storage block shape —
  which rides in the treedef, so jit/scan/vmap treat two PackedArrays of
  the same geometry as one structure.

Packing picks, per block, the minimal split whose format represents the
block's value-cluster top as a normal (``select_k_operand`` — the same
rule the tile-wise multiplier applies to operands), then quantizes with the
bit-exact RNE path and encodes the bits. ``unpack(pack(x))`` is therefore
``quantize_em`` at the chosen splits — pack/unpack is bijective on
quantized values (proven by the pack round-trip property suites), which is
what makes packed and quantized-f32 runs bit-identical.

The pure block-level helpers (:func:`block_storage_k`, :func:`pack_block`,
:func:`unpack_block`) are shared verbatim with the fused Pallas sweep
prologue/epilogue (``repro.kernels.fused``), so in-kernel packing and
XLA-boundary packing can never disagree.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.flexformat import (
    FlexFormat,
    max_exponent,
    pack_r2f2,
    quantize_em,
    unpack_r2f2,
)
from repro.core.r2f2 import select_k_operand

__all__ = [
    "PackedArray",
    "pack_array",
    "unpack_array",
    "pack_state",
    "unpack_state",
    "storage_quantize",
    "is_packed",
    "state_nbytes",
    "payload_dtype",
    "block_storage_k",
    "pack_block",
    "unpack_block",
]


def payload_dtype(fmt: FlexFormat):
    """Narrowest unsigned dtype holding ``fmt.total_bits`` payload bits."""
    if fmt.total_bits <= 8:
        return jnp.uint8
    return jnp.uint16 if fmt.total_bits <= 16 else jnp.uint32


def block_storage_k(x, fmt: FlexFormat, k_min: int = 0):
    """Storage split for one 2-D block: minimal k representing the block's
    finite value-cluster top as a normal (zeros and non-finites excluded,
    empty blocks floor at exponent -127 -> widest-coverage-downward split is
    clamped by ``k_min``). The split comes back as a ``(1, 1)`` vector for a
    2-D block (see :func:`repro.core.flexformat.max_exponent`)."""
    return jnp.clip(select_k_operand(max_exponent(x), fmt), k_min, fmt.fx)


def pack_block(x, fmt: FlexFormat, k):
    """Quantize one block at split ``k`` and encode the storage payload
    (uint32 bits; callers narrow to :func:`payload_dtype`)."""
    e = fmt.eb + jnp.asarray(k, jnp.int32)
    m = fmt.mb + fmt.fx - jnp.asarray(k, jnp.int32)
    q = quantize_em(jnp.asarray(x, jnp.float32), e, m)
    return pack_r2f2(q, fmt, k)


def unpack_block(payload, fmt: FlexFormat, k):
    """Decode one block's payload back to f32 at split ``k``."""
    return unpack_r2f2(jnp.asarray(payload, jnp.uint32), fmt, k)


def _view2d(shape: Tuple[int, ...]) -> Tuple[int, int]:
    """Canonical 2-D view of an arbitrary-rank array: trailing axis stays
    contiguous (the stencil axis), leading axes collapse into rows."""
    if len(shape) == 0:
        return (1, 1)
    if len(shape) == 1:
        return (1, shape[0])
    rows = 1
    for d in shape[:-1]:
        rows *= d
    return (rows, shape[-1])


class PackedArray:
    """One array in packed R2F2 storage — see module docstring.

    Registered pytree node: children ``(payload, k)`` (so it flows through
    jit / scan / vmap / ``repro.ckpt`` like any state leaf), aux data
    ``(fmt, shape, block)`` (static, hashable — part of the treedef).
    """

    __slots__ = ("payload", "k", "fmt", "shape", "block")

    def __init__(self, payload, k, fmt: FlexFormat, shape: Tuple[int, ...], block: Tuple[int, int]):
        self.payload = payload
        self.k = k
        self.fmt = fmt
        self.shape = tuple(shape)
        self.block = tuple(block)

    @property
    def nbytes(self) -> int:
        """Storage footprint: payload plus split metadata."""
        return int(self.payload.nbytes) + int(self.k.nbytes)

    def with_view(self, shape: Tuple[int, ...]) -> "PackedArray":
        """The same packed elements under a different logical shape.

        Only valid for single-block arrays (one split covers every element
        either way, so the payload is a pure reshape) — which is what the
        fused sweep kernels need to re-view e.g. a ``(nx, ny)`` field as the
        kernel's ``(1, nx*ny)`` leaf and back.
        """
        shape = tuple(int(d) for d in shape)
        n_new = 1
        for d in shape:
            n_new *= d
        n_old = 1
        for d in self.shape:
            n_old *= d
        if n_new != n_old:
            raise ValueError(f"cannot view {self.shape} as {shape}: size differs")
        if tuple(self.k.shape[-2:]) != (1, 1):
            raise ValueError(
                "with_view needs a single-block PackedArray; got k of shape "
                f"{tuple(self.k.shape)}"
            )
        view = _view2d(shape)
        payload = self.payload.reshape(self.payload.shape[: -2] + view)
        return PackedArray(payload, self.k, self.fmt, shape, view)

    def __repr__(self) -> str:
        return (
            f"PackedArray({self.fmt}, shape={self.shape}, block={self.block}, "
            f"payload={getattr(self.payload, 'dtype', '?')}{getattr(self.payload, 'shape', '')})"
        )

    def tree_flatten(self):
        return (self.payload, self.k), (self.fmt, self.shape, self.block)

    @classmethod
    def tree_unflatten(cls, aux, children):
        payload, k = children
        fmt, shape, block = aux
        return cls(payload, k, fmt, shape, block)


jax.tree_util.register_pytree_node(
    PackedArray,
    lambda pa: pa.tree_flatten(),
    PackedArray.tree_unflatten,
)


def pack_array(
    x,
    fmt: FlexFormat,
    *,
    block: Optional[Tuple[int, int]] = None,
    k_min: int = 0,
) -> PackedArray:
    """Pack one f32 array. ``block`` is the storage block granularity over
    the canonical 2-D view (one split per block; default: one block —
    per-tensor k, which is exactly the per-block case for the solver's
    whole-extent sweep kernels). Blocks that do not divide are zero-padded;
    the pad is cropped on unpack and excluded from split selection (zeros
    carry no exponent)."""
    x = jnp.asarray(x, jnp.float32)
    shape = tuple(x.shape)
    rows, width = _view2d(shape)
    x2 = x.reshape(rows, width)
    if block is None:
        block = (rows, width)
    br, bw = (min(block[0], rows), min(block[1], width))
    gi, gj = -(-rows // br), -(-width // bw)
    pad_r, pad_w = gi * br - rows, gj * bw - width
    if pad_r or pad_w:
        x2 = jnp.pad(x2, ((0, pad_r), (0, pad_w)))

    # (gi, br, gj, bw) tiling; one split per (gi, gj) block
    xt = x2.reshape(gi, br, gj, bw)
    me = max_exponent(xt, axis=(1, 3))[:, 0, :, 0]
    k = jnp.clip(select_k_operand(me, fmt), k_min, fmt.fx).astype(jnp.int32)

    k_elem = jnp.broadcast_to(k[:, None, :, None], xt.shape)
    payload = pack_block(xt, fmt, k_elem).reshape(gi * br, gj * bw)
    return PackedArray(payload.astype(payload_dtype(fmt)), k, fmt, shape, (br, bw))


def unpack_array(pa: PackedArray):
    """Decode a PackedArray back to its logical-shape f32 array."""
    rows, width = _view2d(pa.shape)
    br, bw = pa.block
    gi, gj = -(-rows // br), -(-width // bw)
    pt = jnp.asarray(pa.payload, jnp.uint32).reshape(gi, br, gj, bw)
    k_elem = jnp.broadcast_to(pa.k[:, None, :, None], pt.shape)
    x2 = unpack_block(pt, pa.fmt, k_elem).reshape(gi * br, gj * bw)
    return x2[:rows, :width].reshape(pa.shape)


def pack_state(state, fmt: FlexFormat, *, block=None, k_min: int = 0):
    """Pack every leaf of a solver-state pytree (ISSUE's ``pack_state``)."""
    return jax.tree_util.tree_map(
        lambda x: pack_array(x, fmt, block=block, k_min=k_min), state
    )


def unpack_state(packed):
    """Inverse of :func:`pack_state`: PackedArray leaves back to f32."""
    return jax.tree_util.tree_map(
        lambda pa: unpack_array(pa),
        packed,
        is_leaf=lambda x: isinstance(x, PackedArray),
    )


def storage_quantize(state, fmt: FlexFormat, *, block=None, k_min: int = 0):
    """The f32-carried reference rounding: ``unpack(pack(state))``. A run
    carrying ``storage="quantized"`` state is bit-identical to the packed
    run at the same splits — by construction, since pack/unpack is
    bijective on quantized values."""
    return unpack_state(pack_state(state, fmt, block=block, k_min=k_min))


def is_packed(tree) -> bool:
    """Does any node of ``tree`` carry packed storage?"""
    found = []
    jax.tree_util.tree_map(
        lambda x: found.append(isinstance(x, PackedArray)) or x,
        tree,
        is_leaf=lambda x: isinstance(x, PackedArray),
    )
    return any(found)


def state_nbytes(tree) -> int:
    """Total carried-state bytes (payload + metadata for packed leaves)."""
    return sum(int(x.nbytes) for x in jax.tree_util.tree_leaves(tree))
