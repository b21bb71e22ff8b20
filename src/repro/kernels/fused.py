"""Shared Pallas stencil-sweep builder — the fused execution plane's engine
room (DESIGN.md §10).

Every fused whole-step kernel in this package is the same machine with a
different body: load state blocks into VMEM, run ``steps`` solver substeps
in one in-kernel ``fori_loop`` (one HBM round trip per *chunk* instead of
per arithmetic op), route every policy multiplication through a per-block
runtime-k R2F2 split (:mod:`repro.kernels.blockops`), and emit — next to
the advanced state — the per-site max-exponent evidence the precision
adjust unit consumes between chunks. :func:`fused_sweep` owns that machine
once: grid/BlockSpec plumbing, row padding-and-cropping for non-divisible
shapes, the substep loop, the evidence output, and the carried-k floor
input for tracked modes.

A kernel body is a plain function over VMEM blocks::

    def body(state, ops):              # state: tuple of (br, bw) f32 blocks
        (u,) = state
        lap = u[:, :-2] - 2.0 * u[:, 1:-1] + u[:, 2:]
        flux = ops.mul(alpha, lap, "heat.flux")       # policy multiplier
        ...
        return (u_next,)

``ops`` is a :class:`FusedOps` — the in-kernel mirror of
``repro.pde.solver.StepOps``: ``mul(a, b, site)`` applies the policy's
arithmetic family (``rr`` per-block shared split / ``bf16`` / ``fixed`` /
``f32``, see :data:`repro.precision.fusion.FUSED_FAMILIES`) and records the
operands' block max exponents as tracker evidence. Stepper code therefore
reads identically inside and outside the kernel, which is what keeps the
fused and reference paths in bit-parity wherever a block covers the whole
field.

Blocking contract: state leaves are 2-D ``(rows, width)``. The row axis is
*independent* (batched rods, ensemble members, or a singleton) and may be
blocked and padded freely; the width axis carries the stencil coupling for
sweep kernels and must then stay whole in the block (``block[1] == width``)
— halos never cross blocks by construction. Purely elementwise bodies
(e.g. the SWE momentum flux) may tile both axes.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.flexformat import quantize_em
from repro.kernels.blockops import (
    block_max_exp,
    rr_add_block,
    rr_div_block,
    rr_mul_block,
    rr_rsqrt_block,
)
from repro.pack.packed import (
    PackedArray,
    block_storage_k,
    pack_block,
    payload_dtype,
    unpack_block,
)
from repro.precision.fusion import fused_family
from repro.profile.capture import block_pair_exp_hist

__all__ = ["on_tpu", "resolve_interpret", "FusedOps", "fused_sweep"]


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``None`` -> interpret off TPU, compile to Mosaic on TPU — every
    kernel entry point routes through this, so no call site hard-codes
    interpreter mode. On a TPU backend the kernels always compile: asking
    for the interpreter there is an error, never a silent slow path."""
    if on_tpu():
        if interpret:
            raise ValueError("Pallas interpret mode requested on a TPU backend")
        return False
    return True if interpret is None else bool(interpret)


def lane(row, j: int):
    """Entry ``j`` of a ``(1, n)`` row as a ``(1, 1)`` vector.

    A masked max over the lanes: exact for every value (NaN and -0.0
    included), and it keeps the entry a vector inside a TPU kernel, where
    per-site values must not become scalars."""
    lanes = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
    if jnp.issubdtype(row.dtype, jnp.integer):
        low = jnp.iinfo(row.dtype).min
    else:
        low = -jnp.inf
    return jnp.max(jnp.where(lanes == j, row, low), axis=1, keepdims=True)


def row_of(cols, dtype):
    """Assemble ``(1, 1)`` vectors into one ``(1, n)`` row (inverse of
    :func:`lane`)."""
    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, len(cols)), 1)
    out = jnp.zeros((1, len(cols)), dtype)
    for j, c in enumerate(cols):
        out = jnp.where(lanes == j, jnp.asarray(c, dtype), out)
    return out


def evidence_row(ops: "FusedOps"):
    """One substep's per-site evidence as an ``(n_sites, 2)`` f32 block —
    built by masked selects so that it is written as one full store (the
    TPU kernel compiler has no scatter)."""
    n = len(ops.sites)
    site = jax.lax.broadcasted_iota(jnp.int32, (n, 2), 0)
    opnd = jax.lax.broadcasted_iota(jnp.int32, (n, 2), 1)
    out = jnp.zeros((n, 2), jnp.float32)
    for j, name in enumerate(ops.sites):
        ae, be = ops.evidence[name]
        out = jnp.where((site == j) & (opnd == 0), ae, out)
        out = jnp.where((site == j) & (opnd == 1), be, out)
    return out


class FusedOps:
    """Per-substep policy arithmetic inside a fused kernel body.

    Mirrors ``repro.pde.solver.StepOps``: stepper bodies write
    ``ops.mul(a, b, "site")`` and this object supplies the family
    arithmetic, the per-block runtime split (floored at the carried tracker
    k for tracked modes), and the evidence capture. One instance lives per
    substep; the builder harvests ``.evidence`` after the body returns.
    """

    __slots__ = (
        "prec", "sites", "site_ops", "family", "k_floor", "collect", "capture",
        "valid", "evidence", "counts",
    )

    def __init__(
        self, prec, sites: Tuple[str, ...], k_floor=None, collect=False,
        capture=None, valid=None, site_ops=None,
    ):
        self.prec = prec
        self.sites = tuple(sites)
        #: per-site declared op ("mul"/"add"/"div"/"rsqrt") — when given, a
        #: body calling the wrong method at a site fails at trace time
        self.site_ops = None if site_ops is None else tuple(site_ops)
        self.family = fused_family(prec.mode)
        if self.family is None:
            raise ValueError(
                f"mode {prec.mode!r} has no fused arithmetic family; "
                "run it on the reference execution path"
            )
        self.k_floor = k_floor  # per-site (1, 1) int32 carried splits, or None
        self.collect = collect
        self.capture = capture  # CaptureSpec: widen evidence to binned counts
        #: (row_ok (br,1)|None, col_ok (1,bw)|None, br, bw) — this block's
        #: valid-lane masks when the grid is padded; capture counts only
        #: valid lanes, so pad constants can never contaminate a profile
        self.valid = valid
        self.evidence = {}  # site -> (a_max_exp, b_max_exp) f32 (1, 1) vectors
        self.counts = {}  # site -> (2, n_bins) int32 operand exponent counts

    def _valid_mask(self, shape):
        """Valid-lane mask broadcast to an operand's shape (None: all valid).

        Row padding needs the operand to keep the block's row extent (sweep
        bodies slice only along width); column padding needs the full block
        width (elementwise bodies). Anything else cannot be attributed to
        lanes and is refused at trace time.
        """
        if self.valid is None:
            return None
        row_ok, col_ok, br, bw = self.valid
        m = None
        if row_ok is not None:
            if len(shape) != 2 or shape[0] != br:
                raise ValueError(
                    f"capture on a row-padded grid needs body operands to keep "
                    f"the block row extent {br}; got shape {shape}"
                )
            m = jnp.broadcast_to(row_ok, shape)
        if col_ok is not None:
            if len(shape) != 2 or shape[1] != bw:
                raise ValueError(
                    f"capture on a width-padded grid needs body operands to "
                    f"keep the block width {bw}; got shape {shape}"
                )
            c = jnp.broadcast_to(col_ok, shape)
            m = c if m is None else (m & c)
        return m

    def _record(self, a, b, site: str, op: str):
        """Broadcast the operands, check the site's declared op, and record
        evidence/counts. Returns ``(a, b, exps)`` with ``exps`` the block max
        exponents (None when neither the rr family nor collection needs them).
        """
        a = jnp.asarray(a, jnp.float32)
        b = jnp.asarray(b, jnp.float32)
        shape = jnp.broadcast_shapes(a.shape, b.shape)
        a = jnp.broadcast_to(a, shape)
        b = jnp.broadcast_to(b, shape)
        if self.site_ops is not None:
            declared = self.site_ops[self.sites.index(site)]
            if declared != op:
                raise ValueError(
                    f"site {site!r} is declared as a {declared!r} op but the "
                    f"fused body called ops.{op} there"
                )

        exps = None
        if self.collect or self.family == "rr":
            exps = (block_max_exp(a), block_max_exp(b))
        if self.collect:
            if site in self.evidence:
                raise ValueError(f"fused body hit site {site!r} twice in one substep")
            self.evidence[site] = tuple(e.astype(jnp.float32) for e in exps)
        if self.capture is not None:
            self.counts[site] = block_pair_exp_hist(
                a, b, self.capture, self._valid_mask(shape)
            )
        return a, b, exps

    def _k_floor_at(self, site: str):
        if self.k_floor is None:
            return None
        return self.k_floor[self.sites.index(site)]

    def mul(self, a, b, site: str):
        """Product of two blocks on the policy's multiplier at a named site."""
        a, b, exps = self._record(a, b, site, "mul")
        if self.family == "f32":
            return a * b
        if self.family == "bf16":
            return (a.astype(jnp.bfloat16) * b.astype(jnp.bfloat16)).astype(jnp.float32)
        if self.family == "fixed":
            e, m = self.prec.fixed_em
            return quantize_em(quantize_em(a, e, m) * quantize_em(b, e, m), e, m)
        # "rr": per-block shared split (same-format rule), grown on demand by
        # construction and floored at the carried adjust-unit split. Under
        # cfg.pinned the carried split IS the split (static profiled
        # deployment — no live widen), mirroring the reference plane.
        k_min = self._k_floor_at(site)
        if self.prec.pinned and k_min is not None:
            return rr_mul_block(
                a, b, self.prec.fmt, self.prec.tail_approx, exps=exps, k_fixed=k_min
            )
        return rr_mul_block(a, b, self.prec.fmt, self.prec.tail_approx, exps=exps, k_min=k_min)

    def _alu(self, a, b, site: str, op: str, substrate, rr_block):
        """Shared family dispatch for the repro.alu ops (add/div/rsqrt):
        same structure as :meth:`mul`, with the rr family routed through the
        op's own blockops primitive (per-op exponent envelope, no tail
        truncation — adder/divider datapaths drop no partial products)."""
        a, b, exps = self._record(a, b, site, op)
        if self.family == "f32":
            return substrate(a, b)
        if self.family == "bf16":
            return substrate(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16)).astype(
                jnp.float32
            )
        if self.family == "fixed":
            e, m = self.prec.fixed_em
            return quantize_em(substrate(quantize_em(a, e, m), quantize_em(b, e, m)), e, m)
        k_min = self._k_floor_at(site)
        if self.prec.pinned and k_min is not None:
            return rr_block(a, b, self.prec.fmt, exps=exps, k_fixed=k_min)
        return rr_block(a, b, self.prec.fmt, exps=exps, k_min=k_min)

    def add(self, a, b, site: str):
        """Sum of two blocks on the policy's flexible adder at a named site
        (alignment-shift evidence law)."""
        return self._alu(a, b, site, "add", lambda x, y: x + y, rr_add_block)

    def div(self, a, b, site: str):
        """Quotient of two blocks on the policy's flexible divider at a
        named site (quotient-range evidence law)."""
        return self._alu(a, b, site, "div", lambda x, y: x / y, rr_div_block)

    def rsqrt(self, x, site: str):
        """Reciprocal square root of one block on the policy's datapath at a
        named site; the unary evidence is the operand exponent doubled."""
        return self._alu(
            x,
            x,
            site,
            "rsqrt",
            lambda v, _w: jax.lax.rsqrt(v),
            lambda a, b, fmt, **kw: rr_rsqrt_block(a, fmt, **kw),
        )


def _sweep_kernel(
    *refs, body, prec, sites, site_ops, steps, n_state, n_out, collect, capture,
    has_floor, extent, packed,
):
    if packed:
        # packed storage: payload + per-leaf storage split arrive instead of
        # f32 state; the prologue decodes in-VMEM (DESIGN.md §13)
        pay_refs = refs[:n_state]
        ks_refs = refs[n_state : 2 * n_state]
        pos = 2 * n_state
    else:
        state_refs = refs[:n_state]
        pos = n_state
    n_sites = len(sites)
    k_floor = None
    if has_floor:
        row = refs[pos][...]  # (1, n_sites) int32
        k_floor = tuple(lane(row, j) for j in range(n_sites))
        pos += 1
    if packed:
        out_refs = refs[pos : pos + n_out]
        kout_refs = refs[pos + n_out : pos + 2 * n_out]
        pos += 2 * n_out
    else:
        out_refs = refs[pos : pos + n_out]
        pos += n_out
    ev_ref = cnt_ref = None
    if collect:
        ev_ref = refs[pos]
        pos += 1
    if capture is not None:
        cnt_ref = refs[pos]

    if packed:
        # prologue: unpack each leaf at its carried storage split
        state = tuple(
            unpack_block(pr[...], prec.fmt, kr[...])
            for pr, kr in zip(pay_refs, ks_refs)
        )
    else:
        state = tuple(r[...] for r in state_refs)
    # counts carried functionally through the substep loop, written once;
    # evidence is stored one full (n_sites, 2) row per substep
    cnt0 = jnp.zeros(
        (2 * n_sites, capture.n_bins) if capture is not None else (1,), jnp.int32
    )

    # valid-lane masks for capture on padded grids: this block's global row/
    # col positions vs the unpadded extents (static), so pad lanes never count
    valid = None
    if capture is not None and extent is not None:
        rows, width = extent
        br, bw = state_refs[0].shape
        row_ok = col_ok = None
        if rows is not None:
            pos = pl.program_id(0) * br + jax.lax.broadcasted_iota(jnp.int32, (br, 1), 0)
            row_ok = pos < rows
        if width is not None:
            pos = pl.program_id(1) * bw + jax.lax.broadcasted_iota(jnp.int32, (1, bw), 1)
            col_ok = pos < width
        valid = (row_ok, col_ok, br, bw)

    def substep(s, carry):
        st, cnt = carry
        ops = FusedOps(
            prec, sites, k_floor=k_floor, collect=collect, capture=capture,
            valid=valid, site_ops=site_ops,
        )
        new = body(st, ops)
        if not isinstance(new, tuple):
            new = (new,)
        if len(new) != n_out:
            raise ValueError(
                f"fused body returned {len(new)} leaves but the sweep was "
                f"declared with n_out={n_out}"
            )
        if collect:
            missing = [n for n in sites if n not in ops.evidence]
            if missing:
                raise ValueError(f"fused body never multiplied at sites {missing}")
            ev_ref[0, 0, pl.ds(s, 1)] = evidence_row(ops)[None]
        if capture is not None:
            # the widened evidence: substep counts accumulate over the chunk
            cnt = cnt + jnp.concatenate([ops.counts[name] for name in sites], axis=0)
        return new, cnt

    if steps == 1:
        # single-substep bodies (e.g. an elementwise flux) may return fewer
        # leaves than they take — no loop carry to keep structurally stable
        state, cnt = substep(0, (state, cnt0))
    else:
        if n_out != n_state:
            raise ValueError(
                f"multi-substep sweeps need body in/out leaf counts to match "
                f"({n_state} != {n_out}): the output is the next substep's input"
            )
        state, cnt = jax.lax.fori_loop(0, steps, substep, (state, cnt0))
    if packed:
        # epilogue: re-pick each leaf's storage split from the advanced
        # values and encode — identical math to repro.pack's XLA-boundary
        # pack (shared helpers), so in-kernel packing can never disagree
        for pr, kr, v in zip(out_refs, kout_refs, state):
            k_st = block_storage_k(v, prec.fmt)
            pr[...] = pack_block(v, prec.fmt, k_st).astype(payload_dtype(prec.fmt))
            kr[...] = k_st.astype(jnp.int32)
    else:
        for r, v in zip(out_refs, state):
            r[...] = v
    if capture is not None:
        cnt_ref[...] = cnt[None, None]  # (1, 1, 2 * n_sites, n_bins) block


def fused_sweep(
    body: Callable,
    state: Sequence,
    *,
    prec,
    sites: Tuple[str, ...],
    site_ops: Optional[Tuple[str, ...]] = None,
    steps: int = 1,
    block: Tuple[int, int],
    n_out: Optional[int] = None,
    pad_values: Optional[Sequence[float]] = None,
    k_floor=None,
    collect_evidence: bool = False,
    capture=None,
    interpret: Optional[bool] = None,
    storage: str = "f32",
):
    """Run ``steps`` substeps of ``body`` over blocked state in ONE
    ``pallas_call``.

    Arguments:
      body: ``body(state_blocks, ops) -> out_blocks`` — pure function of
        VMEM blocks; every policy multiplication through ``ops.mul``.
      state: 2-D ``(rows, width)`` f32 leaves, all the same shape.
      prec: the (static, hashable) :class:`PrecisionConfig`.
      sites: the workload's named multiplication sites, in body call order.
      steps: substeps fused into the kernel's ``fori_loop``.
      block: ``(block_rows, block_width)``; clamped to the state shape.
        Sweep bodies (stencil coupling along width) must keep
        ``block_width >= width`` so the coupled extent stays whole in-block.
      n_out: number of leaves ``body`` returns (default: ``len(state)``).
      pad_values: per-leaf constants used when rows/width don't divide the
        clamped block (default 0.0) — pick values that can't dominate a
        mixed block's max-exponent reduction (e.g. 1.0 for a divisor field).
      k_floor: ``(n_sites,) int32`` carried tracker splits; floors the rr
        family's per-block selection (tracked modes).
      collect_evidence: also return the per-substep per-site operand
        max-exponent evidence, cross-block maxed: ``(steps, n_sites, 2)``.
      capture: a :class:`repro.profile.capture.CaptureSpec` widens the
        evidence stream to binned counts — every policy multiplication's
        elementwise operand exponents are histogrammed in-VMEM and the
        per-block counts summed across blocks and substeps, giving
        ``(n_sites, 2, n_bins) int32`` for the whole chunk. Implies
        ``collect_evidence`` (the profile consumes both). Pad lanes are
        masked out of the counts (zero pads by the zero-exponent
        convention, non-zero pads by the in-kernel valid-lane mask), so a
        padded grid profiles identically to the reference plane.
      site_ops: per-site op declarations (``"mul"``/``"add"``/``"div"``/
        ``"rsqrt"``) — when given, a body calling the wrong ``ops`` method
        at a site fails at trace time.
      storage: ``"f32"`` (default) moves f32 state through HBM; ``"packed"``
        takes :class:`repro.pack.PackedArray` leaves instead, decodes them
        in the kernel prologue, and re-packs the advanced state in the
        epilogue at a freshly-picked per-leaf storage split — HBM traffic
        at ``fmt.total_bits`` instead of 32 (the fusion-boundary rule,
        DESIGN.md §13). Requires the block to cover the whole field (one
        storage block == one sweep block) and ``n_out == n_state``.

    Returns ``(out_leaves_tuple, evidence_or_None)``, plus a trailing
    ``counts`` element when ``capture`` is set. Under ``storage="packed"``
    the out leaves are PackedArrays carrying the input leaves' geometry.
    """
    interpret = resolve_interpret(interpret)
    collect_evidence = bool(collect_evidence) or capture is not None
    if storage not in ("f32", "packed"):
        raise ValueError(f"unknown fused storage {storage!r}; 'f32' | 'packed'")
    packed = storage == "packed"
    n_sites = len(sites)
    if site_ops is not None:
        site_ops = tuple(site_ops)
        if len(site_ops) != n_sites:
            raise ValueError(
                f"site_ops covers {len(site_ops)} entries for {n_sites} sites"
            )

    if packed:
        pas = list(state)
        for pa in pas:
            if not isinstance(pa, PackedArray):
                raise TypeError(
                    "storage='packed' takes repro.pack.PackedArray leaves; "
                    f"got {type(pa).__name__}"
                )
            if pa.fmt != prec.fmt:
                raise ValueError(
                    f"packed leaf format {pa.fmt} disagrees with the policy "
                    f"format {prec.fmt}"
                )
        leaves = [pa.payload for pa in pas]
        rows, width = leaves[0].shape
    else:
        leaves = [jnp.asarray(x, jnp.float32) for x in state]
        rows, width = leaves[0].shape
    for x in leaves[1:]:
        if x.shape != (rows, width):
            raise ValueError(f"state leaves disagree: {x.shape} vs {(rows, width)}")
    n_state = len(leaves)
    n_out = n_state if n_out is None else n_out

    br = min(block[0], rows)
    bw = min(block[1], width)
    pr, pw = -rows % br, -width % bw
    if packed:
        if (br, bw) != (rows, width):
            raise ValueError(
                "in-kernel packed storage requires the sweep block to cover "
                f"the whole field: block {(br, bw)} vs state {(rows, width)} "
                "(one storage block per leaf)"
            )
        if n_out != n_state:
            raise ValueError(
                "in-kernel packed storage needs body in/out leaf counts to "
                f"match ({n_state} != {n_out}): every out leaf re-packs"
            )
        for pa in pas:
            if tuple(pa.k.shape[-2:]) != (1, 1):
                raise ValueError(
                    "in-kernel packed storage takes single-block PackedArrays "
                    f"(one split per leaf); got k of shape {tuple(pa.k.shape)}"
                )
        pr = pw = 0
    if pr or pw:
        pv = tuple(pad_values) if pad_values is not None else (0.0,) * n_state
        leaves = [
            jnp.pad(x, ((0, pr), (0, pw)), constant_values=v)
            for x, v in zip(leaves, pv)
        ]
    rp, wp = rows + pr, width + pw
    gi, gj = rp // br, wp // bw

    state_spec = pl.BlockSpec((br, bw), lambda i, j: (i, j))
    scalar_spec = pl.BlockSpec((1, 1), lambda i, j: (0, 0))
    in_specs = [state_spec] * n_state
    inputs = list(leaves)
    if packed:
        in_specs += [scalar_spec] * n_state
        inputs += [jnp.reshape(pa.k, (1, 1)).astype(jnp.int32) for pa in pas]
    if k_floor is not None:
        in_specs.append(pl.BlockSpec((1, n_sites), lambda i, j: (0, 0)))
        inputs.append(jnp.asarray(k_floor, jnp.int32).reshape(1, n_sites))
    out_specs = [state_spec] * n_out
    if packed:
        pdt = payload_dtype(prec.fmt)
        out_shape = [jax.ShapeDtypeStruct((rp, wp), pdt)] * n_out
        out_specs += [scalar_spec] * n_out
        out_shape += [jax.ShapeDtypeStruct((1, 1), jnp.int32)] * n_out
    else:
        out_shape = [jax.ShapeDtypeStruct((rp, wp), jnp.float32)] * n_out
    if collect_evidence:
        out_specs.append(
            pl.BlockSpec((1, 1, steps, n_sites, 2), lambda i, j: (i, j, 0, 0, 0))
        )
        out_shape.append(jax.ShapeDtypeStruct((gi, gj, steps, n_sites, 2), jnp.float32))
    if capture is not None:
        nb = capture.n_bins
        out_specs.append(
            pl.BlockSpec((1, 1, 2 * n_sites, nb), lambda i, j: (i, j, 0, 0))
        )
        out_shape.append(jax.ShapeDtypeStruct((gi, gj, 2 * n_sites, nb), jnp.int32))

    call = pl.pallas_call(
        functools.partial(
            _sweep_kernel,
            body=body,
            prec=prec,
            sites=tuple(sites),
            site_ops=site_ops,
            steps=steps,
            n_state=n_state,
            n_out=n_out,
            collect=collect_evidence,
            capture=capture,
            has_floor=k_floor is not None,
            extent=(rows if pr else None, width if pw else None) if (pr or pw) else None,
            packed=packed,
        ),
        grid=(gi, gj),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )
    outs = call(*inputs)

    outs = list(outs)
    counts = None
    if capture is not None:
        # global counts = sum of per-block counts (blocks partition elements)
        counts = jnp.sum(outs.pop(), axis=(0, 1), dtype=jnp.int32)
        counts = counts.reshape(n_sites, 2, capture.n_bins)
    evidence = None
    if collect_evidence:
        # the global per-substep site evidence is the max over blocks (max of
        # block maxes); padded-only blocks contribute their pad constants'
        # exponents, which the pad_values contract keeps dominated
        evidence = jnp.max(outs.pop(), axis=(0, 1))
    if packed:
        # reassemble PackedArrays around the epilogue's (payload, split)
        # pairs, carrying each input leaf's logical geometry forward
        k_outs = outs[n_out:]
        outs = [
            PackedArray(p, jnp.reshape(kk, pa.k.shape), pa.fmt, pa.shape, pa.block)
            for p, kk, pa in zip(outs[:n_out], k_outs, pas)
        ]
    elif pr or pw:
        outs = [o[:rows, :width] for o in outs]
    if capture is not None:
        return tuple(outs), evidence, counts
    return tuple(outs), evidence
