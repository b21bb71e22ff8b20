"""Whole-horizon megakernel: the fused plane without the chunking
(DESIGN.md §14).

:func:`repro.kernels.fused.fused_sweep` runs one snapshot interval per
``pallas_call`` and hands the per-substep range evidence back to the host,
where ``fold_evidence`` replays it through the adjust unit between chunks —
a kernel launch plus an HBM round trip per interval. :func:`mega_sweep`
removes both: the ENTIRE horizon (``steps`` substeps, snapshots included)
runs in ONE ``pallas_call``, and the adjust unit itself moves on-chip. The
carried tracker state (per-site k, hi/lo EMAs, §5.3 counters) lives in
registers/SMEM and evolves every substep through the jax-pure scalar law
:func:`repro.core.policy.adjust_step` — the paper's hardware unit sitting
next to the multiplier, not a host callback. Snapshots, per-substep
evidence, and capture histograms stream out as secondary outputs written at
their cadence (``pl.when`` + dynamic-slice stores at snapshot boundaries),
so the state never round-trips HBM mid-horizon.

Semantics contract with the chunked plane (what the parity suite pins):

* Untracked modes (f32 / bf16 / fixed / rr_tile) and ``deploy`` are
  **bit-exact** against chunked-fused: same :class:`FusedOps` arithmetic,
  same whole-field blocks, same boundary storage rounding.
* ``rr_tracked``: the tracker evolves per substep on-chip, but the
  *datapath* floor latches at snapshot boundaries — exactly the cadence at
  which the chunked plane folds evidence and re-enters the kernel with the
  updated k. The arithmetic is therefore bit-identical, and the final
  per-site k and §5.3 grow/shrink counters match the chunked fold exactly.
* Storage: ``"quantized"``/``"packed"`` round the state at every snapshot
  boundary in-kernel with the shared :func:`repro.pack.packed` block
  helpers — one (virtual) pack per boundary, same splits, same bits as the
  chunked boundary pack. Packed-io steppers encode/decode payloads in the
  kernel prologue/epilogue so packed state never materialises f32 in HBM;
  other steppers get the carried storage split streamed out (``kst``) so
  the host-side final pack reuses the in-kernel split instead of re-picking
  one from already-quantized values (which could disagree at power-of-two
  rounding edges).

Eligibility: whole-field-in-VMEM workloads only — the megakernel keeps one
block per leaf, so a stepper whose chunked kernels tile the field (and thus
pick per-tile splits) must gate itself out via ``mega_supported``.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.flexformat import quantize_em
from repro.core.policy import RangeTracker, adjust_step
from repro.kernels.fused import FusedOps, evidence_row, lane, resolve_interpret, row_of
from repro.pack.packed import (
    PackedArray,
    _view2d,
    block_storage_k,
    pack_block,
    payload_dtype,
    unpack_block,
)

__all__ = [
    "MegaResult",
    "mega_sweep",
    "heat1d_mega",
    "heat2d_mega",
    "advection1d_mega",
    "burgers1d_mega",
    "swe2d_mega",
    "swe_sphere_mega",
]


class MegaResult(NamedTuple):
    """Everything one whole-horizon kernel call produces."""

    state: Tuple  # advanced leaves (PackedArray leaves under storage="packed")
    snaps: Tuple  # per-leaf (n_out, *leaf.shape) f32 boundary snapshots
    tracker: Optional[RangeTracker]  # on-chip-evolved adjust-unit state
    evidence: Optional[jnp.ndarray]  # (steps, n_sites, 2) f32, when requested
    exp_time: Optional[jnp.ndarray]  # (n_out, n_sites, 2, n_bins) i32, capture
    exp_total: Optional[jnp.ndarray]  # (n_sites, 2, n_bins) i32, capture


def _mega_kernel(
    *refs,
    body,
    prec,
    sites,
    site_ops,
    steps,
    every,
    n_out,
    n_state,
    evolve,
    has_floor,
    emit_ev,
    capture,
    storage,
    packed_io,
    n_const,
):
    fmt = prec.fmt
    n_sites = len(sites)
    rounding = storage != "f32"

    # ---- input refs ------------------------------------------------------
    pos = 0
    if packed_io:
        pay_refs = refs[pos : pos + n_state]
        ks_refs = refs[pos + n_state : pos + 2 * n_state]
        pos += 2 * n_state
        state = tuple(
            unpack_block(pr[...], fmt, kr[...])
            for pr, kr in zip(pay_refs, ks_refs)
        )
    else:
        state = tuple(r[...] for r in refs[pos : pos + n_state])
        pos += n_state
    trk0 = ()
    k_active = None

    def cols(ref):
        row = ref[...]  # (1, n_sites)
        return tuple(lane(row, j) for j in range(n_sites))

    if evolve:
        # the adjust unit's carried state — per site, one (1, 1) vector per
        # field: (k, hi_ema, lo_ema, overflow_steps, shrink_steps)
        trk0 = tuple(cols(refs[pos + i]) for i in range(5))
        pos += 5
        k_active = trk0[0]  # datapath floor, latched at snapshot boundaries
    elif has_floor:
        k_active = cols(refs[pos])  # pinned: static profiled splits
        pos += 1
    const_refs = refs[pos : pos + n_const]
    pos += n_const

    # ---- output refs -----------------------------------------------------
    out_refs = refs[pos : pos + n_state]
    pos += n_state
    kout_refs = kst_refs = None
    if packed_io:
        kout_refs = refs[pos : pos + n_state]
        pos += n_state
    elif storage == "packed":
        kst_refs = refs[pos : pos + n_state]
        pos += n_state
    snap_refs = ()
    if n_out > 0:
        snap_refs = refs[pos : pos + n_state]
        pos += n_state
    trk_out = ()
    if evolve:
        trk_out = refs[pos : pos + 5]
        pos += 5
    ev_ref = cnt_ref = time_ref = None
    if emit_ev:
        ev_ref = refs[pos]
        pos += 1
    if capture is not None:
        cnt_ref = refs[pos]
        pos += 1
        if n_out > 0:
            time_ref = refs[pos]

    collect = evolve or emit_ev

    def _round_all(st):
        """Boundary storage rounding: the chunked plane's pack/unpack on the
        raw values, via the shared block helpers (same splits, same bits)."""
        qs, ks = [], []
        for v in st:
            # quantize with the split at the leaf's rank (a (1, 1) split
            # broadcast over a rank-3 leaf is a layout the TPU kernel
            # compiler refuses); carry it as (1, 1), folding leading axes
            kb = block_storage_k(v, fmt).astype(jnp.int32)
            qs.append(quantize_em(v, fmt.eb + kb, fmt.mb + fmt.fx - kb))
            ks.append(jnp.max(kb, axis=tuple(range(kb.ndim - 2))) if kb.ndim > 2 else kb)
        return tuple(qs), tuple(ks)

    cnt0 = jnp.zeros(
        (2 * n_sites, capture.n_bins) if capture is not None else (1,), jnp.int32
    )
    kst0 = tuple(jnp.zeros((1, 1), jnp.int32) for _ in state)
    ka0 = k_active if evolve else ()

    def substep(s, carry):
        st, trk, ka, cnt, cnt_last, kst = carry
        floor = (ka if evolve else k_active) if (evolve or has_floor) else None
        ops = FusedOps(
            prec, sites, k_floor=floor, collect=collect, capture=capture,
            site_ops=site_ops,
        )
        # read-only grid fields load every substep (cheap VMEM reads) rather
        # than living across the loop
        new = body(st, ops, tuple(r[...] for r in const_refs)) if n_const else body(st, ops)
        if not isinstance(new, tuple):
            new = (new,)
        if len(new) != n_state:
            raise ValueError(
                f"mega body returned {len(new)} leaves for {n_state} state "
                "leaves: the output is the next substep's input"
            )
        if collect:
            missing = [n for n in sites if n not in ops.evidence]
            if missing:
                raise ValueError(f"mega body never hit sites {missing}")
        if evolve:
            # the on-chip adjust unit: one tick per site, this substep
            k_a, hi_a, lo_a, ov_a, sh_a = trk
            rows = []
            for j, name in enumerate(sites):
                ae, be = ops.evidence[name]
                op = "mul" if site_ops is None else site_ops[j]
                kb = None if prec.k_bounds is None else prec.k_bounds[j]
                rows.append(
                    adjust_step(
                        k_a[j], hi_a[j], lo_a[j], ov_a[j], sh_a[j],
                        ae, be, prec, op, k_bounds=kb,
                    )
                )
            trk = tuple(zip(*rows))
        if emit_ev:
            ev_ref[pl.ds(s, 1)] = evidence_row(ops)[None]
        if capture is not None:
            cnt = cnt + jnp.concatenate([ops.counts[name] for name in sites], axis=0)

        boundary = ((s + 1) % every) == 0
        if rounding:
            qs, ks = _round_all(new)
            new = tuple(jnp.where(boundary, q, v) for q, v in zip(qs, new))
            kst = tuple(jnp.where(boundary, k, k0) for k, k0 in zip(ks, kst))
        if evolve:
            # latch the datapath floor at the chunk cadence — the substeps
            # between boundaries run at the same splits the chunked plane's
            # between-chunk fold would hand the next kernel call
            ka = tuple(jnp.where(boundary, k, k0) for k, k0 in zip(trk[0], ka))
        if n_out > 0:
            idx = (s + 1) // every - 1

            @pl.when(boundary)
            def _store():
                for r, v in zip(snap_refs, new):
                    r[pl.ds(idx, 1)] = v[None].astype(jnp.float32)
                if time_ref is not None:
                    time_ref[pl.ds(idx, 1)] = (cnt - cnt_last)[None]

            if capture is not None:
                cnt_last = jnp.where(boundary, cnt, cnt_last)
        return new, trk, ka, cnt, cnt_last, kst

    carry = (state, trk0, ka0, cnt0, cnt0, kst0)
    state, trk, _ka, cnt, _cl, kst = jax.lax.fori_loop(0, steps, substep, carry)

    rem = steps - n_out * every
    if rem and rounding:
        # the remainder epilogue: same boundary law as the in-loop cadence
        state, kst = _round_all(state)

    if packed_io:
        for i, (pr, kr) in enumerate(zip(out_refs, kout_refs)):
            # idempotent re-encode: the state is already quantized at kst, so
            # packing at the SAME carried split reproduces the chunked
            # plane's pack-from-raw bits exactly
            pr[...] = pack_block(state[i], fmt, kst[i]).astype(payload_dtype(fmt))
            kr[...] = kst[i]
    else:
        for r, v in zip(out_refs, state):
            r[...] = v
        if kst_refs is not None:
            for r, k in zip(kst_refs, kst):
                r[...] = k
    if evolve:
        for r, v in zip(trk_out, trk):
            r[...] = row_of(v, r.dtype)
    if capture is not None:
        cnt_ref[...] = cnt


def mega_sweep(
    body: Callable,
    state: Sequence,
    *,
    prec,
    sites: Tuple[str, ...],
    site_ops: Optional[Tuple[str, ...]] = None,
    steps: int,
    every: int,
    tracker: Optional[RangeTracker] = None,
    collect_evidence: bool = False,
    capture=None,
    interpret: Optional[bool] = None,
    storage: str = "f32",
    consts: Sequence = (),
) -> MegaResult:
    """Run an ENTIRE simulation horizon — ``steps`` substeps with snapshots
    every ``every`` — in one ``pallas_call``.

    Arguments mirror :func:`repro.kernels.fused.fused_sweep` where shared:

      body: ``body(state_leaves, ops) -> out_leaves`` over whole-field
        values (any rank — the megakernel keeps one block per leaf).
      state: the leaves. :class:`repro.pack.PackedArray` leaves (requires
        ``storage="packed"``) ride packed io: decoded in the kernel
        prologue, re-encoded in its epilogue, never f32 in HBM. Plain f32
        leaves under ``storage="packed"`` run the host-pack path: the
        kernel quantizes at boundaries and streams out the carried storage
        split ``kst``; the final pack happens here at that split.
      tracker: a :class:`repro.core.policy.RangeTracker` (site order =
        ``sites``). Non-pinned policies evolve it ON-CHIP per substep via
        :func:`repro.core.policy.adjust_step`; pinned policies use its k
        rows as the static datapath splits. None: untracked.
      every: snapshot cadence; ``steps // every`` boundary snapshots (and
        boundary storage roundings) happen inside the kernel.
      consts: read-only f32 fields of the grid (metric factors, a
        topography's gradients). They are loaded into the kernel, handed
        to ``body(state_leaves, ops, consts)`` every substep, and never
        rounded, snapshotted or returned. Under ``vmap`` an unbatched
        field is shared by every member. Empty: ``body(state_leaves, ops)``.

    Returns a :class:`MegaResult`. ``evidence`` is populated when
    ``collect_evidence`` or ``capture`` asks for it (the tracker fold no
    longer needs it — that happens on-chip); ``exp_time``/``exp_total`` are
    the capture profile's interval/total histograms.
    """
    interpret = resolve_interpret(interpret)
    if storage not in ("f32", "quantized", "packed"):
        raise ValueError(f"unknown mega storage {storage!r}")
    n_sites = len(sites)
    if site_ops is not None:
        site_ops = tuple(site_ops)
        if len(site_ops) != n_sites:
            raise ValueError(
                f"site_ops covers {len(site_ops)} entries for {n_sites} sites"
            )
    emit_ev = bool(collect_evidence) or capture is not None
    evolve = tracker is not None and not prec.pinned
    has_floor = tracker is not None and prec.pinned
    n_out = steps // every

    packed_io = any(isinstance(x, PackedArray) for x in state)
    if packed_io:
        if storage != "packed":
            raise ValueError("PackedArray leaves require storage='packed'")
        pas = list(state)
        for pa in pas:
            if not isinstance(pa, PackedArray):
                raise TypeError("mixed packed/f32 state leaves")
            if pa.fmt != prec.fmt:
                raise ValueError(
                    f"packed leaf format {pa.fmt} disagrees with the policy "
                    f"format {prec.fmt}"
                )
            if tuple(pa.k.shape[-2:]) != (1, 1):
                raise ValueError(
                    "megakernel packed io takes single-block PackedArrays; "
                    f"got k of shape {tuple(pa.k.shape)}"
                )
        leaves = [pa.payload for pa in pas]
    else:
        leaves = [jnp.asarray(x, jnp.float32) for x in state]
    n_state = len(leaves)
    shapes = [tuple(x.shape) for x in leaves]

    inputs = list(leaves)
    if packed_io:
        inputs += [jnp.reshape(pa.k, (1, 1)).astype(jnp.int32) for pa in pas]
    if evolve:
        inputs += [
            jnp.asarray(tracker.k, jnp.int32).reshape(1, n_sites),
            jnp.asarray(tracker.hi_ema, jnp.float32).reshape(1, n_sites),
            jnp.asarray(tracker.lo_ema, jnp.float32).reshape(1, n_sites),
            jnp.asarray(tracker.overflow_steps, jnp.int32).reshape(1, n_sites),
            jnp.asarray(tracker.shrink_steps, jnp.int32).reshape(1, n_sites),
        ]
    elif has_floor:
        inputs.append(jnp.asarray(tracker.k, jnp.int32).reshape(1, n_sites))
    inputs += [jnp.asarray(c, jnp.float32) for c in consts]

    out_shape = []
    if packed_io:
        pdt = payload_dtype(prec.fmt)
        out_shape += [jax.ShapeDtypeStruct(s, pdt) for s in shapes]
        out_shape += [jax.ShapeDtypeStruct((1, 1), jnp.int32)] * n_state
    else:
        out_shape += [jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes]
        if storage == "packed":
            out_shape += [jax.ShapeDtypeStruct((1, 1), jnp.int32)] * n_state
    if n_out > 0:
        out_shape += [jax.ShapeDtypeStruct((n_out,) + s, jnp.float32) for s in shapes]
    if evolve:
        out_shape += [
            jax.ShapeDtypeStruct((1, n_sites), jnp.int32),
            jax.ShapeDtypeStruct((1, n_sites), jnp.float32),
            jax.ShapeDtypeStruct((1, n_sites), jnp.float32),
            jax.ShapeDtypeStruct((1, n_sites), jnp.int32),
            jax.ShapeDtypeStruct((1, n_sites), jnp.int32),
        ]
    if emit_ev:
        out_shape.append(jax.ShapeDtypeStruct((steps, n_sites, 2), jnp.float32))
    if capture is not None:
        nb = capture.n_bins
        # counts ride the kernel as (2 * n_sites, n_bins) rows
        out_shape.append(jax.ShapeDtypeStruct((2 * n_sites, nb), jnp.int32))
        if n_out > 0:
            out_shape.append(jax.ShapeDtypeStruct((n_out, 2 * n_sites, nb), jnp.int32))

    call = (
        pl.pallas_call(
            functools.partial(
                _mega_kernel,
                body=body,
                prec=prec,
                sites=tuple(sites),
                site_ops=site_ops,
                steps=steps,
                every=every,
                n_out=n_out,
                n_state=n_state,
                evolve=evolve,
                has_floor=has_floor,
                emit_ev=emit_ev,
                capture=capture,
                storage=storage,
                packed_io=packed_io,
                n_const=len(consts),
            ),
            out_shape=tuple(out_shape),
            interpret=interpret,
        )
    )
    outs = list(call(*inputs))

    # ---- unpack the flat output list -------------------------------------
    time_cnt = outs.pop() if (capture is not None and n_out > 0) else None
    total_cnt = outs.pop() if capture is not None else None
    evidence = outs.pop() if emit_ev else None
    tracker_out = tracker
    if evolve:
        sh = outs.pop()[0]
        ov = outs.pop()[0]
        lo = outs.pop()[0]
        hi = outs.pop()[0]
        k = outs.pop()[0]
        tracker_out = RangeTracker(
            hi_ema=hi, lo_ema=lo, k=k, overflow_steps=ov, shrink_steps=sh
        )
    snaps = tuple(
        jnp.zeros((0,) + s, jnp.float32) for s in shapes
    )
    if n_out > 0:
        snaps = tuple(outs[-n_state:])
        del outs[-n_state:]
    if packed_io:
        kouts = outs[n_state : 2 * n_state]
        final = tuple(
            PackedArray(p, jnp.reshape(kk, pa.k.shape), pa.fmt, pa.shape, pa.block)
            for p, kk, pa in zip(outs[:n_state], kouts, pas)
        )
    elif storage == "packed":
        kst = [k.reshape(()) for k in outs[n_state : 2 * n_state]]
        final = []
        for i, q in enumerate(outs[:n_state]):
            view = _view2d(shapes[i])
            payload = pack_block(q.reshape(view), prec.fmt, kst[i])
            final.append(
                PackedArray(
                    payload.astype(payload_dtype(prec.fmt)),
                    jnp.reshape(kst[i], (1, 1)),
                    prec.fmt,
                    shapes[i],
                    view,
                )
            )
        final = tuple(final)
    else:
        final = tuple(outs[:n_state])

    exp_time = exp_total = None
    if capture is not None:
        exp_total = total_cnt.reshape(n_sites, 2, capture.n_bins)
        exp_time = (
            time_cnt.reshape(n_out, n_sites, 2, capture.n_bins)
            if time_cnt is not None
            else jnp.zeros((0, n_sites, 2, capture.n_bins), jnp.int32)
        )
    return MegaResult(final, snaps, tracker_out, evidence, exp_time, exp_total)


# ---------------------------------------------------------------------------
# per-stepper whole-horizon entries (the steppers' mega_step hooks)
# ---------------------------------------------------------------------------

_MEGA_STATICS = (
    "prec", "steps", "every", "sites", "collect_evidence", "capture",
    "interpret", "storage",
)


def _single_leaf(res: MegaResult, unwrap, snap_shape) -> MegaResult:
    """Re-view a single-leaf MegaResult into the stepper's natural shapes."""
    (out,) = res.state
    (snaps,) = res.snaps
    return res._replace(
        state=unwrap(out), snaps=snaps.reshape((snaps.shape[0],) + snap_shape)
    )


@functools.partial(jax.jit, static_argnames=_MEGA_STATICS + ("alpha", "dtodx2"))
def heat1d_mega(
    u0, *, alpha, dtodx2, prec, steps, every, sites, tracker=None,
    collect_evidence=False, capture=None, interpret=None, storage="f32",
):
    """Whole-horizon 1-D heat sweep; ``u0`` is the (nx,) rod (PackedArray
    under packed storage)."""
    from repro.kernels.heat_stencil import _heat1d_body

    packed = isinstance(u0, PackedArray)
    nx = u0.shape[-1]
    lead = u0.with_view((1, nx)) if packed else jnp.asarray(u0, jnp.float32)[None, :]
    res = mega_sweep(
        _heat1d_body(float(alpha), float(dtodx2), sites),
        (lead,),
        prec=prec, sites=sites, steps=steps, every=every, tracker=tracker,
        collect_evidence=collect_evidence, capture=capture, interpret=interpret,
        storage=storage,
    )
    unwrap = (lambda o: o.with_view((nx,))) if packed else (lambda o: o[0])
    return _single_leaf(res, unwrap, (nx,))


@functools.partial(jax.jit, static_argnames=_MEGA_STATICS + ("alpha", "dtodx2"))
def heat2d_mega(
    u0, *, alpha, dtodx2, prec, steps, every, sites, tracker=None,
    collect_evidence=False, capture=None, interpret=None, storage="f32",
):
    """Whole-horizon 2-D heat sweep; ``u0`` is the (nx, ny) field."""
    from repro.kernels.pde_steps import _heat2d_body

    lead = u0 if isinstance(u0, PackedArray) else jnp.asarray(u0, jnp.float32)
    res = mega_sweep(
        _heat2d_body(float(alpha), float(dtodx2), sites),
        (lead,),
        prec=prec, sites=sites, steps=steps, every=every, tracker=tracker,
        collect_evidence=collect_evidence, capture=capture, interpret=interpret,
        storage=storage,
    )
    return _single_leaf(res, lambda o: o, tuple(u0.shape))


@functools.partial(jax.jit, static_argnames=_MEGA_STATICS + ("speed", "dtodx"))
def advection1d_mega(
    u0, *, speed, dtodx, prec, steps, every, sites, tracker=None,
    collect_evidence=False, capture=None, interpret=None, storage="f32",
):
    """Whole-horizon upwind advection sweep; ``u0`` is the (nx,) profile."""
    from repro.kernels.pde_steps import _advection1d_body

    packed = isinstance(u0, PackedArray)
    n = u0.shape[-1]
    lead = u0.with_view((1, n)) if packed else jnp.asarray(u0, jnp.float32)[None, :]
    res = mega_sweep(
        _advection1d_body(float(speed), float(dtodx), sites),
        (lead,),
        prec=prec, sites=sites, steps=steps, every=every, tracker=tracker,
        collect_evidence=collect_evidence, capture=capture, interpret=interpret,
        storage=storage,
    )
    unwrap = (lambda o: o.with_view((n,))) if packed else (lambda o: o[0])
    return _single_leaf(res, unwrap, (n,))


@functools.partial(jax.jit, static_argnames=_MEGA_STATICS + ("dt", "dx"))
def burgers1d_mega(
    u0, *, dt, dx, prec, steps, every, sites, tracker=None,
    collect_evidence=False, capture=None, interpret=None, storage="f32",
):
    """Whole-horizon Lax-Friedrichs Burgers sweep; ``u0`` is the (nx,) wave."""
    from repro.kernels.pde_steps import _burgers1d_body

    packed = isinstance(u0, PackedArray)
    n = u0.shape[-1]
    lead = u0.with_view((1, n)) if packed else jnp.asarray(u0, jnp.float32)[None, :]
    res = mega_sweep(
        _burgers1d_body(float(dt), float(dx), sites),
        (lead,),
        prec=prec, sites=sites, steps=steps, every=every, tracker=tracker,
        collect_evidence=collect_evidence, capture=capture, interpret=interpret,
        storage=storage,
    )
    unwrap = (lambda o: o.with_view((n,))) if packed else (lambda o: o[0])
    return _single_leaf(res, unwrap, (n,))


def _swe2d_body(cfg, sites):
    """One whole Richtmyer Lax-Wendroff update in-kernel: the substituted
    momentum-flux equation routes through the megakernel's :class:`FusedOps`
    (same sites, same op order as the chunked ``swe_flux_fused`` kernel);
    every other sub-equation stays f32 jnp, exactly as outside."""
    from repro.pde.swe2d import _lw_step, _momentum_flux

    def body(state, ops):
        (U,) = state
        U = _lw_step(U, cfg, lambda q1, q3: _momentum_flux(q1, q3, ops))
        return (U,)

    return body


@functools.partial(jax.jit, static_argnames=_MEGA_STATICS + ("cfg", "site_ops"))
def swe2d_mega(
    U0, *, cfg, prec, steps, every, sites, site_ops, tracker=None,
    collect_evidence=False, capture=None, interpret=None, storage="f32",
):
    """Whole-horizon shallow-water run; ``U0`` is the stacked (3, nx, ny)
    state. Packed storage takes the XLA-boundary shape the chunked plane
    uses (SWE has no packed-io kernel): a packed carry is decoded here, the
    kernel rounds at boundaries and streams the storage split out, and
    :func:`mega_sweep` re-packs the final state at that split."""
    from repro.pack.packed import unpack_array

    packed = isinstance(U0, PackedArray)
    lead = unpack_array(U0) if packed else jnp.asarray(U0, jnp.float32)
    res = mega_sweep(
        _swe2d_body(cfg, sites),
        (lead,),
        prec=prec, sites=sites, site_ops=site_ops, steps=steps, every=every,
        tracker=tracker, collect_evidence=collect_evidence, capture=capture,
        interpret=interpret, storage=storage,
    )
    (out,) = res.state
    (snaps,) = res.snaps
    return res._replace(state=out, snaps=snaps)


def _swe_sphere_body(cfg, sites):
    """One whole spherical Lax-Wendroff update in-kernel: the substituted
    zonal momentum flux on the megakernel's :class:`FusedOps`, every other
    sub-equation f32 jnp, the grid's metric and topography fields read from
    the kernel's read-only inputs."""
    from repro.pde.swe2d import _momentum_flux
    from repro.pde.swe_sphere import SphereGrid, _sphere_step

    def body(state, ops, consts):
        (U,) = state
        U = _sphere_step(
            U, cfg, SphereGrid(*consts),
            lambda q1, q3: _momentum_flux(q1, q3, ops, cfg.g, sites),
        )
        return (U,)

    return body


@functools.partial(jax.jit, static_argnames=_MEGA_STATICS + ("cfg", "site_ops"))
def swe_sphere_mega(
    U0, *, cfg, prec, steps, every, sites, site_ops, tracker=None,
    collect_evidence=False, capture=None, interpret=None, storage="f32",
):
    """Whole-horizon shallow-water run on the sphere; ``U0`` is the stacked
    (3, nlat, nlon) state with longitude on the lanes. The grid's fields
    enter as the kernel's read-only inputs, shared by every member under
    ``vmap``. Packed storage takes the XLA-boundary shape of
    :func:`swe2d_mega`."""
    from repro.pack.packed import unpack_array
    from repro.pde.swe_sphere import sphere_grid

    packed = isinstance(U0, PackedArray)
    lead = unpack_array(U0) if packed else jnp.asarray(U0, jnp.float32)
    res = mega_sweep(
        _swe_sphere_body(cfg, sites),
        (lead,),
        prec=prec, sites=sites, site_ops=site_ops, steps=steps, every=every,
        tracker=tracker, collect_evidence=collect_evidence, capture=capture,
        interpret=interpret, storage=storage, consts=tuple(sphere_grid(cfg)),
    )
    (out,) = res.state
    (snaps,) = res.snaps
    return res._replace(state=out, snaps=snaps)
