"""Continuous batching onto the fused ensembles, at chunk boundaries.

A :class:`Bucket` is one group of compatible requests (same
:class:`~repro.service.request.BucketKey`) advancing together through ONE
vmapped ``Simulation.run_ensemble`` call per chunk. The fused execution
plane (DESIGN.md §10) already runs whole snapshot intervals as single
Pallas kernel chunks; the bucket exploits exactly that seam:

* **chunk size is event-driven** — ``min`` over members of steps-to-next-
  event (own snapshot point or horizon), so no member is ever stepped past
  a point where a solo run would have paused. Members with heterogeneous
  cadences/horizons coexist; the bucket just pauses more often.
* **join/drain between chunks** — the member list is plain host state
  between chunks: finished requests drain out and queued compatible
  requests pack in. Because each member's carried :class:`SiteTracker`
  rows (split ``k``, EMAs, §5.3 adjustment counters) ride the stack with
  its state, repacking is *semantically invisible* — a member's trajectory
  is bit-identical to its solo ``Simulation.run`` (asserted per
  stepper/mode in ``tests/test_service.py``).
* **the resident batch** — the bucket keeps the chunk program's stacked
  outputs ``(state, tracker)`` on the device and hands them straight to
  the next chunk; members hold no copy of their own while they run. The
  batch is rebuilt (one host round trip: fetch, compose in NumPy, one
  ``device_put``) only when the membership changed since the last chunk
  or there is no batch yet, and a member is materialised on the host (its
  row of one fetch of the batch) only when it leaves — drain, eviction or
  failure, all through :meth:`Bucket.remove` — or when a consumer reads
  ``RequestRecord.state``/``.tracker`` while it runs. Snapshots come to
  the host in one transfer per chunk. The batch's width is the member
  count either way, so the compiled chunk program is the same one.
* **the next chunk runs ahead** — when the bucket is the only live one,
  nothing is queued and no member ends with the current chunk, its
  membership cannot change before the next one; that chunk is then
  dispatched on the current chunk's outputs before the host waits for
  them, so the device runs it while the host syncs, fetches and streams
  the current one, and the next pump finds it done. Chunk boundaries stay
  at the members' events, so joins, evictions and streaming are exactly as
  without it: a ``join`` or ``remove`` (drain, eviction, failure) drops
  the chunk run ahead, and the bucket runs its next chunk anew from the
  resident batch. Each dispatch also starts the host copies the chunk's
  end will need (its frames when a snapshot is due, its batch when a
  member drains).
* **compiled-chunk cache** — chunk programs are jitted once per
  ``(bucket key, chunk steps, member count)`` and reused across repacks, so
  steady-state traffic pays tracing cost only when the packing shape
  actually changes.

Why invisibility holds: a ``lax.scan`` over ``c1 + c2`` steps computes the
same op sequence as two scans of ``c1`` then ``c2`` (no cross-iteration
reassociation), vmapped elementwise/stencil arithmetic is per-lane
identical to the solo program, and snapshots are only recorded when a
member's own ``elapsed`` hits its own cadence — the same states a solo run
observes. The one deliberate relaxation: on the fused plane, ``rr_tracked``
folds kernel evidence at *bucket* chunk boundaries, which may be finer than
a solo run's snapshot intervals when cadences mix — the adjust unit then
sees the same evidence replayed in the same order, just folded earlier, so
final splits and §5.3 counters still match (the same guarantee the fused
plane itself makes vs the stepwise loop).
"""

from __future__ import annotations

import time
from collections import OrderedDict
from typing import Any, Callable, List, NamedTuple, Optional, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding

import repro.obs as obs
import repro.obs.health as health
from repro.dist.sharding import active_mesh, member_spec

from .metrics import ServiceMetrics
from .request import BucketKey, RequestRecord, RequestResult

__all__ = ["Bucket", "ChunkCompiler"]


def _stack(trees):
    """Stack a list of congruent host pytrees along a new leading member dim."""
    return jax.tree_util.tree_map(lambda *xs: np.stack(xs), *trees)


def _row(tree, i: int):
    """Member ``i``'s row of a stacked host pytree, as arrays of its own."""
    return jax.tree_util.tree_map(lambda x: np.array(x[i]), tree)


def _copy_to_host(tree) -> None:
    """Start ``tree``'s device-to-host copies without waiting for them."""
    for x in jax.tree_util.tree_leaves(tree):
        x.copy_to_host_async()


class _Call(NamedTuple):
    """One dispatched chunk program call, not yet synced."""

    out: Any  # (state, snapshots, tracker) on the device
    steps: int
    fresh: bool
    dispatch_s: float  # host seconds the dispatch took


class ChunkCompiler:
    """Jitted chunk programs, cached per (key, chunk, n_members, mesh).

    The program is ``run_ensemble(state_b, chunk, snapshot_every=chunk,
    tracker0_batch=tracker_b)`` — one snapshot interval, vmapped over the
    bucket, trackers threaded through and returned stacked for repacking.
    ``mesh`` must be the active ``axis_rules`` mesh (or None): sharded
    programs bake ``NamedSharding(mesh, ...)`` constraints in at trace
    time, so a program traced under one mesh must never serve another.

    The cache is LRU-bounded (``maxsize``): event-driven chunking produces
    one distinct chunk length per distinct member-event spacing, so a
    long-lived service with heterogeneous traffic would otherwise retain
    compiled executables without limit. Evicted entries simply retrace on
    next use.
    """

    def __init__(self, maxsize: int = 256):
        self.maxsize = maxsize
        self._cache: "OrderedDict[Tuple, Callable]" = OrderedDict()

    def get(
        self, sim, key: BucketKey, chunk: int, n: int, sharded: bool, mesh=None
    ) -> Tuple[Callable, bool]:
        """Returns ``(chunk_fn, fresh)`` — ``fresh`` marks a cache miss, i.e.
        the next call of ``chunk_fn`` will trace + compile. The batcher books
        that call as compile time, not a chunk-latency sample."""
        cache_key = (key, chunk, n, sharded, mesh)
        fn = self._cache.get(cache_key)
        fresh = fn is None
        if fresh:

            def chunk_fn(state_b, tracker_b):
                res = sim.run_ensemble(
                    state_b,
                    chunk,
                    snapshot_every=chunk,
                    tracker0_batch=tracker_b,
                    execution=key.execution,
                    sharded=sharded,
                    storage=key.storage,
                )
                return res.state, res.snapshots, res.tracker

            fn = self._cache[cache_key] = jax.jit(chunk_fn)
            if len(self._cache) > self.maxsize:
                self._cache.popitem(last=False)
        else:
            self._cache.move_to_end(cache_key)
        return fn, fresh

    def __len__(self) -> int:
        return len(self._cache)


class Bucket:
    """One packing of compatible requests; advances one chunk at a time.

    ``members`` is the current packing. The resident batch ``_batch`` is
    the last chunk's stacked ``(state, tracker)`` on the device, its rows in
    the order of ``_rows`` (the members of that chunk); ``_change`` names
    the first membership change since that chunk (None: the batch is
    current). Members join with :meth:`add` and leave only through
    :meth:`remove`.
    """

    def __init__(self, key: BucketKey):
        self.key = key
        self.members: List[RequestRecord] = []
        self._batch = None
        #: the next chunk's call, dispatched ahead on the last chunk's
        #: outputs (or the exception that dispatch raised, for the next
        #: advance to raise)
        self._ahead = None
        self._rows: List[RequestRecord] = []
        #: host copies of the batch's state and tracker, each fetched at most
        #: once per chunk (None: not fetched since the batch last changed)
        self._host: List = [None, None]
        self._change: Optional[str] = None

    def __len__(self) -> int:
        return len(self.members)

    def member_ids(self) -> str:
        """The members' request ids, space-separated (a span arg)."""
        return " ".join(str(m.id) for m in self.members)

    def add(self, rec: RequestRecord) -> None:
        if rec.key != self.key:
            raise ValueError(
                f"request {rec.id} (key {rec.key.short()}) is not compatible "
                f"with bucket {self.key.short()}"
            )
        if rec.joined_at is None:  # the first join counts; a resume keeps it
            rec.joined_at = time.perf_counter()
        with obs.span(
            "service.join",
            request=rec.id,
            bucket=self.key.short(),
            wait_us=(rec.joined_at - rec.submitted_at) * 1e6,
        ):
            self.members.append(rec)
            rec.status = "running"
            self._change = self._change or "join"
            self._ahead = None

    def remove(self, leaving: List[RequestRecord], reason: str) -> None:
        """The only way members leave (``reason``: ``drain``, ``evict`` or
        ``failure``): each takes its ``(state, tracker)`` row from one host
        copy of the resident batch, then drops out; the next chunk rebuilds
        the batch."""
        resident = [m for m in leaving if m.resident_in is self]
        if resident:
            with obs.span(
                "service.unstack",
                members=" ".join(str(m.id) for m in resident),
                reason=reason,
            ):
                for m in resident:
                    m.state, m.tracker = self.row(m, 0, 1)
                    m.resident_in = None
        for m in leaving:
            self.members.remove(m)
        self._change = self._change or reason
        self._ahead = None
        if not self.members:  # nothing left to run: free the device copy
            self._batch, self._rows, self._host = None, [], [None, None]

    def row(self, rec: RequestRecord, *parts: int) -> Tuple:
        """A resident member's rows of the batch's ``parts`` (0: state, 1:
        tracker), copied out of their host copies. Each part is fetched at
        most once per chunk, all missing ones in one transfer."""
        missing = [p for p in parts if self._host[p] is None]
        for p, x in zip(missing, jax.device_get([self._batch[p] for p in missing])):
            self._host[p] = x
        i = self._rows.index(rec)
        return tuple(_row(self._host[p], i) for p in parts)

    def _restack(self, tracked: bool, sharded: bool) -> None:
        """Rebuild the resident batch for the current members: the rows of
        those already in it and the own copies of joiners, composed on the
        host and put on the device in one transfer. A sharded batch goes
        straight onto the member sharding the chunk program returns, so
        restacked and resident batches reach the same executable."""
        joiners = [m for m in self.members if m.resident_in is not self]
        own = dict(zip(joiners, jax.device_get([(m.state, m.tracker) for m in joiners])))
        rows = [own[m] if m in own else self.row(m, 0, 1) for m in self.members]
        state_b = _stack([st for st, _ in rows])
        tracker_b = _stack([tr for _, tr in rows]) if tracked else None
        spec = member_spec(len(self.members)) if sharded else None
        self._batch = jax.device_put(
            (state_b, tracker_b), None if spec is None else NamedSharding(*spec)
        )
        self._host = [state_b, tracker_b]
        self._rows = list(self.members)
        for m in self.members:
            m.state = m.tracker = None
            m.resident_in = self

    def next_chunk(self, after: int = 0) -> int:
        """Steps until the earliest member event, ``after`` steps from now —
        the length of the chunk that starts there."""
        return min(m.steps_to_next_event(after) for m in self.members)

    def _dispatch(self, fn, fresh: bool, steps: int, batch, after: int = 0) -> _Call:
        """Call ``fn`` on ``batch`` for a chunk of ``steps`` that starts
        ``after`` steps from now, and start the host copies its end needs:
        the frames if a member's snapshot falls due there, the batch if a
        member drains there."""
        t0 = time.perf_counter()
        with obs.span("service.dispatch", steps=steps, ahead=after > 0):
            out = fn(*batch)
        ends = [(m, m.elapsed + after + steps) for m in self.members]
        if any(end % m.every == 0 for m, end in ends):
            _copy_to_host(out[1])
        if any(end == m.steps for m, end in ends):
            _copy_to_host((out[0], out[2]))
        return _Call(out, steps, fresh, time.perf_counter() - t0)

    def advance(
        self,
        compiler: ChunkCompiler,
        metrics: ServiceMetrics,
        sharded: Optional[bool] = None,
        alone: bool = False,
    ) -> List[RequestRecord]:
        """Run one chunk for every member; returns the members that drained.

        ``sharded=None`` auto-detects: bucket members ride the logical
        ``batch`` axis whenever a ``dist.sharding.axis_rules`` mesh context
        is active (``repro.dist.sharding.active_mesh``), so the same service
        loop spreads buckets over a mesh's data axes unchanged. ``alone``:
        the scheduler sees no other live bucket and an empty queue, so the
        next chunk may run ahead (module docstring).
        """
        if not self.members:
            return []
        mesh = active_mesh()
        if sharded is None:
            sharded = mesh is not None
        mesh = mesh if sharded else None
        n = len(self.members)
        ids = self.member_ids()
        sim = self.members[0].sim  # identical (stepper, cfg, prec) by key
        tracked = self.members[0].tracked

        call, self._ahead = self._ahead, None
        if isinstance(call, Exception):  # this chunk's dispatch ahead failed
            raise call
        ran_ahead = call is not None
        reason = None
        if not ran_ahead:
            reason = "first" if self._batch is None else self._change
            if reason is not None:
                with obs.span("service.stack", members=ids, reason=reason):
                    self._restack(tracked, sharded)
            self._change = None
            chunk = self.next_chunk()
            fn, fresh = compiler.get(sim, self.key, chunk, n, sharded, mesh=mesh)
        else:
            chunk, fresh = call.steps, call.fresh
        with obs.span(
            "service.chunk",
            bucket=self.key.short(),
            members=ids,
            steps=chunk,
            compile=fresh,
            ahead=ran_ahead,
        ):
            if not ran_ahead:
                call = self._dispatch(fn, fresh, chunk, self._batch)
            if alone and all(m.remaining > chunk for m in self.members):
                # the membership holds past this chunk: queue the next
                # one behind it on the device before waiting for it
                nxt = self.next_chunk(after=chunk)
                try:
                    nfn, nfresh = compiler.get(sim, self.key, nxt, n, sharded, mesh=mesh)
                    self._ahead = self._dispatch(
                        nfn, nfresh, nxt, (call.out[0], call.out[2]), after=chunk)
                except Exception as e:  # raised where that chunk would run
                    self._ahead = e
            t0 = time.perf_counter()
            with obs.span("service.sync"):
                out_state, out_snaps, out_tracker = jax.block_until_ready(call.out)
            # the host's time in this chunk's own calls: a chunk run ahead
            # is not charged for the host work it overlapped
            dt = call.dispatch_s + time.perf_counter() - t0
        self._batch, self._host = (out_state, out_tracker), [None, None]
        metrics.observe_chunk(self.key, n, chunk, dt, compiled=fresh)
        if reason is None:
            metrics.resident_chunks += 1
        else:
            metrics.restacks += 1
        if ran_ahead:
            metrics.chunks_ahead += 1
        mon = health.active()
        o = obs.active()
        # per-chunk tracker consumers read the members' rows of one host
        # copy of the stacked tracker
        watched = tracked and (
            mon is not None or (o is not None and o.telemetry is not None)
        )

        drained: List[RequestRecord] = []
        snaps = None  # one host copy of the chunk's frames, once one is due
        for i, m in enumerate(self.members):
            if watched:
                obs.record_tracker(
                    f"req{m.id}:{m.key.stepper}", m.tracker, m.elapsed + chunk
                )
                if mon is not None:
                    mon.on_tracker(m, chunk)
            m.elapsed += chunk
            m.chunks += 1
            if m.snapshot_due():
                with obs.span("service.snapshot", request=m.id):
                    if snaps is None:
                        snaps = jax.device_get(out_snaps)
                    # snaps lead with (member, n_out=1, ...): this member's frame
                    snap = jax.tree_util.tree_map(lambda x: np.array(x[i, 0]), snaps)
                    m.snapshots.append((m.elapsed, snap))
                    m.stream.emit("snapshot", m.elapsed, snap)
                metrics.snapshots_emitted += 1
                if mon is not None:
                    mon.observe_frame(m, snap)
            if m.remaining == 0:
                drained.append(m)

        # chunk-boundary health evaluation AFTER the member updates, so the
        # detectors see the telemetry this chunk just drained
        if mon is not None:
            mon.on_chunk(self.key, n, chunk, dt, compiled=fresh)

        if drained:
            self.remove(drained, "drain")
        for m in drained:
            self._finalize(m, metrics)
            if mon is not None:
                mon.on_request_done(m)
        return drained

    @staticmethod
    def _finalize(m: RequestRecord, metrics: ServiceMetrics) -> None:
        m.done_at = time.perf_counter()
        with obs.span(
            "service.finalize",
            request=m.id,
            queue_us=(m.joined_at - m.submitted_at) * 1e6,
            service_us=(m.done_at - m.joined_at) * 1e6,
            steps=m.elapsed,
            chunks=m.chunks,
        ):
            final_k, adjustments = m.site_summary()
            m.status = "done"
            m.result = RequestResult(
                state=m.state,
                snapshots=[a for _, a in m.snapshots],
                snapshot_steps=[s for s, _ in m.snapshots],
                tracker=m.tracker,
                final_k=final_k,
                adjustments=adjustments,
                elapsed=m.elapsed,
                chunks=m.chunks,
            )
            m.stream.emit("done", m.elapsed, m.result)
        metrics.observe_completion(adjustments)
