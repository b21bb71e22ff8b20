"""Unified PDE solver framework: Stepper protocol + Simulation driver.

Every PDE workload used to hand-roll its own ``lax.scan`` scaffolding, and
none threaded a tracker through the loop — so the cross-step ``rr_tracked``
engine silently degraded to stateless per-tensor selection exactly where the
paper exercises it. This module owns the simulation loop once:

* a :class:`Stepper` is the workload: ``init_state / step / observables``
  plus static metadata (named multiplication sites, precision failure mode);
* :class:`StepOps` is the per-step arithmetic context handed to
  ``Stepper.step``: ``mul/div/store`` route through the precision engine and
  thread the tracker implicitly, so stepper code never touches tracker
  plumbing;
* :class:`Simulation` drives the scan/snapshot loop, carrying
  ``(state, tracker)`` through every step — tracked modes (``rr_tracked`` /
  ``deploy``, any engine with ``tracks=True``) genuinely carry the flexible
  split ``k`` across time, the paper's precision-adjust-unit persistence;
* ensembles of initial conditions run vmapped
  (:meth:`Simulation.run_ensemble`), optionally sharded over the mesh's
  data axes via :mod:`repro.dist.sharding` logical-axis rules (the ensemble
  member dim is the logical ``batch`` axis).

Steppers register under a string key (:mod:`repro.pde.registry`, mirroring
``precision/registry.py``), so tests, examples and docs enumerate
scenarios instead of importing workload modules. See DESIGN.md §9.

The driver owns THREE arithmetic planes (``run(..., execution=...)``,
DESIGN.md §10/§14): the reference ``StepOps`` path above; a **fused
execution plane** where whole snapshot intervals run as multi-substep
Pallas kernel chunks through the stepper's optional ``fused_step`` hook —
one HBM round trip per chunk, per-block runtime splits selected in VMEM,
and the kernels' per-site range evidence folded into the carried tracker
between chunks (:func:`repro.precision.fold_evidence`), so tracked modes
ride the fast path with the same adjust-unit semantics; and a **megakernel
plane** where the stepper's optional ``mega_step`` hook runs the ENTIRE
horizon — snapshots, boundary storage rounding, and the per-substep
on-chip adjust unit (:func:`repro.core.policy.adjust_step`) — in ONE
``pallas_call``, bit-identical to the chunked plane. ``"auto"`` prefers
the megakernel when :func:`repro.precision.mega_eligible` accepts, then
fused when :func:`repro.precision.fused_eligible` accepts, then the
reference path.
"""

from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp

import repro.obs as _obs
from repro.core.policy import PrecisionConfig
from repro.dist.sharding import member_spec
from repro.pack import is_packed, pack_state, storage_quantize, unpack_state
from repro.precision import (
    fold_evidence,
    fused_eligible,
    get_engine,
    mega_eligible,
    site_tracker_init,
)
from repro.precision.sites import rewrap
from repro.pde.registry import get_stepper
from repro.profile.capture import CaptureResult, CaptureSpec, pair_exp_hist, site_evidence

__all__ = ["Stepper", "StepOps", "Simulation", "SimResult", "STORAGE_MODES"]

#: carried-state storage formats (DESIGN.md §13): "f32" carries raw f32
#: between chunks (the historical behaviour, bit-compatible); "quantized"
#: rounds chunk-boundary state through pack/unpack but carries f32;
#: "packed" carries :class:`repro.pack.PackedArray` payloads — the same
#: values as "quantized" bit-for-bit, at fmt.total_bits per element.
STORAGE_MODES = ("f32", "quantized", "packed")


class StepOps:
    """Per-step policy arithmetic for stepper code.

    Wraps ``(engine, cfg, tracker)`` so a stepper writes
    ``flux = ops.mul(alpha, lap, "heat.flux")`` and the tracker state —
    when one is threaded — is updated in place and returned to the scan
    carry by the driver. With ``tracker=None`` the calls are exactly the
    engine calls the pre-framework solvers made, so untracked numerics are
    bit-identical to the old per-workload loops.
    """

    __slots__ = (
        "prec", "tracker", "_engine", "_cap_spec", "_cap_sites", "cap_counts", "cap_evidence",
    )

    def __init__(self, prec: PrecisionConfig, tracker=None, capture=None):
        self.prec = prec
        self.tracker = tracker
        self._engine = get_engine(prec)
        self._cap_spec = None
        if capture is not None:
            # (CaptureSpec, site tuple, carried (n_sites, 2, n_bins) counts):
            # the driver threads the counts through the scan like the tracker
            self._cap_spec, self._cap_sites, self.cap_counts = capture
            self.cap_evidence = jnp.full(
                (len(self._cap_sites), 2), -127.0, jnp.float32
            )  # per-step site evidence; -127 is the zero-operand floor

    def mul(self, a, b, site: str):
        """Elementwise product on the policy's multiplier at a named site."""
        if self._cap_spec is not None:
            self._capture(a, b, site)
        out, self.tracker = self._engine.multiply(
            a, b, self.prec, tracker=self.tracker, site=site
        )
        return out

    def _capture(self, a, b, site: str):
        """Range capture: bin the (broadcast) operands' elementwise exponents
        and record the site-level max-exponent evidence — the same binning the
        fused kernels apply in-VMEM (:mod:`repro.profile.capture`)."""
        j = self._cap_sites.index(site)
        a = jnp.asarray(a, jnp.float32)
        b = jnp.asarray(b, jnp.float32)
        shape = jnp.broadcast_shapes(a.shape, b.shape)
        a = jnp.broadcast_to(a, shape)
        b = jnp.broadcast_to(b, shape)
        self.cap_counts = self.cap_counts.at[j].add(pair_exp_hist(a, b, self._cap_spec))
        self.cap_evidence = self.cap_evidence.at[j].set(
            jnp.maximum(self.cap_evidence[j], site_evidence(a, b))
        )

    def add(self, a, b, site: str):
        """Elementwise sum on the policy's flexible adder at a named site
        (``repro.alu`` alignment-shift evidence law)."""
        if self._cap_spec is not None:
            self._capture(a, b, site)
        out, self.tracker = self._engine.add(
            a, b, self.prec, tracker=self.tracker, site=site
        )
        return out

    def div(self, a, b, site: Optional[str] = None):
        """Quotient on the policy's divider. With a named ``site`` this is
        the tracked ``repro.alu`` flexible divider (quotient-range evidence
        law); ``site=None`` keeps the historical untracked engine call."""
        if site is None:
            out, _ = self._engine.divide(a, b, self.prec)
            return out
        if self._cap_spec is not None:
            self._capture(a, b, site)
        out, self.tracker = self._engine.divide(
            a, b, self.prec, tracker=self.tracker, site=site
        )
        return out

    def rsqrt(self, x, site: Optional[str] = None):
        """Reciprocal square root on the policy's datapath; the unary
        evidence is the operand's exponent doubled up."""
        if site is None:
            out, _ = self._engine.rsqrt(x, self.prec)
            return out
        if self._cap_spec is not None:
            self._capture(x, x, site)
        out, self.tracker = self._engine.rsqrt(
            x, self.prec, tracker=self.tracker, site=site
        )
        return out

    def store(self, x):
        """Round state to the policy's storage format."""
        return self._engine.store(x, self.prec)


class Stepper:
    """One PDE workload: state initialisation, one update, what to snapshot.

    Subclasses implement ``init_state`` and ``step`` and declare their named
    multiplication sites (``sites``) — the rows a tracked run's SiteTracker
    carries. ``name`` is stamped by ``register_stepper``; ``failure_mode``
    and ``story`` are documentation metadata surfaced by the README scenario
    table and ``examples/pde_zoo.py``.
    """

    name: str = "?"
    sites: Tuple[str, ...] = ()
    #: per-site op declarations aligned with ``sites`` ("mul" | "add" |
    #: "div" | "rsqrt") — selects each site's exponent envelope when fused
    #: evidence replays through the adjust unit (``fold_evidence``). Empty
    #: means all-"mul" (the historical multiplier-only workloads).
    site_ops: Tuple[str, ...] = ()
    #: how this scenario breaks a fixed 16-bit format (README table):
    #: "underflow" | "overflow" | "nonlinear-drift"
    failure_mode: str = "?"
    story: str = ""
    #: default number of snapshots when ``snapshot_every`` is not given
    #: (kept per-stepper so the legacy ``simulate`` shims stay bit-identical)
    snapshots_default: int = 8
    #: Optional fused-plane hook, registered alongside ``step``. A stepper
    #: with a fused body overrides this with a method of signature
    #: ``fused_step(state, cfg, prec, steps, *, k_floor=None,
    #: collect_evidence=False, capture=None, interpret=None) ->
    #: (state, evidence)`` that advances ``steps`` substeps through Pallas
    #: whole-step kernels (:mod:`repro.kernels.fused`) and, when asked,
    #: returns the per-substep per-site max-exponent evidence
    #: ``(steps, len(sites), 2)`` the driver folds into the carried tracker.
    #: With a ``capture`` spec (range profiling, DESIGN.md §11) the return
    #: grows a trailing ``(len(sites), 2, n_bins)`` exponent-count array.
    #: Steppers with ``fused_packed = True`` additionally accept
    #: ``storage="packed"`` and then take/return the state as
    #: :class:`repro.pack.PackedArray` leaves, unpacked/repacked inside the
    #: kernel (one HBM round trip at ``fmt.total_bits`` per element).
    #: ``None`` means "reference path only".
    fused_step = None
    #: True when ``fused_step`` supports in-kernel packed storage — the
    #: Pallas sweep unpacks the payload in its prologue and repacks in its
    #: epilogue, so packed chunks never materialise f32 state in HBM.
    #: False (e.g. SWE's flux-kernel stepper) means the driver packs at the
    #: XLA boundary instead: same bits, f32 traffic inside the chunk.
    fused_packed: bool = False
    #: Optional whole-horizon megakernel hook (DESIGN.md §14). A stepper
    #: with one overrides this with a method of signature
    #: ``mega_step(state, cfg, prec, steps, every, *, tracker=None,
    #: collect_evidence=False, capture=None, interpret=None,
    #: storage="f32") -> repro.kernels.mega.MegaResult`` that runs the
    #: ENTIRE horizon — snapshots, boundary storage rounding, and (for
    #: tracked modes) the per-substep on-chip adjust unit — in ONE
    #: ``pallas_call``. ``tracker`` is the raw RangeTracker state (site
    #: rows ordered like ``sites``); evolved state comes back in the
    #: result. ``None`` means "chunked planes only".
    mega_step = None

    def fused_supported(self, cfg, prec: PrecisionConfig) -> bool:
        """Shape/config eligibility gate for the fused body (mode
        eligibility is the policy's side: ``precision.fused_eligible``)."""
        del cfg, prec
        return True

    def mega_supported(self, cfg, prec: PrecisionConfig) -> bool:
        """Shape/config eligibility gate for the megakernel. The megakernel
        keeps one block per state leaf, so steppers whose chunked kernels
        tile the field must refuse configs that exceed one kernel block
        (per-tile split selection would otherwise diverge from the
        whole-field selection and break cross-plane bit parity)."""
        del cfg, prec
        return True

    def default_config(self):
        raise NotImplementedError

    def init_state(self, cfg):
        """Initial solver state (a pytree of f32 arrays)."""
        raise NotImplementedError

    def step(self, state, cfg, ops: StepOps):
        """One update. All policy multiplications go through ``ops.mul``."""
        raise NotImplementedError

    def observables(self, state, cfg):
        """What one snapshot records (default: the whole state)."""
        del cfg
        return state

    def metric_offset(self, cfg) -> float:
        """Constant background removed before rel-L2 metrics (e.g. the SWE
        resting depth) — used by ``repro.profile``'s validation replay."""
        del cfg
        return 0.0


class SimResult(NamedTuple):
    """What a run returns; ``tracker`` is None for untracked modes and
    ``profile`` is None unless the run captured range distributions."""

    state: Any  # final solver state
    snapshots: Any  # stacked observables, leading dim = n snapshots
    tracker: Optional[Any]  # final SiteTracker (tracked modes)
    profile: Optional[Any] = None  # repro.profile.capture.CaptureResult


@dataclasses.dataclass
class Simulation:
    """The scan/snapshot scaffolding, owned once for every stepper.

    ``stepper`` may be a registered name or a Stepper instance; ``cfg``
    defaults to the stepper's ``default_config()``.
    """

    stepper: Union[str, Stepper]
    cfg: Any
    prec: PrecisionConfig

    def __post_init__(self):
        if isinstance(self.stepper, str):
            self.stepper = get_stepper(self.stepper)
        if self.cfg is None:
            self.cfg = self.stepper.default_config()

    # -- tracker ------------------------------------------------------------

    def init_tracker(self, k0: Optional[int] = None):
        """Fresh SiteTracker over the stepper's sites (tracked modes only;
        returns None when the engine does not track or there are no sites)."""
        if not (get_engine(self.prec).tracks and self.stepper.sites):
            return None
        return site_tracker_init(self.stepper.sites, self.prec.fmt, k0=k0)

    # -- fused-plane dispatch ----------------------------------------------

    def fused_eligible(self) -> bool:
        """Can this (stepper, cfg, prec) run on the fused execution plane?"""
        return fused_eligible(self.prec, self.stepper, self.cfg)

    def mega_eligible(self) -> bool:
        """Can this (stepper, cfg, prec) run on the whole-horizon megakernel
        plane (DESIGN.md §14)?"""
        return mega_eligible(self.prec, self.stepper, self.cfg)

    def _resolve_execution(self, execution: str) -> str:
        if execution not in ("reference", "fused", "megakernel", "auto"):
            raise ValueError(
                f"unknown execution mode {execution!r}; "
                "expected 'reference' | 'fused' | 'megakernel' | 'auto'"
            )
        if execution == "auto":
            if self.mega_eligible():
                return "megakernel"
            return "fused" if self.fused_eligible() else "reference"
        if execution == "fused" and not self.fused_eligible():
            raise ValueError(
                f"stepper {self.stepper.name!r} is not fused-eligible under "
                f"mode {self.prec.mode!r} (no fused_step hook, unknown fused "
                "arithmetic family, or unsupported shape); use "
                "execution='auto' for graceful fallback"
            )
        if execution == "megakernel" and not self.mega_eligible():
            raise ValueError(
                f"stepper {self.stepper.name!r} is not megakernel-eligible "
                f"under mode {self.prec.mode!r} (no mega_step hook, unknown "
                "fused arithmetic family, or unsupported shape); use "
                "execution='auto' for graceful fallback"
            )
        return execution

    # -- carried-state storage (DESIGN.md §13) -------------------------------

    @staticmethod
    def _resolve_storage(storage: str) -> str:
        if storage not in STORAGE_MODES:
            raise ValueError(
                f"unknown storage mode {storage!r}; expected one of {STORAGE_MODES}"
            )
        return storage

    def _storage_in(self, state0, storage: str):
        """Bring an initial state onto the run's storage format. Packed runs
        accept either f32 leaves (packed here — the run's first and only
        pack of that boundary) or an already-packed tree (a resumed carry
        from a previous packed run / service chunk, used verbatim)."""
        fmt = self.prec.fmt
        if storage == "packed":
            return state0 if is_packed(state0) else pack_state(state0, fmt)
        if is_packed(state0):
            state0 = unpack_state(state0)
        return storage_quantize(state0, fmt) if storage == "quantized" else state0

    # -- profiling / policy plumbing ----------------------------------------

    def _resolve_capture(self, capture):
        """``capture`` may be None, True (default spec) or a CaptureSpec."""
        if capture is None or capture is False:
            return None
        if capture is True:
            capture = CaptureSpec()
        if not isinstance(capture, CaptureSpec):
            raise TypeError(f"capture must be bool or CaptureSpec, got {capture!r}")
        if not self.stepper.sites:
            raise ValueError(
                f"stepper {self.stepper.name!r} declares no multiplication "
                "sites; nothing to capture"
            )
        return capture

    def _apply_policy(self, prec, tracker, policy):
        """Load a ``repro.profile`` PrecisionPolicy artifact: per-site tuned
        starting splits for the tracker plus the floor/ceiling hints as
        ``prec.k_bounds`` (ordered by the stepper's site tuple)."""
        sites = self.stepper.sites
        prec = policy.apply(prec, sites)
        if tracker is None and get_engine(prec).tracks and sites:
            tracker = site_tracker_init(sites, prec.fmt, k0=policy.k_array(sites))
        return prec, tracker

    # -- f32 oracle (shadow replay) ------------------------------------------

    def oracle(self) -> "Simulation":
        """This simulation's f32 oracle twin: same stepper and config,
        reference arithmetic. The health plane's shadow sampler replays
        service requests through it to measure live drift (DESIGN.md §16)."""
        return Simulation(self.stepper, self.cfg, PrecisionConfig(mode="f32"))

    def oracle_replay(
        self,
        steps: int,
        *,
        state0=None,
        snapshot_every: Optional[int] = None,
    ) -> SimResult:
        """Replay a workload at f32 on the reference plane — the shadow
        oracle of :mod:`repro.obs.shadow`.

        This is an entirely separate program over copies of the inputs: it
        shares no carried state, tracker or compiled executable with the
        primary run, which is why shadow sampling is passive (the primary
        path is bit-identical with shadowing on or off; proven in
        ``tests/test_health.py``)."""
        return self.oracle().run(
            steps,
            snapshot_every=snapshot_every,
            state0=state0,
            execution="reference",
        )

    # -- single run ---------------------------------------------------------

    def run(
        self,
        steps: int,
        *,
        snapshot_every: Optional[int] = None,
        state0=None,
        tracker=None,
        execution: str = "reference",
        capture=None,
        policy=None,
        storage: str = "f32",
    ) -> SimResult:
        """Advance ``steps`` updates, snapshotting observables periodically.

        The scan carry is ``(state, tracker)`` — tracked engines see the
        tracker every step and their updated state is carried forward, so
        the flexible split ``k`` genuinely evolves across time. Pass an
        explicit ``tracker`` to resume from saved adjust-unit state; by
        default tracked modes start from :meth:`init_tracker`.

        ``execution`` selects the arithmetic plane (DESIGN.md §10):

        * ``"reference"`` — the stepwise ``StepOps`` engine path (default;
          bit-exact emulation semantics, every mode).
        * ``"fused"`` — whole snapshot intervals run as multi-substep Pallas
          kernel chunks via the stepper's ``fused_step`` hook; tracked modes
          fold the kernels' per-site range evidence into the carried tracker
          between chunks. Raises if the stepper/mode is not fused-eligible.
        * ``"megakernel"`` — the ENTIRE horizon runs in ONE ``pallas_call``
          via the stepper's ``mega_step`` hook (DESIGN.md §14): snapshots
          stream out at their cadence and the precision adjust unit evolves
          on-chip per substep, so there is no per-chunk launch or HBM round
          trip. Bit-identical to ``"fused"`` (same arithmetic, same
          boundary rounding, same adjust law at the same cadence). Raises
          if the stepper/mode is not megakernel-eligible.
        * ``"auto"`` — ``"megakernel"`` when eligible, else ``"fused"``
          when eligible, else ``"reference"``.

        ``capture`` (None | True | :class:`repro.profile.capture.CaptureSpec`)
        turns on range-distribution capture (DESIGN.md §11): the result's
        ``profile`` field carries the per-step site evidence stream and the
        per-site operand exponent histograms, on BOTH execution planes.

        ``policy`` loads a ``repro.profile`` PrecisionPolicy artifact:
        tracked modes start their tracker at the artifact's per-site tuned
        splits and clamp re-picks to its floor/ceiling hints. Combine with
        ``prec.pinned`` for the static profiled-deployment emulation.

        ``storage`` selects the carried-state format between chunk
        boundaries (snapshot intervals — :data:`STORAGE_MODES`, DESIGN.md
        §13). ``"quantized"`` rounds boundary state through the packed
        format but carries f32; ``"packed"`` carries
        :class:`repro.pack.PackedArray` payloads (``fmt.total_bits`` per
        element — the result's ``state`` and any resumed carry are packed
        trees) and is bit-identical to ``"quantized"`` by construction:
        both apply exactly one pack per boundary to the same f32 values.

        With :mod:`repro.obs` enabled the run is wrapped in a ``sim.run``
        span and — for tracked modes — its final tracker (and, when the run
        captured evidence, the full chunk-boundary k series replayed from
        that evidence) is drained into the precision telemetry. All of it is
        passive host-side observation: the numerics are bit-identical with
        observability on or off (``tests/test_obs.py``).
        """
        resolved = self._resolve_execution(execution)
        with _obs.span(
            "sim.run",
            stepper=self.stepper.name,
            mode=self.prec.mode,
            steps=steps,
            execution=resolved,
            storage=storage,
        ):
            _obs.inc(
                "repro_sim_runs_total",
                help="Simulation.run calls by plane",
                stepper=self.stepper.name,
                mode=self.prec.mode,
                execution=resolved,
            )
            res = self._run(
                steps,
                snapshot_every=snapshot_every,
                state0=state0,
                tracker=tracker,
                execution=resolved,
                capture=capture,
                policy=policy,
                storage=storage,
            )
        self._drain_telemetry(res, steps, snapshot_every, tracker, policy)
        return res

    def _run(
        self,
        steps: int,
        *,
        snapshot_every: Optional[int] = None,
        state0=None,
        tracker=None,
        execution: str = "reference",
        capture=None,
        policy=None,
        storage: str = "f32",
    ) -> SimResult:
        stepper, cfg, prec = self.stepper, self.cfg, self.prec
        storage = self._resolve_storage(storage)
        if policy is not None:
            prec, tracker = self._apply_policy(prec, tracker, policy)
        state0 = stepper.init_state(cfg) if state0 is None else state0
        state0 = self._storage_in(state0, storage)
        if tracker is None:
            tracker = self.init_tracker()
        spec = self._resolve_capture(capture)
        every = snapshot_every or max(1, steps // stepper.snapshots_default)
        resolved = self._resolve_execution(execution)
        if resolved == "megakernel":
            return self._run_mega(
                steps, every, state0, tracker, prec=prec, capture=spec, storage=storage
            )
        if resolved == "fused":
            return self._run_fused(
                steps, every, state0, tracker, prec=prec, capture=spec, storage=storage
            )

        def body(carry, _):
            state, tr = carry
            ops = StepOps(prec, tr)
            state = stepper.step(state, cfg, ops)
            return (state, ops.tracker), None

        n_out = steps // every
        rem = steps - n_out * every
        if spec is not None:
            return self._run_reference_captured(
                steps, every, n_out, rem, state0, tracker, prec, spec, storage
            )

        if storage == "packed":
            # the outer carry stays packed; each interval unpacks once,
            # advances in f32, and packs once at the boundary
            def outer(carry, _):
                (state, tr), _ = jax.lax.scan(
                    body, (unpack_state(carry[0]), carry[1]), None, length=every
                )
                packed = pack_state(state, prec.fmt)
                return (packed, tr), stepper.observables(unpack_state(packed), cfg)

            carry = (state0, tracker)
            carry, snaps = jax.lax.scan(outer, carry, None, length=n_out)
            if rem:
                (state, tr), _ = jax.lax.scan(
                    body, (unpack_state(carry[0]), carry[1]), None, length=rem
                )
                carry = (pack_state(state, prec.fmt), tr)
            state, tracker = carry
            return SimResult(state, snaps, tracker)

        def outer(carry, _):
            carry, _ = jax.lax.scan(body, carry, None, length=every)
            state = carry[0]
            if storage == "quantized":
                state = storage_quantize(state, prec.fmt)
            return (state, carry[1]), stepper.observables(state, cfg)

        carry = (state0, tracker)
        carry, snaps = jax.lax.scan(outer, carry, None, length=n_out)
        if rem:
            carry, _ = jax.lax.scan(body, carry, None, length=rem)
            if storage == "quantized":
                carry = (storage_quantize(carry[0], prec.fmt), carry[1])
        state, tracker = carry
        return SimResult(state, snaps, tracker)

    # -- precision-telemetry drain (passive; repro.obs) ----------------------

    def _drain_telemetry(self, res, steps, snapshot_every, tracker_arg, policy):
        """Feed a finished run's tracker into ``repro.obs`` telemetry.

        Passivity guard: if any tracker/evidence leaf is a jax tracer (this
        run is being traced inside jit/vmap — e.g. the service's compiled
        chunk programs) nothing is drained; the concrete values are observed
        by whoever executes the compiled program (the batcher)."""
        o = _obs.active()
        if o is None or o.telemetry is None or res.tracker is None:
            return
        leaves = jax.tree_util.tree_leaves(
            (res.tracker, None if res.profile is None else res.profile.evidence)
        )
        if any(isinstance(x, jax.core.Tracer) for x in leaves):
            return
        stepper = self.stepper
        scope = o.telemetry.unique_scope(f"sim:{stepper.name}")
        if res.profile is None:
            o.telemetry.record_tracker(scope, res.tracker, steps)
            return
        # captured run: replay the evidence stream through the adjust law to
        # reconstruct the k series at every chunk boundary, plus coverage of
        # the final carried splits (repro.obs.precision — no new kernel
        # outputs, the capture plane already emits this stream)
        from repro.obs.precision import coverage_fraction, replay_k_series

        prec, tr0 = self.prec, tracker_arg
        if policy is not None:
            prec, tr0 = self._apply_policy(prec, tr0, policy)
        if tr0 is None:
            tr0 = self.init_tracker()
        every = snapshot_every or max(1, steps // stepper.snapshots_default)
        sites = list(stepper.sites)
        ops = stepper.site_ops or None
        bsteps, k, grew, shrank = replay_k_series(
            res.profile.evidence, prec, sites, site_ops=ops, every=every,
            tracker0=tr0,
        )
        st = res.tracker.state
        final_k = {n: int(st.k[i]) for i, n in enumerate(res.tracker.names)}
        cov = coverage_fraction(
            res.profile.evidence, prec, sites, final_k, site_ops=ops
        )
        o.telemetry.record_series(
            scope, sites, bsteps, k, grew, shrank, coverage=cov
        )

    def _drain_ensemble_telemetry(self, res, steps):
        """Per-member final-tracker drain after a concrete run_ensemble."""
        o = _obs.active()
        if o is None or o.telemetry is None or res.tracker is None:
            return
        leaves = jax.tree_util.tree_leaves(res.tracker)
        if any(isinstance(x, jax.core.Tracer) for x in leaves):
            return
        base = o.telemetry.unique_scope(f"ens:{self.stepper.name}")
        n_members = res.tracker.state.k.shape[0]
        for i in range(n_members):
            tr_i = jax.tree_util.tree_map(lambda x: x[i], res.tracker)
            o.telemetry.record_tracker(f"{base}/m{i}", tr_i, steps)

    def _run_reference_captured(
        self, steps, every, n_out, rem, state0, tracker, prec, spec, storage="f32"
    ) -> SimResult:
        """The reference loop with range capture: the exponent-count
        accumulator rides the scan carry next to the tracker, per-step site
        evidence is a scan output, and each snapshot interval emits its
        count delta (the profile's time axis). Boundary storage rounding is
        applied exactly as in the uncaptured loop (one pack per boundary)."""
        stepper, cfg = self.stepper, self.cfg
        n_sites = len(stepper.sites)
        counts0 = jnp.zeros((n_sites, 2, spec.n_bins), jnp.int32)
        packed_mode = storage == "packed"

        def body(carry, _):
            state, tr, counts = carry
            ops = StepOps(prec, tr, capture=(spec, stepper.sites, counts))
            state = stepper.step(state, cfg, ops)
            return (state, ops.tracker, ops.cap_counts), ops.cap_evidence

        def _boundary(state):
            if storage == "quantized":
                return storage_quantize(state, prec.fmt)
            return pack_state(state, prec.fmt) if packed_mode else state

        def outer(carry, _):
            state, tr, counts = carry
            before = counts
            if packed_mode:
                state = unpack_state(state)
            (state, tr, counts), evs = jax.lax.scan(
                body, (state, tr, counts), None, length=every
            )
            state = _boundary(state)
            obs = stepper.observables(
                unpack_state(state) if packed_mode else state, cfg
            )
            return (state, tr, counts), (obs, evs, counts - before)

        carry = (state0, tracker, counts0)
        carry, (snaps, evs, exp_time) = jax.lax.scan(outer, carry, None, length=n_out)
        evidence = evs.reshape((n_out * every, n_sites, 2))
        if rem:
            state, tr, counts = carry
            if packed_mode:
                state = unpack_state(state)
            (state, tr, counts), evs_rem = jax.lax.scan(
                body, (state, tr, counts), None, length=rem
            )
            carry = (_boundary(state), tr, counts)
            evidence = jnp.concatenate([evidence, evs_rem], axis=0)
        state, tracker, exp_total = carry
        return SimResult(state, snaps, tracker, CaptureResult(evidence, exp_time, exp_total))

    def _run_fused(
        self,
        steps: int,
        every: int,
        state0,
        tracker,
        *,
        prec=None,
        capture=None,
        storage: str = "f32",
    ) -> SimResult:
        """The fused plane's chunked loop: one multi-substep kernel call per
        snapshot interval, tracker evidence folded in between chunks.

        The carried tracker's per-site splits enter each chunk as the rr
        family's k floor (the adjust unit's persistent format choice); the
        chunk's per-substep evidence then replays through the same
        adjust-unit math the stepwise loop applies
        (:func:`repro.precision.fold_evidence`). With ``capture``, the
        kernels' widened evidence stream (per-site exponent counts) comes
        back per chunk and assembles into the run's profile.

        Packed storage has two shapes here. Steppers with
        ``fused_packed = True`` take the PackedArray carry straight into the
        kernel (``fused_step(..., storage="packed")``): unpack rides the
        sweep prologue and repack its epilogue, so the chunk's HBM traffic
        is the payload — ``fmt.total_bits`` per element instead of 32.
        Otherwise the driver packs at the XLA boundary around the f32
        ``fused_step``: same bits (one pack per boundary either way), no
        bandwidth win inside the chunk.
        """
        stepper, cfg = self.stepper, self.cfg
        prec = self.prec if prec is None else prec
        in_kernel = storage == "packed" and getattr(stepper, "fused_packed", False)

        def chunk(carry, n):
            state, tr = carry
            if storage == "packed" and not in_kernel:
                state = unpack_state(state)
            res = stepper.fused_step(
                state,
                cfg,
                prec,
                n,
                k_floor=None if tr is None else tr.state.k,
                # pinned runs never fold evidence, so don't collect it either
                collect_evidence=capture is not None
                or (tr is not None and not prec.pinned),
                capture=capture,
                **({"storage": "packed"} if in_kernel else {}),
            )
            state, ev = res[:2]
            if storage == "quantized":
                state = storage_quantize(state, prec.fmt)
            elif storage == "packed" and not in_kernel:
                state = pack_state(state, prec.fmt)
            if tr is not None:
                tr = fold_evidence(tr, ev, prec, ops=stepper.site_ops or None)
            return (state, tr), ev, (res[2] if capture is not None else None)

        def outer(carry, _):
            carry, ev, counts = chunk(carry, every)
            obs = stepper.observables(
                unpack_state(carry[0]) if storage == "packed" else carry[0], cfg
            )
            return carry, (obs if capture is None else (obs, ev, counts))

        n_out = steps // every
        rem = steps - n_out * every
        carry = (state0, tracker)
        carry, snaps = jax.lax.scan(outer, carry, None, length=n_out)
        evidence = exp_time = exp_total = None
        if capture is not None:
            snaps, evs, exp_time = snaps
            evidence = evs.reshape((n_out * every, len(stepper.sites), 2))
            exp_total = jnp.sum(exp_time, axis=0, dtype=jnp.int32)
        if rem:
            # the one remainder epilogue: a short chunk under the same law as
            # the in-loop cadence (storage rounding included), its evidence
            # and counts appended to the captured stream when profiling
            carry, ev_rem, counts_rem = chunk(carry, rem)
            if capture is not None:
                evidence = jnp.concatenate([evidence, ev_rem], axis=0)
                exp_total = exp_total + counts_rem
        state, tracker = carry
        return SimResult(
            state, snaps, tracker,
            self._assemble_profile(capture, evidence, exp_time, exp_total),
        )

    @staticmethod
    def _assemble_profile(capture, evidence, exp_time, exp_total):
        """Shared capture epilogue for the fused and megakernel planes."""
        if capture is None:
            return None
        return CaptureResult(evidence, exp_time, exp_total)

    def _run_mega(
        self,
        steps: int,
        every: int,
        state0,
        tracker,
        *,
        prec=None,
        capture=None,
        storage: str = "f32",
    ) -> SimResult:
        """The megakernel plane (DESIGN.md §14): the whole horizon in ONE
        ``pallas_call``.

        Where :meth:`_run_fused` re-enters a kernel per snapshot interval
        and folds range evidence on the host between chunks, here the
        stepper's ``mega_step`` keeps state AND adjust unit on-chip for all
        ``steps`` substeps: tracker rows evolve per substep through the
        jax-pure scalar law :func:`repro.core.policy.adjust_step`, the
        *datapath* floor latches at snapshot boundaries — the chunked
        plane's fold cadence, which is what keeps the two planes
        bit-identical — and snapshots / evidence / capture histograms
        stream out as secondary kernel outputs at their cadence. Boundary
        storage rounding (``"quantized"``/``"packed"``) happens in-kernel
        with the shared pack helpers: same splits, same bits, one (virtual)
        pack per boundary.
        """
        stepper, cfg = self.stepper, self.cfg
        prec = self.prec if prec is None else prec
        res = stepper.mega_step(
            state0,
            cfg,
            prec,
            steps,
            every,
            tracker=None if tracker is None else tracker.state,
            capture=capture,
            storage=storage,
        )
        snaps = jax.vmap(lambda s: stepper.observables(s, cfg))(res.snaps)
        if tracker is not None:
            tracker = rewrap(tracker, res.tracker)
        return SimResult(
            res.state, snaps, tracker,
            self._assemble_profile(capture, res.evidence, res.exp_time, res.exp_total),
        )

    # -- ensembles ----------------------------------------------------------

    def run_ensemble(
        self,
        state0_batch,
        steps: int,
        *,
        snapshot_every: Optional[int] = None,
        sharded: bool = False,
        execution: str = "reference",
        capture=None,
        policy=None,
        tracker0_batch=None,
        storage: str = "f32",
    ) -> SimResult:
        """Vmapped ensemble over a batch of initial conditions.

        ``state0_batch`` is the stepper's state pytree with a leading member
        dim on every leaf. Each member carries its own tracker rows (the
        per-member precision-adjust state the hardware would have). With
        ``sharded=True`` the member dim is annotated as the logical
        ``batch`` axis, so inside a ``dist.sharding.axis_rules(mesh)``
        context the ensemble spreads over the mesh's data axes — the
        production-scale path for parameter sweeps and uncertainty
        quantification. The members run under a ``shard_map``: each device
        advances its own members with no collective, so the Pallas kernels
        (which the compiler cannot partition) stay local to their device.
        Outside a context, or when the mesh's batch axes do not divide the
        member count, the ensemble runs unsharded.
        ``capture``/``policy`` behave as in :meth:`run`,
        per member (each member gets its own histograms and evidence).

        ``tracker0_batch`` resumes tracked modes from a *stacked* tracker
        (a SiteTracker whose state arrays lead with the member dim — e.g.
        the ``tracker`` a previous ``run_ensemble`` returned). This is the
        repacking contract ``repro.service`` builds its continuous batching
        on: the serving plane hands a chunk's returned ``(state, tracker)``
        straight back in, restacked only when members drained or joined —
        each member's carried split ``k`` and §5.3 adjustment counters
        survive the repack because they are handed straight back here.

        ``storage`` behaves as in :meth:`run`, per member; a packed
        ensemble's state batch (initial and returned) is a PackedArray tree
        whose children lead with the member dim — the repacking contract
        above carries packed members between service chunks without ever
        widening them to f32 in HBM.
        """
        with _obs.span(
            "sim.run_ensemble",
            stepper=self.stepper.name,
            mode=self.prec.mode,
            steps=steps,
            execution=execution,
            sharded=bool(sharded),
        ):
            res = self._run_ensemble(
                state0_batch,
                steps,
                snapshot_every=snapshot_every,
                sharded=sharded,
                execution=execution,
                capture=capture,
                policy=policy,
                tracker0_batch=tracker0_batch,
                storage=storage,
            )
        self._drain_ensemble_telemetry(res, steps)
        return res

    def _run_ensemble(
        self,
        state0_batch,
        steps: int,
        *,
        snapshot_every: Optional[int] = None,
        sharded: bool = False,
        execution: str = "reference",
        capture=None,
        policy=None,
        tracker0_batch=None,
        storage: str = "f32",
    ) -> SimResult:
        # resolve once outside the vmap so an ineligible explicit "fused"
        # raises eagerly with the real reason rather than from inside a trace
        execution = self._resolve_execution(execution)
        storage = self._resolve_storage(storage)

        def one(s0, tr0=None):
            return self.run(
                steps,
                snapshot_every=snapshot_every,
                state0=s0,
                tracker=tr0,
                execution=execution,
                capture=capture,
                policy=policy,
                storage=storage,
            )

        args = (state0_batch,) if tracker0_batch is None else (state0_batch, tracker0_batch)
        run = jax.vmap(one)
        n_members = jax.tree_util.tree_leaves(state0_batch)[0].shape[0]
        spec = member_spec(n_members) if sharded else None
        if spec is not None:
            # every argument and result leaf (state, snapshots, tracker rows)
            # leads with the member dim: split them all over the batch axes
            mesh, members = spec
            run = jax.shard_map(
                run, mesh=mesh, in_specs=(members,) * len(args), out_specs=members,
                check_vma=False,
            )
        return run(*args)
