"""The service's job model: what one simulation request is, resolved once.

A :class:`SimRequest` is the wire-level ask — stepper name, config
overrides, precision mode, an optional validated
:class:`~repro.profile.artifact.PrecisionPolicy` artifact, horizon and
snapshot cadence. Admission resolves it into a :class:`RequestRecord`, the
mutable runtime record the scheduler buckets and the batcher advances:

* the precision string/preset becomes an **effective**
  :class:`~repro.core.policy.PrecisionConfig` — policy artifacts are
  resolved through the shared :func:`repro.profile.artifact.resolve_policy`
  gate (validated-only, format re-base) and their ``[k_lo, k_hi]`` hints
  installed via ``PrecisionPolicy.apply`` (site names are the stepper's
  own, so the positional install is safe here, unlike the LM path);
* tracked modes get a per-request :class:`~repro.precision.sites.SiteTracker`
  seeded at the artifact's tuned splits (or the wide default) — this is the
  per-member adjust-unit state that survives bucket repacking;
* ``execution="auto"`` is resolved **at admission**, so the bucket key is
  concrete and an ineligible explicit ``"fused"`` fails fast at submit
  instead of mid-flight.

The :class:`BucketKey` is the compatibility contract of the scheduler:
requests sharing ``(stepper, cfg, effective precision, execution plane,
state-shape signature)`` step through bit-identical per-member programs and
may therefore share one vmapped fused ensemble call. ``cfg`` (a frozen
dataclass) subsumes the grid shape for builtin steppers; the explicit shape
signature additionally guards custom ``state0`` pytrees.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core.policy import PRESETS, PrecisionConfig
from repro.pde.solver import Simulation
from repro.profile.artifact import PrecisionPolicy, resolve_policy

from .stream import ResultStream

__all__ = [
    "SimRequest",
    "RequestRecord",
    "RequestResult",
    "BucketKey",
    "resolve_request",
    "scaled_state0",
]


@dataclasses.dataclass
class SimRequest:
    """One client ask. Everything beyond ``stepper``/``steps`` is optional.

    ``precision`` may be a preset name (``"r2f2_16"``, ``"e5m10"``, ...), a
    bare mode name (``"rr_tracked"``, ``"deploy"``), or a full
    :class:`PrecisionConfig`. ``overrides`` are ``dataclasses.replace``
    fields on the stepper's default config (or on ``cfg`` when given).
    ``policy`` is a PrecisionPolicy artifact (object or JSON path) — it must
    be validated-accepted and profiled for this stepper. ``state0`` replaces
    the stepper's initial condition (a pytree matching ``init_state``'s
    structure). ``storage`` selects the carried-state format between chunks
    (:data:`repro.pde.solver.STORAGE_MODES` — ``"packed"`` members carry
    R2F2 payloads through the whole bucket lifecycle, including eviction).
    ``tag`` is a free-form client label echoed in reports.
    """

    stepper: str
    steps: int
    precision: Union[str, PrecisionConfig] = "f32"
    overrides: Optional[Dict[str, Any]] = None
    cfg: Any = None
    policy: Union[str, PrecisionPolicy, None] = None
    snapshot_every: Optional[int] = None
    execution: str = "auto"
    state0: Any = None
    storage: str = "f32"
    tag: str = ""


class RequestResult(NamedTuple):
    """Terminal payload of a completed request (host-side arrays)."""

    state: Any  # final solver state (numpy pytree)
    snapshots: List[Any]  # observable frames, arrival order
    snapshot_steps: List[int]
    tracker: Optional[Any]  # final SiteTracker (tracked modes)
    final_k: Optional[Dict[str, int]]  # per-site converged splits
    adjustments: Optional[Dict[str, Tuple[int, int]]]  # site -> (grew, shrank)
    elapsed: int
    chunks: int  # how many bucket chunks this request rode


class BucketKey(NamedTuple):
    """Scheduler compatibility key — see module docstring. ``storage`` is
    part of the key: members carrying packed state step through a different
    compiled program (PackedArray carry) than f32 members and must never
    share a stack with them."""

    stepper: str
    cfg: Any
    prec: PrecisionConfig
    execution: str
    shape_sig: Any
    storage: str = "f32"

    def short(self) -> str:
        s = f"{self.stepper}/{self.prec.mode}/{self.execution}"
        return s if self.storage == "f32" else f"{s}/{self.storage}"


def _shape_sig(state) -> Tuple:
    leaves, treedef = jax.tree_util.tree_flatten(state)
    return (treedef, tuple((tuple(x.shape), str(x.dtype)) for x in leaves))


def _resolve_precision(precision: Union[str, PrecisionConfig]) -> PrecisionConfig:
    if isinstance(precision, PrecisionConfig):
        return precision
    if precision in PRESETS:
        return PRESETS[precision]
    # bare mode name ("rr_tracked", "deploy", a registered third-party mode);
    # PrecisionConfig validates against the registry's known modes
    return PrecisionConfig(mode=precision)


class RequestRecord:
    """The live, mutable runtime record of one admitted request.

    ``state``/``tracker`` are the member's carried simulation state between
    chunks. While the member runs, they live as its row of its bucket's
    resident batch (``resident_in``) and a read returns that row from the
    host copy of the batch, fetched at most once per chunk; otherwise
    (queued, evicted, finished) the record holds its own copy. The batcher stacks a joining member's
    copy into the batch and materialises it again when the member leaves,
    so the adjust unit's ``k`` and §5.3 counters genuinely survive
    repacking, eviction and resume.

    Lifecycle (``status``): ``queued`` -> ``running`` -> (``evicted`` <->
    ``running``) -> ``done`` | ``failed``.
    """

    def __init__(self, rid: int, req: SimRequest, sim: Simulation, key: BucketKey,
                 state, tracker, steps: int, every: int):
        self.id = rid
        self.req = req
        self.sim = sim
        self.key = key
        #: the Bucket whose resident batch holds this member's carried
        #: (state, tracker), or None while the record holds its own copy
        self.resident_in = None
        self.state = state
        self.tracker = tracker
        self.tracked = tracker is not None
        self.steps = steps
        self.every = every
        self.elapsed = 0
        self.chunks = 0
        self.status = "queued"
        self.stream = ResultStream()
        self.snapshots: List[Tuple[int, Any]] = []
        self.result: Optional[RequestResult] = None
        self.error: Optional[str] = None  # set when status == "failed"
        self.ckpt_dir: Optional[str] = None
        self.templates = None  # ShapeDtypeStruct tree for ckpt restore
        # time.perf_counter() when admission resolved the request, when it
        # first joined a bucket (a resume keeps that) and when it finished
        self.submitted_at: float = time.perf_counter()
        self.joined_at: Optional[float] = None
        self.done_at: Optional[float] = None

    # -- carried state -------------------------------------------------------

    @property
    def state(self):
        if self.resident_in is not None:
            return self.resident_in.row(self, 0)[0]
        return self._state

    @state.setter
    def state(self, value):
        self._state = value

    @property
    def tracker(self):
        if self.resident_in is not None:
            return self.resident_in.row(self, 1)[0]
        return self._tracker

    @tracker.setter
    def tracker(self, value):
        self._tracker = value

    # -- scheduling queries --------------------------------------------------

    @property
    def remaining(self) -> int:
        return self.steps - self.elapsed

    def steps_to_next_event(self, after: int = 0) -> int:
        """Steps until this member next needs the bucket to pause — its own
        snapshot point or its horizon, whichever is sooner — counted from
        ``after`` steps past ``elapsed``. The bucket chunk is the min of this
        over members (continuous batching never steps a member past one of
        its events)."""
        at = self.elapsed + after
        return min(self.steps - at, self.every - (at % self.every))

    def snapshot_due(self) -> bool:
        """Does the current ``elapsed`` coincide with one of the snapshot
        points a solo ``Simulation.run(steps, snapshot_every=every)`` would
        record? Exactly the positive multiples of the cadence: chunking
        never advances past the horizon, so every such multiple is one the
        solo run snapshots (remainder steps never land on one)."""
        return self.elapsed > 0 and self.elapsed % self.every == 0

    def site_summary(self):
        """(final_k, adjustments) dicts from the carried tracker, or Nones."""
        if self.tracker is None:
            return None, None
        st = self.tracker.state
        names = self.tracker.names
        final_k = {n: int(st.k[i]) for i, n in enumerate(names)}
        adjustments = {
            n: (int(st.overflow_steps[i]), int(st.shrink_steps[i]))
            for i, n in enumerate(names)
        }
        return final_k, adjustments

    def __repr__(self) -> str:
        return (
            f"RequestRecord(id={self.id}, {self.key.short()}, "
            f"{self.elapsed}/{self.steps}, {self.status})"
        )


def resolve_request(rid: int, req: SimRequest) -> RequestRecord:
    """Admission-time resolution: validate and freeze everything static.

    Raises (rejecting the request before it enters the queue) on: unknown
    stepper/mode, invalid horizon, unvalidated or foreign policy artifacts,
    format-mismatched artifacts, and explicitly-requested-but-ineligible
    fused execution.
    """
    if req.steps <= 0:
        raise ValueError(f"request horizon must be positive, got {req.steps}")
    if req.snapshot_every is not None and req.snapshot_every <= 0:
        raise ValueError(
            f"snapshot_every must be positive, got {req.snapshot_every} — a "
            "non-positive cadence would drive bucket chunking backwards"
        )

    prec = _resolve_precision(req.precision)
    sim0 = Simulation(req.stepper, req.cfg, prec)  # resolves stepper + default cfg
    stepper, cfg = sim0.stepper, sim0.cfg
    if req.overrides:
        cfg = dataclasses.replace(cfg, **req.overrides)

    policy = None
    if req.policy is not None:
        prec, policy = resolve_policy(prec, req.policy)  # accepted-gate + fmt rebase
        if policy.stepper != stepper.name:
            raise ValueError(
                f"policy artifact was profiled for stepper {policy.stepper!r} "
                f"but the request targets {stepper.name!r}; per-site splits "
                "do not transfer across steppers"
            )
        prec = policy.apply(prec, stepper.sites)  # [k_lo, k_hi] -> prec.k_bounds

    sim = Simulation(stepper, cfg, prec)
    execution = sim._resolve_execution(req.execution)  # "auto" -> concrete plane
    storage = sim._resolve_storage(req.storage)  # reject unknown formats at admit

    state0 = stepper.init_state(cfg) if req.state0 is None else req.state0
    state0 = jax.tree_util.tree_map(jnp.asarray, state0)
    tracker = sim.init_tracker(
        k0=None if policy is None else policy.k_array(stepper.sites)
    )
    every = req.snapshot_every or max(1, req.steps // stepper.snapshots_default)

    key = BucketKey(stepper.name, cfg, prec, execution, _shape_sig(state0), storage)
    return RequestRecord(rid, req, sim, key, state0, tracker, req.steps, every)


def scaled_state0(stepper_name: str, scale: float = 1.0, overrides=None):
    """A stepper's default initial condition scaled by ``scale`` (with
    optional config-override fields) — the burst drivers' way of submitting
    members that genuinely differ while staying bucket-compatible."""
    from repro.pde.registry import get_stepper

    stepper = get_stepper(stepper_name)
    cfg = stepper.default_config()
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    return jax.tree_util.tree_map(
        lambda x: (scale * x).astype(x.dtype), stepper.init_state(cfg)
    )
