"""Budget-elastic streaming trainer: live re-plan + state remap (paper §5.2).

Ferret's headline claim is adaptivity to *varying* memory budgets (Ferret_M,
Alg. 2+3), but a plan is chosen once per run everywhere else in the repo.
This module runs one stream in **segments**: when the memory budget changes
mid-stream — a scheduled ``BudgetEvent``, a callback, or a simulated device
loss escalated through ``Supervisor.on_fatal`` — it

  1. re-enters the planner for the new budget (Alg. 3 ∘ Alg. 2),
  2. rebuilds the ``EngineSchedule``/``FerretEngine`` for the new partition
     (the worker-interleave ``phase`` continues from the stream cursor), and
  3. **remaps live state across partition boundaries** through
     ``repro.state.StateRemapper``: stage params are merged
     (``T.merge_stage_params``) and re-split on the new
     ``plan.partition.bounds``; per-parameter optimizer moments,
     Iter-Fisher λ statistics, *and the gradient-accumulation/Δθ rings*
     all travel with them — no learned or in-flight state is thrown
     away. Across *same-schedule* boundaries (stage count and pipeline
     config unchanged — segment caps, callable polls, A→A switches, and
     bounds-only re-partitions) each segment runs a slice of one
     per-structure schedule build (``slice_schedule``; construction is
     causal, so slicing one big build *is* the continuation) and the
     rings continue — remapped slot-wise when the bounds moved. A
     schedule-*restarting* switch (stage count or config changed)
     flushes every in-flight accumulation group into the weights before
     the remap, so ``rounds_lost_per_switch == 0`` either way; the only
     way to drop in-flight rounds is the explicit
     ``carry_rings=False`` escape hatch, which reports what it dropped.

Compile-once hot path: engines are cached in an ``EngineCache`` keyed on
``(partition bounds, ring geometry, bucketed segment length)``. Segment
lengths are padded up to a small geometric bucket set with *inert*
schedule rounds (identity on engine state), so repeated and A→B→A budget
switches reuse already-compiled scans instead of re-tracing; hit/miss
counts ride in ``ElasticStreamResult``.

Incremental streaming: ``run_stream`` consumes a ``StreamSource`` directly
(a dict-of-arrays is wrapped in a compat ``ArrayStreamSource``). The
segment loop pulls ``take(segment_rounds)`` per segment through a
``BufferedStreamSource`` feeder — peak stream residency is
O(segment_rounds + prefetch window) on host *and* device, never O(R) —
and prefetches segment k+1 on a background thread while segment k runs on
device. Unknown stream length (``length=None``) works end to end: the
per-structure schedule is grown causally (a longer ``build_schedule`` is
bit-identical on its prefix — the same continuation ``warmup=`` computes),
and the run ends when the source does. The algorithm's pipeline-path
stream preparation (``prepare_stream``: ER replay mixing, LwF teacher
logits) is applied per pulled chunk, exactly once and in stream order, so
the incremental run is bit-exact with the materialized whole-stream
preparation.

The stream cursor advances only when a segment completes: the feeder
retains every handed-out round until the segment is acked, so a failed or
re-planned segment replays the *same* rounds from the retained buffer —
no item is lost and none is consumed twice, without requiring ``seek`` on
unbounded sources.

A crashed run resumes the same way: ``load_resume_state`` reads the newest
per-segment checkpoint (state + the partition it was split on + the stream
cursor from the manifest extras), remaps it onto whatever partition the
*restart's* budget plans, and ``run_stream(..., resume=...)`` continues
from the saved cursor — seekable sources are positioned there; a live
(non-seekable) source must already be positioned at the resume cursor.
Every stream item is still consumed exactly once.

Note: this trainer is the internal engine behind the ``"elastic"`` runner
of ``repro.api.FerretSession`` — prefer the session layer for new code.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import math
import os
import warnings
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.streams import (
    BufferedStreamSource,
    LimitedStreamSource,
    StreamSource,
    coerce_trainer_stream,
)
from repro.checkpointing.checkpoint import (
    CheckpointCorruptError,
    checkpoint_schema,
    latest_checkpoint,
    plan_manifest,
    restore_checkpoint,
    save_checkpoint,
    verify_checkpoint,
)
from repro.faults import TransientFaultError
from repro import faults as faults_lib
from repro.core import compensation as comp_lib
from repro.core import planner as planner_lib
from repro.core import schedule as sched_lib
from repro.core.ferret import (
    EngineCache,
    FerretConfig,
    IdentityKey,
    StreamResult,
    empirical_adaptation_rate,
    split_penalty_extras,
    stage_penalty_fn,
)
from repro.core.pipeline import FerretEngine, staged_from_transformer
from repro.core.profiler import ModelProfile, profile_for
from repro.core.schedule import RingGeometry
from repro.core.spans import span
from repro.models import shard_hints as shard_hints_lib
from repro.models.config import ModelConfig
from repro.ocl.registry import OCLAlgorithm, PrepareContext, get_algorithm
from repro.optim.optimizers import Optimizer, adamw
from repro.runtime.elastic import DeviceLossError
from repro.runtime.supervisor import Supervisor, SupervisorCfg
from repro.state import StateRemapper
from repro.state import remap as state_remap
from repro.state.engine_state import EngineState

Pytree = Any
BudgetSchedule = Union[Sequence["BudgetEvent"], Callable[[int], Optional[float]]]

# A segment that keeps losing devices faster than shrink-replans can help is
# a cluster problem, not a planning problem — surface it instead of looping.
_MAX_FAULTS_PER_SEGMENT = 5


@dataclasses.dataclass(frozen=True)
class BudgetEvent:
    """From stream round ``round`` on, the memory budget is ``budget_bytes``."""

    round: int
    budget_bytes: float


@dataclasses.dataclass
class SegmentReport:
    start: int  # first stream round of the segment (inclusive)
    end: int  # one past the last round
    budget_bytes: float
    replanned: bool  # did this segment start with a re-plan + remap?
    replan_s: float  # host-side planner time (0.0 when not replanned)
    remap_s: float  # merge/re-split remap time (0.0 when not replanned)
    run_s: float  # schedule build to the fetch of the results: engine build, compile, scan
    result: StreamResult
    cache_hit: bool = False  # compiled scan reused from the engine cache
    rounds_compiled: int = 0  # bucketed scan length this segment ran under
    take_s: float = 0.0  # wall time blocked pulling this segment's rounds
    # in-flight accumulated backward rounds discarded entering this segment
    # (0 on the default lossless path: rings are carried or flushed; only
    # the carry_rings=False escape hatch, or a geometry-mismatched resume,
    # can make this non-zero)
    rounds_lost: int = 0


@dataclasses.dataclass
class ElasticStreamResult:
    segments: List[SegmentReport]
    online_acc: float
    online_acc_curve: np.ndarray  # continuous across segments (no restart)
    losses: np.ndarray
    admitted_frac: float
    empirical_rate: float  # round-weighted across segments
    final_params: Pytree
    rounds: int  # stream rounds consumed this run (each exactly once)
    num_replans: int
    num_faults: int
    engine_cache_hits: int = 0  # compiled-scan reuses during this run
    engine_cache_misses: int = 0  # fresh compiles during this run
    peak_buffered_rounds: int = 0  # max stream rounds resident in the feeder
    stream_wait_s: float = 0.0  # total un-overlapped time blocked on the source
    # max over segments of SegmentReport.rounds_lost: 0 means every switch
    # this run made was lossless (in-flight rings carried or flushed)
    rounds_lost_per_switch: int = 0


# ---------------------------------------------------------------------------
# State remap across partition boundaries — moved to repro.state.
# The old import paths below keep working but warn; new code should use
# repro.state.StateRemapper / repro.state.remap_* directly.
# ---------------------------------------------------------------------------


def _deprecated_remap(name: str, target: Callable) -> Callable:
    @functools.wraps(target)
    def wrapper(*args, **kwargs):
        warnings.warn(
            f"repro.runtime.elastic_trainer.{name} moved to "
            f"repro.state.{name}; this alias will be removed",
            DeprecationWarning,
            stacklevel=2,
        )
        return target(*args, **kwargs)

    wrapper.__name__ = name
    wrapper.__qualname__ = name
    return wrapper


remap_stage_params = _deprecated_remap(
    "remap_stage_params", state_remap.remap_stage_params
)
remap_opt_states = _deprecated_remap(
    "remap_opt_states", state_remap.remap_opt_states
)
remap_comp_states = _deprecated_remap(
    "remap_comp_states", state_remap.remap_comp_states
)


def remap_engine_state(
    model_cfg: ModelConfig,
    engine_state,
    old_bounds,
    new_bounds,
    optimizer: Optimizer,
):
    """Deprecated: use ``repro.state.StateRemapper`` instead.

    This legacy helper keeps its historical contract — it returns only
    ``(stage_params, opt_states, comp_states)`` and **drops the rings** —
    but no longer does so silently: the warning below names the lossless
    replacement. ``StateRemapper.remap`` carries (or flushes) the rings
    and reports ``rounds_lost``; ``carry_rings=False`` is its documented
    escape hatch for the old behavior.
    """
    warnings.warn(
        "repro.runtime.elastic_trainer.remap_engine_state drops the "
        "gradient-accumulation/Δθ rings; use repro.state.StateRemapper "
        "for a lossless remap (carry_rings=False reproduces this "
        "behavior explicitly)",
        DeprecationWarning,
        stacklevel=2,
    )
    stages, _rings, _deltas, opts, comps = engine_state
    new_sp = state_remap.remap_stage_params(model_cfg, list(stages), new_bounds)
    new_opts = state_remap.remap_opt_states(
        model_cfg, opts, old_bounds, new_bounds, optimizer, new_sp
    )
    new_comps = state_remap.remap_comp_states(
        model_cfg, comps, old_bounds, new_bounds
    )
    return new_sp, new_opts, new_comps


# ---------------------------------------------------------------------------
# Crash-restore: checkpointed state → a new partition
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ResumeState:
    """Live state recovered from a checkpoint, plus where it came from.

    ``bounds`` is the partition the per-stage trees are split on (from the
    checkpoint manifest); ``cursor`` is the first not-yet-consumed stream
    round. ``run_stream(..., resume=...)`` remaps onto the restart's plan.
    """

    stage_params: List[Pytree]
    opt_states: Tuple
    comp_states: Tuple
    bounds: List[int]
    cursor: int
    budget_bytes: float
    # ring plane (schema-2 checkpoints): the gradient-accumulation and Δθ
    # rings plus the schedule coordinates they are valid under. ``None``
    # rings (schema-1 checkpoints, or a geometry mismatch at resume) mean
    # the restart re-warms its accumulation from zero.
    rings: Optional[Tuple] = None
    deltas: Optional[Tuple] = None
    sched_origin: Optional[int] = None
    geometry: Optional[RingGeometry] = None


# ---------------------------------------------------------------------------
# Steppable runs
# ---------------------------------------------------------------------------

_STOP = object()  # sent into the run generator to end at a segment boundary


class ElasticRun:
    """A steppable handle over one elastic stream run.

    ``step()`` executes exactly one segment (blocking until its rounds are
    available) and returns the ``SegmentReport``, or ``None`` once the
    source is exhausted — at which point ``result()`` holds the final
    ``ElasticStreamResult``. ``stop()`` ends the run early at the current
    segment boundary with everything consumed so far accounted. This is
    the primitive the multi-tenant ``FerretServer`` interleaves across
    tenants: one ``step()`` per scheduling decision, budget re-divisions
    landing through ``trainer.request_budget`` between steps.
    """

    def __init__(self, trainer: "ElasticStreamTrainer", gen, params: Pytree):
        self.trainer = trainer
        self._gen = gen
        self._params = params
        self._started = False
        self._finished = False
        self._broken = False  # an exception escaped the segment generator
        self._result: Optional[ElasticStreamResult] = None
        self.segments: List[SegmentReport] = []

    @property
    def finished(self) -> bool:
        return self._finished

    @property
    def broken(self) -> bool:
        """Did an exception escape a ``step()``? A broken run cannot step
        again (the generator is dead) — ``abort()`` salvages a partial
        result from the segments that did complete."""
        return self._broken

    def buffered_rounds(self) -> int:
        """Rounds pulled into the run's feeder and not yet consumed."""
        feeder = self.trainer._feeder
        return 0 if feeder is None else feeder.pending_round_count()

    def step(self) -> Optional[SegmentReport]:
        """Run exactly one segment; ``None`` once the source is exhausted."""
        if self._finished:
            return None
        try:
            self._started = True
            report = self._gen.send(None)  # None = keep going (starts the gen)
        except StopIteration as stop:
            self._finished = True
            self._result = stop.value
            return None
        except BaseException:
            # the generator is dead (its finally already closed the
            # feeder); mark it so abort() can salvage a partial result
            self._broken = True
            raise
        self.segments.append(report)
        return report

    def stop(self) -> ElasticStreamResult:
        """End the run at the current segment boundary.

        Every round consumed so far stays accounted (exactly-once); an
        unstarted run returns an empty result without touching the source.
        """
        if self._finished:
            return self._result
        self._finished = True
        if not self._started:
            self._gen.close()
            self._result = _empty_elastic_result(self._params)
            return self._result
        try:
            self._gen.send(_STOP)
        except StopIteration as stop:
            self._result = stop.value
        else:  # pragma: no cover — the generator always honors _STOP
            self._gen.close()
            raise RuntimeError("elastic run generator ignored the stop request")
        return self._result

    def abort(self) -> ElasticStreamResult:
        """End the run even after an escaped exception, losing nothing
        already accounted.

        A healthy run stops at the current boundary (same as ``stop()``).
        A broken run's generator is dead, so the completed segments are
        re-assembled into a partial ``ElasticStreamResult`` — the server's
        tenant-quarantine path uses this so one crashing tenant still
        returns what it finished instead of poisoning the serve loop.
        """
        if self._finished:
            return self._result
        if not self._broken:
            return self.stop()
        self._finished = True
        self._gen.close()
        self._result = self._salvage_result()
        return self._result

    def _salvage_result(self) -> ElasticStreamResult:
        segs = self.segments
        if not segs:
            return _empty_elastic_result(self._params)
        # per-segment curves are cumulative within the segment; invert to
        # raw per-round accuracies, then rebuild the continuous curve
        accs = []
        for s in segs:
            c = np.asarray(s.result.online_acc_curve, dtype=np.float64)
            n = np.arange(1, c.size + 1)
            raw = c * n
            raw[1:] -= c[:-1] * n[:-1]
            accs.append(raw)
        acc_cat = np.concatenate(accs)
        consumed = sum(s.end - s.start for s in segs)
        rs = self.trainer.live_resume_state()
        if rs is not None:
            from repro.models import transformer as T

            final_params = T.merge_stage_params(
                self.trainer.model_cfg, list(rs.stage_params)
            )
        else:
            final_params = self._params
        admitted = sum(
            s.result.admitted_frac * (s.end - s.start) for s in segs
        ) / max(consumed, 1)
        rate = sum(
            s.result.empirical_rate * (s.end - s.start) for s in segs
        ) / max(consumed, 1)
        return ElasticStreamResult(
            segments=list(segs),
            online_acc=float(acc_cat.mean()),
            online_acc_curve=np.cumsum(acc_cat) / np.arange(1, acc_cat.size + 1),
            losses=np.concatenate([np.asarray(s.result.losses) for s in segs]),
            admitted_frac=admitted,
            empirical_rate=rate,
            final_params=final_params,
            rounds=int(consumed),
            num_replans=sum(1 for s in segs if s.replanned),
            num_faults=0,  # fault count lived in the dead generator
            rounds_lost_per_switch=max(
                (s.rounds_lost for s in segs), default=0
            ),
        )

    def result(self) -> ElasticStreamResult:
        if not self._finished:
            raise RuntimeError(
                "run still open: step() to exhaustion or stop() first"
            )
        return self._result

    def close(self) -> None:
        """``stop()`` that is safe to call on an already-finished run."""
        if not self._finished:
            if self._broken:
                self.abort()
            else:
                self.stop()


def _empty_elastic_result(params: Pytree) -> ElasticStreamResult:
    return ElasticStreamResult(
        segments=[], online_acc=0.0, online_acc_curve=np.zeros(0),
        losses=np.zeros(0), admitted_frac=0.0, empirical_rate=0.0,
        final_params=params, rounds=0, num_replans=0, num_faults=0,
    )


# ---------------------------------------------------------------------------
# The elastic trainer
# ---------------------------------------------------------------------------


class ElasticStreamTrainer:
    """Runs one stream across a schedule of memory budgets, re-planning and
    remapping live state at every budget change instead of restarting."""

    def __init__(
        self,
        model_cfg: ModelConfig,
        ferret_cfg: FerretConfig,
        batch: int,
        seq: int,
        optimizer: Optional[Optimizer] = None,
        profile: Optional[ModelProfile] = None,
        algorithm: Optional[Union[str, OCLAlgorithm]] = None,
        engine_cache: Optional[EngineCache] = None,
        carry_rings: bool = True,
        topology=None,
    ):
        from repro.runtime.topology import as_topology

        self.model_cfg = model_cfg
        self.cfg = ferret_cfg
        self.batch = batch
        self.seq = seq
        # Topology-aware execution: ``topology`` (a DeviceTopology or
        # "discover") bounds every plan by per-device memory, scales the
        # profile for the data-parallel replicas, runs the engine scans
        # under the topology's mesh, and turns a DeviceLossError into a
        # topology *shrink* (request_shrink) instead of a budget scale.
        # topology=None — and a trivial 1-device topology — is the exact
        # historical single-device path.
        self.topology = as_topology(topology)
        self._mesh = (
            None
            if self.topology is None or self.topology.is_trivial
            else self.topology.mesh()
        )
        self._shard_hints = shard_hints_lib.for_topology(self.topology)
        # store-aware default (Alg. 3 profile(θ)): a persisted on-device
        # measurement for this geometry wins, analytic roofline otherwise.
        # Kept *single-device*: plan_for applies the topology scaling, so a
        # topology shrink replans from the right per-replica numbers.
        self.profile = profile or profile_for(model_cfg, batch, seq)
        self.t_d = ferret_cfg.t_d or planner_lib.default_data_interval(
            self._effective_profile()
        )
        self.optimizer = optimizer or adamw(lr=ferret_cfg.lr)
        self.algorithm = (
            get_algorithm(algorithm, ferret_cfg.ocl)
            if algorithm is not None
            else get_algorithm(ferret_cfg.ocl)
        )
        # carry_rings=False is the documented escape hatch back to the
        # pre-refactor behavior: every re-plan drops the in-flight
        # gradient-accumulation/Δθ rings instead of carrying or flushing
        # them, and the discarded backward rounds are reported per segment
        # as SegmentReport.rounds_lost. Default True: lossless switches.
        self.carry_rings = bool(carry_rings)
        self._remapper = StateRemapper(model_cfg, self.optimizer)
        # Compiled engines survive across run_stream calls on one trainer;
        # pass a shared EngineCache to also share across trainers, or
        # EngineCache(enabled=False) to disable bucketing + reuse.
        self.engine_cache = engine_cache or EngineCache()
        # Cache-key scope: a compiled engine bakes in the model, the
        # algorithm's loss wrapper, the optimizer update rule, lr and
        # compensation config — trainers differing in any of these must
        # never share an engine through a shared EngineCache, even for
        # equal bounds. The scope is *structural* where structure is
        # exact (frozen model config, the algorithm's engine_fingerprint,
        # the optimizer's hyperparameter fingerprint), so same-geometry
        # tenants built from separate-but-equal pieces share one compile;
        # a fingerprint-less optimizer falls back to IdentityKey, which
        # pins the referent so a recycled id can never alias.
        self._cache_scope = self._compute_cache_scope()
        self._pending_budget: Optional[float] = None
        # a topology shrink requested between segments (Supervisor.on_fatal
        # / request_shrink): consumed at the next boundary, where the mesh,
        # cache scope and plan all rebuild over the survivors
        self._pending_topology = None
        # memo for the per-stage split of the algorithm's penalty extras:
        # (bounds, extras dict, split) — recomputed only when the anchor
        # objects or the partition change, so steady-state segments skip
        # the O(model) re-split/re-upload (the entry pins the keyed
        # objects, so identity comparison cannot alias a recycled id)
        self._penalty_split: Optional[Tuple] = None
        # live-run snapshot read by fatal_handler: initialized here so a
        # Supervisor wired *before* the first segment (or between runs) can
        # escalate a device loss into a shrink request instead of tripping
        # over attributes that only exist once run_stream is underway
        self._current_budget: float = float(ferret_cfg.budget_bytes)
        self._current_plan: Optional[planner_lib.Plan] = None
        self._prep_ctx: Optional[PrepareContext] = None
        # the live run's feeder (set while a run/_run_gen is underway):
        # schedulers peek its pending-round count to size segments
        self._feeder: Optional[BufferedStreamSource] = None
        # end-of-segment state snapshot for graceful drain (see
        # live_resume_state / save_live_checkpoint)
        self._live_resume: Optional[ResumeState] = None

    # -- budget control ---------------------------------------------------
    def request_budget(self, budget_bytes: float) -> None:
        """Ask for a re-plan at the next segment boundary (fault path).

        This is what a ``Supervisor.on_fatal`` handler calls when a device
        loss shrinks the cluster: the current segment's failed attempt is
        abandoned (state unchanged), and the re-run happens under the new
        budget from the same stream cursor.
        """
        self._pending_budget = float(budget_bytes)

    def request_shrink(self, lost_devices: int = 1) -> None:
        """Ask for a topology shrink at the next segment boundary.

        This is the device-loss escalation under a discovered topology:
        the trainer's ``DeviceTopology`` loses ``lost_devices`` devices,
        and at the boundary the mesh is rebuilt over the survivors, the
        planner re-enters under the shrunken topology's per-device budget
        and re-scaled profile, and live ``EngineState`` remaps through
        ``StateRemapper`` (``rounds_lost == 0`` on the default lossless
        path). Raises when the trainer has no topology (use
        ``request_budget`` / ``fatal_handler``'s scale path) or when no
        device would survive.
        """
        if self.topology is None:
            raise RuntimeError(
                "request_shrink needs a topology-aware trainer "
                "(ElasticStreamTrainer(topology=...)); use request_budget "
                "for scalar budget shrinks"
            )
        self._pending_topology = self.topology.shrink(lost_devices)

    def fatal_handler(self, scale: float = 0.5) -> Callable[[BaseException], None]:
        """An ``on_fatal`` callback for device-loss escalation.

        Topology-aware trainers turn a ``DeviceLossError`` into a topology
        shrink (``request_shrink(e.lost_devices)``): mesh, plan and cache
        scope rebuild over the surviving devices at the next boundary.
        Without a topology — or when nothing would survive the shrink —
        the legacy policy applies: ``scale`` models the surviving fraction
        of the cluster and shrinks the budget. Under an unconstrained
        budget (Ferret_M+) that shrink is taken relative to the live
        plan's actual footprint — ``inf × scale`` would be a no-op.
        """

        def handler(exc: BaseException) -> None:
            if (
                self.topology is not None
                and not self.topology.is_trivial
                and isinstance(exc, DeviceLossError)
            ):
                try:
                    self.request_shrink(getattr(exc, "lost_devices", 1))
                    return
                except ValueError:
                    pass  # no survivors: fall through to the budget scale
            base = self._current_budget
            if not math.isfinite(base):
                # before the first segment no plan snapshot exists yet —
                # plan for the configured budget instead of crashing
                plan = self._current_plan or self.plan_for(base)
                base = plan.memory
            self.request_budget(base * scale)

        return handler

    def _effective_profile(self) -> ModelProfile:
        """The profile the planner sees: topology-scaled when one is set
        (times and activations divide by the data-parallel width, weights
        replicate), the raw single-device profile otherwise."""
        if self.topology is None:
            return self.profile
        from repro.profile.bridge import for_topology

        return for_topology(self.profile, self.topology)

    def _compute_cache_scope(self) -> Tuple:
        # Cache-key scope: a compiled engine bakes in the model, the
        # algorithm's loss wrapper, the optimizer update rule, lr,
        # compensation config — and, when topology-aware, the topology it
        # was partitioned over (a shrink must never reuse an executable
        # compiled for the lost mesh). The scope is *structural* where
        # structure is exact (frozen model config, the algorithm's
        # engine_fingerprint, the optimizer's hyperparameter fingerprint),
        # so same-geometry tenants built from separate-but-equal pieces
        # share one compile; a fingerprint-less optimizer falls back to
        # IdentityKey, which pins the referent so a recycled id can never
        # alias.
        opt_fp = self.optimizer.fingerprint
        scope = (
            self.model_cfg,
            self.algorithm.engine_fingerprint(),
            opt_fp if opt_fp is not None else IdentityKey(self.optimizer),
            self.cfg.lr,
            self.cfg.compensation,
        )
        if self.topology is not None:
            scope = scope + (self.topology.fingerprint(),)
        return scope

    def _set_topology(self, topology) -> None:
        """Swap the live topology (a consumed shrink): rebuild the mesh
        over the survivors and re-key the engine cache so the next segment
        compiles — and future same-topology segments reuse — executables
        partitioned for the new world."""
        self.topology = topology
        self._mesh = None if topology.is_trivial else topology.mesh()
        self._shard_hints = shard_hints_lib.for_topology(topology)
        self._cache_scope = self._compute_cache_scope()
        if self.cfg.t_d is None:
            self.t_d = planner_lib.default_data_interval(
                self._effective_profile()
            )

    def plan_for(self, budget_bytes: float) -> planner_lib.Plan:
        return planner_lib.plan(
            self._effective_profile(),
            self.t_d,
            budget_bytes,
            c=self.cfg.decay_c,
            V_D=self.cfg.data_value,
            max_workers=self.cfg.max_workers,
            max_stages=self.cfg.max_stages,
            topology=self.topology,
        )

    # -- main entry -------------------------------------------------------
    def run_stream(
        self,
        params: Pytree,
        stream: Union[Dict[str, np.ndarray], StreamSource],
        schedule: BudgetSchedule = (),
        *,
        segment_rounds: Optional[Union[int, Callable[[int], int]]] = None,
        supervisor_cfg: Optional[SupervisorCfg] = None,
        fault_rounds: Sequence[int] = (),
        fault_budget_scale: float = 0.5,
        resume: Optional[ResumeState] = None,
        prefetch: bool = True,
    ) -> ElasticStreamResult:
        """Run a stream across the budget ``schedule``, segment by segment.

        stream: a ``StreamSource`` (consumed incrementally — rounds are
        pulled per segment, never materialized up front) or a dict of
        ``(R, b, ...)`` arrays (compat; wrapped in an ``ArrayStreamSource``
        and still consumed per segment). Unbounded sources
        (``length=None``) run until the source ends; cap them upstream
        (``LimitedStreamSource`` / ``session.run(max_rounds=...)``) for a
        bounded run. The algorithm's ``prepare_stream`` is applied per
        pulled chunk, exactly once, in stream order — pass *raw* rounds,
        not pre-prepared ones.
        schedule: ``BudgetEvent`` list (budget switches at fixed rounds) or a
        callable ``round -> budget_bytes | None`` polled at segment
        boundaries (None keeps the current budget).
        segment_rounds: optional cap on segment length; callable schedules
        and fault injection are only observed at segment boundaries, so this
        bounds their reaction latency. Defaults to 16 for callable
        schedules and for unbounded sources (which need finite segments).
        May itself be a callable ``cursor -> rounds`` re-evaluated at every
        boundary — how the multi-tenant server sizes segments to what a
        live feed has actually buffered instead of blocking a shared serve
        loop on a fixed-size ``take``.
        supervisor_cfg: when given, every segment executes as one supervised
        step — NaN rollback, retries, async checkpoints (plan + cursor in
        the manifest extras), and ``on_fatal`` escalation all active.
        fault_rounds: stream rounds at which a device loss is simulated
        (each fires once); the escalation path shrinks the budget by
        ``fault_budget_scale`` and re-plans. The failed segment re-runs
        from the feeder's retained buffer — exactly-once without ``seek``.
        resume: state recovered by ``load_resume_state`` — the run starts
        at ``resume.cursor`` with the checkpointed state remapped from
        ``resume.bounds`` onto this run's planned partition. Seekable
        sources (arrays) are positioned at the cursor; a live feed must
        already be positioned there.
        prefetch: pull segment k+1 from the source on a background thread
        while segment k runs on device.
        """
        run = self.open_stream(
            params, stream, schedule,
            segment_rounds=segment_rounds, supervisor_cfg=supervisor_cfg,
            fault_rounds=fault_rounds, fault_budget_scale=fault_budget_scale,
            resume=resume, prefetch=prefetch,
        )
        try:
            while run.step() is not None:
                pass
        finally:
            run.close()
        return run.result()

    def open_stream(
        self,
        params: Pytree,
        stream: Union[Dict[str, np.ndarray], StreamSource],
        schedule: BudgetSchedule = (),
        *,
        segment_rounds: Optional[Union[int, Callable[[int], int]]] = None,
        supervisor_cfg: Optional[SupervisorCfg] = None,
        fault_rounds: Sequence[int] = (),
        fault_budget_scale: float = 0.5,
        resume: Optional[ResumeState] = None,
        prefetch: bool = True,
    ) -> "ElasticRun":
        """Open the stream as a *steppable* run (same options as
        ``run_stream``): each ``ElasticRun.step()`` executes exactly one
        segment and returns its ``SegmentReport``; ``stop()`` ends the run
        at the current boundary with every consumed round accounted. This
        is the multiplexing primitive of the multi-tenant server — a
        scheduler interleaves ``step()`` calls across tenants, and budget
        re-divisions land through ``request_budget`` between steps.

        One trainer drives at most one open run at a time (the run borrows
        the trainer's live-state snapshot fields).
        """
        gen = self._run_gen(
            params, stream, schedule,
            segment_rounds=segment_rounds, supervisor_cfg=supervisor_cfg,
            fault_rounds=fault_rounds, fault_budget_scale=fault_budget_scale,
            resume=resume, prefetch=prefetch,
        )
        return ElasticRun(self, gen, params)

    def _run_gen(
        self,
        params: Pytree,
        stream: Union[Dict[str, np.ndarray], StreamSource],
        schedule: BudgetSchedule,
        *,
        segment_rounds,
        supervisor_cfg: Optional[SupervisorCfg],
        fault_rounds: Sequence[int],
        fault_budget_scale: float,
        resume: Optional[ResumeState],
        prefetch: bool,
    ):
        """The segment loop as a generator: yields one ``SegmentReport``
        per segment, receives ``_STOP`` to end at a boundary, and returns
        the final ``ElasticStreamResult`` (``StopIteration.value``)."""
        from repro.models import transformer as T

        source = coerce_trainer_stream(stream, "ElasticStreamTrainer.run_stream")
        events, budget_fn = self._normalize_schedule(schedule)
        pending_faults = sorted(set(int(r) for r in fault_rounds))

        origin = 0
        if resume is not None:
            origin = int(resume.cursor)
            if not _try_seek(source, origin):
                # non-seekable (live/unbounded) source: it must already be
                # positioned at the resume cursor; the feeder's retained
                # buffer still guarantees exactly-once within this run
                pass
        remaining = source.remaining
        R: Optional[int] = None if remaining is None else origin + int(remaining)
        if callable(schedule) and segment_rounds is None:
            segment_rounds = 16
        if segment_rounds is None and (R is None or _base_is_unbounded(source)):
            # a live feed needs finite segments even when a max_rounds cap
            # makes its length known — one O(R) segment would materialize
            # the whole window and defeat the O(segment) residency bound
            segment_rounds = 16

        # per-run preparation context: the algorithm's pipeline-path stream
        # prep (replay mixing, teacher logits) anchors at the params
        # entering the stream, exactly like the materialized whole-stream
        # preparation did; re-plans refresh it (see _refresh_buffered)
        self._prep_ctx = PrepareContext(
            params=params,
            forward_fn=lambda p, b: T.forward(self.model_cfg, p, b)[0],
        )
        feeder = BufferedStreamSource(
            source, transform=self._prepare_rows, prefetch=prefetch
        )
        self._feeder = feeder
        self._live_resume = None  # stale snapshot from a prior run

        event_idx = 0
        budget = self.cfg.budget_bytes
        if budget_fn is not None:
            b0 = budget_fn(0)
            budget = float(b0) if b0 is not None else budget
        while event_idx < len(events) and events[event_idx].round <= 0:
            budget = events[event_idx].budget_bytes
            event_idx += 1
        self._current_budget = budget
        plan = self.plan_for(budget)
        self._current_plan = plan
        bounds = list(plan.partition.bounds)
        opt_states: Optional[Tuple] = None  # None → engine initializes fresh
        comp_states: Optional[Tuple] = None
        cursor = origin
        # Same-structure continuation state: ``prev_plan`` is the plan the
        # carried rings are valid under, ``sched_origin`` the round its
        # schedule structure started at, and ``full_sched`` the one O(R)
        # build for that structure — each segment is a pure slice of it,
        # so host-side schedule work stays O(R) per structure instead of
        # O(R²) over the stream.
        prev_plan: Optional[planner_lib.Plan] = None
        sched_origin = cursor
        full_sched: Optional[sched_lib.EngineSchedule] = None
        rings = deltas = None
        if resume is not None:
            old_bounds = list(resume.bounds)
            geom_now = sched_lib.ring_geometry(
                plan.config, plan.partition.num_stages
            )
            if old_bounds != bounds:
                # Cross-partition restore: the checkpointed run's schedule
                # cannot be reconstructed here, so the rings do not survive
                # — params, moments and λ statistics remap; gradient
                # accumulation re-warms from zero.
                if resume.rings is not None:
                    warnings.warn(
                        "resume partition differs from the restart's plan: "
                        "checkpointed accumulation/Δθ rings were dropped; "
                        "gradient accumulation re-warms over the next "
                        f"~{geom_now.ring_size} rounds",
                        stacklevel=2,
                    )
                stage_params = state_remap.remap_stage_params(
                    self.model_cfg, list(resume.stage_params), bounds
                )
                opt_states = state_remap.remap_opt_states(
                    self.model_cfg, tuple(resume.opt_states), old_bounds,
                    bounds, self.optimizer, stage_params,
                )
                comp_states = state_remap.remap_comp_states(
                    self.model_cfg, tuple(resume.comp_states), old_bounds, bounds
                )
            else:
                stage_params = list(resume.stage_params)
                opt_states = tuple(resume.opt_states)
                comp_states = tuple(resume.comp_states)
                if (
                    resume.rings is not None
                    and resume.sched_origin is not None
                    and resume.geometry == geom_now
                ):
                    # Drain→restore continuation: same partition and ring
                    # geometry, so this run re-enters the *same* causal
                    # schedule at the saved origin — rings and Δθ history
                    # carry, making the restarted stream bit-exact with
                    # the uninterrupted one.
                    rings = tuple(resume.rings)
                    deltas = (
                        None if resume.deltas is None else tuple(resume.deltas)
                    )
                    sched_origin = int(resume.sched_origin)
                    prev_plan = plan  # prime the same-structure check
                elif resume.rings is not None:
                    warnings.warn(
                        "checkpointed rings do not match the restart's ring "
                        "geometry (or lack a schedule origin): dropped; "
                        "gradient accumulation re-warms over the next "
                        f"~{geom_now.ring_size} rounds",
                        stacklevel=2,
                    )
        else:
            stage_params = T.split_stage_params(self.model_cfg, params, bounds)

        segments: List[SegmentReport] = []
        acc_all: List[np.ndarray] = []
        loss_all: List[np.ndarray] = []
        admitted_all: List[np.ndarray] = []
        num_faults = 0
        faults_at_cursor = 0
        cache_hits0 = self.engine_cache.hits
        cache_misses0 = self.engine_cache.misses

        try:
            while R is None or cursor < R:
                with span("ferret.segment", step=len(segments)):
                    # ---- budget for this segment: fault request beats the
                    # schedule. Events are consumed exactly once, so a
                    # fault-shrunk budget is not clobbered by re-reading an
                    # already-applied event.
                    target = budget
                    if budget_fn is not None:
                        b = budget_fn(cursor)
                        if b is not None:
                            target = float(b)
                    while event_idx < len(events) and events[event_idx].round <= cursor:
                        target = events[event_idx].budget_bytes
                        event_idx += 1
                    if self._pending_budget is not None:
                        target, self._pending_budget = self._pending_budget, None
                    replanned, replan_s, remap_s = False, 0.0, 0.0
                    seg_rounds_lost = 0
                    # A pending topology shrink forces the replan even when the
                    # budget number is unchanged (a pure data-parallel loss
                    # keeps the per-device bound but changes the mesh, the
                    # profile scaling, and the cache scope): the survivors'
                    # world replaces the lost one before planning.
                    if self._pending_topology is not None:
                        topo, self._pending_topology = self._pending_topology, None
                        self._set_topology(topo)
                        do_replan = True
                    else:
                        do_replan = target != budget
                    if do_replan:
                        with span("ferret.replan") as replan:
                            new_plan = self.plan_for(target)
                        replan_s = replan.seconds
                        new_bounds = list(new_plan.partition.bounds)
                        P_new = new_plan.partition.num_stages
                        # the schedule depends only on (config, stage count,
                        # phase) — when those survive the switch, the carried
                        # rings stay valid slot-for-slot even across a bounds
                        # change; otherwise the remapper flushes them
                        same_sched = (
                            prev_plan is not None
                            and prev_plan.partition.num_stages == P_new
                            and prev_plan.config == new_plan.config
                        )
                        with span("ferret.remap") as remap:
                            if opt_states is None:
                                if new_bounds != bounds:
                                    # no segment ran yet: only params exist to remap
                                    stage_params = state_remap.remap_stage_params(
                                        self.model_cfg, stage_params, new_bounds
                                    )
                            elif new_bounds != bounds or not same_sched:
                                old_sched = full_sched
                                if old_sched is None and rings is not None:
                                    # resumed rings whose schedule was never built
                                    # this run (a replan before the first segment):
                                    # rebuild the causal prefix they were filled
                                    # under so the remapper can flush/account
                                    old_sched = sched_lib.build_schedule(
                                        plan.config, plan.partition.num_stages,
                                        max(cursor - sched_origin, 1),
                                        phase=sched_origin,
                                    )
                                remapped, seg_rounds_lost = self._remapper.remap(
                                    EngineState(
                                        stage_params=tuple(stage_params),
                                        rings=rings,
                                        deltas=deltas,
                                        opt_states=tuple(opt_states),
                                        comp_states=tuple(comp_states),
                                        bounds=tuple(bounds),
                                        geometry=sched_lib.ring_geometry(
                                            plan.config, plan.partition.num_stages
                                        ),
                                        sched_origin=sched_origin,
                                    ),
                                    new_bounds,
                                    new_geometry=sched_lib.ring_geometry(
                                        new_plan.config, P_new
                                    ),
                                    same_schedule=same_sched,
                                    old_schedule=old_sched,
                                    rounds_into_schedule=cursor - sched_origin,
                                    carry_rings=self.carry_rings,
                                )
                                stage_params = list(remapped.stage_params)
                                opt_states = remapped.opt_states
                                comp_states = remapped.comp_states
                                rings = remapped.rings
                                deltas = remapped.deltas
                        remap_s = remap.seconds
                        budget, plan, bounds, replanned = target, new_plan, new_bounds, True
                        self._current_budget = budget
                        self._current_plan = plan
                        # segment-boundary hook: the algorithm may refresh
                        # segment-constant state (e.g. the LwF teacher) — the
                        # physically buffered rounds in place, future rounds via
                        # the refreshed preparation context.
                        with span("ferret.refresh"):
                            self._refresh_buffered(feeder, stage_params)

                    # ---- pull this segment's rounds (replayed rows first)
                    want = self._segment_end(cursor, R, events, segment_rounds) - cursor
                    with span("ferret.take") as take:
                        rows = feeder.take(want)
                    take_s = take.seconds
                    if rows is None:
                        break  # source exhausted
                    seg_len = next(iter(rows.values())).shape[0]
                    seg_end = cursor + seg_len
                    if seg_len < want:
                        R = seg_end  # source ended early: true stream end found
                    fault_round = next(
                        (r for r in pending_faults if cursor <= r < seg_end), None
                    )

                    # from here to the fetch of its results: the segment's
                    # run time (run_s)
                    with span("ferret.schedule") as schedule:
                        P = plan.partition.num_stages
                        same_struct = (
                            prev_plan is not None
                            and prev_plan.partition.num_stages == P
                            and prev_plan.config == plan.config
                        )
                        if not same_struct:
                            # The schedule restarts here (first segment, or a
                            # stage-count/config change). Ring contents were
                            # already handled by the remapper — flushed into the
                            # weights, Δθ history re-timed — so only the schedule
                            # coordinates reset.
                            sched_origin = cursor
                            full_sched = None
                        need = seg_end - sched_origin
                        if full_sched is None or full_sched.num_rounds < need:
                            # one causal build per structure; segments slice it. A
                            # bounded stream builds straight to its end; an unknown
                            # end grows geometrically — construction is causal, so
                            # a longer rebuild is bit-identical on its prefix (the
                            # same continuation ``build_schedule(warmup=)``
                            # computes), and doubling keeps total host-side
                            # schedule work O(R) per structure.
                            if R is not None:
                                build_len = max(R - sched_origin, need)
                            else:
                                built = 0 if full_sched is None else full_sched.num_rounds
                                build_len = max(need, 2 * built, 64)
                            full_sched = sched_lib.build_schedule(
                                plan.config, P, build_len, phase=sched_origin
                            )
                        bucket_rounds = self.engine_cache.bucket_len(seg_len)
                        engine_sched = sched_lib.pad_schedule(
                            sched_lib.slice_schedule(
                                full_sched, cursor - sched_origin, seg_end - sched_origin
                            ),
                            bucket_rounds,
                        )
                        struct_key = (self._cache_scope, tuple(bounds))
                        compile_key = struct_key + (
                            engine_sched.ring_size, engine_sched.delta_ring, bucket_rounds,
                            self.batch, self.seq, tuple(sorted(rows)),
                        )

                        def _factory(bounds=bounds, engine_sched=engine_sched):
                            staged = self.algorithm.wrap_staged(
                                staged_from_transformer(self.model_cfg, bounds)
                            )
                            return FerretEngine(
                                staged, engine_sched, self.optimizer,
                                self.cfg.compensation, lr=self.cfg.lr,
                                penalty_fn=stage_penalty_fn(self.algorithm),
                                mesh=self._mesh, hints=self._shard_hints,
                            )

                        engine = self.engine_cache.engine_for(struct_key, _factory)
                    # exec_lock spans seen → set_schedule → run → record: a
                    # shared engine (multi-tenant, same geometry) never has its
                    # schedule swapped under an in-flight scan, and concurrent
                    # first-users cannot both count a miss for one compile
                    with engine.exec_lock:
                        with span("ferret.schedule"):
                            cache_hit = self.engine_cache.seen(compile_key)
                            engine.set_schedule(engine_sched)
                            state = engine.init_state(
                                stage_params, opt_states, comp_states,
                                rings=rings, deltas=deltas,
                                bounds=bounds, sched_origin=sched_origin,
                            )
                        with span("ferret.upload"):
                            # only this segment's rounds ever reach the
                            # device: stream residency stays O(segment),
                            # not O(R)
                            seg_stream = {k: jnp.asarray(v) for k, v in rows.items()}
                            if bucket_rounds > seg_len:
                                # bucket padding: repeat the last item (inert
                                # schedule rounds never admit it, so
                                # state/metrics are untouched)
                                seg_stream = {
                                    k: jnp.concatenate(
                                        [v, jnp.repeat(v[-1:], bucket_rounds - seg_len,
                                                       axis=0)]
                                    )
                                    for k, v in seg_stream.items()
                                }
                        # overlap: pull segment k+1 on the host while k computes
                        if R is None or seg_end < R:
                            nxt = self._segment_end(seg_end, R, events, segment_rounds)
                            feeder.prefetch(nxt - seg_end)
                        # segment-constant penalty extras (MAS Ω/ref): re-read
                        # at every boundary so a re-plan refresh is picked up;
                        # rides the compiled scan as an argument, never a
                        # retrace
                        penalty = (
                            self._split_penalty_cached(bounds)
                            if engine.penalty_fn is not None else None
                        )
                        try:
                            with span("ferret.dispatch"):
                                final_state, ys = self._execute_segment(
                                    engine, state, seg_stream, supervisor_cfg,
                                    fault_round, fault_budget_scale, plan, cursor,
                                    seg_end, budget, penalty, sched_origin=sched_origin,
                                )
                            if faults_at_cursor:
                                # a previously-faulted segment just completed:
                                # close out its recovery latency
                                faults_lib.resolved("engine.step")
                            faults_at_cursor = 0
                        except (DeviceLossError, TransientFaultError) as e:
                            # Re-run this segment from the same cursor — state
                            # is unchanged and the feeder re-serves the retained
                            # rows, so the stream stays exactly-once. Injected
                            # faults fire once; a genuine device loss may not
                            # have gone through a Supervisor, so make sure a
                            # shrink was requested, and bail out if shrinking
                            # stops making progress. A transient error re-runs
                            # at the *same* budget: lost capacity shrinks the
                            # plan, a hiccup does not.
                            feeder.rewind()
                            if fault_round is not None:
                                pending_faults.remove(fault_round)
                            num_faults += 1
                            faults_at_cursor += 1
                            if (
                                isinstance(e, DeviceLossError)
                                and self._pending_budget is None
                                and self._pending_topology is None
                            ):
                                self.fatal_handler(fault_budget_scale)(e)
                            if faults_at_cursor > _MAX_FAULTS_PER_SEGMENT:
                                raise
                            continue
                        feeder.ack()  # segment complete: retained rows consumed
                        # account the compile/hit only now: a faulted attempt
                        # above never compiled, and must not poison the perf
                        # counters
                        self.engine_cache.record(compile_key, cache_hit)

                    with span("ferret.fetch") as fetch:
                        ys = jax.device_get(ys)
                    # schedule to the results on the host: the dispatch
                    # returns before the device has run the segment
                    run_s = fetch.end - schedule.start
                    ys = {k: v[:seg_len] for k, v in ys.items()}  # drop bucket padding
                    stage_params = list(final_state.stage_params)
                    rings = tuple(final_state.rings)
                    deltas = tuple(final_state.deltas)
                    opt_states = tuple(final_state.opt_states)
                    comp_states = tuple(final_state.comp_states)
                    prev_plan = plan
                    if self.cfg.profile_feedback and cache_hit:
                        # online refinement: fold observed wall-clock (cache-hit
                        # segments only — a compile would swamp the signal) into
                        # the profile + store; the *next* replan (BudgetEvent,
                        # request_budget, on_fatal) plans from these numbers
                        from repro.profile.bridge import observe_segment

                        refined = observe_segment(
                            self.model_cfg, self.batch, self.seq,
                            self.profile, plan, bucket_rounds, run_s,
                        )
                        if refined is not None:
                            self.profile = refined[0]
                            if self.cfg.t_d is None:
                                self.t_d = planner_lib.default_data_interval(self.profile)

                    acc = np.asarray(ys["acc"], dtype=np.float64)
                    admitted = np.asarray(ys["admitted"], dtype=np.float64)
                    result = StreamResult(
                        online_acc=float(acc.mean()),
                        online_acc_curve=np.cumsum(acc) / np.arange(1, seg_len + 1),
                        losses=np.asarray(ys["loss"]),
                        admitted_frac=float(admitted.mean()),
                        memory_bytes=plan.memory,
                        planned_rate=plan.rate,
                        empirical_rate=empirical_adaptation_rate(self.cfg, plan, admitted, seg_len),
                        lam_curve=np.asarray(ys["lam"]),
                        plan=plan,
                    )
                    segments.append(
                        SegmentReport(
                            start=cursor, end=seg_end, budget_bytes=budget,
                            replanned=replanned, replan_s=replan_s, remap_s=remap_s,
                            run_s=run_s, result=result,
                            cache_hit=cache_hit, rounds_compiled=bucket_rounds,
                            take_s=take_s, rounds_lost=seg_rounds_lost,
                        )
                    )
                    acc_all.append(acc)
                    loss_all.append(np.asarray(ys["loss"]))
                    admitted_all.append(admitted)
                    cursor = seg_end
                    # live end-of-segment snapshot: what a graceful drain
                    # checkpoints (save_live_checkpoint) so a restart resumes
                    # from this exact boundary — exactly-once across restarts
                    self._live_resume = ResumeState(
                        stage_params=list(stage_params),
                        opt_states=tuple(opt_states),
                        comp_states=tuple(comp_states),
                        bounds=list(bounds),
                        cursor=cursor,
                        budget_bytes=budget,
                        rings=tuple(rings),
                        deltas=tuple(deltas),
                        sched_origin=int(sched_origin),
                        geometry=RingGeometry(
                            ring_size=int(engine_sched.ring_size),
                            delta_ring=int(engine_sched.delta_ring),
                        ),
                    )
                # hand the segment to the driver; a _STOP reply ends the
                # run at this boundary with everything consumed accounted
                if (yield segments[-1]) is _STOP:
                    break
        finally:
            feeder.close()
            self._feeder = None

        acc_cat = np.concatenate(acc_all) if acc_all else np.zeros(0)
        admitted_cat = np.concatenate(admitted_all) if admitted_all else np.zeros(0)
        final_params = T.merge_stage_params(self.model_cfg, list(stage_params))
        self.final_params = final_params
        consumed = sum(s.end - s.start for s in segments)
        # round-weighted over the rounds this run actually consumed — a
        # resumed run covers R - resume.cursor rounds, and dividing by the
        # full stream length would dilute the rate by the skipped prefix
        rate = sum(
            s.result.empirical_rate * (s.end - s.start) for s in segments
        ) / max(consumed, 1)
        return ElasticStreamResult(
            segments=segments,
            online_acc=float(acc_cat.mean()) if acc_cat.size else 0.0,
            online_acc_curve=np.cumsum(acc_cat) / np.arange(1, acc_cat.size + 1),
            losses=np.concatenate(loss_all) if loss_all else np.zeros(0),
            admitted_frac=float(admitted_cat.mean()) if admitted_cat.size else 0.0,
            empirical_rate=rate,
            final_params=final_params,
            rounds=int(consumed),
            num_replans=sum(1 for s in segments if s.replanned),
            num_faults=num_faults,
            engine_cache_hits=self.engine_cache.hits - cache_hits0,
            engine_cache_misses=self.engine_cache.misses - cache_misses0,
            peak_buffered_rounds=feeder.peak_buffered_rounds,
            stream_wait_s=feeder.take_wait_s,
            rounds_lost_per_switch=max(
                (s.rounds_lost for s in segments), default=0
            ),
        )

    # -- graceful drain ---------------------------------------------------
    def live_resume_state(self) -> Optional[ResumeState]:
        """The last completed segment's end-of-segment state snapshot.

        ``None`` until the open run completes a segment. Unlike the
        supervised per-segment checkpoints (optional, I/O-bound), this is
        always maintained — it is what a server drain saves.
        """
        return self._live_resume

    def save_live_checkpoint(self, directory: str) -> Optional[str]:
        """Checkpoint the live snapshot for an exactly-once restart.

        Writes the full engine-state tuple — stage params, the in-flight
        gradient-accumulation and Δθ rings, optimizer moments and
        compensation state — plus the partition bounds, stream cursor,
        budget, and the ring/schedule coordinates as extras: everything
        ``load_drain_state`` needs to resume this run on a fresh process
        *bit-exactly* (schema 2; schema-1 drains lacked the rings).
        Returns the checkpoint path, or ``None`` when no segment has
        completed yet (nothing consumed → a restart starts from scratch,
        still exactly-once).
        """
        rs = self._live_resume
        if rs is None:
            return None
        budget = rs.budget_bytes
        extras = {
            "bounds": [int(b) for b in rs.bounds],
            "cursor": int(rs.cursor),
            "budget_bytes": float(budget) if math.isfinite(budget) else "inf",
        }
        if rs.sched_origin is not None:
            extras["sched_origin"] = int(rs.sched_origin)
        if rs.geometry is not None:
            extras["ring_size"] = int(rs.geometry.ring_size)
            extras["delta_ring"] = int(rs.geometry.delta_ring)
        if rs.rings is not None and rs.geometry is not None:
            state = (
                list(rs.stage_params),
                tuple(rs.rings),
                tuple(rs.deltas),
                tuple(rs.opt_states),
                tuple(rs.comp_states),
            )
        else:  # ring-less snapshot: fall back to the schema-1 payload shape
            extras.pop("ring_size", None)
            extras.pop("delta_ring", None)
            state = (
                list(rs.stage_params),
                tuple(rs.opt_states),
                tuple(rs.comp_states),
            )
        return save_checkpoint(directory, rs.cursor, state, extras)

    def load_drain_state(self, params_template: Pytree, directory: str) -> ResumeState:
        """Recover a ``save_live_checkpoint`` snapshot for ``resume=``.

        Corrupt checkpoints are quarantined with fallback-to-previous-good
        (the directory may hold several drains). ``params_template`` only
        provides shapes/dtypes; the saved bounds may differ from what this
        process plans — ``run_stream(resume=...)`` remaps.

        Schema 2 drains carry the accumulation/Δθ rings and the schedule
        coordinates they are valid under, so a same-plan restart continues
        bit-exactly. Schema 1 drains (pre-ring) still load — forward
        migration fills ``rings=None`` and the restart re-warms its
        accumulation, with a warning naming the horizon.
        """
        from repro.models import transformer as T

        while True:
            path = latest_checkpoint(directory)
            if path is None:
                raise FileNotFoundError(f"no drain checkpoint under {directory!r}")
            try:
                manifest = verify_checkpoint(path)
                schema = checkpoint_schema(manifest)
                extras = manifest["extras"]
                bounds = [int(b) for b in extras["bounds"]]
                raw_budget = extras.get("budget_bytes", "inf")
                budget = math.inf if raw_budget == "inf" else float(raw_budget)
                split = T.split_stage_params(self.model_cfg, params_template, bounds)
                opts_t = tuple(self.optimizer.init(sp) for sp in split)
                comps_t = tuple(
                    comp_lib.init_state(sp, self.cfg.compensation) for sp in split
                )
                with_rings = schema >= 2 and "ring_size" in extras
                if with_rings:
                    # ring shapes come from the saved geometry — no engine
                    # or schedule rebuild needed to shape the template
                    ring_size = int(extras["ring_size"])
                    delta_ring = int(extras["delta_ring"])
                    f32 = jnp.float32
                    rings_t = tuple(
                        jax.tree.map(
                            lambda p: jnp.zeros((ring_size, *p.shape), f32), sp
                        )
                        for sp in split
                    )
                    deltas_t = tuple(
                        jax.tree.map(
                            lambda p: jnp.zeros((delta_ring, *p.shape), f32), sp
                        )
                        for sp in split
                    )
                    template = (list(split), rings_t, deltas_t, opts_t, comps_t)
                else:
                    template = (list(split), opts_t, comps_t)
                state, _step, _extras = restore_checkpoint(path, template)
            except CheckpointCorruptError:
                # quarantine and fall back to the previous drain, same as
                # restore_latest_good — but re-deriving the per-candidate
                # template (bounds may differ between drains)
                try:
                    os.replace(path, path + ".corrupt")
                except OSError:
                    pass
                continue
            if with_rings:
                return ResumeState(
                    stage_params=list(state[0]),
                    opt_states=tuple(state[3]),
                    comp_states=tuple(state[4]),
                    bounds=bounds,
                    cursor=int(extras["cursor"]),
                    budget_bytes=budget,
                    rings=tuple(state[1]),
                    deltas=tuple(state[2]),
                    sched_origin=(
                        int(extras["sched_origin"])
                        if "sched_origin" in extras else None
                    ),
                    geometry=RingGeometry(
                        ring_size=int(extras["ring_size"]),
                        delta_ring=int(extras["delta_ring"]),
                    ),
                )
            warnings.warn(
                f"schema-{schema} drain checkpoint has no accumulation/Δθ "
                "rings: the restart re-warms its accumulation from zero "
                "(a few rounds of in-flight gradients are not replayed)",
                stacklevel=2,
            )
            return ResumeState(
                stage_params=list(state[0]),
                opt_states=tuple(state[1]),
                comp_states=tuple(state[2]),
                bounds=bounds,
                cursor=int(extras["cursor"]),
                budget_bytes=budget,
            )

    # -- crash restore ----------------------------------------------------
    def load_resume_state(self, params_template: Pytree, checkpoint_dir: str) -> ResumeState:
        """Recover the newest per-segment checkpoint under ``checkpoint_dir``.

        The manifest extras (written by supervised segments via
        ``plan_manifest``) say which partition the per-stage state was
        split on and where the stream cursor was; the state itself is
        restored into a template rebuilt from the *saved* budget's plan.
        ``params_template`` only provides shapes/dtypes (e.g. freshly
        initialized params) — its values are overwritten by the restore.
        """
        seg_dirs = sorted(
            d for d in os.listdir(checkpoint_dir) if d.startswith("seg_")
        )
        path = None
        for seg in reversed(seg_dirs):
            path = latest_checkpoint(os.path.join(checkpoint_dir, seg))
            if path is not None:
                break
        if path is None:
            raise FileNotFoundError(
                f"no segment checkpoint under {checkpoint_dir!r}"
            )
        with open(os.path.join(path, "manifest.json")) as f:
            manifest = json.load(f)
        schema = checkpoint_schema(manifest)
        extras = manifest["extras"]
        bounds = [int(b) for b in extras["bounds"]]
        cursor = int(extras["cursor"])
        raw_budget = extras.get("budget_bytes", "inf")
        budget = math.inf if raw_budget == "inf" else float(raw_budget)
        plan = self.plan_for(budget)
        if list(plan.partition.bounds) != bounds:
            raise ValueError(
                "cannot rebuild the saved plan: planning for budget "
                f"{raw_budget} gives bounds {list(plan.partition.bounds)} "
                f"but the checkpoint was split on {bounds} — the profile "
                "or planner limits changed since the checkpoint was taken"
            )
        from repro.models import transformer as T

        staged = self.algorithm.wrap_staged(
            staged_from_transformer(self.model_cfg, bounds)
        )
        # ring shapes depend only on plan.config, not the segment length
        sched = sched_lib.build_schedule(plan.config, len(bounds) - 1, 1)
        engine = FerretEngine(
            staged, sched, self.optimizer, self.cfg.compensation, lr=self.cfg.lr
        )
        template = engine.init_state(
            T.split_stage_params(self.model_cfg, params_template, bounds)
        )
        if schema < 2:
            # schema-1 supervised checkpoints stored the positional
            # 5-tuple (index key paths); restore into the tuple view and
            # migrate forward. Rings are present in the payload but carry
            # no schedule origin, so the restart cannot re-enter the
            # schedule they were filled under — drop them and re-warm.
            state, _step, _extras = restore_checkpoint(path, template.as_tuple())
            warnings.warn(
                f"schema-{schema} segment checkpoint: accumulation/Δθ rings "
                "have no schedule origin and were dropped; gradient "
                "accumulation re-warms over the next "
                f"~{engine.sched.ring_size} rounds",
                stacklevel=2,
            )
            return ResumeState(
                stage_params=list(state[0]),
                opt_states=tuple(state[3]),
                comp_states=tuple(state[4]),
                bounds=bounds,
                cursor=cursor,
                budget_bytes=budget,
            )
        state, _step, _extras = restore_checkpoint(path, template)
        sched_origin = (
            int(extras["sched_origin"]) if "sched_origin" in extras else None
        )
        geometry = None
        if "ring_size" in extras:
            geometry = RingGeometry(
                ring_size=int(extras["ring_size"]),
                delta_ring=int(extras["delta_ring"]),
            )
        return ResumeState(
            stage_params=list(state.stage_params),
            opt_states=tuple(state.opt_states),
            comp_states=tuple(state.comp_states),
            bounds=bounds,
            cursor=cursor,
            budget_bytes=budget,
            rings=tuple(state.rings),
            deltas=tuple(state.deltas),
            sched_origin=sched_origin,
            geometry=geometry,
        )

    # -- internals --------------------------------------------------------
    def _split_penalty_cached(self, bounds) -> Tuple:
        """Per-stage split of the algorithm's penalty extras, memoized.

        The anchor objects (MAS Ω/ref) only change at a re-plan refresh,
        but segments are frequent — reuse the split (and its stable jit
        argument identity) until the extras or the partition actually
        change, instead of re-splitting two model-sized trees per segment.
        """
        extras = self.algorithm.engine_penalty_extras()
        cached = self._penalty_split
        if cached is not None and extras is not None:
            c_bounds, c_extras, c_split = cached
            if (
                c_bounds == tuple(bounds)
                and c_extras.keys() == extras.keys()
                and all(c_extras[k] is extras[k] for k in extras)
            ):
                return c_split
        split = split_penalty_extras(self.algorithm, self.model_cfg, bounds)
        self._penalty_split = (tuple(bounds), extras, split)
        return split

    def _prepare_rows(self, rows: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
        """The feeder's one-shot transform: per-chunk stream preparation.

        Chunks arrive in stream order and are prepared exactly once, so a
        stateful preparation (ER's reservoir mixing) chained over chunks is
        bit-identical to the materialized whole-stream preparation, and a
        rewound (faulted) segment replays prepared rows without advancing
        the algorithm's state twice.
        """
        algo = self.algorithm
        if type(algo).prepare_stream is OCLAlgorithm.prepare_stream:
            return rows  # identity prep: skip the call entirely
        return algo.prepare_stream(rows, self._prep_ctx)

    def _refresh_buffered(self, feeder: BufferedStreamSource, stage_params) -> None:
        """The algorithm's segment-boundary refresh hook, incrementally.

        The materialized path refreshed the whole un-consumed tail at a
        re-plan. Here the tail is split in two: rounds already pulled into
        the feeder are refreshed in place via ``segment_refresh``; rounds
        not yet pulled are covered by re-anchoring the preparation context
        at the live weights, so subsequent ``prepare_stream`` calls produce
        exactly what a whole-tail refresh would have.
        """
        algo = self.algorithm
        prep_default = type(algo).prepare_stream is OCLAlgorithm.prepare_stream
        refresh_default = type(algo).segment_refresh is OCLAlgorithm.segment_refresh
        if prep_default and refresh_default:
            return  # no prep and no refresh: skip the O(model-size) merge
        from repro.models import transformer as T

        merged = T.merge_stage_params(self.model_cfg, list(stage_params))
        ctx = PrepareContext(
            params=merged,
            forward_fn=lambda p, b: T.forward(self.model_cfg, p, b)[0],
        )
        self._prep_ctx = ctx
        if refresh_default:
            return
        # the refresh hook fires even when nothing is physically buffered
        # (state-only refreshes like the MAS Ω re-anchor have no rows to
        # rewrite); returned field updates only apply to buffered rows
        tail = feeder.buffered_rows()
        tail = (
            {} if tail is None else {k: np.asarray(v) for k, v in tail.items()}
        )
        updated = algo.segment_refresh(merged, tail, ctx)
        if not updated or not tail:
            return
        out = dict(tail)
        for k, arr in updated.items():
            if k in out:
                out[k] = np.asarray(arr)
        feeder.replace_buffered(out)

    def _execute_segment(
        self,
        engine: FerretEngine,
        state,
        seg_stream: Dict[str, jnp.ndarray],
        supervisor_cfg: Optional[SupervisorCfg],
        fault_round: Optional[int],
        fault_budget_scale: float,
        plan: planner_lib.Plan,
        cursor: int,
        seg_end: int,
        budget: float,
        penalty=None,
        *,
        sched_origin: Optional[int] = None,
    ):
        """One segment, either direct or as a single supervised step."""
        out: Dict[str, Any] = {}
        seg_len = seg_end - cursor  # engine may run bucket-padded rounds
        supervised = supervisor_cfg is not None

        def _injected(kind_nan_ok: bool):
            """The ``engine.step`` injection point (before any state change).

            ``transient`` raises retry-safe, ``device_loss`` raises the
            escalation path, ``nan`` returns True to poison the monitored
            loss (only observable under a Supervisor's NaN probe — specs
            gate on the ``supervised`` ctx key).
            """
            spec = faults_lib.fire("engine.step", cursor=cursor, supervised=supervised)
            if spec is None:
                return False
            if spec.kind == "transient":
                raise TransientFaultError("injected transient engine error")
            if spec.kind == "device_loss":
                # spec.arg sizes the loss (0 → the default single device),
                # so a topology-aware run shrinks by exactly that many
                raise DeviceLossError(
                    "injected device loss",
                    lost_devices=max(1, int(spec.arg)),
                )
            return spec.kind == "nan" and kind_nan_ok

        def step_fn(st, batch):
            if fault_round is not None:
                raise DeviceLossError(
                    f"simulated device loss at stream round {fault_round}"
                )
            poison = _injected(kind_nan_ok=True)
            new_st, ys = engine.run(st, batch, penalty)
            out["ys"] = ys
            # monitored loss over the real rounds only — bucket-padding
            # rows are zeros and must not dilute NaN checks / thresholds
            loss = jnp.mean(ys["loss"][:seg_len])
            if poison:
                loss = loss * jnp.nan  # a poisoned batch: NaN probe trips
            return new_st, {"loss": loss}

        if supervisor_cfg is None:
            if fault_round is not None:
                raise DeviceLossError(
                    f"simulated device loss at stream round {fault_round}"
                )
            _injected(kind_nan_ok=False)
            return engine.run(state, seg_stream, penalty)

        # Per-segment checkpoint dir: state shapes are partition-dependent,
        # so a NaN/timeout rollback inside this segment must never restore a
        # checkpoint written under a different partition.
        seg_cfg = dataclasses.replace(
            supervisor_cfg,
            checkpoint_dir=f"{supervisor_cfg.checkpoint_dir}/seg_{cursor:06d}",
        )
        sup = Supervisor(
            seg_cfg,
            step_fn,
            state,
            on_fatal=self.fatal_handler(fault_budget_scale),
        )
        # Saves happen only after the segment step succeeds, i.e. the saved
        # state is the *end-of-segment* state — the cursor must say so, or a
        # restore would re-consume the whole segment.
        rep = sup.run_step(
            seg_stream,
            extras=plan_manifest(
                plan, cursor=seg_end, budget_bytes=budget,
                sched_origin=sched_origin,
                ring_size=engine.sched.ring_size,
                delta_ring=engine.sched.delta_ring,
            ),
        )
        if rep.restarted:
            # the Supervisor recovered in place (NaN rollback / transient
            # retry): close out the injected fault's recovery latency
            faults_lib.resolved("engine.step")
        sup.manager.wait()
        return sup.state, out["ys"]

    @staticmethod
    def _normalize_schedule(schedule: BudgetSchedule):
        if callable(schedule):
            return [], schedule
        events = sorted(
            (BudgetEvent(int(e.round), float(e.budget_bytes)) for e in schedule),
            key=lambda e: e.round,
        )
        return events, None

    @staticmethod
    def _segment_end(cursor, R, events, segment_rounds) -> int:
        """Next segment boundary; ``R is None`` (unknown stream end) relies
        on ``segment_rounds``, which ``run_stream`` defaults for that case.
        A callable ``segment_rounds`` is re-evaluated here, at every
        boundary — dynamic segment sizing (clamped to ≥ 1 so the loop
        always makes progress)."""
        cap = segment_rounds(cursor) if callable(segment_rounds) else segment_rounds
        if cap is not None:
            cap = max(1, int(cap))
        end = R if R is not None else cursor + cap
        for e in events:
            if cursor < e.round < end:
                end = e.round
        if cap is not None:
            end = min(end, cursor + cap)
        return end


def _base_is_unbounded(source: StreamSource) -> bool:
    """Is the underlying feed unbounded (walking cap/buffer wrappers)?"""
    while isinstance(source, (BufferedStreamSource, LimitedStreamSource)):
        source = source.source
    return source.length is None


def _try_seek(source: StreamSource, round_idx: int) -> bool:
    """Position ``source`` at an absolute round if it supports seeking."""
    if isinstance(source, BufferedStreamSource):
        return source.try_seek(round_idx)
    seek = getattr(source, "seek", None)
    if seek is None:
        return False
    seek(round_idx)
    return True
