"""Ferret trainer: plan → schedule → pipeline-execute an OCL stream.

This is the user-facing composition of the paper's three contributions:

    profile = analytic/measured per-layer profile
    plan    = Alg. 3 ∘ Alg. 2  (partition L*, config C* s.t. M_F ≤ M)
    engine  = fine-grained async pipeline with Iter-Fisher compensation

``FerretTrainer.run_stream`` executes a stream and reports online accuracy,
the empirical adaptation rate (Def. 4.1), and the planned memory footprint
(for agm/tagm comparisons). It consumes a ``StreamSource`` incrementally —
segment-by-segment ``take()`` through a ``BufferedStreamSource`` feeder
with background prefetch, per-chunk stream preparation, and O(segment)
peak stream residency; a dict of stacked arrays is wrapped for compat —
and is bit-exact with a single materialized scan (each segment runs a
slice of one causal schedule build with the engine rings carried across
slices). Algorithms with a parameter-space penalty (MAS) apply it inside
the engine via the ``penalty_fn`` hook.

Note: ``FerretTrainer`` / ``sequential_oracle_run`` are the internal
engines behind ``repro.api.FerretSession`` — prefer the session layer for
new code; these entrypoints stay importable for compatibility.
"""

from __future__ import annotations

import dataclasses
import math
import os
import threading
from typing import Any, Callable, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import compensation as comp_lib
from repro.core import planner as planner_lib
from repro.core import schedule as sched_lib
from repro.core.pipeline import FerretEngine, staged_from_transformer
from repro.core.profiler import ModelProfile, profile_for
from repro.core.spans import span
from repro.models.config import ModelConfig
from repro.ocl.algorithms import OCLConfig
from repro.ocl.registry import OCLAlgorithm, PrepareContext, get_algorithm
from repro.optim.optimizers import Optimizer, adamw

Pytree = Any


@dataclasses.dataclass(frozen=True)
class FerretConfig:
    budget_bytes: float = math.inf  # M (Ferret_M+ := inf)
    decay_c: float = 1.0  # data-value decay rate c (Def. 4.1)
    data_value: float = 1.0  # V_D
    t_d: Optional[float] = None  # arrival interval; default max_i t̂_i^f (§12)
    lr: float = 1e-3
    max_workers: Optional[int] = 8
    max_stages: Optional[int] = None
    compensation: comp_lib.CompensationConfig = dataclasses.field(
        default_factory=comp_lib.CompensationConfig
    )
    ocl: OCLConfig = dataclasses.field(default_factory=OCLConfig)
    # Online profile refinement: feed observed segment wall-clock back
    # into the profile store (repro.profile.bridge.observe_segment) so
    # replans — and future runs — plan from real numbers. Host-side only;
    # never changes what the engine computes.
    profile_feedback: bool = False


# ---------------------------------------------------------------------------
# Engine compile cache (bucketed segment lengths)
# ---------------------------------------------------------------------------

# The pipelined (single-plan) runner's feeder chunk length: rounds are
# pulled from the stream source this many at a time, so peak stream
# residency is O(segment), and every slice pads to this length so the
# whole run reuses one compiled scan. Override per run with
# run_stream(segment_rounds=...).
DEFAULT_PIPELINE_SEGMENT_ROUNDS = 32

# Geometric bucket set for segment lengths: a segment of n rounds runs a
# compiled scan of the smallest bucket ≥ n (padded with inert schedule
# rounds, which are the identity on engine state), so repeated and A→B→A
# budget switches land on identical shapes and reuse compiled engines.
# Override with REPRO_SEGMENT_BUCKETS="8,16,..." or EngineCache(buckets=...).
DEFAULT_SEGMENT_BUCKETS = (8, 16, 32, 64, 128, 256, 512, 1024, 2048, 4096)


def _buckets_from_env() -> Tuple[int, ...]:
    """Bucket ladder precedence: REPRO_SEGMENT_BUCKETS env > the backend's
    autotune record (repro.profile.autotune) > the built-in geometric set."""
    raw = os.environ.get("REPRO_SEGMENT_BUCKETS", "").strip()
    if raw:
        return tuple(sorted(int(tok) for tok in raw.split(",") if tok.strip()))
    try:
        from repro.profile.autotune import tuned_defaults

        tuned = tuned_defaults()
        if tuned.segment_buckets:
            return tuple(sorted(tuned.segment_buckets))
    except Exception:
        pass
    return DEFAULT_SEGMENT_BUCKETS


class IdentityKey:
    """Hashable identity wrapper for cache keys.

    A bare ``id()`` in a long-lived shared cache can alias two objects if
    the first is garbage-collected and its address reused; holding the
    referent pins it for the cache's lifetime, so identity keys stay
    unambiguous.
    """

    __slots__ = ("obj",)

    def __init__(self, obj: Any):
        self.obj = obj

    def __hash__(self) -> int:
        return id(self.obj)

    def __eq__(self, other: Any) -> bool:
        return isinstance(other, IdentityKey) and other.obj is self.obj


class EngineCache:
    """Compiled-engine cache for segmented/elastic runs.

    One ``FerretEngine`` is kept per structure (``struct_key`` = trainer
    scope + stage boundaries); segments reuse it with ``set_schedule`` —
    schedule content is scan *data*, so a same-shape swap reuses the
    engine's compiled scan outright, and ``jax.jit`` keys further compiles
    on array shapes only. ``hits``/``misses`` count compiled-scan reuse at
    the shape level (``compile_key`` = struct_key + ring geometry +
    bucketed rounds + stream shape): the caller checks ``seen`` before a
    segment and ``record``s after it *succeeds*, so aborted segments never
    skew the perf accounting. An A→B→A budget schedule compiles 2 engines
    and hits once.

    Thread-safe: one cache may be shared by concurrent trainers (the
    multi-tenant server path). The internal lock covers the engine map and
    the compile bookkeeping; callers who need ``seen``/``record`` to stay
    truthful across a whole segment additionally serialize execution on
    the shared engine's ``exec_lock`` (see ``FerretEngine``), which also
    protects the engine's mutable schedule.
    """

    def __init__(self, buckets: Optional[Tuple[int, ...]] = None, enabled: bool = True):
        self.buckets = tuple(sorted(buckets)) if buckets else _buckets_from_env()
        self.enabled = enabled
        self._engines: Dict[Tuple, Any] = {}
        self._compiled: set = set()
        self._lock = threading.RLock()
        self.hits = 0
        self.misses = 0

    def bucket_len(self, n: int) -> int:
        """Smallest bucket ≥ n (multiples of the top bucket beyond it)."""
        if not self.enabled:
            return n
        for b in self.buckets:
            if n <= b:
                return b
        top = self.buckets[-1]
        return ((n + top - 1) // top) * top

    def engine_for(self, struct_key: Tuple, factory: Callable[[], Any]) -> Any:
        """The cached engine for ``struct_key`` (built by ``factory`` on
        first use; always fresh when the cache is disabled)."""
        if not self.enabled:
            return factory()
        with self._lock:
            engine = self._engines.get(struct_key)
            if engine is None:
                engine = factory()
                self._engines[struct_key] = engine
            return engine

    def seen(self, compile_key: Tuple) -> bool:
        """Was this shape already compiled (i.e. will the run be a hit)?"""
        with self._lock:
            return self.enabled and compile_key in self._compiled

    def record(self, compile_key: Tuple, hit: bool) -> None:
        """Account one *completed* segment run under ``compile_key``."""
        with self._lock:
            if hit:
                self.hits += 1
            else:
                self.misses += 1
                if self.enabled:
                    self._compiled.add(compile_key)

    @property
    def counts(self) -> Dict[str, int]:
        return {"hits": self.hits, "misses": self.misses}


@dataclasses.dataclass
class StreamResult:
    online_acc: float
    online_acc_curve: np.ndarray
    losses: np.ndarray
    admitted_frac: float
    memory_bytes: float
    planned_rate: float
    empirical_rate: float
    lam_curve: np.ndarray
    plan: planner_lib.Plan
    rounds: int = 0  # stream rounds consumed (exactly once)
    peak_buffered_rounds: int = 0  # max rounds resident in the feeder
    stream_wait_s: float = 0.0  # un-overlapped time blocked on the source


# ---------------------------------------------------------------------------
# Engine parameter-penalty adapters (shared by the pipelined and elastic
# trainers): an OCLAlgorithm's penalty operates on a params-shaped tree,
# the engine holds per-stage slices — these bridge the two.
# ---------------------------------------------------------------------------


def stage_penalty_fn(algorithm: OCLAlgorithm) -> Optional[Callable]:
    """``algorithm.engine_penalty`` lifted to the engine's per-stage weight
    tuple: evaluated on each stage's slice and summed (the hook's contract
    requires the penalty to decompose over parameter groups)."""
    fn = algorithm.engine_penalty()
    if fn is None:
        return None

    def stage_fn(stages, extras):
        total = jnp.zeros((), jnp.float32)
        for sp, ex in zip(stages, extras):
            total = total + fn(sp, ex)
        return total

    return stage_fn


def split_penalty_extras(
    algorithm: OCLAlgorithm, model_cfg: ModelConfig, bounds
) -> Tuple:
    """The algorithm's current penalty extras, split per pipeline stage.

    Called at every segment boundary — after ``prepare_stream`` /
    ``segment_refresh`` have run, so the extras reflect this segment's
    anchor. Raising (instead of silently running without the penalty) is
    the point: MAS-as-Vanilla was exactly that silent fallback.
    """
    from repro.models import transformer as T

    extras = algorithm.engine_penalty_extras()
    if extras is None:
        raise RuntimeError(
            f"algorithm {algorithm.name!r} declares engine_penalty() but "
            "engine_penalty_extras() is None at segment start — its "
            "prepare_stream/segment_refresh must populate the penalty "
            "state before the engine runs"
        )
    parts = {
        k: T.split_stage_params(model_cfg, v, bounds) for k, v in extras.items()
    }
    P = len(bounds) - 1
    return tuple({k: parts[k][j] for k in parts} for j in range(P))


def empirical_adaptation_rate(
    cfg: FerretConfig, plan: planner_lib.Plan, admitted: np.ndarray, R: int
) -> float:
    """Def. 4.1 empirically: admitted items complete after one full pipeline
    traversal; dropped items contribute 0 (r = ∞)."""
    active = plan.config.active_workers()
    cr = max(w.recompute for w in active) if active else 0
    traversal = plan.partition.num_stages * (
        plan.stats.t_f + plan.stats.t_b + cr * plan.stats.t_f
    )
    contrib = admitted * math.exp(-cfg.decay_c * traversal) * cfg.data_value
    return float(contrib.sum() / max(R, 1))


class FerretTrainer:
    def __init__(
        self,
        model_cfg: ModelConfig,
        ferret_cfg: FerretConfig,
        batch: int,
        seq: int,
        optimizer: Optional[Optimizer] = None,
        profile: Optional[ModelProfile] = None,
        algorithm: Optional[Union[str, OCLAlgorithm]] = None,
        topology=None,
    ):
        from repro.runtime.topology import as_topology

        self.model_cfg = model_cfg
        self.cfg = ferret_cfg
        self.batch = batch
        self.seq = seq
        # Topology-aware execution: a DeviceTopology (or "discover") makes
        # the planner budget per-device-bounded, scales the profile for the
        # data-parallel replicas, and runs the engine scan under the
        # topology's mesh. topology=None — and a trivial 1-device topology —
        # is the exact historical single-device path.
        self.topology = as_topology(topology)
        self.mesh = (
            None
            if self.topology is None or self.topology.is_trivial
            else self.topology.mesh()
        )
        from repro.models import shard_hints as shard_hints_lib

        self.shard_hints = shard_hints_lib.for_topology(self.topology)
        self.algorithm = (
            get_algorithm(algorithm, ferret_cfg.ocl)
            if algorithm is not None
            else get_algorithm(ferret_cfg.ocl)
        )
        # Default resolution is store-aware (Alg. 3 profile(θ)): a persisted
        # on-device measurement for this geometry wins, the analytic
        # roofline is the fallback — identical to the old default when no
        # measurement exists.
        self.profile = profile or profile_for(model_cfg, batch, seq)
        # self.profile stays single-device (so delegating to the elastic
        # trainer never double-scales); the plan sees the topology-scaled
        # view — data-parallel replicas divide times/activations, weights
        # replicate
        eff_profile = self.profile
        if self.topology is not None:
            from repro.profile.bridge import for_topology

            eff_profile = for_topology(self.profile, self.topology)
        t_d = ferret_cfg.t_d or planner_lib.default_data_interval(eff_profile)
        self.t_d = t_d
        self.plan = planner_lib.plan(
            eff_profile,
            t_d,
            ferret_cfg.budget_bytes,
            c=ferret_cfg.decay_c,
            V_D=ferret_cfg.data_value,
            max_workers=ferret_cfg.max_workers,
            max_stages=ferret_cfg.max_stages,
            topology=self.topology,
        )
        self.boundaries = list(self.plan.partition.bounds)
        staged = staged_from_transformer(model_cfg, self.boundaries)
        self.staged = self.algorithm.wrap_staged(staged)
        self.optimizer = optimizer or adamw(lr=ferret_cfg.lr)

    # ------------------------------------------------------------------
    def _prepare_rows(self, rows: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
        """The feeder's one-shot transform: per-chunk stream preparation.

        Chunks arrive in stream order and are prepared exactly once, so a
        stateful preparation (ER reservoir mixing) chained over chunks is
        bit-identical to preparing the whole stream at once (PR 4's
        incremental-elastic guarantee, now shared by the pipelined path).
        """
        algo = self.algorithm
        if type(algo).prepare_stream is OCLAlgorithm.prepare_stream:
            return rows  # identity prep: skip the call entirely
        return algo.prepare_stream(rows, self._prep_ctx)

    def run_stream(
        self,
        params: Pytree,
        stream: Union[Dict[str, np.ndarray], "StreamSource"],
        *,
        segment_rounds: Optional[int] = None,
        prefetch: bool = True,
    ) -> StreamResult:
        """Execute a stream through the single-plan pipeline engine.

        stream: a ``StreamSource`` — consumed *incrementally*: rounds are
        pulled ``take(segment_rounds)`` at a time through a
        ``BufferedStreamSource`` feeder, so peak stream residency on host
        and device is O(segment_rounds), never O(R), and unbounded sources
        (``length=None``) run until the feed ends. A dict of ``(R, b,
        ...)`` arrays is accepted for compat (wrapped in an
        ``ArrayStreamSource``; still consumed per segment). Pass *raw*
        rounds — the algorithm's ``prepare_stream`` (replay mixing,
        teacher logits) is applied per pulled chunk, exactly once, in
        stream order, which is bit-identical to whole-stream preparation.

        Each segment runs a slice of one causal schedule build with the
        engine's gradient-accumulation/Δθ rings carried across slices, so
        the chunked run is bit-exact with the materialized single-scan
        run; segments pad to ``segment_rounds`` with inert rounds, so the
        whole run reuses one compiled scan. ``prefetch`` pulls segment
        k+1 on a background thread while segment k computes.

        Algorithms that declare an ``engine_penalty`` (MAS) have their
        parameter-space term applied *inside* the engine — no silent
        Vanilla fallback remains on the pipeline path.
        """
        from repro.api.streams import BufferedStreamSource, coerce_trainer_stream
        from repro.models import transformer as T

        source = coerce_trainer_stream(stream, "FerretTrainer.run_stream")
        seg = int(segment_rounds) if segment_rounds else DEFAULT_PIPELINE_SEGMENT_ROUNDS
        remaining = source.remaining
        R: Optional[int] = None if remaining is None else int(remaining)

        # stream prep anchors at the weights entering the stream, exactly
        # like the materialized whole-stream preparation did
        self._prep_ctx = PrepareContext(
            params=params,
            forward_fn=lambda p, b: T.forward(self.model_cfg, p, b)[0],
        )
        feeder = BufferedStreamSource(
            source, transform=self._prepare_rows, prefetch=prefetch
        )

        P = self.plan.partition.num_stages
        penalty_fn = stage_penalty_fn(self.algorithm)
        penalty = None  # split once after the first chunk anchors it
        engine: Optional[FerretEngine] = None
        full_sched: Optional[sched_lib.EngineSchedule] = None
        stages = T.split_stage_params(self.model_cfg, params, self.boundaries)
        rings = deltas = opt_states = comp_states = None
        cursor = 0
        seg_index = 0
        acc_all: list = []
        loss_all: list = []
        adm_all: list = []
        lam_all: list = []
        try:
            while R is None or cursor < R:
                with span("ferret.segment", step=seg_index):
                    want = seg if R is None else min(seg, R - cursor)
                    with span("ferret.take"):
                        rows = feeder.take(want)
                    if rows is None:
                        break  # source exhausted
                    seg_len = next(iter(rows.values())).shape[0]
                    seg_end = cursor + seg_len
                    if seg_len < want:
                        R = seg_end  # source ended early: true stream end found
                    with span("ferret.schedule"):
                        # one causal build; segments slice it. A bounded
                        # stream builds straight to its end; an unknown end
                        # grows geometrically — construction is causal, so
                        # a longer rebuild is bit-identical on its prefix
                        # (the same continuation ``build_schedule(warmup=)``
                        # computes), and doubling keeps host-side schedule
                        # work O(R) per run.
                        if full_sched is None or full_sched.num_rounds < seg_end:
                            if R is not None:
                                build_len = max(R, seg_end)
                            else:
                                built = 0 if full_sched is None else full_sched.num_rounds
                                build_len = max(seg_end, 2 * built, 2 * seg)
                            full_sched = sched_lib.build_schedule(
                                self.plan.config, P, build_len
                            )
                        # pad every slice to the segment length with inert
                        # rounds (identity on engine state): one compiled
                        # scan serves the whole run, ragged tail included
                        engine_sched = sched_lib.pad_schedule(
                            sched_lib.slice_schedule(full_sched, cursor, seg_end), seg
                        )
                        if engine is None:
                            engine = FerretEngine(
                                self.staged, engine_sched, self.optimizer,
                                self.cfg.compensation, lr=self.cfg.lr,
                                penalty_fn=penalty_fn, mesh=self.mesh,
                                hints=self.shard_hints,
                            )
                        else:
                            engine.set_schedule(engine_sched)
                        state = engine.init_state(
                            stages, opt_states, comp_states, rings=rings, deltas=deltas,
                            bounds=self.boundaries, sched_origin=0,
                        )
                    with span("ferret.upload"):
                        # only this segment's rounds ever reach the device
                        seg_stream = {k: jnp.asarray(v) for k, v in rows.items()}
                        if seg > seg_len:
                            # padding rounds repeat the last item (never admitted)
                            seg_stream = {
                                k: jnp.concatenate(
                                    [v, jnp.repeat(v[-1:], seg - seg_len, axis=0)]
                                )
                                for k, v in seg_stream.items()
                            }
                    # overlap: pull segment k+1 on the host while k computes
                    if R is None or seg_end < R:
                        feeder.prefetch(seg if R is None else min(seg, R - seg_end))
                    if penalty_fn is not None and penalty is None:
                        # single-plan run: the anchor never refreshes after
                        # the first chunk sets it, so split Ω/θ* once and
                        # reuse the same pytree every segment (stable jit
                        # arguments, no per-segment re-split/re-upload of
                        # 2× model size)
                        penalty = split_penalty_extras(
                            self.algorithm, self.model_cfg, self.boundaries
                        )
                    with span("ferret.dispatch") as dispatch:
                        final_state, ys = engine.run(state, seg_stream, penalty)
                    with span("ferret.fetch") as fetch:
                        ys = jax.device_get(ys)
                    # dispatch to the results on the host: the segment's
                    # device time, which the dispatch alone returns before
                    seg_wall = fetch.end - dispatch.start
                    feeder.ack()  # segment complete: retained rows consumed
                    if self.cfg.profile_feedback and seg_index > 0 and seg_len > 0:
                        # skip segment 0: its wall-clock includes the
                        # compile. The single-plan run never replans, so the
                        # refinement lands in the store for future
                        # runs/replans.
                        from repro.profile.bridge import observe_segment

                        # the compiled scan executes `seg` rounds (inert
                        # padding included), so that is the wall-clock's
                        # denominator
                        refined = observe_segment(
                            self.model_cfg, self.batch, self.seq,
                            self.profile, self.plan, seg, seg_wall,
                        )
                        if refined is not None:
                            self.profile = refined[0]
                    seg_index += 1
                    ys = {k: v[:seg_len] for k, v in ys.items()}  # drop padding
                    stages = list(final_state.stage_params)
                    rings = tuple(final_state.rings)
                    deltas = tuple(final_state.deltas)
                    opt_states = tuple(final_state.opt_states)
                    comp_states = tuple(final_state.comp_states)
                    acc_all.append(np.asarray(ys["acc"], dtype=np.float64))
                    loss_all.append(ys["loss"])
                    adm_all.append(np.asarray(ys["admitted"], dtype=np.float64))
                    lam_all.append(ys["lam"])
                    cursor = seg_end
        finally:
            feeder.close()

        self.final_params = T.merge_stage_params(self.model_cfg, list(stages))
        self.engine = engine
        rounds = cursor
        acc = np.concatenate(acc_all) if acc_all else np.zeros(0)
        admitted = np.concatenate(adm_all) if adm_all else np.zeros(0)
        empirical_rate = empirical_adaptation_rate(
            self.cfg, self.plan, admitted, rounds
        )
        return StreamResult(
            # a zero-round stream reports 0.0, not an empty-mean NaN (the
            # elastic path's twin guard landed in PR 4)
            online_acc=float(acc.mean()) if acc.size else 0.0,
            online_acc_curve=np.cumsum(acc) / np.arange(1, acc.size + 1),
            losses=np.concatenate(loss_all) if loss_all else np.zeros(0),
            admitted_frac=float(admitted.mean()) if admitted.size else 0.0,
            memory_bytes=self.plan.memory,
            planned_rate=self.plan.rate,
            empirical_rate=empirical_rate,
            lam_curve=np.concatenate(lam_all) if lam_all else np.zeros(0),
            plan=self.plan,
            rounds=rounds,
            peak_buffered_rounds=feeder.peak_buffered_rounds,
            stream_wait_s=feeder.take_wait_s,
        )

    # ------------------------------------------------------------------
    def run_stream_elastic(self, params: Pytree, stream: Dict[str, np.ndarray],
                           schedule=(), **kwargs):
        """Segmented run under a varying memory budget (Ferret_M live).

        Delegates to ``repro.runtime.elastic_trainer.ElasticStreamTrainer``:
        the stream executes in segments, re-planning and remapping live
        state at every budget change. ``schedule`` is a list of
        ``BudgetEvent`` or a ``round -> budget_bytes | None`` callable; see
        ``ElasticStreamTrainer.run_stream`` for the remaining kwargs.
        Returns an ``ElasticStreamResult`` with per-segment ``StreamResult``s
        and the stitched online-accuracy curve.
        """
        from repro.runtime.elastic_trainer import ElasticStreamTrainer

        et = ElasticStreamTrainer(
            self.model_cfg, self.cfg, batch=self.batch, seq=self.seq,
            optimizer=self.optimizer, profile=self.profile,
            algorithm=self.algorithm, topology=self.topology,
        )
        result = et.run_stream(params, stream, schedule, **kwargs)
        self.final_params = result.final_params
        return result


def sequential_oracle_run(
    model_cfg: ModelConfig,
    params: Pytree,
    stream: Dict[str, np.ndarray],
    lr: float = 1e-3,
    trained_mask: Optional[np.ndarray] = None,
    optimizer: Optional[Optimizer] = None,
) -> Dict[str, np.ndarray]:
    """Plain predict-then-train loop (Oracle / skip baselines).

    trained_mask: bool (R,) — items that actually get a gradient update
    (admission policies produce it). Prediction happens for every item."""
    from repro.core import schedule as sched_lib
    from repro.core.cost_model import PipelineConfig, StageKnobs, WorkerConfig
    from repro.models import transformer as T

    R = next(iter(stream.values())).shape[0]
    opt = optimizer or adamw(lr=lr)
    boundaries = [0, model_cfg.num_layers]
    staged = staged_from_transformer(model_cfg, boundaries)
    pcfg = PipelineConfig(workers=[WorkerConfig(0, 0, [StageKnobs()])])
    schedule = sched_lib.build_schedule(pcfg, 1, R, sync_period=1)
    if trained_mask is not None:
        schedule.process[:] = trained_mask
    engine = FerretEngine(
        staged, schedule, opt, comp_lib.CompensationConfig(method="none"), lr=lr
    )
    stages = T.split_stage_params(model_cfg, params, boundaries)
    state = engine.init_state(stages)
    final_state, ys = engine.run(state, {k: jnp.asarray(v) for k, v in stream.items()})
    return {
        "acc": np.asarray(ys["acc"]),
        "loss": np.asarray(ys["loss"]),
        "final_params": T.merge_stage_params(
            model_cfg, list(final_state.stage_params)
        ),
    }
