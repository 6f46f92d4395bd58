"""Runner protocol + registry: four execution modes, one result shape.

A ``Runner`` turns (session, params, stream-of-arrays) into the unified
``repro.api.StreamResult``. The four built-ins cover the repo's execution
modes, previously reachable only through divergent entrypoints:

- ``pipelined``  — plan once, run the fine-grained async pipeline engine
                   (was ``FerretTrainer.run_stream``); streaming-native:
                   consumes a ``StreamSource`` segment by segment
- ``elastic``    — segmented run under a varying budget with live replan +
                   state remap (was ``ElasticStreamTrainer.run_stream``)
- ``sequential`` — exact per-item predict-then-train loop (the Oracle;
                   alias ``oracle``), with the OCL algorithm's exact
                   sequential path (true MIR, LwF teacher, MAS Ω)
- ``baseline``   — the same sequential loop gated by a stream-admission
                   policy (1-Skip / Random-N / Last-N / Camel)

Register your own:

    from repro.api import Runner, register_runner

    @register_runner
    class MyRunner(Runner):
        name = "my-runner"
        def run(self, session, params, stream, **opts): ...
"""

from __future__ import annotations

import collections
from typing import Any, Dict, List, Optional, Type, Union

import jax.numpy as jnp
import numpy as np

from repro.api.results import StreamResult
from repro.ocl import metrics as metrics_lib
from repro.ocl.baselines import AdmissionPolicy, make_admission_mask
from repro.ocl.registry import make_sequential_step

Pytree = Any

_RUNNERS: Dict[str, Type["Runner"]] = {}


def register_runner(cls: Type["Runner"]) -> Type["Runner"]:
    """Class decorator: register ``cls`` under ``cls.name`` (+ aliases)."""
    name = getattr(cls, "name", None)
    if not name or not isinstance(name, str):
        raise ValueError(f"{cls!r} needs a string class attribute `name`")
    _RUNNERS[name] = cls
    for alias in getattr(cls, "aliases", ()):
        _RUNNERS[alias] = cls
    return cls


def available_runners() -> List[str]:
    return sorted(_RUNNERS)


def get_runner(spec: Union[str, "Runner"]) -> "Runner":
    if isinstance(spec, Runner):
        return spec
    if spec not in _RUNNERS:
        raise ValueError(
            f"unknown runner {spec!r}; registered runners: "
            f"{', '.join(available_runners())}. Add your own with "
            "@repro.api.register_runner."
        )
    return _RUNNERS[spec]()


class Runner:
    """Base runner. ``consumes_source`` says the runner takes a
    ``StreamSource`` and pulls rounds incrementally (no up-front
    materialization; stream preparation happens inside the runner, per
    pulled chunk) — the session then resolves the stream to a source
    instead of arrays. Both pipeline-path built-ins (pipelined, elastic)
    declare it.

    ``prepare_stream`` says a *materializing* runner wants the session to
    run the algorithm's whole-stream preparation (replay mixing, LwF
    teacher logits) before handing the arrays over — kept for custom
    runners; the sequential paths manage replay/teacher state exactly,
    per step, instead.

    Concrete runners declare their options explicitly — a misspelled
    option to ``session.run`` raises ``TypeError`` instead of being
    silently ignored."""

    name: str = ""
    aliases: tuple = ()
    prepare_stream: bool = False
    consumes_source: bool = False

    def run(
        self, session, params: Pytree, stream: Dict[str, np.ndarray], **opts
    ) -> StreamResult:
        raise NotImplementedError


def _rounds(stream: Dict[str, np.ndarray]) -> int:
    return next(iter(stream.values())).shape[0]


def _model_bytes(model_cfg) -> float:
    return float(model_cfg.param_count()) * 4.0


# ---------------------------------------------------------------------------
# Pipelined + elastic (the planned pipeline engine)
# ---------------------------------------------------------------------------


@register_runner
class PipelinedRunner(Runner):
    """Single-plan fine-grained async pipeline (Ferret proper).

    Streaming-native: the session hands over a ``StreamSource`` (unbounded
    live feeds included) and the trainer pulls ``take(segment_rounds)``
    per segment through a prefetching feeder — peak stream residency stays
    O(segment), never O(R), and the chunked run is bit-exact with the
    materialized single-scan run. Stream preparation (ER mixing, LwF
    teacher logits) runs inside the trainer, per pulled chunk; algorithms
    with a parameter-space penalty (MAS) apply it through the
    ``FerretEngine`` hook instead of degrading to Vanilla."""

    name = "pipelined"
    consumes_source = True

    def run(self, session, params, stream, *, segment_rounds=None, prefetch=True):
        from repro.core.ferret import FerretTrainer

        trainer = FerretTrainer(
            session.model_cfg, session.ferret_cfg,
            batch=session.batch, seq=session.seq,
            optimizer=session.optimizer, profile=session.profile,
            algorithm=session.algorithm,
            topology=getattr(session, "topology", None),
        )
        raw = trainer.run_stream(
            params, stream, segment_rounds=segment_rounds, prefetch=prefetch
        )
        return StreamResult(
            runner=self.name,
            algorithm=session.algorithm.name,
            online_acc=raw.online_acc,
            online_acc_curve=raw.online_acc_curve,
            losses=np.asarray(raw.losses),
            # consumed-rounds accounting, same semantics as the elastic
            # runner (a capped/early-ending source reports what it ran)
            rounds=int(raw.rounds),
            admitted_frac=raw.admitted_frac,
            memory_bytes=raw.memory_bytes,
            empirical_rate=raw.empirical_rate,
            final_params=trainer.final_params,
            plan=raw.plan,
            extras={
                "raw": raw,
                "engine": trainer.engine,
                "lam_curve": raw.lam_curve,
                "peak_buffered_rounds": raw.peak_buffered_rounds,
                "stream_wait_s": raw.stream_wait_s,
            },
        )


@register_runner
class ElasticRunner(Runner):
    """Segmented run under a (possibly varying) budget: live replan + state
    remap at every budget change, crash-restore via ``resume=``.

    Consumes its stream incrementally: the session hands over a
    ``StreamSource`` (unbounded live feeds included) and the trainer pulls
    ``take(segment_rounds)`` per segment with prefetch — stream residency
    stays O(segment), never O(R). Stream preparation (ER mixing, LwF
    teacher logits) runs inside the trainer, per pulled chunk."""

    name = "elastic"
    consumes_source = True

    def run(
        self, session, params, stream, *,
        schedule=(), segment_rounds=None, supervisor_cfg=None,
        fault_rounds=(), fault_budget_scale=0.5, resume=None,
        engine_cache=None, prefetch=True,
    ):
        from repro.runtime.elastic_trainer import ElasticStreamTrainer

        trainer = ElasticStreamTrainer(
            session.model_cfg, session.ferret_cfg,
            batch=session.batch, seq=session.seq,
            optimizer=session.optimizer, profile=session.profile,
            algorithm=session.algorithm,
            engine_cache=engine_cache,
            topology=getattr(session, "topology", None),
        )
        raw = trainer.run_stream(
            params, stream, schedule,
            segment_rounds=segment_rounds, supervisor_cfg=supervisor_cfg,
            fault_rounds=fault_rounds, fault_budget_scale=fault_budget_scale,
            resume=resume, prefetch=prefetch,
        )
        return stream_result_from_elastic(
            raw, runner=self.name, algorithm=session.algorithm.name,
            model_cfg=session.model_cfg,
        )


def stream_result_from_elastic(
    raw, *, runner: str, algorithm: str, model_cfg
) -> StreamResult:
    """Fold an ``ElasticStreamResult`` into the unified ``StreamResult``.

    Shared by the elastic runner and the multi-tenant server's per-tenant
    reporting, so both surfaces present identical accounting."""
    # a zero-round stream plans nothing: report the resident weights,
    # not the inf that max(..., default=...) used to produce
    peak_mem = max(
        (s.result.memory_bytes for s in raw.segments),
        default=_model_bytes(model_cfg),
    )
    return StreamResult(
        runner=runner,
        algorithm=algorithm,
        online_acc=raw.online_acc,
        online_acc_curve=raw.online_acc_curve,
        losses=np.asarray(raw.losses),
        rounds=raw.rounds,
        admitted_frac=raw.admitted_frac,
        memory_bytes=peak_mem,
        empirical_rate=raw.empirical_rate,
        final_params=raw.final_params,
        plan=raw.segments[0].result.plan if raw.segments else None,
        segments=list(raw.segments),
        num_replans=raw.num_replans,
        engine_cache_hits=raw.engine_cache_hits,
        engine_cache_misses=raw.engine_cache_misses,
        extras={
            "raw": raw,
            "num_faults": raw.num_faults,
            "peak_buffered_rounds": raw.peak_buffered_rounds,
            "stream_wait_s": raw.stream_wait_s,
            # 0 ⇔ every budget switch this run made was lossless (in-flight
            # accumulation rings carried or flushed, never dropped)
            "rounds_lost_per_switch": raw.rounds_lost_per_switch,
            # stream-wide λ trajectory, same key the pipelined runner
            # reports (stitched across segments here)
            "lam_curve": (
                np.concatenate([s.result.lam_curve for s in raw.segments])
                if raw.segments else np.zeros(0)
            ),
        },
    )


# ---------------------------------------------------------------------------
# Sequential paths (exact OCL algorithms; Oracle + admission baselines)
# ---------------------------------------------------------------------------


def _sequential_loop(session, params, stream, trained_mask=None):
    """Exact predict-then-train loop with the algorithm's sequential path.

    Accuracy is measured pre-update (online accuracy); ``trained_mask``
    gates the parameter update (admission baselines) while prediction
    still happens for every item.
    """
    from repro.models import transformer as T
    from repro.models.layers import cross_entropy_loss

    cfg = session.model_cfg
    algo = session.algorithm
    algo.reset()

    def loss_fn(p, batch):
        logits, _aux = T.forward(cfg, p, batch)
        ce = cross_entropy_loss(logits, batch["labels"], batch.get("mask"))
        acc = jnp.mean((jnp.argmax(logits, -1) == batch["labels"]).astype(jnp.float32))
        return ce, {"acc": acc}

    def forward_fn(p, batch):
        return T.forward(cfg, p, batch)[0]

    algo.bind_forward(forward_fn)
    opt = session.optimizer
    opt_state = opt.init(params)
    step, eval_fn, helpers = make_sequential_step(algo, loss_fn, forward_fn, opt)

    R = _rounds(stream)
    refresh = int(algo.cfg.refresh_every)
    recent: collections.deque = collections.deque(maxlen=4)
    losses, accs = [], []
    skip_fields = ("new_mask", "teacher_logits")
    for m in range(R):
        batch = {
            k: jnp.asarray(v[m]) for k, v in stream.items() if k not in skip_fields
        }
        if refresh > 0 and m > 0 and m % refresh == 0:
            algo.sequential_refresh(params, list(recent))
        extras = algo.host_extras(params, opt_state, batch, helpers)
        if trained_mask is None or bool(trained_mask[m]):
            params, opt_state, loss, metrics = step(params, opt_state, batch, extras)
        else:
            loss, metrics = eval_fn(params, batch)
        algo.observe(batch)
        recent.append(batch)
        losses.append(float(loss))
        accs.append(float(metrics["acc"]))
    return params, np.asarray(losses), np.asarray(accs)


def _sequential_result(
    session, runner_name, params, losses, accs, delays, admitted, memory, extras
) -> StreamResult:
    fc = session.ferret_cfg
    values = np.full(delays.shape, fc.data_value, np.float64)
    rate = metrics_lib.adaptation_rate_empirical(delays, c=fc.decay_c, values=values)
    return StreamResult(
        runner=runner_name,
        algorithm=session.algorithm.name,
        online_acc=float(accs.mean()) if accs.size else 0.0,
        online_acc_curve=np.cumsum(accs) / np.arange(1, accs.size + 1),
        losses=losses,
        rounds=int(accs.size),
        admitted_frac=float(np.mean(admitted)) if len(admitted) else 0.0,
        memory_bytes=memory,
        empirical_rate=rate,
        final_params=params,
        extras=extras,
    )


@register_runner
class SequentialRunner(Runner):
    """Oracle: every item trained on arrival, zero delay."""

    name = "sequential"
    aliases = ("oracle", "sequential-oracle")

    def run(self, session, params, stream):
        R = _rounds(stream)
        params, losses, accs = _sequential_loop(session, params, stream)
        return _sequential_result(
            session, self.name, params, losses, accs,
            delays=np.zeros(R), admitted=np.ones(R, bool),
            memory=_model_bytes(session.model_cfg), extras={},
        )


@register_runner
class BaselineRunner(Runner):
    """Stream-admission baselines: the sequential loop gated by a policy.

    opts: ``policy`` (an ``AdmissionPolicy`` or a method name such as
    ``"one_skip"``), ``slowdown`` (t_train / t_d — how much slower training
    is than arrival), ``features`` ((R, d) array for Camel's coreset).
    """

    name = "baseline"

    def run(
        self, session, params, stream, *,
        policy: Union[str, AdmissionPolicy] = "one_skip",
        slowdown: float = 3.0, features: Optional[np.ndarray] = None,
    ):
        pol = policy if isinstance(policy, AdmissionPolicy) else AdmissionPolicy(policy)
        R = _rounds(stream)
        trace = make_admission_mask(
            pol, R, t_d=1.0, t_train=float(slowdown), features=features
        )
        params, losses, accs = _sequential_loop(
            session, params, stream, trained_mask=trace.admitted
        )
        memory = _model_bytes(session.model_cfg)
        if pol.method in ("random_n", "last_n", "camel"):
            item_bytes = sum(
                np.asarray(v[0]).nbytes for k, v in stream.items()
            )
            memory += pol.buffer * item_bytes
        return _sequential_result(
            session, self.name, params, losses, accs,
            delays=trace.delays, admitted=trace.admitted, memory=memory,
            extras={"raw": trace, "delays": trace.delays, "policy": pol},
        )
