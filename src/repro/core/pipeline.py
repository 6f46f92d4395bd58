"""Fine-grained asynchronous pipeline engine (paper §5.1.1).

Executes the *learning dynamics* of Ferret's async 1F1B pipeline — per-stage
gradient staleness τ_j = P-1-j, gradient accumulation (T2), back-prop
omission (T3), worker interleave/removal (T4) — as one jit'd ``lax.scan``
over arriving stream items, driven by the statically precomputed
``EngineSchedule`` (repro.core.schedule).

Hardware adaptation note (DESIGN.md §2): XLA/TPU is SPMD-synchronous, so
wall-clock asynchrony is replaced by an exact deterministic emulation of
the staleness pattern; stage j's gradient, computed against the version-m
weights, is applied once the stage has advanced τ versions, and Iter-Fisher
compensates it at application time — precisely the paper's Fig. 9 model.
Throughput/latency effects are captured by the analytic cost model
(Eq. 3/4) that the planner optimizes.

Synchronous baselines (DAPPLE/GPipe-style flushes) run through the same
engine with ``sync_period=P`` schedules (fresh gradients, delayed updates).
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp

from repro.core import compensation as comp_lib
from repro.core.schedule import EngineSchedule, RingGeometry
from repro.optim.optimizers import Optimizer
from repro.state.engine_state import EngineState

Pytree = Any


@dataclasses.dataclass(frozen=True)
class StagedModel:
    """Model split into P sequential stages.

    forward_stage(j, stage_params, x, batch) -> activations (stage j<P-1)
                                                or logits  (stage P-1)
    loss(logits, batch) -> (scalar loss, metrics dict)
    """

    num_stages: int
    forward_stage: Callable
    loss: Callable


def staged_from_transformer(cfg, boundaries) -> StagedModel:
    """Adapter: repro.models.transformer -> StagedModel."""
    from repro.models import transformer as T
    from repro.models.layers import cross_entropy_loss

    P = len(boundaries) - 1

    def fwd(j, sp, x, batch):
        out, _aux = T.stage_forward(cfg, sp, x, j, P, boundaries, batch)
        return out

    def loss(logits, batch):
        ce = cross_entropy_loss(logits, batch["labels"], batch.get("mask"))
        preds = jnp.argmax(logits, axis=-1)
        acc = jnp.mean((preds == batch["labels"]).astype(jnp.float32))
        return ce, {"acc": acc}

    return StagedModel(P, fwd, loss)


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------


def _dyn_index(tree: Pytree, idx) -> Pytree:
    return jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, idx, 0, keepdims=False), tree)


def _dyn_update(tree: Pytree, val: Pytree, idx) -> Pytree:
    return jax.tree.map(
        lambda a, v: jax.lax.dynamic_update_index_in_dim(a, v.astype(a.dtype), idx, 0), tree, val
    )


class FerretEngine:
    """Builds and runs the scan. Construct once per (model, partition).

    The compiled scan is held by one persistent ``jax.jit`` wrapper, so
    repeated ``run`` calls — and schedule swaps via ``set_schedule`` that
    keep the array shapes — reuse the compiled executable instead of
    re-tracing. The *content* of the schedule is scan data (xs), not a
    trace constant; only its shapes (rounds, stages, ring depths) key the
    compile cache.

    ``penalty_fn(stage_params, penalty) -> scalar`` adds a
    *parameter-space* loss term (MAS/EWC-style pulls, weight decay against
    a reference) that the staged ``(logits, batch)`` loss cannot express:
    it sees the per-stage weight tuple directly and its gradient flows into
    the same backward as the data loss. ``penalty`` is the segment-constant
    state the term needs (e.g. Ω and the reference weights, split per
    stage) — it is passed through the jitted scan as an *argument*, not a
    closure constant, so refreshing it at a segment boundary reuses the
    compiled executable as long as shapes hold.
    """

    def __init__(
        self,
        staged: StagedModel,
        schedule: EngineSchedule,
        optimizer: Optimizer,
        comp_cfg: comp_lib.CompensationConfig,
        lr: float = 1e-3,
        penalty_fn: Optional[Callable] = None,
        mesh=None,
        hints=None,
    ):
        self.staged = staged
        self.sched = schedule
        self.opt = optimizer
        self.comp_cfg = comp_cfg
        self.lr = lr
        self.penalty_fn = penalty_fn
        # Optional jax Mesh (from DeviceTopology.mesh()): when set, run()
        # commits the stream's batch dim to the "data" axis and the engine
        # carry to full replication before the scan, and GSPMD partitions
        # the compiled executable across the mesh. mesh=None is the exact
        # historical single-device path — no array is ever re-placed.
        # ``hints`` (models.shard_hints.ShardHints, usually built with
        # shard_hints.for_topology) are installed around the sharded scan's
        # trace so the model's internal constraint points (logits, block
        # boundaries) pin their batch dim to the data axis.
        self.mesh = mesh
        self.hints = hints
        self._compiled = jax.jit(self._scan)
        # ``set_schedule`` mutates ``self.sched`` and ``run`` reads it —
        # callers sharing one engine across threads (a shared EngineCache,
        # the multi-tenant server) hold this across the whole
        # set_schedule → init_state → run span so one tenant's schedule
        # swap can never leak into another's in-flight scan
        self.exec_lock = threading.Lock()

    def set_schedule(self, schedule: EngineSchedule) -> None:
        """Swap the schedule. Same (rounds, stages, ring_size, delta_ring)
        → the already-compiled scan is reused; different shapes retrace."""
        self.sched = schedule

    @property
    def ring_geometry(self) -> RingGeometry:
        """Ring depths the live schedule shapes engine state for — what
        ``repro.state.StateRemapper`` re-time-indexes rings against."""
        return RingGeometry(
            ring_size=self.sched.ring_size, delta_ring=self.sched.delta_ring
        )

    # -- state ------------------------------------------------------------
    def init_state(
        self,
        stage_params: List[Pytree],
        opt_states=None,
        comp_states=None,
        rings=None,
        deltas=None,
        *,
        bounds=None,
        sched_origin=None,
    ) -> EngineState:
        """Typed ``EngineState`` for ``stage_params``.

        ``opt_states`` / ``comp_states`` carry per-stage optimizer and
        compensation state across a re-plan (runtime/elastic_trainer.py);
        when omitted they are freshly initialized. ``rings`` / ``deltas``
        carry in-flight gradient-accumulation groups and the Δθ history
        across segment boundaries — a cross-partition switch remaps them
        through ``repro.state.StateRemapper`` (they are zero-filled only
        when omitted, i.e. genuinely fresh). ``bounds`` / ``sched_origin``
        are recorded as state metadata for the remapper and checkpoints.
        """
        Rsz, K = self.sched.ring_size, self.sched.delta_ring
        f32 = jnp.float32
        if rings is None:
            rings = tuple(
                jax.tree.map(lambda p: jnp.zeros((Rsz, *p.shape), f32), sp)
                for sp in stage_params
            )
        if deltas is None:
            deltas = tuple(
                jax.tree.map(lambda p: jnp.zeros((K, *p.shape), f32), sp)
                for sp in stage_params
            )
        if opt_states is None:
            opt_states = tuple(self.opt.init(sp) for sp in stage_params)
        if comp_states is None:
            comp_states = tuple(
                comp_lib.init_state(sp, self.comp_cfg) for sp in stage_params
            )
        return EngineState(
            stage_params=tuple(stage_params),
            rings=tuple(rings),
            deltas=tuple(deltas),
            opt_states=tuple(opt_states),
            comp_states=tuple(comp_states),
            bounds=None if bounds is None else tuple(int(b) for b in bounds),
            geometry=self.ring_geometry,
            sched_origin=None if sched_origin is None else int(sched_origin),
        )

    # -- schedule arrays as scan xs ----------------------------------------
    def _schedule_xs(self) -> Dict[str, jnp.ndarray]:
        s = self.sched
        compute = (
            s.compute if s.compute is not None
            else jnp.ones(s.num_rounds, bool)
        )
        return {
            "process": jnp.asarray(s.process),
            "backward": jnp.asarray(s.backward),
            "push_slot": jnp.asarray(s.push_slot),
            "push_reset": jnp.asarray(s.push_reset),
            "pop_slot": jnp.asarray(s.pop_slot),
            "pop_scale": jnp.asarray(s.pop_scale),
            "delta_mask": jnp.asarray(s.delta_mask),
            "delta_push": jnp.asarray(s.delta_push_slot),
            "tau": jnp.asarray(s.tau),
            "compute": jnp.asarray(compute),
        }

    # -- one round ----------------------------------------------------------
    def _round(self, carry, xs, penalty):
        """One scan step. Bucket-padding rounds (``compute=False``, only
        ever emitted by ``pad_schedule``) skip the forward/backward through
        the cond — the carry passes through untouched and the per-round
        outputs are zeros, which the caller slices off."""

        def skip(carry, _xs):
            zero = jnp.zeros((), jnp.float32)
            ys = {
                "loss": zero, "acc": zero, "admitted": zero,
                "lam": zero, "tau_mean": zero,
            }
            return carry, ys

        def live(carry, xs):
            return self._live_round(carry, xs, penalty)

        return jax.lax.cond(xs["compute"], live, skip, carry, xs)

    def _live_round(self, carry, xs, penalty):
        stages, rings, deltas, opts, comps = carry
        batch = xs["batch"]
        P = self.staged.num_stages
        K = self.sched.delta_ring
        f32 = jnp.float32

        def full_loss(stages_t):
            x = None
            for j in range(P):
                x = self.staged.forward_stage(j, stages_t[j], x, batch)
            loss, metrics = self.staged.loss(x, batch)
            if self.penalty_fn is not None:
                loss = loss + self.penalty_fn(stages_t, penalty)
            return loss, metrics

        (loss, metrics), grads = jax.value_and_grad(full_loss, has_aux=True)(stages)
        pmask = xs["process"].astype(f32)

        new_stages, new_rings, new_deltas, new_opts, new_comps = [], [], [], [], []
        lam_sum = jnp.zeros((), f32)
        for j in range(P):
            bmask = pmask * xs["backward"][j].astype(f32)
            g_j = jax.tree.map(lambda g: g.astype(f32) * bmask, grads[j])

            # ---- push (accumulate into the gradient ring, T2) ----
            slot = jnp.maximum(xs["push_slot"][j], 0)

            def do_push(ring, g_j=g_j, slot=slot, reset=xs["push_reset"][j]):
                cur = _dyn_index(ring, slot)
                base = jax.tree.map(lambda c, g: jnp.where(reset, g, c + g), cur, g_j)
                return _dyn_update(ring, base, slot)

            ring_j = jax.lax.cond(xs["push_slot"][j] >= 0, do_push, lambda r: r, rings[j])

            # ---- pop (compensate + apply, Alg. 1) ----
            def do_pop(args, j=j):
                params, opt_s, comp_s, ring, dring = args
                pslot = jnp.maximum(xs["pop_slot"][j], 0)
                g = jax.tree.map(
                    lambda a: a * xs["pop_scale"][j], _dyn_index(ring, pslot)
                )
                order = (xs["delta_push"][j] + jnp.arange(K)) % K  # oldest→newest
                mask = xs["delta_mask"][j]
                dl = jax.tree.map(
                    lambda a: a[order] * mask.reshape((K,) + (1,) * (a.ndim - 1)), dring
                )
                comp_s, gc = comp_lib.compensate(
                    self.comp_cfg, comp_s, g, dl, lr=self.lr, tau=xs["tau"][j]
                )
                newp, new_opt = self.opt.update(params, gc, opt_s)
                dnew = jax.tree.map(
                    lambda a, b: a.astype(f32) - b.astype(f32), newp, params
                )
                dslot = jnp.maximum(xs["delta_push"][j], 0)
                dring = _dyn_update(dring, dnew, dslot)
                return (newp, new_opt, comp_s, ring, dring)

            operands = (stages[j], opts[j], comps[j], ring_j, deltas[j])
            st_j, opt_j, comp_j, ring_j, delta_j = jax.lax.cond(
                xs["pop_slot"][j] >= 0, do_pop, lambda a: a, operands
            )
            new_stages.append(st_j)
            new_rings.append(ring_j)
            new_deltas.append(delta_j)
            new_opts.append(opt_j)
            new_comps.append(comp_j)
            lam_sum = lam_sum + comp_j.lam

        ys = {
            "loss": loss,
            "acc": metrics["acc"],
            "admitted": xs["process"].astype(f32),
            "lam": lam_sum / P,
            "tau_mean": jnp.mean(xs["tau"].astype(f32)),
        }
        carry = (
            tuple(new_stages),
            tuple(new_rings),
            tuple(new_deltas),
            tuple(new_opts),
            tuple(new_comps),
        )
        return carry, ys

    # -- run ------------------------------------------------------------
    def _scan(self, state, xs, penalty):
        def round_fn(carry, x):
            return self._round(carry, x, penalty)

        return jax.lax.scan(round_fn, state, xs)

    def lower(self, state, stream: Dict[str, jnp.ndarray], penalty: Pytree = None):
        """The single-device scan ``run`` executes, lowered for these
        arguments (arrays or ``jax.ShapeDtypeStruct``s); ``.compile()`` on
        the result gives the program, e.g. to look for its kernels."""
        carry, xs = self._scan_args(state, stream)
        return self._compiled.lower(carry, xs, penalty)

    def _scan_args(self, state, stream):
        xs = dict(self._schedule_xs())
        xs["batch"] = stream
        return (state.as_tuple() if isinstance(state, EngineState) else state), xs

    def run(self, state, stream: Dict[str, jnp.ndarray], penalty: Pytree = None):
        """stream: dict of arrays stacked over rounds, e.g. tokens (R, b, s).

        ``penalty`` is the extras pytree for ``penalty_fn`` (required iff
        the engine was built with one); it rides through the jitted scan as
        an argument, so a same-shape refresh never retraces.

        ``state`` may be an ``EngineState`` (preferred — the returned final
        state keeps its bounds/geometry/schedule-origin metadata) or the
        legacy plain 5-tuple. Either way the *jitted scan* carries the
        plain tuple: the conversion happens here, outside the compiled
        function, so metadata changes (a new ``sched_origin`` every
        segment) never key the compile cache or force a retrace.

        Returns (final_state, ys dict of per-round metrics)."""
        if (self.penalty_fn is not None) and penalty is None:
            raise ValueError(
                "engine built with penalty_fn but run() got penalty=None — "
                "the algorithm must populate its penalty extras before the "
                "segment runs (see OCLAlgorithm.engine_penalty_extras)"
            )
        carry, xs = self._scan_args(state, stream)
        meta = state if isinstance(state, EngineState) else None
        if self.mesh is not None and self.mesh.devices.size > 1:
            from repro.launch import shardings as sh
            from repro.models import shard_hints as hints_lib

            # Commit placements at the jit boundary: batch dim of every
            # stream leaf over "data", carry replicated. device_put is a
            # no-op when the arrays already live there (steady state).
            xs["batch"] = jax.device_put(
                stream, sh.stream_shardings(self.mesh, stream)
            )
            carry = jax.device_put(carry, sh.state_shardings(self.mesh, carry))
            # The mesh context resolves the hints' PartitionSpecs inside
            # the traced scan (first call traces; later calls reuse the
            # executable, the context is then just a cheap no-op).
            with jax.set_mesh(self.mesh), hints_lib.use_hints(
                self.hints if self.hints is not None else hints_lib.ShardHints()
            ):
                final, ys = self._compiled(carry, xs, penalty)
        else:
            final, ys = self._compiled(carry, xs, penalty)
        if meta is not None:
            final = EngineState.from_tuple(
                final, bounds=meta.bounds, geometry=meta.geometry,
                sched_origin=meta.sched_origin,
            )
        return final, ys


# ---------------------------------------------------------------------------
# Delta-ring ordering: update u writes slot (u mod K). At pop time,
# delta_push = U mod K (U updates applied so far), and slot (U mod K) still
# holds update U-K — the *oldest* of the last K. Hence
# order = (delta_push + arange(K)) % K walks updates U-K..U-1 oldest→newest,
# and delta_mask keeps the most recent τ of them (the live staleness window).
# Verified against a reference simulation in tests/test_pipeline.py.
# ---------------------------------------------------------------------------
