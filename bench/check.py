"""The comparison that decides ``correct``: the plain reference of a run's
first rounds, and the readings compared with their limits.

The reference follows the training that the cell's files state, written
from their description and not from the program:

- experience replay: each round trains its ``batch`` new rows followed by
  ``replay_rows`` rows drawn with replacement from a reservoir of every
  earlier row (capacity ``replay_capacity``; its own NumPy generator seeded
  with ``replay_seed``); an empty reservoir repeats the round's first row;
- the asynchronous pipeline of a plan: ``bounds`` split the layers into P
  stages, round m belongs to worker m mod ``workers``, and only the
  ``active`` workers' rounds train. Stage j back-propagates one in
  ``omit[j] + 1`` of its worker's rounds, counted from the plan's start.
  Every round's loss is taken at the current weights; a trained round's
  gradient of stage j is applied at round m + workers * (P - 1 - j);
- Iter-Fisher compensation at apply time: tau is the number of updates
  of that stage since the gradient was taken (at most P); first the
  global lambda of the stage takes one step on its EMA statistics, then
  g <- g + lambda * g * g * dtheta over the last tau updates, oldest first;
- AdamW on each stage;
- a budget switch that changes the plan (at a segment boundary): every
  gradient still waiting is applied at once, oldest first, by AdamW alone
  (no compensation, no lambda step); the weights, AdamW's moments and the
  Iter-Fisher EMAs are then merged over the layers and split by the new
  bounds. A new stage takes the smallest AdamW step count and the
  layer-weighted mean lambda of the old stages it overlaps, and staleness
  counts from the switch.

Compared with what the program's timed path reported for the same rounds
(the traffic's ``check`` names them; each number has its limit in the
cell's file):

- ``loss0_gap``: round 0's loss, the forward pass at the seed's weights;
- ``loss_gap``: the widest loss gap over rounds 1 .. first_rounds - 1;
- each of ``loss_windows``: the widest loss gap over its rounds;
- each of ``changes``: the weights' change from the seed's weights when
  that round starts, leaf by leaf (a leaf of the blocks is one layer's):
  the gap between the program's and the reference's norm of a leaf's
  change, over the larger of the reference's norm of that leaf and of the
  median leaf; the worst leaf. Leaves whose first gradient in the
  reference is under a thousandth of the median leaf's are left out.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import statistics
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Tree = dict
Phases = List[Tuple[int, dict]]  # (first round, stated plan), ascending


# ---------------------------------------------------------------------------
# experience replay
# ---------------------------------------------------------------------------


def replay_batches(tokens: np.ndarray, labels: np.ndarray, traffic: dict) -> List[Tuple]:
    """The trained rows of each round: the new rows, then the replay rows."""
    rng = np.random.default_rng(traffic["replay_seed"])
    cap, rb = traffic["replay_capacity"], traffic["replay_rows"]
    store: List[Tuple[np.ndarray, np.ndarray]] = []
    seen = 0
    out = []
    for m in range(tokens.shape[0]):
        if store:
            idx = rng.integers(0, len(store), size=rb)
            rt = np.stack([store[i][0] for i in idx])
            rl = np.stack([store[i][1] for i in idx])
        else:
            rt = np.repeat(tokens[m][:1], rb, axis=0)
            rl = np.repeat(labels[m][:1], rb, axis=0)
        out.append((np.concatenate([tokens[m], rt]), np.concatenate([labels[m], rl])))
        for row in zip(tokens[m], labels[m]):
            seen += 1
            if len(store) < cap:
                store.append(row)
            else:
                k = rng.integers(0, seen)
                if k < cap:
                    store[k] = row
    return out


# ---------------------------------------------------------------------------
# stages and leaves
# ---------------------------------------------------------------------------


def split_stages(params: Tree, bounds: List[int]) -> List[Tree]:
    stages = []
    for j in range(len(bounds) - 1):
        lo, hi = bounds[j], bounds[j + 1]
        sp = {"blocks": jax.tree.map(lambda a: a[lo:hi], params["blocks"])}
        if j == 0:
            sp["embed"] = params["embed"]
        if j == len(bounds) - 2:
            sp["final_norm"] = params["final_norm"]
            sp["lm_head"] = params["lm_head"]
        stages.append(sp)
    return stages


def merge_stages(stages: List[Tree]) -> Tree:
    blocks = jax.tree.map(lambda *a: jnp.concatenate(a, axis=0), *[s["blocks"] for s in stages])
    return {"embed": stages[0]["embed"], "blocks": blocks,
            "final_norm": stages[-1]["final_norm"], "lm_head": stages[-1]["lm_head"]}


@functools.partial(jax.jit, static_argnums=1)
def leaf_norms(stages: List[Tree], bounds: Tuple[int, ...], base: Optional[Tree] = None):
    """Norm of each leaf of stage trees split on ``bounds`` (a leaf of the
    blocks is one layer's), of its difference from the whole-model ``base``
    when given. Slice by slice, so nothing the size of the model is made."""
    out = {}
    for j, sp in enumerate(stages):
        for k, a in sp["blocks"].items():
            for i in range(a.shape[0]):
                d = a[i] if base is None else a[i] - base["blocks"][k][bounds[j] + i]
                out[f"blocks.{k}.{bounds[j] + i}"] = jnp.sqrt(jnp.sum(jnp.square(d)))
        for k in ("embed", "final_norm", "lm_head"):
            if k in sp:
                d = sp[k] if base is None else sp[k] - base[k]
                out[k] = jnp.sqrt(jnp.sum(jnp.square(d)))
    return out


def change_norms(stages: List[Tree], bounds, params: Tree) -> Dict[str, float]:
    """Each leaf's norm of its change from ``params``, on the host."""
    norms = jax.device_get(leaf_norms(list(stages), tuple(int(b) for b in bounds), params))
    return {k: float(v) for k, v in norms.items()}


# ---------------------------------------------------------------------------
# the pipeline's training dynamics
# ---------------------------------------------------------------------------


def _tree_sum(fn, *trees) -> jax.Array:
    return sum(jnp.sum(fn(*xs)) for xs in zip(*(jax.tree.leaves(t) for t in trees)))


def _adamw(opt: dict):
    lr, b1, b2, eps = opt["lr"], opt["b1"], opt["b2"], opt["eps"]

    def adamw(p, g, m, v, count):
        count = count + 1
        m = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
        v = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, v, g)
        c1 = 1 - b1 ** count.astype(jnp.float32)
        c2 = 1 - b2 ** count.astype(jnp.float32)
        new = jax.tree.map(
            lambda p_, m_, v_: p_ - lr * (m_ / c1) / (jnp.sqrt(v_ / c2) + eps), p, m, v)
        return new, m, v, count

    return adamw


def _make_update(opt: dict, comp: dict):
    alpha, eta, nu = comp["alpha"], comp["eta_lambda"], comp["nu"]
    adamw = _adamw(opt)

    @jax.jit
    def update(p, g, m, v, count, lam, vr, va, last, live):
        s1 = _tree_sum(lambda g_, r, a: (1 - alpha) * (g_ - r) * a, g, vr, va)
        s2 = _tree_sum(lambda a: a * a, va)
        lam = lam - eta * (-2.0 * s1 + 2.0 * lam * s2 + 2.0 * nu * lam)
        vr = jax.tree.map(lambda r, g_: alpha * r + (1 - alpha) * g_, vr, g)
        va = jax.tree.map(lambda a, g_, d: alpha * a + (1 - alpha) * g_ * g_ * d, va, g, last)
        for d in live:  # oldest first
            g = jax.tree.map(lambda g_, d_: g_ + lam * g_ * g_ * d_, g, d)
        new, m, v, count = adamw(p, g, m, v, count)
        delta = jax.tree.map(lambda a, b: a - b, new, p)
        return new, m, v, count, lam, vr, va, delta

    return update, jax.jit(adamw)


@dataclasses.dataclass
class _State:
    """The reference's training state under one plan."""

    bounds: List[int]
    workers: int
    active: List[int]
    omit: List[int]
    seen: Dict[Tuple[int, int], int]  # rounds each (worker, stage) has trained
    stages: List[Tree]
    m: List[Tree]
    v: List[Tree]
    vr: List[Tree]
    va: List[Tree]
    count: List[jax.Array]
    lam: List[jax.Array]
    hist: List[List[Tree]]  # applied dtheta since the plan began, oldest first
    updates: List[int]
    queue: List[List[Tuple[int, Tree, int]]]  # (apply round, gradient, updates then)

    @property
    def P(self) -> int:
        return len(self.bounds) - 1


def _overlap(old: List[int], lo: int, hi: int) -> List[Tuple[int, int]]:
    """(old stage, layers it shares with [lo, hi))."""
    out = []
    for i in range(len(old) - 1):
        n = min(hi, old[i + 1]) - max(lo, old[i])
        if n > 0:
            out.append((i, n))
    return out


class Reference:
    """The reference's training over given rounds, its programs built once.

    ``quant`` computes it at a lower precision (the control); ``fault``
    plants a fault in it: ``"frozen"`` applies no update, ``"half_batch"``
    takes the loss and its gradient over the first half of the rows only.
    """

    def __init__(self, ref, model: dict, traffic: dict, *,
                 quant: Optional[str] = None, fault: Optional[str] = None):
        self.comp = traffic["compensation"]
        self.fault = fault

        def stage_loss(stages, tokens, labels):
            return ref.loss(model, merge_stages(stages), tokens, labels, quant)

        self.grad_fn = jax.jit(jax.value_and_grad(stage_loss))
        self.update, self.adamw = _make_update(traffic["optimizer"], self.comp)

    def _begin(self, params: Tree, plan: dict) -> _State:
        stages = split_stages(params, plan["bounds"])
        zeros = lambda: [jax.tree.map(jnp.zeros_like, s) for s in stages]  # noqa: E731
        P = len(stages)
        return _State(list(plan["bounds"]), int(plan["workers"]), list(plan["active"]),
                      list(plan["omit"]), {}, stages,
                      zeros(), zeros(), zeros(), zeros(),
                      [jnp.zeros((), jnp.int32)] * P, [jnp.float32(self.comp["lam0"])] * P,
                      [[] for _ in range(P)], [0] * P, [[] for _ in range(P)])

    def _switch(self, st: _State, plan: dict) -> _State:
        """The plan changes: flush what waits, then merge and re-split."""
        for j in range(st.P):
            for _, g, _ in st.queue[j]:
                if self.fault != "frozen":
                    st.stages[j], st.m[j], st.v[j], st.count[j] = self.adamw(
                        st.stages[j], g, st.m[j], st.v[j], st.count[j])
        new = self._begin(merge_stages(st.stages), plan)
        for name in ("m", "v", "vr", "va"):
            setattr(new, name, split_stages(merge_stages(getattr(st, name)), new.bounds))
        for j in range(new.P):
            ov = _overlap(st.bounds, new.bounds[j], new.bounds[j + 1])
            new.count[j] = min((st.count[i] for i, _ in ov), key=int)
            new.lam[j] = sum(n * st.lam[i] for i, n in ov) / sum(n for _, n in ov)
        return new

    def run(self, params: Tree, batches: List[Tuple], phases: Phases,
            loss_rounds: set, change_rounds: set) -> dict:
        """Losses of ``loss_rounds``, each leaf's change from ``params`` as
        each of ``change_rounds`` starts, and then also the norms of round
        0's gradient; rounds that train nothing and are not asked for are
        skipped, since they change nothing."""
        starts = dict(phases)
        st: Optional[_State] = None
        losses: Dict[int, float] = {}
        changes: Dict[int, Dict[str, float]] = {}
        grad0: Optional[Dict[str, float]] = None
        for r in range(len(batches) + 1):
            if r in change_rounds:
                changes[r] = change_norms(st.stages, st.bounds, params)
            if r == len(batches):
                break
            if r in starts:
                st = self._begin(params, starts[r]) if st is None else self._switch(st, starts[r])
            trains = r % st.workers in st.active
            due = any(q and q[0][0] == r for q in st.queue)
            if not (trains or due or r in loss_rounds):
                continue
            tokens, labels = batches[r]
            if self.fault == "half_batch":
                half = tokens.shape[0] // 2
                tokens, labels = tokens[:half], labels[:half]
            loss, grads = self.grad_fn(st.stages, jnp.asarray(tokens), jnp.asarray(labels))
            losses[r] = float(loss)
            if grad0 is None and change_rounds:
                grad0 = {k: float(v) for k, v in
                         jax.device_get(leaf_norms(grads, tuple(st.bounds))).items()}
            for j in range(st.P if trains else 0):
                k = st.seen.get((r % st.workers, j), 0)
                st.seen[(r % st.workers, j)] = k + 1
                if k % (st.omit[j] + 1) == 0:
                    st.queue[j].append((r + st.workers * (st.P - 1 - j), grads[j], st.updates[j]))
            del grads
            for j in range(st.P):
                if not st.queue[j] or st.queue[j][0][0] != r:
                    continue
                _, g, seen = st.queue[j].pop(0)
                if self.fault == "frozen":
                    continue
                tau = min(st.updates[j] - seen, st.P)
                live = tuple(st.hist[j][len(st.hist[j]) - tau:]) if tau else ()
                last = live[-1] if live else jax.tree.map(jnp.zeros_like, g)
                (st.stages[j], st.m[j], st.v[j], st.count[j], st.lam[j], st.vr[j], st.va[j],
                 delta) = self.update(st.stages[j], g, st.m[j], st.v[j], st.count[j], st.lam[j],
                                      st.vr[j], st.va[j], last, live)
                st.hist[j] = (st.hist[j] + [delta])[-st.P:]
                st.updates[j] += 1
        return {"loss": losses, "change": changes, "grad0": grad0}


# ---------------------------------------------------------------------------
# readings and limits
# ---------------------------------------------------------------------------


def asked(spec: dict) -> Tuple[set, set]:
    """The rounds whose loss, and the rounds at whose start the change,
    the traffic's ``check`` compares."""
    loss = set(range(spec["first_rounds"]))
    for a, b in spec.get("loss_windows", {}).values():
        loss |= set(range(a, b))
    return loss, set(spec.get("changes", {}).values())


def rounds_needed(spec: dict) -> int:
    loss, change = asked(spec)
    return max(max(loss) + 1, max(change, default=0))


def change_gap(program: Dict[str, float], reference: Dict[str, float],
               grad0: Dict[str, float]) -> float:
    g_med = statistics.median(grad0.values())
    keep = [k for k in reference if grad0[k] >= 1e-3 * g_med]
    med = statistics.median(reference[k] for k in keep)
    return max(abs(program[k] - reference[k]) / max(reference[k], med) for k in keep)


def readings(spec: dict, program: dict, reference: dict) -> Dict[str, float]:
    """Numbers compared between the program and the reference (the
    module's docstring); ``program["loss"]`` is indexed by round."""

    def gap(r):
        return abs(float(program["loss"][r]) - reference["loss"][r])

    out = {"loss0_gap": gap(0),
           "loss_gap": max(gap(r) for r in range(1, spec["first_rounds"]))}
    for name, (a, b) in spec.get("loss_windows", {}).items():
        out[name] = max(gap(r) for r in range(a, b))
    for name, r in spec.get("changes", {}).items():
        out[name] = change_gap(program["change"][r], reference["change"][r], reference["grad0"])
    return out


def judge(values: Dict[str, float], limits: Dict[str, float]) -> Tuple[bool, Dict[str, dict]]:
    """Each compared number beside its limit; correct iff none is over."""
    checks = {k: {"value": values[k], "limit": limits[k]} for k in limits}
    ok = all(math.isfinite(c["value"]) and c["value"] <= c["limit"] for c in checks.values())
    return ok, checks


def reference_for(cell, **variant) -> Reference:
    return Reference(cell.reference, cell.config["model"], cell.traffic, **variant)


def reference_run(reference: Reference, cell, params: Tree, outcome) -> dict:
    spec = cell.traffic["check"]
    loss, change = asked(spec)
    batches = replay_batches(outcome.kept["tokens"], outcome.kept["labels"], cell.traffic)
    with jax.default_matmul_precision("highest"):
        return reference.run(params, batches, outcome.phases, loss, change)


def compare(cell, params: Tree, outcome) -> Dict[str, float]:
    """Run the reference over the rounds the traffic's ``check`` names and
    read the gaps to what the program reported."""
    with jax.default_matmul_precision("highest"):
        reference = reference_for(cell)
    want = reference_run(reference, cell, params, outcome)
    return readings(cell.traffic["check"], outcome.program, want)
