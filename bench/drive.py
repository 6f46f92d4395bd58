"""One run of one cell: set-up, the measured window, then the check.

The system under test is driven only through its session API and the
``StreamSource`` protocol:

- ``runner: "pipelined"`` calls ``FerretSession.run("pipelined")`` once.
  Warm-up happens in that same call (a second call would build a new
  engine and trace again). The source is unbounded; the trainer's feeder
  pulls one segment ahead, so the take for segment j arrives as segment
  j - 1 starts. The window opens with the take that arrives as segment
  ``warmup_segments`` starts, and the source ends the stream once one
  more segment would carry the window past ``--seconds``. The window then
  holds whole segments only and closes when ``run`` returns.
- ``runner: "elastic"`` opens ``FerretSession.open_stream_run`` and steps
  it segment by segment under a budget that alternates between the
  unconstrained plan and a fraction of its memory. Warm-up steps through
  one whole cycle, so both engines and both remap directions are built;
  the window then runs whole cycles. The weights' change that the check
  compares is read from the run's live snapshot (``live_resume_state``)
  after the warm-up segments that the traffic's ``check`` names.
"""

from __future__ import annotations

import dataclasses
import gc
import math
import threading
import time
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

import check
import trace_scopes
from stream_gen import DriftStream


# ---------------------------------------------------------------------------
# compile time, from JAX's own monitoring events
# ---------------------------------------------------------------------------


class CompileMeter:
    """Trace + lower + backend-compile seconds, each stamped with the host
    clock when JAX reported it."""

    EVENTS = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self):
        import jax

        self.events: List[tuple] = []  # (perf_counter, seconds)
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_duration(self, event, secs, **_):
        if event in self.EVENTS:
            with self._lock:
                self.events.append((time.perf_counter(), float(secs)))

    def seconds(self, t0: float, t1: float) -> float:
        with self._lock:
            return sum(s for t, s in self.events if t0 <= t < t1)


# ---------------------------------------------------------------------------
# the profiler over the window (--trace 1)
# ---------------------------------------------------------------------------


class Tracer:
    def __init__(self, directory: Optional[Path]):
        self.dir = directory
        self.on = False

    def start(self) -> None:
        if self.dir is None or self.on:
            return
        import jax

        jax.profiler.start_trace(str(self.dir))
        self.on = True

    def stop(self) -> None:
        if self.on:
            import jax

            jax.profiler.stop_trace()
            self.on = False


# ---------------------------------------------------------------------------
# what a run hands to the metric readers
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Segment:
    """One segment of the window, as the harness saw it."""

    rounds: int
    trained_rounds: float  # admitted rounds (rounds the plan trains)
    replanned: bool = False
    replan_s: float = 0.0
    remap_s: float = 0.0
    take_s: float = 0.0
    plan_memory: float = 0.0


@dataclasses.dataclass
class Run:
    cell: object
    devices: list
    peaks: dict  # the device's row of bench/peaks.json
    setup_s: float = 0.0
    window_s: float = 0.0
    window_segments: List[Segment] = dataclasses.field(default_factory=list)
    stream_tokens: float = 0.0  # new (not replayed) tokens trained in the window
    feeder_wait_s: float = 0.0
    compile_setup_s: float = 0.0
    compile_window_s: float = 0.0
    plan_memory: float = 0.0  # the largest plan in force in the window
    peak_bytes: int = 0
    # bench/trace_reduce.summarize(...), and from bench/trace_scopes.summarize(...)
    # the program's names: run.SCOPED
    trace: Optional[dict] = None
    segment_s: List[float] = dataclasses.field(default_factory=list)  # wall time of each
    gen_s_per_round: float = 0.0

    @property
    def chips(self) -> int:
        return len(self.devices)

    @property
    def window_rounds(self) -> int:
        return sum(s.rounds for s in self.window_segments)


# ---------------------------------------------------------------------------
# weights from the seed, on the device, in one call
# ---------------------------------------------------------------------------


def prng_key(seed: int, salt: int):
    import jax
    import jax.numpy as jnp

    words = np.random.SeedSequence([int(seed), salt]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def make_params(shapes: dict, seed: int):
    """fp32 weights of the shapes a reference states (``param_shapes``):
    norms 0 (scale 1 + w), embedding N(0, 0.02), every other leaf N(0,
    1/fan_in) with its second-to-last axis the fan-in; one jitted call on
    the default device."""
    import jax
    import jax.numpy as jnp

    flat, treedef = jax.tree.flatten_with_path(shapes, is_leaf=lambda s: isinstance(s, tuple))

    def init(key):
        leaves = []
        for i, (path, shape) in enumerate(flat):
            name = str(path[-1].key)
            k = jax.random.fold_in(key, i)
            if "norm" in name:
                leaves.append(jnp.zeros(shape, jnp.float32))
            elif name == "embed":
                leaves.append(0.02 * jax.random.normal(k, shape, jnp.float32))
            else:
                leaves.append(jax.random.normal(k, shape, jnp.float32) / math.sqrt(shape[-2]))
        return jax.tree.unflatten(treedef, leaves)

    return jax.jit(init)(prng_key(seed, 1))


# ---------------------------------------------------------------------------
# the stream as the session sees it
# ---------------------------------------------------------------------------


def windowed_source(gen: DriftStream, traffic: dict, seconds: float, keep_rounds: int,
                    tracer: "Tracer"):
    from repro.api.streams import StreamSource

    warmup = int(traffic["warmup_segments"])
    traced = int(traffic.get("trace_segments", 0))

    class WindowSource(StreamSource):
        """Unbounded drift stream whose end the harness sets by the clock.

        Take j brings the rows of segment j and arrives as segment j - 1
        starts. The window opens with take ``warmup + 1``; a take is
        refused, ending the stream, once the segment it would add could
        not finish within ``seconds`` of that (with half a segment to
        spare). The profiler starts when about ``trace_segments`` segments
        are left. The first ``keep_rounds`` rounds are kept for the check."""

        def __init__(self):
            self.calls: List[float] = []  # when each take arrived
            self.spent: List[float] = []  # host seconds each take took
            self.admitted = 0
            self.cursor = 0
            self.t_start: Optional[float] = None
            self.closed = False
            self.kept: List[Dict[str, np.ndarray]] = []
            self.lock = threading.Lock()

        @property
        def length(self):
            return None

        @property
        def remaining(self):
            return None

        def admit(self, j: int, t: float) -> bool:
            if seconds == math.inf or j <= warmup:
                return True
            if j == warmup + 1:
                self.t_start = t
            seg_est = self.calls[warmup + 1] - self.calls[warmup]
            left = seconds - (t - self.t_start)
            if left < (traced + 1.5) * seg_est:
                tracer.start()
            return left >= 1.5 * seg_est or j == warmup + 1

        def take(self, n: int):
            with self.lock:
                t = time.perf_counter()
                self.calls.append(t)
                if self.closed or not self.admit(len(self.calls) - 1, t):
                    self.closed = True
                    self.spent.append(time.perf_counter() - t)
                    return None
                self.admitted += 1
                rows = gen.rows(self.cursor, n)
                if self.cursor < keep_rounds:
                    self.kept.append({k: v[:keep_rounds - self.cursor].copy()
                                      for k, v in rows.items()})
                self.cursor += n
                self.spent.append(time.perf_counter() - t)
                return rows

        def kept_rows(self) -> Dict[str, np.ndarray]:
            return {k: np.concatenate([p[k] for p in self.kept]) for k in self.kept[0]}

    return WindowSource()


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------


def program_model_config(config: dict, reference):
    """The program's ModelConfig for the configuration file: the registry's
    architecture with every field the reference's ``PROGRAM`` names set
    from the file, which must then be the block the reference implements
    (its ``requires``)."""
    import dataclasses as dc

    from repro.models.registry import get_config

    m, prog = config["model"], reference.PROGRAM
    unknown = sorted(set(m) - set(prog["fields"]) - set(prog["optional"]))
    if unknown:
        raise ValueError(f"{config['name']}: reference {config['reference']!r} implements "
                         f"no {unknown}")
    fields = {k: m[k] for k in prog["fields"]}
    fields.update({k: m.get(k) for k in prog["optional"]})
    pcfg = dc.replace(get_config(config["registry_name"]), **fields)
    got = {k: getattr(pcfg, k) for k in prog["requires"]}
    if got != prog["requires"]:
        raise ValueError(f"{config['name']}: the program's block {got} is not the block of "
                         f"reference {config['reference']!r}, {prog['requires']}")
    return pcfg


def make_session(cell, params):
    from repro.api import FerretSession
    from repro.core.compensation import CompensationConfig
    from repro.ocl.algorithms import OCLConfig
    from repro.optim.optimizers import adamw

    tr = cell.traffic
    comp, opt = tr["compensation"], tr["optimizer"]
    topology = None
    if cell.chips > 1:
        from repro.runtime.topology import DeviceTopology

        topology = DeviceTopology.discover(max_devices=cell.chips)
    return FerretSession(
        program_model_config(cell.config, cell.reference), math.inf, tr["algorithm"],
        batch=tr["batch"], seq=tr["seq"], lr=opt["lr"],
        compensation=CompensationConfig(
            method=comp["method"], lam0=comp["lam0"], alpha=comp["alpha"],
            eta_lambda=comp["eta_lambda"], nu=comp["nu"]),
        ocl=OCLConfig(method=tr["algorithm"], replay_size=tr["replay_capacity"],
                      replay_batch=tr["replay_rows"], seed=tr["replay_seed"]),
        optimizer=adamw(lr=opt["lr"], b1=opt["b1"], b2=opt["b2"], eps=opt["eps"]),
        params=params, topology=topology,
    )


def plan_departures(plan, stated: dict) -> int:
    """How far the program's plan departs from the one the cell states
    (bounds, workers, which of them train, each stage's omission, no
    accumulation)."""
    bad = int(list(plan.partition.bounds) != list(stated["bounds"]))
    workers = plan.config.workers
    bad += int(len(workers) != stated["workers"])
    bad += int([i for i, w in enumerate(workers) if not w.removed] != list(stated["active"]))
    bad += sum(int(k.accum != 1 or k.omit != o)
               for w in workers for k, o in zip(w.stages, stated["omit"]))
    return bad


@dataclasses.dataclass
class Outcome:
    run: Run
    program: dict  # what the timed path reported: "loss" by round, "change" by round
    phases: list  # (first round, stated plan) that the reference follows
    exact: Dict[str, float]  # exact checks (limit 0)
    kept: Dict[str, np.ndarray]  # raw rows of the rounds the check needs
    attempted: int
    failed: int


def warm_merge(session, params) -> None:
    """Compile, in set-up, the stage split and merge that ``run`` does on
    its way out (the final weights), so that none compiles in the window."""
    import jax

    from repro.models import transformer as T

    stages = T.split_stage_params(session.model_cfg, params, list(session.plan.partition.bounds))
    jax.block_until_ready(T.merge_stage_params(session.model_cfg, stages))


def run_pipelined(cell, session, gen, run: Run, seconds, meter, tracer, t0, params) -> Outcome:
    tr = cell.traffic
    n = check.rounds_needed(tr["check"])
    src = windowed_source(gen, tr, seconds, n, tracer)
    plan = cell.stated["plan"]
    exact = {"plan_departures": float(plan_departures(session.plan, plan))}
    warm_merge(session, params)
    res = session.run("pipelined", stream=src, segment_rounds=tr["segment_rounds"])
    t_end = time.perf_counter()
    run.peak_bytes = peak_bytes(run.devices)
    tracer.stop()
    seg = int(tr["segment_rounds"])
    segs = src.admitted - int(tr["warmup_segments"])
    t_start = src.t_start if src.t_start is not None else t_end
    run.setup_s = t_start - t0
    run.window_s = t_end - t_start
    run.window_segments = [Segment(seg, float(seg), plan_memory=float(res.plan.memory))
                           for _ in range(segs)]
    run.stream_tokens = float(segs * seg * tr["batch"] * tr["seq"])
    # take j + 1 arrives as segment j starts
    starts = src.calls[int(tr["warmup_segments"]) + 1:][:segs] + [t_end]
    run.segment_s = [b - a for a, b in zip(starts, starts[1:])]
    # the feeder's wait over the run, less the first take, which is
    # synchronous by design (nothing to overlap it with)
    run.feeder_wait_s = max(float(res.extras["stream_wait_s"]) - src.spent[0], 0.0)
    run.plan_memory = float(res.plan.memory)
    run.compile_setup_s = meter.seconds(t0, t_start)
    run.compile_window_s = meter.seconds(t_start, t_end)
    program = {"loss": np.asarray(res.losses[:n]), "change": {}}
    return Outcome(run, program, [(0, plan)], exact, src.kept_rows(), attempted=segs * seg,
                   failed=0)


def budget_for(tr: dict, high_memory: float):
    b = tr["budget"]
    low = high_memory * b["low_fraction"]

    def budget(cursor: int) -> float:
        if cursor < b["first_high_rounds"]:
            return math.inf
        phase = (cursor - b["first_high_rounds"]) // b["phase_rounds"]
        return low if phase % 2 == 0 else math.inf

    return budget


def run_elastic(cell, session, gen, run: Run, seconds, meter, tracer, t0, params) -> Outcome:
    tr = cell.traffic
    n = check.rounds_needed(tr["check"])
    change_at = set(tr["check"].get("changes", {}).values())
    src = windowed_source(gen, tr, math.inf, n, tracer)
    budget = budget_for(tr, float(session.plan.memory))

    def stated(cursor: int) -> dict:
        return cell.stated["plan"] if math.isinf(budget(cursor)) else cell.stated["low_plan"]

    er = session.open_stream_run(stream=src, schedule=budget,
                                 segment_rounds=tr["segment_rounds"], prefetch=True)
    reports, changes = [], {}
    for _ in range(int(tr["warmup_segments"])):
        reports.append(er.step())
        if reports[-1].end in change_at:
            # the weights as this segment left them (the run's live
            # snapshot), read before the next segment starts
            live = er.trainer.live_resume_state()
            changes[reports[-1].end] = check.change_norms(live.stage_params, live.bounds, params)
    warm = len(reports)
    if reports[-1].end < n or set(changes) != change_at:
        raise ValueError(f"{cell.name}: the check's rounds must lie in the warm-up segments")
    t_start = time.perf_counter()
    unit = int(tr["window_unit_segments"])
    # window: whole units (budget cycles) while another one ends within
    # ``seconds`` (half a unit to spare); the profiler takes the last unit
    # (none when the first is the last)
    unit_s = None
    while True:
        if unit_s is not None and (time.perf_counter() - t_start) + 1.5 * unit_s > seconds:
            tracer.start()
        t_u = time.perf_counter()
        for _ in range(unit):
            t_s = time.perf_counter()
            reports.append(er.step())
            run.segment_s.append(time.perf_counter() - t_s)
        now = time.perf_counter()
        unit_s = now - t_u
        if (now - t_start) + 0.5 * unit_s > seconds:
            break
    t_end = time.perf_counter()
    run.peak_bytes = peak_bytes(run.devices)
    tracer.stop()
    er.stop()
    window = reports[warm:]
    run.setup_s = t_start - t0
    run.window_s = t_end - t_start
    run.window_segments = [
        Segment(r.end - r.start, r.result.admitted_frac * (r.end - r.start), r.replanned,
                r.replan_s, r.remap_s, r.take_s, float(r.result.plan.memory))
        for r in window]
    run.stream_tokens = sum(s.trained_rounds for s in run.window_segments) * tr["batch"] * tr["seq"]
    run.feeder_wait_s = sum(s.take_s for s in run.window_segments)
    run.plan_memory = max(s.plan_memory for s in run.window_segments)
    run.compile_setup_s = meter.seconds(t0, t_start)
    run.compile_window_s = meter.seconds(t_start, t_end)
    # every segment under the plan stated for its budget; exactly once:
    # segments tile the stream and no switch loses a round
    tiles = [(r.start, r.end) for r in reports]
    gaps = sum(int(a[1] != b[0]) for a, b in zip(tiles, tiles[1:])) + int(tiles[0][0] != 0)
    exact = {
        "plan_departures": float(sum(plan_departures(r.result.plan, stated(r.start))
                                     for r in reports)),
        "rounds_lost": float(sum(r.rounds_lost for r in reports)),
        "tiling_gaps": float(gaps),
    }
    phases = []
    for r in reports:
        if r.start < n and (not phases or phases[-1][1] is not stated(r.start)):
            phases.append((r.start, stated(r.start)))
    program = {"loss": np.concatenate([np.asarray(r.result.losses) for r in reports[:warm]]),
               "change": changes}
    rounds = sum(s.rounds for s in run.window_segments)
    return Outcome(run, program, phases, exact, src.kept_rows(), attempted=rounds,
                   failed=int(exact["rounds_lost"]))


RUNNERS = {"pipelined": run_pipelined, "elastic": run_elastic}
# engine runs at the head of a trace that may have begun inside a run: the
# pipelined window's trace starts from the feeder while segment
# ``warmup_segments`` is already on the device
TRACE_SKIP_RUNS = {"pipelined": 1, "elastic": 0}


def peak_bytes(devices) -> int:
    return max(int(d.memory_stats()["peak_bytes_in_use"]) for d in devices)


def execute(cell, seed: int, seconds: float, trace_dir: Optional[Path], t0: float,
            devices: list, peaks: dict) -> tuple:
    """Set-up, window and free; returns (Outcome, params) with the
    program's state gone and the harness's weights kept for the check."""
    meter = CompileMeter()
    tracer = Tracer(trace_dir)
    tr = cell.traffic
    params = make_params(cell.reference.param_shapes(cell.config["model"]), seed)
    gen = DriftStream.from_traffic(tr, cell.config["model"]["vocab_size"], seed)
    run = Run(cell=cell, devices=list(devices), peaks=peaks)
    session = make_session(cell, params)
    outcome = RUNNERS[tr["runner"]](cell, session, gen, run, seconds, meter, tracer, t0, params)
    session.stream = None
    del session
    gc.collect()
    run.gen_s_per_round = gen.gen_s / max(gen.rounds_made, 1)
    return outcome, params


# ---------------------------------------------------------------------------
# what the trace reduction looks for
# ---------------------------------------------------------------------------


def is_engine_module(name: str) -> bool:
    """The engine's compiled scan (``jax.jit(FerretEngine._scan)``)."""
    return name.startswith("jit__scan")


def is_kernel_op(hlo: str) -> bool:
    """An Iter-Fisher kernel (compensation or lambda-statistics), by the
    name the program gives each launch in its HLO instruction's
    ``kernel_metadata``; another kernel, or one without a name, is not."""
    name = trace_scopes.kernel_of(hlo)
    return name is not None and name.startswith("iter_fisher_")
