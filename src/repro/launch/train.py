"""End-to-end OCL training driver.

Two modes:

- ``ferret`` (default): plan → fine-grained pipeline engine over a drifting
  token stream, with Iter-Fisher compensation (the paper's full system).
- ``plain``: supervised step loop with the fault-tolerant runtime
  (checkpoint/restart, NaN rollback, bounded-queue admission control) —
  the substrate a 1000-node deployment runs per host group.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch h2o-danube-1.8b --smoke \
      --steps 200 --mode ferret --budget-gb 2
  PYTHONPATH=src python -m repro.launch.train --arch mamba2-780m --smoke \
      --steps 100 --mode plain --ckpt-dir /tmp/ckpt
"""

from __future__ import annotations

import argparse
import dataclasses
import math
import time

import jax
import numpy as np

from repro.api import FerretSession
from repro.core.compensation import CompensationConfig
from repro.data.pipeline import DataPipeline, PipelineCfg, TokenStreamSource
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.steps import make_train_step
from repro.models import transformer as T
from repro.models.registry import get_config
from repro.ocl.streams import StreamConfig, make_stream
from repro.optim.optimizers import adamw
from repro.runtime.supervisor import Supervisor, SupervisorCfg


def parse_budget_schedule(spec: str):
    """``"0:inf,120:2,180:0.5"`` → BudgetEvents (round : budget in GiB).

    ``inf`` (or ``0``) means unconstrained (Ferret_M+)."""
    from repro.runtime import BudgetEvent

    events = []
    for item in spec.split(","):
        try:
            r, v = item.split(":")
            gib = math.inf if v.strip() == "inf" else float(v)
            if gib == 0:  # 0 = unconstrained, same semantics as --budget-gb
                gib = math.inf
            budget = gib if gib == math.inf else gib * 2**30
            events.append(BudgetEvent(round=int(r), budget_bytes=budget))
        except ValueError:
            raise SystemExit(
                f"--budget-schedule: bad entry {item!r} — expected "
                "'round:GiB' items like '0:inf,120:2,180:0.5'"
            ) from None
    return events


def _incremental_feed(args, cfg):
    """The training stream as a lazy, unbounded-style feed.

    Chunks of the drifting stream are generated on demand (per-chunk
    seeds) and handed over one round at a time, so the driver never holds
    more than one chunk — the elastic runner pulls it segment by segment
    and peak stream residency stays O(segment_rounds), not O(steps).
    """
    from repro.api import IterableStreamSource

    def rounds():
        chunk_len, produced, chunk_idx = 64, 0, 0
        while produced < args.steps:
            n = min(chunk_len, args.steps - produced)
            arrays = make_stream(StreamConfig(
                kind=args.stream, modality="tokens", length=n, batch=args.batch,
                vocab=min(cfg.vocab_size, 64), seq=args.seq,
                seed=args.seed + chunk_idx,
            ))
            for k in ("tokens", "labels"):
                arrays[k] = arrays[k] % cfg.vocab_size
            for m in range(n):
                yield {k: v[m] for k, v in arrays.items()}
            produced += n
            chunk_idx += 1

    return IterableStreamSource(rounds())  # length undeclared: live-feed path


def run_ferret(args) -> None:
    cfg = get_config(args.arch, smoke=args.smoke)
    cfg = dataclasses.replace(cfg, compute_dtype="float32" if args.smoke else cfg.compute_dtype)
    if args.incremental:
        stream = _incremental_feed(args, cfg)
    else:
        stream = make_stream(
            StreamConfig(
                kind=args.stream, modality="tokens", length=args.steps,
                batch=args.batch, vocab=min(cfg.vocab_size, 64), seq=args.seq,
            )
        )
        # clamp token ids into the model vocab
        for k in ("tokens", "labels"):
            stream[k] = stream[k] % cfg.vocab_size
    params = T.init_params(cfg, jax.random.PRNGKey(args.seed))
    budget = math.inf if args.budget_gb <= 0 else args.budget_gb * 2**30
    session = FerretSession(
        cfg, budget, args.ocl, stream,
        batch=args.batch, seq=args.seq, lr=args.lr,
        compensation=CompensationConfig(method=args.compensation),
        max_workers=4, max_stages=8, params=params,
        profile=args.profile, profile_feedback=args.profile_feedback,
    )
    plan = session.plan
    print(
        f"plan: P={plan.partition.num_stages} N={len(plan.config.active_workers())} "
        f"R={plan.rate:.3f} M={plan.memory/2**20:.1f}MiB feasible={plan.feasible} "
        f"profile={plan.profile_provenance}"
    )
    t0 = time.time()
    if args.budget_schedule:
        res = session.run("elastic", schedule=parse_budget_schedule(args.budget_schedule))
        dt = time.time() - t0
        for s in res.segments:
            p = s.result.plan
            b = "inf" if math.isinf(s.budget_bytes) else f"{s.budget_bytes/2**30:.2f}GiB"
            tag = (f" replan={1e3*s.replan_s:.0f}ms remap={1e3*s.remap_s:.0f}ms"
                   if s.replanned else "")
            cache = "hit" if s.cache_hit else "compile"
            print(f"  seg [{s.start},{s.end}) budget={b} P={p.partition.num_stages} "
                  f"N={len(p.config.active_workers())} M={p.memory/2**20:.1f}MiB "
                  f"engine={cache}@{s.rounds_compiled} "
                  f"oacc={s.result.online_acc:.4f}{tag}")
        resident = ""
        if args.incremental:
            resident = (
                f" peak-stream-residency={res.peak_buffered_rounds} "
                f"rounds (of {res.rounds}; no materialization)"
            )
        print(
            f"oacc={res.online_acc:.4f} admitted={res.admitted_frac:.2f} "
            f"replans={res.num_replans} "
            f"engine-cache misses={res.engine_cache_misses} "
            f"hits={res.engine_cache_hits} "
            f"({res.rounds} items, exactly once, in {dt:.1f}s){resident}"
        )
        return
    # the pipelined runner is streaming-native: a lazy --incremental feed
    # is pulled segment by segment with prefetch, same as a materialized
    # stream — only the residency report differs
    res = session.run("pipelined")
    dt = time.time() - t0
    lam = res.lam_curve
    resident = ""
    if args.incremental:
        resident = (
            f" peak-stream-residency={res.peak_buffered_rounds} "
            f"rounds (of {res.rounds}; no materialization)"
        )
    print(
        f"oacc={res.online_acc:.4f} admitted={res.admitted_frac:.2f} "
        f"loss {res.losses[0]:.3f}→{res.losses[-1]:.3f} λ={lam[-1]:.4f} "
        f"({res.rounds} items in {dt:.1f}s){resident}"
    )


def run_plain(args) -> None:
    cfg = get_config(args.arch, smoke=args.smoke)
    params = T.init_params(cfg, jax.random.PRNGKey(args.seed))
    opt = adamw(lr=args.lr)
    opt_state = opt.init(params)
    step_fn_raw = jax.jit(make_train_step(cfg, opt, remat=False))

    def step_fn(state, batch):
        params, opt_state = state
        b = {"tokens": batch["tokens"] % cfg.vocab_size,
             "labels": batch["labels"] % cfg.vocab_size}
        params, opt_state, metrics = step_fn_raw(params, opt_state, b)
        return (params, opt_state), metrics

    sup = Supervisor(
        SupervisorCfg(
            checkpoint_dir=args.ckpt_dir,
            checkpoint_every=args.ckpt_every,
            step_timeout_s=600.0,
            nan_check_every=1,
        ),
        step_fn,
        (params, opt_state),
    )
    source = TokenStreamSource(
        cfg.vocab_size, PipelineCfg(batch=args.batch, seq=args.seq, prefetch=4)
    )
    restored = sup.try_restore(extras_hook=lambda ex: source.seek(ex.get("cursor", 0)))
    if restored:
        print(f"restored from checkpoint @ step {sup.step}")
    pipe = DataPipeline(source, PipelineCfg(batch=args.batch, seq=args.seq, prefetch=4)).start()
    t0 = time.time()
    losses = []
    try:
        while sup.step < args.steps:
            batch = pipe.get()
            rep = sup.run_step(
                batch, extras={"cursor": int(batch["_cursor"])}, dropped=pipe.dropped
            )
            if not np.isnan(rep.loss):
                losses.append(rep.loss)
    finally:
        pipe.stop()
        sup.finalize(extras={"cursor": source.cursor})
    span = f"loss {losses[0]:.3f}→{losses[-1]:.3f}; " if losses else ""
    print(f"{sup.step} steps in {time.time()-t0:.1f}s; {span}dropped={pipe.dropped}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--mode", default="ferret", choices=["ferret", "plain"])
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--budget-gb", type=float, default=0.0, help="0 = unconstrained (M+)")
    ap.add_argument(
        "--budget-schedule", default=None,
        help="mid-stream budget changes as 'round:GiB,...' e.g. '0:inf,120:2,180:0.5' "
             "(ferret mode; live replan + state remap, no restart)",
    )
    ap.add_argument(
        "--incremental", action="store_true",
        help="feed the runner from a lazy round generator instead of "
             "materializing the stream — segment-by-segment take() with "
             "prefetch, peak stream residency O(segment), not O(steps) "
             "(works on the default pipelined runner and, with "
             "--budget-schedule, the elastic runner)",
    )
    ap.add_argument(
        "--profile", default="auto", choices=["auto", "analytic", "measured"],
        help="planner profile source: 'auto' uses a stored on-device "
             "measurement when one exists (analytic roofline otherwise), "
             "'measured' measures-and-persists on a store miss, 'analytic' "
             "never touches the store (ferret mode)",
    )
    ap.add_argument(
        "--profile-feedback", action="store_true",
        help="refine the persisted profile from observed segment wall-clock "
             "(host-side; later replans use the refined numbers)",
    )
    ap.add_argument("--compensation", default="iter_fisher")
    ap.add_argument("--ocl", default="vanilla")
    ap.add_argument("--stream", default="drift", choices=["iid", "split", "drift"])
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    args = ap.parse_args()
    enable_compile_cache()
    if args.mode == "ferret":
        run_ferret(args)
    else:
        run_plain(args)


if __name__ == "__main__":
    main()
