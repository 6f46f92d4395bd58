"""Chip smoke: the Ferret online-continual-learning trainer end to end on a TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips: the multi-device paths only

One chip, one process, three phases at musicgen-medium's published widths
(d_model 1536, 24 heads, d_ff 6144, vocab 2048), cut only in depth
(48 -> 2 layers), random weights from a seed:

1. kernels   - the Iter-Fisher Pallas kernels (packed and per-leaf, τ = 1
               and 3, compensation and λ-statistics) against the jnp
               reference over the model's whole parameter tree;
2. pipelined - ``FerretSession(...).run("pipelined")`` over a seeded drift
               token stream with ER replay and Iter-Fisher compensation;
               the engine's compiled program must hold the kernels;
3. elastic   - the same session under ``run("elastic")`` with a budget
               drop that forces a different partition (replan + state
               remap on the device), exactly-once and lossless.

``--chips 4`` runs only what exists across chips: the data-parallel engine
on four devices against the same stream on one, and the stage-axis
wavefront (``core/stage_parallel.py``) against the sequential loss.

Every phase prints one JSON line; the last line is
``{"ok": true, "device": {...}}``. Any failed check or exception exits
non-zero before that line is printed, and the script refuses to run
without a TPU. Times printed here are smoke timings, not benchmark numbers.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "musicgen-medium"
LAYERS = 2  # 48 in the published config: the one cut, in depth
BATCH, SEQ = 4, 512
ROUNDS = 32
SEED = 0
LAM = 0.3  # λ for the kernel check
ALPHA = 0.9  # EMA decay for the kernel check
KERNEL_TOL = 1e-5  # fp32: max |kernel - ref| / (1 + max |ref|), per tree
STATS_RTOL = 1e-4  # s1/s2: |kernel - ref| / Σ|terms| over the whole tree


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


class CompileMeter:
    """Trace + lower + backend-compile seconds and persistent-cache hits,
    read from JAX's own monitoring events."""

    EVENTS = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self):
        import jax

        self.secs = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event in self.EVENTS:
            self.secs += secs

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return self.secs, self.cache_hits


def peak_bytes(device) -> int:
    return int(device.memory_stats()["peak_bytes_in_use"])


def model_config(layers: int = LAYERS):
    from repro.models.registry import get_config

    return dataclasses.replace(get_config(ARCH), num_layers=layers)


def token_stream(cfg, rounds: int, batch: int, seq: int, seed: int = SEED):
    from repro.ocl.streams import StreamConfig, make_stream

    return make_stream(StreamConfig(
        kind="drift", modality="tokens", length=rounds, batch=batch,
        vocab=cfg.vocab_size, seq=seq, seed=seed,
    ))


def session_for(cfg, stream, params, budget=math.inf, topology=None, batch=BATCH, seq=SEQ):
    from repro.api import FerretSession
    from repro.core.compensation import CompensationConfig

    return FerretSession(
        cfg, budget, "er", stream, batch=batch, seq=seq, seed=SEED,
        compensation=CompensationConfig(method="iter_fisher"),
        params=params, topology=topology,
    )


def plan_fields(plan) -> dict:
    return {
        "P": plan.partition.num_stages,
        "bounds": list(plan.partition.bounds),
        "N": len(plan.config.active_workers()),
        "plan_memory_bytes": float(plan.memory),
    }


# ---------------------------------------------------------------------------
# phase 1: kernels against the reference at real width
# ---------------------------------------------------------------------------


def _tree_err(got, want) -> float:
    """max |got - want| / (1 + max |want|), worst leaf; reduced on device."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def err(got, want):
        return jnp.max(jnp.stack([
            jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))
            / (1.0 + jnp.max(jnp.abs(b.astype(jnp.float32))))
            for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want))
        ]))

    return float(err(got, want))


def _rel(a, b, scale=None) -> float:
    scale = abs(float(b)) if scale is None else float(scale)
    return abs(float(a) - float(b)) / max(scale, 1e-30)


def kernel_check(params, interpret: bool = False) -> dict:
    """Pallas Iter-Fisher kernels vs the jnp reference over ``params``'s tree."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops, packing
    from repro.kernels import ref as kref

    if not interpret:  # the per-leaf path must dispatch to the chip's kernels
        check(ops._use_pallas() and not ops._pallas_interpret(),
              "ops dispatch does not pick the Pallas kernels on this device")
    leaves, treedef = jax.tree.flatten(params)
    keys = iter(jax.random.split(jax.random.PRNGKey(SEED + 1), 8 * len(leaves)))

    def rand(scale, lead=()):
        return jax.tree.unflatten(treedef, [
            scale * jax.random.normal(next(keys), lead + p.shape, jnp.float32)
            for p in leaves
        ])

    g, d1, d3 = rand(1.0), rand(0.01, (1,)), rand(0.01, (3,))
    vr, va = rand(1.0), rand(1.0)
    lam = jnp.float32(LAM)
    out = {"phase": "kernels", "leaves": len(leaves),
           "elements": int(sum(p.size for p in leaves))}

    packed_comp = jax.jit(lambda g, d, lam: packing.compensate_tree(
        g, d, lam, use_pallas=True, interpret=interpret))
    ref_comp = jax.jit(lambda g, d, lam: packing.compensate_tree(g, d, lam))
    leaf_comp = jax.jit(lambda g, d, lam: ops.iter_fisher_compensate_tree(
        g, d, lam, packed=False))
    leaf_ref_comp = jax.jit(lambda g, d, lam: jax.tree.map(
        lambda a, b: kref.iter_fisher_compensate_ref(a, b, lam), g, d))
    for tau, d in ((1, d1), (3, d3)):
        out[f"compensate_packed_tau{tau}_err"] = _tree_err(
            packed_comp(g, d, lam), ref_comp(g, d, lam))
        out[f"compensate_leaf_tau{tau}_err"] = _tree_err(
            leaf_comp(g, d, lam), leaf_ref_comp(g, d, lam))

    d = jax.tree.map(lambda a: a[0], d1)
    packed_stats = jax.jit(lambda *a: packing.stats_tree(
        *a, ALPHA, use_pallas=True, interpret=interpret))
    ref_stats = jax.jit(lambda *a: packing.stats_tree(*a, ALPHA))
    leaf_stats = jax.jit(lambda *a: ops.iter_fisher_stats_tree(*a, ALPHA, packed=False))

    def leaf_ref(g, d, vr, va):
        per = [kref.iter_fisher_leaf_stats_ref(*x, ALPHA) for x in zip(
            *(jax.tree.leaves(t) for t in (g, d, vr, va)))]
        return (jax.tree.unflatten(treedef, [p[0] for p in per]),
                jax.tree.unflatten(treedef, [p[1] for p in per]),
                sum(p[2] for p in per), sum(p[3] for p in per))

    # s1 = Σ (1-α)(g - v_r)·v_a cancels to near zero: judge both sums
    # against the magnitude of their terms
    s1_scale = sum(jnp.sum(jnp.abs((1 - ALPHA) * (a - b) * c)) for a, b, c in zip(
        *(jax.tree.leaves(t) for t in (g, vr, va))))
    for name, got, want in (
        ("stats_packed", packed_stats(g, d, vr, va), ref_stats(g, d, vr, va)),
        ("stats_leaf", leaf_stats(g, d, vr, va), jax.jit(leaf_ref)(g, d, vr, va)),
    ):
        out[f"{name}_ema_err"] = max(_tree_err(got[0], want[0]), _tree_err(got[1], want[1]))
        out[f"{name}_s1_rel"] = _rel(got[2], want[2], s1_scale)
        out[f"{name}_s2_rel"] = _rel(got[3], want[3])

    for k, v in out.items():
        if k.endswith("_err"):
            check(v <= KERNEL_TOL, f"{k}={v} above {KERNEL_TOL}")
        elif k.endswith("_rel"):
            check(v <= STATS_RTOL, f"{k}={v} above {STATS_RTOL}")
    out["tolerances"] = {"err": KERNEL_TOL, "stats_rel": STATS_RTOL}
    return out


# ---------------------------------------------------------------------------
# phases 2 and 3: the session's pipelined and elastic runs
# ---------------------------------------------------------------------------


def check_stream_result(res, rounds: int) -> dict:
    losses = np.asarray(res.losses)
    check(res.rounds == rounds, f"consumed {res.rounds} rounds, asked for {rounds}")
    check(losses.shape == (rounds,), f"losses shape {losses.shape}")
    check(bool(np.all(np.isfinite(losses))), "non-finite loss")
    check(0.0 <= res.online_acc <= 1.0, f"online_acc {res.online_acc}")
    return {
        "rounds": int(res.rounds),
        "loss_first": float(losses[0]),
        "loss_last": float(losses[-1]),
        "online_acc": float(res.online_acc),
    }


def engine_program(res, session, params) -> dict:
    """Compile the pipelined run's own engine scan and look for the kernels."""
    import jax

    from repro.models import transformer as T

    engine = res.extras["engine"]
    bounds = list(res.plan.partition.bounds)
    state = jax.eval_shape(lambda: engine.init_state(
        T.split_stage_params(session.model_cfg, params, bounds)))
    rows = token_stream(session.model_cfg, 1, session.batch, session.seq, SEED)
    rows = session.algorithm.prepare_stream(rows, None)
    stream = {k: jax.ShapeDtypeStruct((engine.sched.num_rounds, *v.shape[1:]), v.dtype)
              for k, v in rows.items()}
    t0 = time.perf_counter()
    text = engine.lower(state, stream).compile().as_text()
    return {
        "engine_recompile_s": time.perf_counter() - t0,
        "engine_tpu_custom_calls": text.count("tpu_custom_call"),
    }


def pipelined_phase(session, meter, device, expect_kernels: bool = True) -> dict:
    out = {"phase": "pipelined", **plan_fields(session.plan)}
    c0, h0 = meter.snapshot()
    t0 = time.perf_counter()
    res = session.run("pipelined")
    wall = time.perf_counter() - t0
    c1, h1 = meter.snapshot()
    out.update(check_stream_result(res, ROUNDS))
    out.update(
        compile_s=c1 - c0, run_s=wall - (c1 - c0), wall_s=wall,
        persistent_cache_hits=h1 - h0, peak_bytes_in_use=peak_bytes(device),
    )
    out.update(engine_program(res, session, session.params))
    if expect_kernels:
        check(out["engine_tpu_custom_calls"] > 0,
              "the engine's compiled program holds no Pallas kernel")
    return out


def lower_budget(session):
    """A budget at which the elastic trainer's planner picks a different
    partition than at the session's budget."""
    from repro.runtime.elastic_trainer import ElasticStreamTrainer

    trainer = ElasticStreamTrainer(
        session.model_cfg, session.ferret_cfg, batch=session.batch, seq=session.seq,
        optimizer=session.optimizer, profile=session.profile, algorithm=session.algorithm,
    )
    plan = trainer.plan_for(session.ferret_cfg.budget_bytes)
    for frac in (0.5, 0.3, 0.2, 0.1, 0.05):
        low = trainer.plan_for(plan.memory * frac)
        if low.feasible and low.memory > 0 and low.partition.bounds != plan.partition.bounds:
            return plan.memory * frac
    raise AssertionError("no budget moves the partition")


def elastic_phase(session, meter, device) -> dict:
    from repro.runtime import BudgetEvent

    budget = lower_budget(session)
    c0, h0 = meter.snapshot()
    t0 = time.perf_counter()
    res = session.run(
        "elastic", schedule=[BudgetEvent(round=ROUNDS // 2, budget_bytes=budget)])
    wall = time.perf_counter() - t0
    c1, h1 = meter.snapshot()
    out = {"phase": "elastic", **check_stream_result(res, ROUNDS)}
    segs = res.segments
    check([s.start for s in segs[1:]] == [s.end for s in segs[:-1]]
          and segs[0].start == 0 and segs[-1].end == ROUNDS,
          "segments do not tile the stream exactly once")
    switches = [s for s in segs if s.replanned]
    lost = [s.rounds_lost for s in switches]
    check(lost == [0], f"rounds lost per switch {lost}")
    before = list(segs[0].result.plan.partition.bounds)
    after = list(segs[-1].result.plan.partition.bounds)
    check(before != after, f"partition did not change: {before}")
    out.update(
        budget_bytes=float(budget), bounds_before=before, bounds_after=after,
        plan_memory_before=float(segs[0].result.plan.memory),
        plan_memory_after=float(segs[-1].result.plan.memory),
        segments=[[s.start, s.end] for s in segs], rounds_lost_per_switch=lost,
        replan_s=[s.replan_s for s in switches], remap_s=[s.remap_s for s in switches],
        compile_s=c1 - c0, run_s=wall - (c1 - c0), wall_s=wall,
        persistent_cache_hits=h1 - h0, peak_bytes_in_use=peak_bytes(device),
    )
    return out


# ---------------------------------------------------------------------------
# four chips: data-parallel engine and stage-axis wavefront
# ---------------------------------------------------------------------------

DP_ROUNDS = 16
DP_LOSS_RTOL = 5e-3  # bf16 compute: below its 2^-7 resolution
WAVE_MICRO, WAVE_BATCH, WAVE_LAYERS = 4, 2, 4
WAVE_LOSS_RTOL = 1e-3
WAVE_GRAD_RTOL = 1e-2  # relative L2 error of the whole gradient


def dp_parity(cfg, params, batch=BATCH, seq=SEQ, rounds=DP_ROUNDS) -> dict:
    from repro.runtime.topology import DeviceTopology

    topo = DeviceTopology.discover(max_devices=4)
    check(topo.mesh_shape == (4, 1), f"topology {topo.mesh_shape}")
    stream = token_stream(cfg, rounds, batch, seq)
    one_s = session_for(cfg, stream, params, batch=batch, seq=seq)
    dp_s = session_for(cfg, stream, params, topology=topo, batch=batch, seq=seq)
    check(one_s.plan.partition.bounds == dp_s.plan.partition.bounds
          and one_s.plan.config == dp_s.plan.config,
          "the 4-device plan differs from the 1-device plan")
    one = one_s.run("pipelined")
    dp = dp_s.run("pipelined")
    a, b = np.asarray(one.losses), np.asarray(dp.losses)
    check(bool(np.all(np.isfinite(b))), "non-finite data-parallel loss")
    rel = float(np.max(np.abs(a - b) / np.abs(a)))
    import jax

    used = {d.id for leaf in jax.tree.leaves(dp.final_params)
            for d in leaf.sharding.device_set}
    devices = jax.devices()[:4]
    peaks = [peak_bytes(d) for d in devices]
    param_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    check(used == {d.id for d in devices}, f"final params live on devices {sorted(used)}")
    check(min(peaks) >= param_bytes, f"a device peaked below the param bytes: {peaks}")
    check(rel <= DP_LOSS_RTOL, f"data-parallel losses differ by {rel} (> {DP_LOSS_RTOL})")
    return {
        "phase": "dp_parity", "mesh_shape": list(topo.mesh_shape), **plan_fields(dp_s.plan),
        "rounds": int(dp.rounds), "loss_max_rel_diff": rel, "tolerance": DP_LOSS_RTOL,
        "loss_first": [float(a[0]), float(b[0])], "loss_last": [float(a[-1]), float(b[-1])],
        "devices_holding_params": sorted(used), "peak_bytes_in_use": peaks,
    }


def wavefront_parity(cfg, params, seq=SEQ) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.core.stage_parallel import mesh_for_topology, spatial_pipeline_loss
    from repro.models import transformer as T
    from repro.runtime.topology import DeviceTopology

    topo = DeviceTopology.discover(model_axis=4)
    mesh = mesh_for_topology(topo, 4)
    toks = jax.random.randint(jax.random.PRNGKey(SEED + 2),
                              (WAVE_MICRO, WAVE_BATCH, seq + 1), 0, cfg.vocab_size)
    batch = {"tokens": toks[:, :, :-1], "labels": toks[:, :, 1:]}

    def sequential(p):
        return sum(
            T.loss_fn(cfg, p, {"tokens": batch["tokens"][m], "labels": batch["labels"][m]})[0]
            for m in range(WAVE_MICRO)
        ) / WAVE_MICRO

    with jax.set_mesh(mesh):
        l_sp, g_sp = jax.jit(jax.value_and_grad(
            lambda p: spatial_pipeline_loss(cfg, p, batch, mesh, 4)))(params)
    l_ref, g_ref = jax.jit(jax.value_and_grad(sequential))(params)
    num = sum(float(jnp.sum((a.astype(jnp.float32) - b.astype(jnp.float32)) ** 2))
              for a, b in zip(jax.tree.leaves(g_sp), jax.tree.leaves(g_ref)))
    den = sum(float(jnp.sum(b.astype(jnp.float32) ** 2)) for b in jax.tree.leaves(g_ref))
    grad_rel = math.sqrt(num / den)
    loss_rel = _rel(l_sp, l_ref)
    check(math.isfinite(float(l_sp)), "non-finite wavefront loss")
    check(loss_rel <= WAVE_LOSS_RTOL, f"wavefront loss differs by {loss_rel}")
    check(grad_rel <= WAVE_GRAD_RTOL, f"wavefront gradient differs by {grad_rel}")
    return {
        "phase": "wavefront_parity", "stages": 4, "layers": cfg.num_layers,
        "microbatches": WAVE_MICRO, "batch": WAVE_BATCH, "seq": seq,
        "loss": [float(l_sp), float(l_ref)], "loss_rel_diff": loss_rel,
        "grad_rel_l2": grad_rel, "tolerances": [WAVE_LOSS_RTOL, WAVE_GRAD_RTOL],
    }


# ---------------------------------------------------------------------------


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (jax sees {devices[0].platform}); refusing to run")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but jax sees {len(devices)} devices")

    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import transformer as T

    cache_dir = enable_compile_cache()
    meter = CompileMeter()
    dev = devices[0]
    emit(phase="device", platform=dev.platform, kind=dev.device_kind, count=len(devices),
         jax=jax.__version__, compile_cache=cache_dir)

    if args.chips == 4:
        cfg = model_config()
        params = T.init_params(cfg, jax.random.PRNGKey(SEED))
        emit(**dp_parity(cfg, params))
        del params
        cfg4 = model_config(WAVE_LAYERS)
        emit(**wavefront_parity(cfg4, T.init_params(cfg4, jax.random.PRNGKey(SEED))))
    else:
        cfg = model_config()
        emit(phase="config", arch=ARCH, d_model=cfg.d_model, num_heads=cfg.num_heads,
             d_ff=cfg.d_ff, vocab=cfg.vocab_size, param_dtype=cfg.param_dtype,
             compute_dtype=cfg.compute_dtype, reduced={"num_layers": [48, LAYERS]},
             params=cfg.param_count(), batch=BATCH, seq=SEQ, rounds=ROUNDS,
             algorithm="er", compensation="iter_fisher")
        params = T.init_params(cfg, jax.random.PRNGKey(SEED))
        emit(**kernel_check(params), peak_bytes_in_use=peak_bytes(dev))
        session = session_for(cfg, token_stream(cfg, ROUNDS, BATCH, SEQ), params)
        emit(**pipelined_phase(session, meter, dev))
        emit(**elastic_phase(session, meter, dev))

    secs, hits = meter.snapshot()
    emit(phase="total", compile_s=secs, persistent_cache_hits=hits)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}}))


if __name__ == "__main__":
    main()
