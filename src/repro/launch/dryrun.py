import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 " + os.environ.get("XLA_FLAGS", "")
)

# ruff: noqa: E402  — the two lines above MUST precede any jax-touching import.
"""Multi-pod dry-run: lower + compile every (architecture × input-shape × mesh).

For each cell this proves, without hardware:
  - the sharding config is coherent (compile succeeds, no GSPMD conflicts),
  - the program fits (memory_analysis of the full scanned program), and
  - the roofline terms. XLA's cost analysis counts scan bodies once, so
    FLOPs/bytes/collectives come from a 2-point extrapolation over *unrolled*
    small-L variants:  total = C(L1) + (L/G − 1)·(C(L2) − C(L1)),
    with G the layer-group size (6 for gemma3's 5:1 pattern, else 1),
    L1 = G, L2 = 2G. Exact for homogeneous stacks.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
      --mesh both --out results/dryrun.json
"""

import argparse
import dataclasses
from math import prod as np_prod
import json
import time
import traceback
from typing import Dict, Optional

import jax
from jax.sharding import PartitionSpec as P

from repro.configs.common import SHAPES, InputShape, input_specs, shape_applicable
from repro.launch import shardings as sh
from repro.launch.hlo_analysis import (
    RooflineTerms,
    analytic_memory_bytes,
    parse_collectives,
)
from repro.launch.mesh import axis_sizes, data_axes, make_production_mesh
from repro.launch.steps import make_decode_step, make_prefill_step, make_train_step
from repro.models import shard_hints
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.models.registry import ARCHITECTURES, get_config
from repro.optim.optimizers import adamw

ACTIVATION_BUDGET_BYTES = 7 * 2**30  # per-device activation target (train)
ACT_BYTES_PER_TOKEN_LAYER = 6.5  # measured: ~3 bf16 copies of (tok, d) per layer


def _mem_analysis_dict(compiled) -> Optional[Dict]:
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return None
    if ma is None:
        return None
    out = {}
    for k in (
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
        "alias_size_in_bytes",
        "generated_code_size_in_bytes",
    ):
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = int(v)
    out["total_hbm_bytes"] = (
        out.get("argument_size_in_bytes", 0)
        + out.get("output_size_in_bytes", 0)
        + out.get("temp_size_in_bytes", 0)
        - out.get("alias_size_in_bytes", 0)
    )
    return out


def _hints(dp, model_ax) -> shard_hints.ShardHints:
    dp_spec = dp if len(dp) > 1 else dp[0]
    return shard_hints.ShardHints(
        logits=P(dp_spec, None, model_ax),  # model_ax=None -> batch-only
        activations=P(dp_spec, None, None),
        moe_buffer=P(dp_spec, None),
    )


def _microbatch_for(cfg: ModelConfig, shape: InputShape, dp_size: int) -> int:
    """Pick the accumulation factor so per-device activations fit the budget.

    Activation bytes ≈ c · L · d_model · tokens_per_device / n, with c the
    measured ~6.5 B/(token·layer·d) (see EXPERIMENTS.md §Perf iteration 2);
    MoE blocks hold expert buffers too (≈ +2·k·ff/d relative)."""
    if shape.kind != "train":
        return 1
    per_dev_tokens = shape.batch * shape.seq // dp_size
    scale = ACT_BYTES_PER_TOKEN_LAYER
    if cfg.uses_moe:
        scale *= 1.0 + 2.0 * cfg.experts_per_token * cfg.d_ff / max(cfg.d_model, 1) / 3.0
    act = scale * cfg.num_layers * cfg.d_model * per_dev_tokens
    n = 1
    while act / n > ACTIVATION_BUDGET_BYTES and shape.batch % (2 * n) == 0:
        n *= 2
    return n


def _lower_compile(cfg, shape, mesh, remat: bool, microbatch: int, variant: str = "baseline"):
    """Build + lower + compile one program. Returns the compiled artifact.

    variant:
      baseline — batch over (pod, data); weights FSDP(data) ⊗ TP(model)
      fsdp     — batch AND weights over every axis (pod, data, model): no TP
    """
    maxes = axis_sizes(mesh)
    if variant == "fsdp":
        dp = tuple(a for a in ("pod", "data", "model") if a in maxes)
        model_ax = None
    else:
        dp = data_axes(mesh)
        model_ax = "model"
    params_s = jax.eval_shape(lambda: T.init_params(cfg, jax.random.PRNGKey(0)))
    pspecs = T.param_pspecs(cfg, maxes, data_axes=dp, model_axis=model_ax)
    p_shard = sh.named(mesh, pspecs)
    batch_s = input_specs(cfg, shape)
    b_shard = sh.named(mesh, sh.batch_pspecs(cfg, shape, maxes, dp, model_ax))

    with jax.set_mesh(mesh), shard_hints.use_hints(_hints(dp, model_ax)):
        if shape.kind == "train":
            opt = adamw(lr=1e-3)
            opt_s = jax.eval_shape(opt.init, params_s)
            o_shard = sh.named(mesh, sh.opt_pspecs(pspecs, opt_s))
            step = make_train_step(cfg, opt, remat=remat, microbatch=microbatch)
            lowered = jax.jit(
                step,
                in_shardings=(p_shard, o_shard, b_shard),
                out_shardings=(p_shard, o_shard, None),
                donate_argnums=(0, 1),
            ).lower(params_s, opt_s, batch_s)
        elif shape.kind == "prefill":
            cache_s = jax.eval_shape(lambda: T.init_cache(cfg, shape.batch, shape.seq))
            c_specs = sh.cache_pspecs(cfg, cache_s, maxes, dp, model_ax)
            c_shard = sh.named(mesh, c_specs)
            step = make_prefill_step(cfg, max_len=shape.seq)
            lowered = jax.jit(
                step, in_shardings=(p_shard, b_shard), out_shardings=(None, c_shard)
            ).lower(params_s, batch_s)
        else:
            cache_s = jax.eval_shape(lambda: T.init_cache(cfg, shape.batch, shape.seq))
            c_specs = sh.cache_pspecs(cfg, cache_s, maxes, dp, model_ax)
            c_shard = sh.named(mesh, c_specs)
            step = make_decode_step(cfg)
            lowered = jax.jit(
                step,
                in_shardings=(p_shard, c_shard, b_shard),
                out_shardings=(None, c_shard),
                donate_argnums=(1,),
            ).lower(params_s, cache_s, batch_s)
        compiled = lowered.compile()
    return compiled


def _cost_point(cfg, shape, mesh, remat, num_layers, variant="baseline"):
    """Unrolled small-L lowering; returns (flops, bytes, coll_bytes, counts)."""
    from repro.models import scan_util

    small = dataclasses.replace(cfg, num_layers=num_layers)
    scan_util.UNROLL = True
    try:
        compiled = _lower_compile(small, shape, mesh, remat, microbatch=1, variant=variant)
    finally:
        scan_util.UNROLL = False
    ca = compiled.cost_analysis()
    text = compiled.as_text()
    colls = parse_collectives(text, default_group=mesh.devices.size)
    return float(ca.get("flops", 0.0)), float(ca.get("bytes accessed", 0.0)), colls


def dryrun_cell(
    arch: str,
    shape: InputShape,
    multi_pod: bool,
    remat: bool = True,
    cost_points: bool = True,
    variant: str = "baseline",
) -> Dict:
    cfg = get_config(arch)
    rec: Dict = {
        "arch": arch,
        "shape": shape.name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "variant": variant,
    }
    if not shape_applicable(cfg, shape):
        rec["status"] = "skipped"
        rec["reason"] = "full-attention arch: long_500k requires sub-quadratic attention"
        return rec

    mesh = make_production_mesh(preset="multi_pod" if multi_pod else "pod")
    dp_size = 1
    for a in data_axes(mesh):
        dp_size *= axis_sizes(mesh)[a]
    microbatch = _microbatch_for(cfg, shape, dp_size)

    t0 = time.time()
    compiled = _lower_compile(cfg, shape, mesh, remat, microbatch, variant=variant)
    t_compile = time.time() - t0
    mem = _mem_analysis_dict(compiled)

    rec.update(
        {
            "status": "ok",
            "compile_s": round(t_compile, 2),
            "microbatch": microbatch,
            "memory_analysis": mem,
        }
    )

    if cost_points:
        G = cfg.local_global_ratio + 1 if cfg.local_global_ratio > 0 else 1
        L = cfg.num_layers
        f1, b1, c1 = _cost_point(cfg, shape, mesh, remat, G, variant)
        f2, b2, c2 = _cost_point(cfg, shape, mesh, remat, 2 * G, variant)
        groups = L // G
        flops = f1 + (groups - 1) * (f2 - f1)
        byts = b1 + (groups - 1) * (b2 - b1)
        coll = c1.total_bytes + (groups - 1) * (c2.total_bytes - c1.total_bytes)
        counts = {
            k: c1.counts[k] + (groups - 1) * (c2.counts[k] - c1.counts[k])
            for k in c1.counts
        }
        # microbatching multiplies per-step activation traffic & collectives
        # of the fwd/bwd but not the optimizer; the cost points run with
        # microbatch=1 over the full batch — equal total compute.
        terms = RooflineTerms(
            flops_per_device=flops,
            bytes_per_device=byts,
            collective_bytes_per_device=coll,
            chips=mesh.devices.size,
        )
        rec["cost_points"] = {
            "L1": {"flops": f1, "bytes": b1, "coll": c1.total_bytes},
            "L2": {"flops": f2, "bytes": b2, "coll": c2.total_bytes},
            "group_size": G,
        }
        rec["collectives"] = {
            "counts": counts,
            "wire_bytes": {
                k: c1.wire_bytes[k] + (groups - 1) * (c2.wire_bytes[k] - c1.wire_bytes[k])
                for k in c1.wire_bytes
            },
        }
        rd = terms.as_dict()
        # analytic HBM-traffic lower bound (see hlo_analysis.analytic_memory_bytes)
        model_shard = 16 if variant == "baseline" else 1
        cache_bytes = 0
        if shape.kind != "train":
            cache_s = jax.eval_shape(lambda: T.init_cache(cfg, shape.batch, shape.seq))
            cache_bytes = sum(
                int(np_prod(leaf.shape)) * leaf.dtype.itemsize for leaf in jax.tree.leaves(cache_s)
            )
        mem_model = analytic_memory_bytes(
            cfg, shape, mesh.devices.size, model_shard, microbatch, cache_bytes
        )
        rd["t_memory_model_s"] = mem_model / 819e9
        rd["bytes_model_per_device"] = mem_model
        rec["roofline"] = rd
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="results/dryrun.json")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--no-cost", action="store_true", help="skip cost extrapolation points")
    ap.add_argument("--variant", default="baseline", choices=["baseline", "fsdp"])
    args = ap.parse_args()

    archs = list(ARCHITECTURES) if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    records = []
    for arch in archs:
        for sname in shapes:
            for multi in meshes:
                tag = f"{arch} × {sname} × {'2x16x16' if multi else '16x16'}"
                t0 = time.time()
                try:
                    rec = dryrun_cell(
                        arch, SHAPES[sname], multi,
                        remat=not args.no_remat, cost_points=not args.no_cost,
                        variant=args.variant,
                    )
                except Exception as e:
                    rec = {
                        "arch": arch, "shape": sname,
                        "mesh": "2x16x16" if multi else "16x16",
                        "status": "error", "error": f"{type(e).__name__}: {e}",
                        "trace": traceback.format_exc()[-2000:],
                    }
                rec["wall_s"] = round(time.time() - t0, 1)
                records.append(rec)
                status = rec["status"]
                extra = ""
                if status == "ok" and "roofline" in rec:
                    r = rec["roofline"]
                    ma = rec.get("memory_analysis") or {}
                    extra = (
                        f" wall={rec['wall_s']}s"
                        f" hbm={ma.get('total_hbm_bytes', 0)/2**30:.2f}GiB"
                        f" tC={r['t_compute_s']:.4f} tM={r['t_memory_s']:.4f}"
                        f" tX={r['t_collective_s']:.4f} → {r['bottleneck']}"
                    )
                elif status == "error":
                    extra = " " + rec["error"][:200]
                print(f"[{status:7s}] {tag}{extra}", flush=True)
                os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
                with open(args.out, "w") as f:
                    json.dump(records, f, indent=1)

    ok = sum(1 for r in records if r["status"] == "ok")
    sk = sum(1 for r in records if r["status"] == "skipped")
    err = sum(1 for r in records if r["status"] == "error")
    print(f"\ndone: {ok} ok, {sk} skipped, {err} errors → {args.out}")


if __name__ == "__main__":
    main()
