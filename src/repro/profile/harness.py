"""Measurement harness: timed on-device executions for profiles and tuning.

This is the one place in the repo that times real executions (paper
appendix Alg. 3, ``profile(θ)``). Every timing goes through ``time_jit``:
explicit ``lower().compile()`` so compile time is measured separately,
``warmup`` executions to flush first-touch costs, ``repeats`` timed runs
with ``block_until_ready``, and the compiled executable's
``cost_analysis`` (FLOPs / bytes accessed) recorded as a cross-check
against the analytic roofline.

Consumers:
- ``measure_model_profile`` → a measured ``ModelProfile`` for the planner
  (``core.profiler.measured_profile`` delegates here — one code path).
- ``measure_kernel_variants`` → packed-vs-per-leaf Iter-Fisher latency per
  candidate pack block, consumed by ``repro.profile.autotune``.
- ``measure_scan_segment`` → scan compile + per-round step time, feeding
  the segment-bucket cost model.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig

DEFAULT_WARMUP = 2
DEFAULT_REPEATS = 5


@dataclasses.dataclass(frozen=True)
class Timing:
    """One timed compiled executable."""

    mean_s: float
    best_s: float
    compile_s: float
    repeats: int
    flops: float  # XLA cost_analysis estimate (0.0 if unavailable)
    bytes_accessed: float

    def to_dict(self) -> Dict:
        return dataclasses.asdict(self)


def time_jit(
    fn: Callable,
    *args,
    warmup: int = DEFAULT_WARMUP,
    repeats: int = DEFAULT_REPEATS,
) -> Timing:
    """Compile ``fn(*args)`` and time it: warmup + repeated blocking runs."""
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    compile_s = time.perf_counter() - t0
    try:
        cost = compiled.cost_analysis()
    except Exception:
        cost = {}
    for _ in range(max(warmup, 1)):
        jax.block_until_ready(compiled(*args))
    times = []
    for _ in range(max(repeats, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(compiled(*args))
        times.append(time.perf_counter() - t0)
    return Timing(
        mean_s=sum(times) / len(times),
        best_s=min(times),
        compile_s=compile_s,
        repeats=len(times),
        flops=float(cost.get("flops", 0.0) or 0.0),
        bytes_accessed=float(cost.get("bytes accessed", 0.0) or 0.0),
    )


# ---------------------------------------------------------------------------
# Per-layer forward/backward blocks → measured ModelProfile
# ---------------------------------------------------------------------------


def measure_model_profile(
    cfg: ModelConfig,
    batch: int,
    seq: int,
    warmup: int = DEFAULT_WARMUP,
    repeats: int = DEFAULT_REPEATS,
    rng_seed: int = 0,
):
    """Wall-clock ``ModelProfile`` from timing one real block fwd/bwd.

    Byte sizes stay analytic (they are exact layout facts, not
    measurements); only the times are replaced by device wall-clock.
    """
    from repro.core import profiler as P
    from repro.models import transformer as T
    from repro.models.transformer import _block_train

    one = dataclasses.replace(cfg, num_layers=1)
    params = T.init_params(one, jax.random.PRNGKey(rng_seed))
    block = jax.tree.map(lambda a: a[0], params["blocks"])
    x = jnp.zeros((batch, seq, cfg.d_model), dtype=jnp.dtype(cfg.compute_dtype))
    pos = jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32)[None], (batch, seq))
    if cfg.mrope_sections is not None:
        pos = jnp.broadcast_to(pos[None], (3, batch, seq))

    fwd = time_jit(
        lambda p, xx: _block_train(cfg, p, xx, jnp.int32(0), pos)[0],
        block, x, warmup=warmup, repeats=repeats,
    )
    bwd = time_jit(
        jax.grad(lambda p, xx: jnp.sum(_block_train(cfg, p, xx, jnp.int32(0), pos)[0] ** 2)),
        block, x, warmup=warmup, repeats=repeats,
    )

    w_b = P._block_w_bytes(cfg)
    a_b = P._block_a_bytes(cfg, batch, seq)
    a_int = P._block_a_internal_bytes(cfg, batch, seq)
    layers = [
        P.LayerProfile(fwd.mean_s, bwd.mean_s, w_b, a_b, a_int)
        for _ in range(cfg.num_layers)
    ]
    embed_bytes = cfg.vocab_size * cfg.d_model * 4 * (1 if cfg.tie_embeddings else 2)
    return P.ModelProfile(
        layers=layers, embed_bytes=embed_bytes, batch=batch, seq=seq,
        provenance="measured",
    ), {"fwd": fwd.to_dict(), "bwd": bwd.to_dict()}


# ---------------------------------------------------------------------------
# Iter-Fisher kernel variants (packed vs per-leaf, candidate pack blocks)
# ---------------------------------------------------------------------------


def default_tuning_tree(scale: int = 1) -> Dict:
    """A stage-params-shaped pytree: mixed 2D matmuls + ragged 1D vectors."""
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 6)
    d = 64 * scale
    return {
        "wq": jax.random.normal(ks[0], (d, d), jnp.float32),
        "w_ff1": jax.random.normal(ks[1], (d, 2 * d), jnp.float32),
        "w_ff2": jax.random.normal(ks[2], (2 * d, d), jnp.float32),
        "b1": jax.random.normal(ks[3], (2 * d,), jnp.float32),
        "scale": jax.random.normal(ks[4], (d,), jnp.float32),
        "b2": jax.random.normal(ks[5], (3,), jnp.float32),  # ragged: pad path
    }


def measure_kernel_variants(
    tree: Optional[Dict] = None,
    tau: int = 4,
    alpha: float = 0.9,
    blocks: Sequence[int] = (),
    warmup: int = DEFAULT_WARMUP,
    repeats: int = DEFAULT_REPEATS,
) -> Dict:
    """Time compensate+stats per dispatch variant on the current backend.

    Variants: ``per_leaf`` (the O(leaves) loop) and ``packed@<block>`` for
    each candidate pack block (``()`` → just the default block). Dispatch
    flags (Pallas vs jnp, interpret) follow the live ``ops`` heuristics so
    the measurement matches what a real run would execute.
    """
    from repro.kernels import ops, packing

    tree = tree if tree is not None else default_tuning_tree()
    lam = jnp.float32(0.01)
    deltas = jax.tree.map(
        lambda a: jnp.stack([a * (0.01 * (i + 1)) for i in range(tau)]), tree
    )
    delta1 = jax.tree.map(lambda a: a * 0.01, tree)
    zeros = jax.tree.map(jnp.zeros_like, tree)
    use_pallas = ops._use_pallas()
    interpret = ops._pallas_interpret()

    def per_leaf(g, d, d1, vr, va):
        comp = jax.tree.map(lambda gg, dd: ops.iter_fisher_compensate(gg, dd, lam), g, d)
        _, _, s1, s2 = ops.iter_fisher_stats_tree(g, d1, vr, va, alpha, packed=False)
        return comp, s1, s2

    out: Dict[str, Dict] = {
        "per_leaf": time_jit(
            per_leaf, tree, deltas, delta1, zeros, zeros,
            warmup=warmup, repeats=repeats,
        ).to_dict()
    }

    block_list: List[Optional[int]] = list(blocks) if blocks else [None]
    for block in block_list:
        def packed_fn(g, d, d1, vr, va, _block=block):
            comp = packing.compensate_tree(
                g, d, lam, use_pallas=use_pallas, interpret=interpret, block=_block
            )
            _, _, s1, s2 = packing.stats_tree(
                g, d1, vr, va, alpha,
                use_pallas=use_pallas, interpret=interpret, block=_block,
            )
            return comp, s1, s2

        label = f"packed@{block if block is not None else packing.BLOCK}"
        out[label] = time_jit(
            packed_fn, tree, deltas, delta1, zeros, zeros,
            warmup=warmup, repeats=repeats,
        ).to_dict()
        out[label]["block"] = block if block is not None else packing.BLOCK
    return out


# ---------------------------------------------------------------------------
# Segment-bucket cost inputs (compile time vs per-round step time)
# ---------------------------------------------------------------------------


def measure_scan_segment(
    cfg: ModelConfig,
    batch: int,
    seq: int,
    rounds: int = 8,
    warmup: int = 1,
    repeats: int = 3,
    rng_seed: int = 0,
) -> Tuple[float, float]:
    """(compile_s, per_round_s) for a scanned block-train segment.

    A proxy for ``FerretEngine`` segment execution: one jitted
    ``lax.scan`` of the block fwd/bwd over ``rounds`` rounds. Bucketing
    trades these two numbers — each distinct bucket costs one compile;
    each padded round costs one per-round step.
    """
    from repro.models import transformer as T
    from repro.models.transformer import _block_train

    one = dataclasses.replace(cfg, num_layers=1)
    params = T.init_params(one, jax.random.PRNGKey(rng_seed))
    block = jax.tree.map(lambda a: a[0], params["blocks"])
    x = jnp.zeros((rounds, batch, seq, cfg.d_model), dtype=jnp.dtype(cfg.compute_dtype))
    pos = jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32)[None], (batch, seq))
    if cfg.mrope_sections is not None:
        pos = jnp.broadcast_to(pos[None], (3, batch, seq))

    grad_fn = jax.grad(
        lambda p, xx: jnp.sum(_block_train(cfg, p, xx, jnp.int32(0), pos)[0] ** 2)
    )

    def segment(p, xs):
        def step(carry, xx):
            g = grad_fn(carry, xx)
            return jax.tree.map(lambda a, b: a - 1e-3 * b, carry, g), jnp.float32(0)

        final, _ = jax.lax.scan(step, p, xs)
        return final

    t = time_jit(segment, block, x, warmup=warmup, repeats=repeats)
    return t.compile_s, t.mean_s / max(rounds, 1)
