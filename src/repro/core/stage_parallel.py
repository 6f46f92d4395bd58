"""Spatial pipeline parallelism: stages mapped onto a mesh axis.

The emulation engine (repro.core.pipeline) reproduces Ferret's *learning
dynamics*; this module executes the pipeline *spatially* the TPU-native
way: each device group along a mesh axis holds one stage's weights, and
activations travel stage→stage with `lax.ppermute` inside a scan over
schedule ticks — the classic GPipe wavefront with P−1 bubble ticks.

Differentiating through the scan gives the reverse wavefront for free
(ppermute's transpose is the reverse permute), so `jax.grad` over
``spatial_pipeline_loss`` IS a spatially-pipelined backward pass; XLA
overlaps the ppermute transfers of tick t+1 with the block compute of
tick t (compute/comm overlap — the same latency-hiding the paper gets
from asynchrony, here inside one SPMD step).

Used by tests/test_stage_parallel.py (8 host devices) and available to the
serving driver for stage-sharded scoring at pod scale.
"""

from __future__ import annotations

from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.config import ModelConfig


def mesh_for_topology(topology, num_stages: int):
    """A 1-D ``("stage",)`` mesh over the topology's model axis.

    The wavefront needs one device group per stage: the topology's model
    axis must span ``num_stages`` devices (build the topology with
    ``discover(model_axis=num_stages)``). This is the model-axis execution
    path of the topology plane — `FerretEngine`'s scan covers the data
    axis, this mesh covers the stage dimension.
    """
    if topology.model_parallel != num_stages:
        raise ValueError(
            f"topology model axis spans {topology.model_parallel} devices "
            f"but the pipeline has {num_stages} stages — discover the "
            f"topology with model_axis={num_stages}"
        )
    import numpy as np

    devices = jax.devices()
    if len(devices) < topology.device_count:
        raise RuntimeError(
            f"topology wants {topology.device_count} devices but only "
            f"{len(devices)} are visible"
        )
    # stage axis varies fastest in the (data, model) mesh layout, so the
    # first `num_stages` devices are exactly data-row 0's stage groups
    arr = np.array(devices[: topology.device_count]).reshape(
        topology.mesh_shape
    )[0]
    return jax.sharding.Mesh(arr, ("stage",))


def stack_stage_blocks(cfg: ModelConfig, params: Dict, num_stages: int) -> Dict:
    """(L, ...) stacked block params -> (P, L/P, ...) stage-stacked."""
    L = cfg.num_layers
    assert L % num_stages == 0, (L, num_stages)
    per = L // num_stages
    return jax.tree.map(
        lambda a: a.reshape(num_stages, per, *a.shape[1:]), params["blocks"]
    )


def _stage_apply(cfg: ModelConfig, stage_blocks: Dict, x: jax.Array, positions) -> jax.Array:
    """Run this device's block slice ((L/P, ...) leading dim) over x."""
    from repro.models.transformer import _block_train

    def body(x, p):
        x, _ = _block_train(cfg, p, x, jnp.int32(cfg.layer_kinds()[0]), positions)
        return x, None

    x, _ = jax.lax.scan(body, x, stage_blocks)
    return x


def spatial_pipeline_logits(
    cfg: ModelConfig,
    params: Dict,
    batch: Dict,
    mesh,
    num_stages: int,
    axis: str = "stage",
) -> jax.Array:
    """Forward the microbatched batch through the spatial pipeline.

    batch['tokens']: (M, b, s) — M microbatches flow down the stage axis;
    the embedding/head run data-parallel outside the pipelined region.
    Returns logits (M, b, s, V).
    """
    from repro.models.layers import embed_tokens, lm_head_logits, rms_norm

    M, b, s = batch["tokens"].shape
    cd = jnp.dtype(cfg.compute_dtype)
    x_all = embed_tokens(params["embed"], batch["tokens"], cd)  # (M, b, s, d)
    stage_blocks = stack_stage_blocks(cfg, params, num_stages)

    T = M + num_stages - 1  # wavefront ticks

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(axis), stage_blocks), P(None)),
        out_specs=P(None),
    )
    def run(blocks_local, x_feed):
        # blocks_local leaves: (1, L/P, ...) — this device's stage
        blocks_local = jax.tree.map(lambda a: a[0], blocks_local)
        idx = jax.lax.axis_index(axis)
        # built inside the body: shard_map may not close over arrays of a
        # mesh with Explicit axes (what jax.make_mesh gives by default)
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
        last = num_stages - 1
        zero = jax.lax.pcast(jnp.zeros((b, s, cfg.d_model), cd), (axis,), to="varying")

        def tick(carry, t):
            buf = carry  # activation held by this stage
            # stage 0 injects microbatch t (if in range); others use buf
            feed = jnp.where(t < M, x_feed[jnp.minimum(t, M - 1)], zero)
            x_in = jnp.where(idx == 0, feed, buf)
            y = _stage_apply(cfg, blocks_local, x_in, positions)
            # last stage's finished microbatch index at tick t is t - (P-1)
            out = jnp.where(idx == last, y, zero)
            # pass activations down the pipe (ring; last->0 output is unused)
            nxt = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % num_stages) for i in range(num_stages)]
            )
            return nxt, out

        _, outs = jax.lax.scan(tick, zero, jnp.arange(T))  # (T, b, s, d)
        # collect the last stage's valid outputs: microbatch m done at tick m+P-1
        outs = jax.lax.psum(outs, axis)  # only the last stage contributed
        return outs[num_stages - 1 :]

    acts = run(stage_blocks, x_all)  # (M, b, s, d)
    acts = rms_norm(acts, params["final_norm"], cfg.norm_eps)
    return lm_head_logits(cfg, params, acts)


def spatial_pipeline_loss(
    cfg: ModelConfig, params: Dict, batch: Dict, mesh, num_stages: int, axis: str = "stage"
) -> jax.Array:
    """Mean CE over all microbatches — differentiable end-to-end; its grad
    is the spatially-pipelined backward wavefront."""
    from repro.models.layers import cross_entropy_loss

    logits = spatial_pipeline_logits(cfg, params, batch, mesh, num_stages, axis)
    return cross_entropy_loss(
        logits.reshape(-1, *logits.shape[2:]),
        batch["labels"].reshape(-1, batch["labels"].shape[-1]),
    )
