"""Pallas TPU kernel: fused Iter-Fisher gradient compensation.

The compensation inner loop (Eq. 9) is elementwise over every parameter and
runs once per stage-update:

    for i in 0..τ-1:   g ← g + λ · g ⊙ g ⊙ Δθ_i

A naïve XLA lowering materializes τ intermediate g arrays (τ+1 HBM round
trips). The kernel streams one VMEM tile of g and the τ matching Δθ tiles,
iterates in registers/VMEM, and writes once: HBM traffic drops from
(2τ+... ) to (τ+2) array passes and the λ-statistics pass fuses the same
way. Blocks are (8·128)-aligned 1-D tiles of the flattened parameter.

``compensate_call`` / ``stats_call`` are the two ``pl.pallas_call``s; the
per-leaf entry points below and the flat-packed path
(``repro.kernels.packing``) both launch them.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec

BLOCK = 4096  # elements per tile (multiple of 8·128 lanes)

# fp32 words of SMEM per λ-statistic output. Grid step i adds into slot
# i % PARTIAL_SLOTS, so the two outputs take 64 KiB of the v5e's 1 MiB SMEM
# whatever the buffer length.
PARTIAL_SLOTS = 8192


def _per_device(call):
    """Mosaic kernels are not partitioned automatically. Under a mesh
    (``jax.set_mesh``, as the data-parallel engine traces its scan) every
    device runs the kernel over the whole, replicated operands."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return call
    return jax.shard_map(
        call, mesh=mesh, in_specs=PartitionSpec(), out_specs=PartitionSpec(),
        check_vma=False,
    )


# ---------------------------------------------------------------------------
# compensation kernel
# ---------------------------------------------------------------------------


def _compensate_kernel(lam_ref, g_ref, d_ref, o_ref, *, tau: int):
    g = g_ref[...].astype(jnp.float32)
    lam = lam_ref[0].astype(jnp.float32)
    for i in range(tau):
        delta = d_ref[i, :].astype(jnp.float32)
        g = g + lam * g * g * delta
    o_ref[...] = g.astype(o_ref.dtype)


def compensate_call(
    gf: jax.Array, df: jax.Array, lam: jax.Array, block: int, interpret: bool
) -> jax.Array:
    """Eq. 9 over a flat ``(n,)`` buffer and its ``(τ, n)`` Δθ, n % block == 0."""
    tau = df.shape[0]
    call = pl.pallas_call(
        functools.partial(_compensate_kernel, tau=tau),
        grid=(gf.shape[0] // block,),
        in_specs=[
            pl.BlockSpec((1,), lambda i: (0,)),  # λ broadcast to every tile
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((tau, block), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct(gf.shape, gf.dtype),
        interpret=interpret,
        name="iter_fisher_compensate",
        metadata={"kernel": "iter_fisher_compensate"},
    )
    return _per_device(call)(jnp.asarray(lam).reshape(1).astype(jnp.float32), gf, df)


def iter_fisher_compensate_pallas(
    grad: jax.Array, deltas: jax.Array, lam: jax.Array, interpret: bool = False
) -> jax.Array:
    """grad: any shape; deltas: (τ, *grad.shape); lam: scalar."""
    shape = grad.shape
    tau = deltas.shape[0]
    if tau == 0:
        return grad
    n = grad.size
    pad = (-n) % BLOCK
    gf = jnp.pad(grad.reshape(-1), (0, pad))
    df = jnp.pad(deltas.reshape(tau, -1), ((0, 0), (0, pad)))
    out = compensate_call(gf, df, lam, BLOCK, interpret)
    return out[:n].reshape(shape)


# ---------------------------------------------------------------------------
# λ-statistics kernel (EMA updates + partial dot products)
# ---------------------------------------------------------------------------


def _stats_kernel(
    g_ref, d_ref, vr_ref, va_ref, nvr_ref, nva_ref, s1_ref, s2_ref, *, alpha: float, slots: int
):
    # s1/s2 partials are SMEM scalars: Mosaic refuses a 1-element VMEM block.
    # The grid runs in order ("arbitrary"), so the first `slots` steps zero
    # their slot and every step accumulates into slot i % slots; the
    # slots→1 sum happens on device after the call.
    i = pl.program_id(0)
    slot = i % slots

    @pl.when(i < slots)
    def _():
        s1_ref[slot] = jnp.float32(0.0)
        s2_ref[slot] = jnp.float32(0.0)

    g = g_ref[...].astype(jnp.float32)
    d = d_ref[...].astype(jnp.float32)
    vr = vr_ref[...].astype(jnp.float32)
    va = va_ref[...].astype(jnp.float32)

    dv_r = (1.0 - alpha) * (g - vr)
    s1_ref[slot] += jnp.sum(dv_r * va)
    s2_ref[slot] += jnp.sum(va * va)
    nvr_ref[...] = (alpha * vr + (1.0 - alpha) * g).astype(nvr_ref.dtype)
    nva_ref[...] = (alpha * va + (1.0 - alpha) * (g * g * d)).astype(nva_ref.dtype)


def stats_call(
    gf: jax.Array,
    df: jax.Array,
    vrf: jax.Array,
    vaf: jax.Array,
    alpha: float,
    block: int,
    interpret: bool,
    out_dtypes: Tuple = (jnp.float32, jnp.float32),
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Alg. 1 λ-statistics over flat ``(n,)`` buffers, n % block == 0.
    Returns (v_r', v_a', s1, s2) with s1/s2 on-device fp32 scalars."""
    nb = gf.shape[0] // block
    slots = min(nb, PARTIAL_SLOTS)
    tile = pl.BlockSpec((block,), lambda i: (i,))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    call = pl.pallas_call(
        functools.partial(_stats_kernel, alpha=alpha, slots=slots),
        grid=(nb,),
        in_specs=[tile] * 4,
        out_specs=[tile, tile, smem, smem],
        out_shape=[
            jax.ShapeDtypeStruct(gf.shape, out_dtypes[0]),
            jax.ShapeDtypeStruct(gf.shape, out_dtypes[1]),
            jax.ShapeDtypeStruct((slots,), jnp.float32),
            jax.ShapeDtypeStruct((slots,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="iter_fisher_stats",
        metadata={"kernel": "iter_fisher_stats"},
    )
    nvr, nva, s1, s2 = _per_device(call)(gf, df, vrf, vaf)
    return nvr, nva, jnp.sum(s1), jnp.sum(s2)


def iter_fisher_leaf_stats_pallas(
    grad: jax.Array,
    delta: jax.Array,
    v_r: jax.Array,
    v_a: jax.Array,
    alpha: float,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    shape = grad.shape
    n = grad.size
    pad = (-n) % BLOCK

    def flat(a):
        return jnp.pad(a.reshape(-1).astype(jnp.float32), (0, pad))

    nvr, nva, s1, s2 = stats_call(
        flat(grad), flat(delta), flat(v_r), flat(v_a), alpha, BLOCK, interpret,
        out_dtypes=(v_r.dtype, v_a.dtype),
    )
    return nvr[:n].reshape(shape), nva[:n].reshape(shape), s1, s2
