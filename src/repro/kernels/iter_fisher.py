"""Pallas TPU kernels: fused Iter-Fisher gradient compensation.

The compensation inner loop (Eq. 9) is elementwise over every parameter and
runs once per stage-update:

    for i in 0..τ-1:   g ← g + λ · g ⊙ g ⊙ Δθ_i

A naïve XLA lowering materializes τ intermediate g arrays (τ+1 HBM round
trips). The kernel streams one VMEM tile of g and the τ matching Δθ tiles,
iterates in registers/VMEM, and writes once: HBM traffic drops to τ+2 array
passes, and the λ-statistics pass fuses the same way.

Each kernel runs on one parameter leaf in the leaf's own layout: the leaf's
major dims collapse to ``(rows, cols) = (prod(shape[:-1]), shape[-1])``
(0-d and 1-d leaves become ``(1, n)``), which keeps the TPU's tiled HBM
layout, so XLA passes the leaf in without a copy. The grid walks
``(tm, tn)`` tiles of about 0.5–2 MiB sized from that shape alone
(``tile_for``); a ragged last tile is allowed, and results are written in
the leaf's shape.

Statistics and compensation stay two passes. Eq. 9 uses the λ that Alg. 1
has just updated from s1 and s2 summed over the whole parameter tree, so no
leaf can be compensated before every leaf has been read; and for τ ≥ 2 the
compensation is not linear in λ, so it cannot be finished afterwards.

``compensate_call`` / ``stats_call`` are the two ``pl.pallas_call``s; the
per-leaf entry points below and the flat-packed path
(``repro.kernels.packing``) both launch them.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec

# Scoped VMEM the double-buffered operand tiles of one kernel may take (the
# v5e default scope is 16 MiB; the rest is left to the compiler), and the
# largest tile of one operand.
VMEM_BUDGET = 12 * 2**20
TILE_BYTES = 2 * 2**20
LANES = 128


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


def matrix_shape(shape: Tuple[int, ...]) -> Tuple[int, int]:
    """The 2-D view a kernel takes of a leaf: major dims collapsed."""
    if len(shape) < 2:
        return 1, math.prod(shape)
    return math.prod(shape[:-1]), shape[-1]


def tile_for(rows: int, cols: int, operands: int, itemsize: int = 4) -> Tuple[int, int]:
    """The ``(tm, tn)`` tile of a ``(rows, cols)`` operand, from its shape.

    One fp32 tile holds at most ``min(TILE_BYTES, VMEM_BUDGET / (2 ·
    operands))`` bytes, so the double-buffered tiles of every operand fit
    the scoped VMEM. A tile spans whole rows unless ``sub`` of them exceed
    that; ``tm`` is all rows when they fit, else a multiple of the dtype's
    sublane count ``sub``, preferring one that divides ``rows``.
    """
    sub = 8 * max(1, 4 // itemsize)
    cap = min(TILE_BYTES, VMEM_BUDGET // (2 * operands)) // 4
    tn = cols if sub * cols <= cap else max(LANES, cap // sub // LANES * LANES)
    most = cap // tn
    if rows <= most:
        return rows, tn
    top = max(sub, most // sub * sub)
    tm = next((t for t in range(top, top // 2, -sub) if rows % t == 0), top)
    return tm, tn


def _grid(shape: Tuple[int, int], tile: Tuple[int, int]) -> Tuple[int, int]:
    return pl.cdiv(shape[0], tile[0]), pl.cdiv(shape[1], tile[1])


def _params():
    return pltpu.CompilerParams(dimension_semantics=("parallel", "parallel"))


# ---------------------------------------------------------------------------
# compensation kernel
# ---------------------------------------------------------------------------


def _compensate_kernel(lam_ref, g_ref, d_ref, o_ref):
    g = g_ref[...].astype(jnp.float32)
    lam = lam_ref[0]
    for i in range(d_ref.shape[0]):
        g = g + lam * g * g * d_ref[i].astype(jnp.float32)
    o_ref[...] = g.astype(o_ref.dtype)


def compensate_call(
    g: jax.Array,
    d: jax.Array,
    lam: jax.Array,
    interpret: bool,
    tile: Optional[Tuple[int, int]] = None,
) -> jax.Array:
    """Eq. 9 over a ``(rows, cols)`` operand and its ``(τ, rows, cols)`` Δθ."""
    tau = d.shape[0]
    itemsize = min(g.dtype.itemsize, d.dtype.itemsize)
    tm, tn = tile or tile_for(*g.shape, tau + 2, itemsize)
    call = pl.pallas_call(
        _compensate_kernel,
        grid=_grid(g.shape, (tm, tn)),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # λ
            pl.BlockSpec((tm, tn), lambda i, j: (i, j)),
            pl.BlockSpec((tau, tm, tn), lambda i, j: (0, i, j)),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(g.shape, g.dtype),
        compiler_params=_params(),
        interpret=interpret,
        name="iter_fisher_compensate",
        metadata={"kernel": "iter_fisher_compensate"},
    )
    return _per_device(call)(jnp.asarray(lam, jnp.float32).reshape(1), g, d)


def iter_fisher_compensate_pallas(
    grad: jax.Array, deltas: jax.Array, lam: jax.Array, interpret: bool = False
) -> jax.Array:
    """grad: any shape; deltas: (τ, *grad.shape); lam: scalar."""
    tau = deltas.shape[0]
    if tau == 0:
        return grad
    rows, cols = matrix_shape(grad.shape)
    out = compensate_call(
        grad.reshape(rows, cols), deltas.reshape(tau, rows, cols), lam, interpret
    )
    return out.reshape(grad.shape)


# ---------------------------------------------------------------------------
# λ-statistics kernel (EMA updates + partial dot products)
# ---------------------------------------------------------------------------


def _lane_partial(x: jax.Array) -> jax.Array:
    """Sum of a ``(tm, tn)`` tile as a ``(1, 128)`` vector."""
    s = jnp.sum(x, axis=0, keepdims=True)
    tn = s.shape[1]
    if tn % LANES:
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
        return jnp.where(lane == 0, jnp.sum(s), 0.0)
    out = s[:, :LANES]
    for k in range(1, tn // LANES):
        out = out + s[:, k * LANES : (k + 1) * LANES]
    return out


def _stats_kernel(
    g_ref, d_ref, vr_ref, va_ref, nvr_ref, nva_ref, s1_ref, s2_ref,
    *, alpha: float, shape: Tuple[int, int],
):
    # Every grid step writes its own s1/s2 partials, so the steps are
    # independent; the partials are summed on the device after the call.
    g = g_ref[...].astype(jnp.float32)
    d = d_ref[...].astype(jnp.float32)
    vr = vr_ref[...].astype(jnp.float32)
    va = va_ref[...].astype(jnp.float32)

    dv_r = (1.0 - alpha) * (g - vr)
    p1 = dv_r * va
    p2 = va * va
    tm, tn = g.shape
    if shape[0] % tm or shape[1] % tn:
        # a ragged edge tile reads past the operand: leave that out of the sums
        r = pl.program_id(0) * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, tn), 0)
        c = pl.program_id(1) * tn + jax.lax.broadcasted_iota(jnp.int32, (tm, tn), 1)
        inside = (r < shape[0]) & (c < shape[1])
        p1 = jnp.where(inside, p1, 0.0)
        p2 = jnp.where(inside, p2, 0.0)
    s1_ref[...] = _lane_partial(p1)
    s2_ref[...] = _lane_partial(p2)
    nvr_ref[...] = (alpha * vr + (1.0 - alpha) * g).astype(nvr_ref.dtype)
    nva_ref[...] = (alpha * va + (1.0 - alpha) * (g * g * d)).astype(nva_ref.dtype)


def stats_call(
    g: jax.Array,
    d: jax.Array,
    vr: jax.Array,
    va: jax.Array,
    alpha: float,
    interpret: bool,
    row: int = 0,
    tile: Optional[Tuple[int, int]] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Alg. 1 λ-statistics over ``(rows, cols)`` operands, reading Δθ from
    row ``row`` of the ``(K, rows, cols)`` stack ``d`` in place. Returns
    (v_r', v_a', s1, s2), s1/s2 on-device fp32 scalars; v_r' and v_a' take
    the buffers of v_r and v_a where the caller lets them go."""
    itemsize = min(a.dtype.itemsize for a in (g, d, vr, va))
    tm, tn = tile or tile_for(*g.shape, 6, itemsize)
    grid = _grid(g.shape, (tm, tn))
    block = pl.BlockSpec((tm, tn), lambda i, j: (i, j))
    partial = pl.BlockSpec((None, None, 1, LANES), lambda i, j: (i, j, 0, 0))
    call = pl.pallas_call(
        functools.partial(_stats_kernel, alpha=alpha, shape=g.shape),
        grid=grid,
        in_specs=[block, pl.BlockSpec((None, tm, tn), lambda i, j: (row, i, j)), block, block],
        out_specs=[block, block, partial, partial],
        out_shape=[
            jax.ShapeDtypeStruct(g.shape, vr.dtype),
            jax.ShapeDtypeStruct(g.shape, va.dtype),
            jax.ShapeDtypeStruct((*grid, 1, LANES), jnp.float32),
            jax.ShapeDtypeStruct((*grid, 1, LANES), jnp.float32),
        ],
        input_output_aliases={2: 0, 3: 1},
        compiler_params=_params(),
        interpret=interpret,
        name="iter_fisher_stats",
        metadata={"kernel": "iter_fisher_stats"},
    )
    nvr, nva, s1, s2 = _per_device(call)(g, d, vr, va)
    return nvr, nva, jnp.sum(s1), jnp.sum(s2)


def iter_fisher_leaf_stats_pallas(
    grad: jax.Array,
    delta: jax.Array,
    v_r: jax.Array,
    v_a: jax.Array,
    alpha: float,
    interpret: bool = False,
    row: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """One leaf's λ-statistics. ``delta`` has the leaf's shape, or with
    ``row`` it is the ``(K, *shape)`` Δθ history and its row ``row`` is
    read in place."""
    shape = grad.shape
    rows, cols = matrix_shape(shape)
    d = delta.reshape(-1, rows, cols)
    nvr, nva, s1, s2 = stats_call(
        grad.reshape(rows, cols), d, v_r.reshape(rows, cols), v_a.reshape(rows, cols),
        alpha, interpret, row=0 if row is None else row % d.shape[0],
    )
    return nvr.reshape(shape), nva.reshape(shape), s1, s2
