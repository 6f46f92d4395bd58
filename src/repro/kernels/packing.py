"""Flat-packed Iter-Fisher megakernels: one launch per compensation step.

The engine calls the compensator once per stage-update on a parameter
*pytree*.  Dispatching one ``pl.pallas_call`` per leaf (the previous
``repro.kernels.iter_fisher`` path) costs O(leaves) kernel launches per
step, and the old ``size % 128 == 0`` gate silently dropped most biases
and norm scales to the jnp reference.  This module removes both costs:

- ``PackSpec`` lays the whole pytree out in one contiguous fp32 buffer.
  Each leaf starts at an 8·128-aligned offset; the gaps are zero-padded.
  Zero is the identity for every Iter-Fisher quantity (Δθ = 0 ⇒ no
  compensation; g = v_r = v_a = 0 ⇒ no statistics), so padding never
  leaks into results.  Specs are computed once per partition structure
  and cached by (treedef, shapes, dtypes).
- ``compensate_tree`` / ``stats_tree`` run the Eq. 9 inner loop and the
  Alg. 1 λ-statistics as **one** ``pl.pallas_call`` each over the packed
  buffer — the λ-statistics s1/s2 block-reduce on-device in the same data
  pass (per-grid-step partials, plus a tiny on-device epilogue sum).  The
  kernels themselves are the per-leaf ones of ``repro.kernels.iter_fisher``,
  launched once over a ``(1, total)`` view in ``(1, BLOCK)`` tiles.  The
  engine no longer takes this path by default: the view, the pack and the
  unpack are relayout copies of parameter-sized arrays on a TPU, which the
  per-leaf kernels avoid.  When packing is
  forced without Pallas (``REPRO_PACK=1`` on CPU), the same packed buffer
  goes through the jnp reference in one fused elementwise op instead of
  an O(leaves) Python loop.

``KERNEL_LAUNCHES`` counts actual ``pl.pallas_call`` invocations so tests
and ``benchmarks/bench_hotpath.py`` can assert the launch count is 1
regardless of leaf count.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import iter_fisher as _kernels
from repro.kernels import ref as _ref

Pytree = Any

ALIGN = 8 * 128  # fp32 VPU tile: every leaf starts on an (8, 128) boundary
BLOCK = 4096  # default grid tile of the packed buffer (a multiple of ALIGN)
assert BLOCK % ALIGN == 0, "packed grid tile must cover whole leaf slots"


def _resolve_block(block: Optional[int]) -> int:
    """The grid tile for this call: explicit argument > tuned/env default
    (``ops._pack_block``) > the module default. Must cover whole
    ALIGN-aligned leaf slots so a leaf never straddles two grid steps."""
    if block is None:
        from repro.kernels import ops

        block = ops._pack_block()
    if block is None:
        return BLOCK
    block = int(block)
    if block <= 0 or block % ALIGN != 0:
        raise ValueError(f"pack block must be a positive multiple of {ALIGN}, got {block}")
    return block

# pl.pallas_call invocations issued by this module (trace-time counter).
KERNEL_LAUNCHES = 0


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


# ---------------------------------------------------------------------------
# Packing layout
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PackSpec:
    """Flat layout of one pytree: leaf i occupies ``[offsets[i],
    offsets[i] + sizes[i])`` of a ``(total,)`` fp32 buffer; the tail of its
    ALIGN-rounded slot (and of the BLOCK-rounded buffer) is zero padding."""

    treedef: Any
    shapes: Tuple[Tuple[int, ...], ...]
    dtypes: Tuple[str, ...]
    offsets: Tuple[int, ...]
    sizes: Tuple[int, ...]
    slots: Tuple[int, ...]  # ALIGN-rounded width of each leaf's slot
    total: int  # BLOCK-multiple buffer length

    @property
    def num_leaves(self) -> int:
        return len(self.sizes)


_SPEC_CACHE: Dict[Tuple, PackSpec] = {}


def pack_spec(tree: Pytree, block: Optional[int] = None) -> PackSpec:
    """The (cached) flat layout for ``tree``'s structure and leaf shapes.

    ``block`` is the kernel grid tile the buffer length rounds up to
    (default: the tuned/module block); specs are cached per block since
    ``total`` depends on it.
    """
    block = _resolve_block(block)
    leaves, treedef = jax.tree.flatten(tree)
    shapes = tuple(tuple(leaf.shape) for leaf in leaves)
    dtypes = tuple(str(jnp.result_type(leaf)) for leaf in leaves)
    key = (treedef, shapes, dtypes, block)
    spec = _SPEC_CACHE.get(key)
    if spec is None:
        sizes, slots, offsets = [], [], []
        cursor = 0
        for shape in shapes:
            size = 1
            for d in shape:
                size *= d
            slot = max(_round_up(size, ALIGN), ALIGN)
            offsets.append(cursor)
            sizes.append(size)
            slots.append(slot)
            cursor += slot
        spec = PackSpec(
            treedef=treedef,
            shapes=shapes,
            dtypes=dtypes,
            offsets=tuple(offsets),
            sizes=tuple(sizes),
            slots=tuple(slots),
            total=max(_round_up(cursor, block), block),
        )
        _SPEC_CACHE[key] = spec
    return spec


def pack(spec: PackSpec, tree: Pytree, lead: int = 0) -> jax.Array:
    """Pack ``tree`` into a ``(*lead_dims, total)`` fp32 buffer.

    ``lead`` leading axes of every leaf (e.g. the stacked-Δθ axis) are kept;
    the remaining axes flatten into the leaf's slot. Gaps are zeros.
    Implemented as dynamic-update-slices into one zero buffer — XLA turns
    the chain into in-place writes, measurably cheaper than pad+concat.
    """
    leaves = jax.tree.leaves(tree)
    lead_shape = tuple(leaves[0].shape[:lead]) if leaves else ()
    out = jnp.zeros(lead_shape + (spec.total,), jnp.float32)
    for leaf, off in zip(leaves, spec.offsets):
        flat = jnp.asarray(leaf).reshape(lead_shape + (-1,)).astype(jnp.float32)
        out = jax.lax.dynamic_update_slice(out, flat, (0,) * lead + (off,))
    return out


def unpack(
    spec: PackSpec, flat: jax.Array, dtypes: Optional[Tuple[str, ...]] = None
) -> Pytree:
    """Invert ``pack`` for a ``(total,)`` buffer (casts back per-leaf)."""
    dtypes = dtypes or spec.dtypes
    leaves = [
        flat[off : off + size].reshape(shape).astype(dtype)
        for off, size, shape, dtype in zip(spec.offsets, spec.sizes, spec.shapes, dtypes)
    ]
    return jax.tree.unflatten(spec.treedef, leaves)


# ---------------------------------------------------------------------------
# Packed kernels (inputs are (total,) / (τ, total) fp32, total % BLOCK == 0)
# ---------------------------------------------------------------------------


def compensate_packed(
    gflat: jax.Array,
    dflat: jax.Array,
    lam: jax.Array,
    interpret: bool = False,
    block: Optional[int] = None,
) -> jax.Array:
    """Eq. 9 over the packed buffer: one launch for the whole pytree."""
    global KERNEL_LAUNCHES
    if dflat.shape[0] == 0:
        return gflat
    KERNEL_LAUNCHES += 1
    n, tau = gflat.shape[0], dflat.shape[0]
    out = _kernels.compensate_call(
        gflat.reshape(1, n), dflat.reshape(tau, 1, n), lam, interpret,
        tile=(1, _resolve_block(block)),
    )
    return out.reshape(n)


def stats_packed(
    gflat: jax.Array,
    dflat: jax.Array,
    vrflat: jax.Array,
    vaflat: jax.Array,
    alpha: float,
    interpret: bool = False,
    block: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Alg. 1 λ-statistics over the packed buffer: one launch, s1/s2
    block-reduced on-device in the same pass. Returns (v_r', v_a', s1, s2)."""
    global KERNEL_LAUNCHES
    KERNEL_LAUNCHES += 1
    n = gflat.shape[0]
    nvr, nva, s1, s2 = _kernels.stats_call(
        gflat.reshape(1, n), dflat.reshape(1, 1, n), vrflat.reshape(1, n),
        vaflat.reshape(1, n), alpha, interpret, tile=(1, _resolve_block(block)),
    )
    return nvr.reshape(n), nva.reshape(n), s1, s2


# ---------------------------------------------------------------------------
# Tree-level entrypoints (pack → one kernel / one fused jnp op → unpack)
# ---------------------------------------------------------------------------


def compensate_tree(
    grad: Pytree,
    deltas: Pytree,  # per leaf: (τ, *leaf.shape), oldest first
    lam: jax.Array,
    use_pallas: bool = False,
    interpret: bool = False,
    block: Optional[int] = None,
) -> Pytree:
    """Whole-pytree Iter-Fisher compensation in a single pass."""
    leaves_d = jax.tree.leaves(deltas)
    tau = leaves_d[0].shape[0] if leaves_d else 0
    if tau == 0:
        return grad
    block = _resolve_block(block)
    spec = pack_spec(grad, block)
    gflat = pack(spec, grad)
    dflat = pack(spec, deltas, lead=1)
    if use_pallas:
        out = compensate_packed(gflat, dflat, lam, interpret=interpret, block=block)
    else:
        out = _ref.iter_fisher_compensate_ref(gflat, dflat, lam)
    return unpack(spec, out)


def stats_tree(
    grad: Pytree,
    delta: Pytree,
    v_r: Pytree,
    v_a: Pytree,
    alpha: float,
    use_pallas: bool = False,
    interpret: bool = False,
    block: Optional[int] = None,
) -> Tuple[Pytree, Pytree, jax.Array, jax.Array]:
    """Whole-pytree λ-statistics: (v_r', v_a', Σ s1, Σ s2) in a single pass.

    The returned s1/s2 are on-device fp32 scalars — there is no per-leaf
    host accumulation anywhere on this path.
    """
    block = _resolve_block(block)
    spec = pack_spec(grad, block)
    gflat = pack(spec, grad)
    dflat = pack(spec, delta)
    vrflat = pack(spec, v_r)
    vaflat = pack(spec, v_a)
    if use_pallas:
        nvr, nva, s1, s2 = stats_packed(
            gflat, dflat, vrflat, vaflat, alpha, interpret, block=block
        )
    else:
        nvr, nva, s1, s2 = _ref.iter_fisher_leaf_stats_ref(
            gflat, dflat, vrflat, vaflat, alpha
        )
    vr_dtypes = tuple(str(leaf.dtype) for leaf in jax.tree.leaves(v_r))
    va_dtypes = tuple(str(leaf.dtype) for leaf in jax.tree.leaves(v_a))
    return (
        unpack(spec, nvr, vr_dtypes),
        unpack(spec, nva, va_dtypes),
        s1,
        s2,
    )
