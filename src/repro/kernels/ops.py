"""Public jit'd wrappers for the Pallas kernels (with jnp reference fallback).

Dispatch policy
---------------
- On TPU, the Pallas kernels are used (``pl.pallas_call`` with explicit
  BlockSpec VMEM tiling).
- On CPU (this container), the kernels only execute under
  ``interpret=True`` — correct but slow — so the default execution path is
  the jnp reference, and the Pallas path is exercised by the kernel tests
  and by setting ``REPRO_USE_PALLAS=1`` (interpret mode) / running on TPU.
"""

from __future__ import annotations

import os
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref


def _backend() -> str:
    return jax.default_backend()


def _use_pallas() -> bool:
    env = os.environ.get("REPRO_USE_PALLAS", "").strip()
    if env == "1":
        return True
    if env == "0":
        return False
    return _backend() == "tpu"


def _pallas_interpret() -> bool:
    return _backend() != "tpu"


# ---------------------------------------------------------------------------
# SSD chunked scan (Mamba-2)
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(
    x: jax.Array,
    dt: jax.Array,
    A: jax.Array,
    B: jax.Array,
    C: jax.Array,
    chunk: int,
    initial_state: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    slen = x.shape[1]
    pad = (-slen) % chunk
    if pad:
        # dt=0 padding is a no-op on the state (decay exp(0)=1, increment 0).
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    if _use_pallas():
        from repro.kernels import ssd_scan as _k

        y, state = _k.ssd_scan_pallas(
            x, dt, A, B, C, chunk, initial_state, interpret=_pallas_interpret()
        )
    else:
        y, state = _ref.ssd_scan_ref(x, dt, A, B, C, chunk, initial_state)
    return (y[:, :slen] if pad else y), state


@jax.jit
def ssd_decode_step(
    x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array, C: jax.Array, state: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    # Single-token recurrence is tiny & fusion-friendly; XLA handles it.
    return _ref.ssd_decode_step_ref(x, dt, A, B, C, state)


# ---------------------------------------------------------------------------
# Iter-Fisher gradient compensation
# ---------------------------------------------------------------------------


def _tuned():
    """Persisted autotune record for this backend (all-None when absent).

    Lazy + exception-safe: dispatch must keep working with no store on
    disk, a corrupt store, or during partial imports.
    """
    try:
        from repro.profile.autotune import tuned_defaults

        return tuned_defaults()
    except Exception:
        from repro.profile.autotune import TunedDefaults

        return TunedDefaults()


def _use_packed() -> bool:
    # Flat-packed single-launch path (repro.kernels.packing). Precedence:
    # REPRO_PACK=1/0 forces either way; else a measured autotune record
    # for this backend decides; else per leaf on every backend. On a TPU
    # the pack, the (1, n) view and the unpack are relayout copies of
    # parameter-sized arrays, which the per-leaf kernels, reading each
    # leaf in its own layout, avoid; on CPU (interpret mode included) the
    # per-leaf path measures ~7× faster (BENCH_hotpath.json).
    env = os.environ.get("REPRO_PACK", "").strip()
    if env == "1":
        return True
    if env == "0":
        return False
    tuned = _tuned()
    if tuned.pack is not None:
        return bool(tuned.pack)
    return False


def _pack_block():
    # PackSpec grid tile: REPRO_PACK_BLOCK env > tuned winner > None
    # (packing.BLOCK module default).
    env = os.environ.get("REPRO_PACK_BLOCK", "").strip()
    if env:
        return int(env)
    tuned = _tuned()
    return int(tuned.pack_block) if tuned.pack_block else None


def iter_fisher_compensate(grad: jax.Array, deltas: jax.Array, lam: jax.Array) -> jax.Array:
    """Apply τ iterative Fisher compensations; deltas: (τ, *grad.shape).

    Every leaf, whatever its size, takes the Pallas kernel on that path,
    in its own layout (``repro.kernels.iter_fisher``).
    """
    if _use_pallas():
        from repro.kernels import iter_fisher as _k

        return _k.iter_fisher_compensate_pallas(
            grad, deltas, lam, interpret=_pallas_interpret()
        )
    return _ref.iter_fisher_compensate_ref(grad, deltas, lam)


def iter_fisher_leaf_stats(
    grad: jax.Array,
    delta: jax.Array,
    v_r: jax.Array,
    v_a: jax.Array,
    alpha: float,
    row: Optional[int] = None,
):
    """Per-leaf λ-statistics + EMA updates. Returns (v_r', v_a', s1, s2).

    With ``row``, ``delta`` is the (K, *grad.shape) Δθ history and its row
    ``row`` is the Δθ; the kernel reads that row in place.
    """
    if _use_pallas():
        from repro.kernels import iter_fisher as _k

        return _k.iter_fisher_leaf_stats_pallas(
            grad, delta, v_r, v_a, alpha, interpret=_pallas_interpret(), row=row
        )
    if row is not None:
        delta = delta[row]
    return _ref.iter_fisher_leaf_stats_ref(grad, delta, v_r, v_a, alpha)


def iter_fisher_compensate_tree(
    grad, deltas, lam: jax.Array, packed: Optional[bool] = None
):
    """Whole-pytree compensation: one kernel launch per leaf.

    ``packed=None`` honors ``REPRO_PACK`` (default off); ``packed=True``
    packs the tree into one buffer for a single launch
    (``repro.kernels.packing``).
    """
    if _use_packed() if packed is None else packed:
        from repro.kernels import packing

        return packing.compensate_tree(
            grad, deltas, lam,
            use_pallas=_use_pallas(), interpret=_pallas_interpret(),
        )
    return jax.tree.map(lambda g, d: iter_fisher_compensate(g, d, lam), grad, deltas)


def iter_fisher_stats_tree(
    grad, delta, v_r, v_a, alpha: float, packed: Optional[bool] = None,
    row: Optional[int] = None,
):
    """Whole-pytree λ-statistics: (v_r', v_a', Σ s1, Σ s2), one launch per
    leaf (one in all when packed). ``row`` as in ``iter_fisher_leaf_stats``.

    Both paths accumulate s1/s2 as on-device fp32 scalars — never as host
    Python floats.
    """
    if _use_packed() if packed is None else packed:
        from repro.kernels import packing

        if row is not None:
            delta = jax.tree.map(lambda d: d[row], delta)
        return packing.stats_tree(
            grad, delta, v_r, v_a, alpha,
            use_pallas=_use_pallas(), interpret=_pallas_interpret(),
        )
    new_vr, new_va = [], []
    s1 = jnp.zeros((), jnp.float32)
    s2 = jnp.zeros((), jnp.float32)
    leaves = zip(
        jax.tree.leaves(grad), jax.tree.leaves(delta),
        jax.tree.leaves(v_r), jax.tree.leaves(v_a),
    )
    for g, d, vr, va in leaves:
        nvr, nva, l1, l2 = iter_fisher_leaf_stats(g, d, vr, va, alpha, row)
        new_vr.append(nvr)
        new_va.append(nva)
        s1 = s1 + l1
        s2 = s2 + l2
    treedef = jax.tree.structure(grad)
    return (
        jax.tree.unflatten(treedef, new_vr),
        jax.tree.unflatten(treedef, new_va),
        s1,
        s2,
    )
