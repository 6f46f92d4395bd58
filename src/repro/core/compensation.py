"""Gradient compensation for stale gradients (paper §5.1.2, Alg. 1).

The flagship algorithm is **Iter-Fisher**: iterative first-order Taylor
compensation with a diagonal-Fisher Hessian proxy and an online-optimized
global λ (Eq. 8–12). Baselines from Table 4 are included:

- ``none``        : use the stale gradient as-is (zero-order)
- ``step_aware``  : shrink the step by 1/(τ+1)            [33, 41]
- ``gap_aware``   : per-parameter penalty by the weight gap [7]
- ``fisher``      : one-shot Fisher compensation with the *total* Δθ [14, 85]
- ``iter_fisher`` : Alg. 1 (ours)

All functions operate on parameter pytrees; the elementwise hot loops are
Pallas kernels on TPU (``repro.kernels``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops

Pytree = Any


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class CompensationState:
    """λ and its EMA statistics (paper: v_r, v_a; space 2·Σ|w|)."""

    lam: jax.Array  # scalar float32
    v_r: Pytree  # EMA of gradients       (E_k ∇L)
    v_a: Pytree  # EMA of g⊙g⊙Δθ          (the λ-feature F)
    steps: jax.Array  # scalar int32


@dataclasses.dataclass(frozen=True)
class CompensationConfig:
    method: str = "iter_fisher"  # none|step_aware|gap_aware|fisher|iter_fisher
    lam0: float = 0.2  # paper §12: λ = 0.2
    alpha: float = 0.9  # EMA coefficient
    eta_lambda: float = 1e-3  # λ learning rate (0 disables auto-tuning: fixed λ)
    nu: float = 2e-6  # ℓ2 regularizer on λ (paper's μ)


def init_state(params: Pytree, cfg: CompensationConfig) -> CompensationState:
    zeros = jax.tree.map(lambda p: jnp.zeros_like(p, dtype=jnp.float32), params)
    if cfg.eta_lambda == 0.0:
        # Fixed-λ mode (paper: η_λ = 0 frees v_r/v_a) — keep empty pytrees.
        zeros = jax.tree.map(lambda p: jnp.zeros((0,), dtype=jnp.float32), params)
    return CompensationState(
        lam=jnp.asarray(cfg.lam0, jnp.float32),
        v_r=zeros,
        v_a=jax.tree.map(jnp.copy, zeros),
        steps=jnp.zeros((), jnp.int32),
    )


# ---------------------------------------------------------------------------
# Iter-Fisher (Alg. 1)
# ---------------------------------------------------------------------------


def _update_lambda(
    state: CompensationState, grad: Pytree, deltas: Pytree, cfg: CompensationConfig
) -> CompensationState:
    """Alg. 1 lines 3–7: one λ-descent step + EMA updates (global λ).

    The statistics use the most recent version step (θ^t − θ^{t-1}), the
    last row of each leaf's stacked ``deltas``, which the kernels read in
    place. s1/s2 accumulate as on-device scalars on every path — no
    per-leaf host round-trips.
    """
    new_vr, new_va, s1_total, s2_total = ops.iter_fisher_stats_tree(
        grad, deltas, state.v_r, state.v_a, cfg.alpha, row=-1
    )
    grad_lam = -2.0 * s1_total + 2.0 * state.lam * s2_total + 2.0 * cfg.nu * state.lam
    new_lam = state.lam - cfg.eta_lambda * grad_lam
    return CompensationState(
        lam=new_lam,
        v_r=new_vr,
        v_a=new_va,
        steps=state.steps + 1,
    )


def compensate(
    cfg: CompensationConfig,
    state: CompensationState,
    grad: Pytree,
    deltas: Pytree,  # stacked (K, ...) per leaf: θ^{t+i} − θ^{t+i-1}, oldest first
    lr: float = 1e-3,
    tau: Optional[jax.Array] = None,  # traced staleness; default: K (static)
) -> Tuple[CompensationState, Pytree]:
    """Compensate a gradient that is ≤ K versions stale.

    The stacked ``deltas`` axis is oldest→newest; entries beyond the true
    staleness must be zero (a zero Δθ is the identity for every method
    except step_aware, which takes ``tau`` explicitly).
    Returns (new_state, compensated_grad). K = 0 is a no-op.
    """
    method = cfg.method
    K = jax.tree.leaves(deltas)[0].shape[0] if jax.tree.leaves(deltas) else 0

    if method == "none" or K == 0:
        return state, grad

    if tau is None:
        tau = jnp.asarray(float(K), jnp.float32)

    if method == "step_aware":
        scale = 1.0 / (1.0 + tau.astype(jnp.float32))
        return state, jax.tree.map(lambda g: (g * scale).astype(g.dtype), grad)

    if method == "gap_aware":
        # Barkai et al.: divide by the per-parameter gap 1 + |Δθ_total| / η.
        def leaf(g, d):
            total = jnp.sum(d.astype(jnp.float32), axis=0)
            gap = 1.0 + jnp.abs(total) / jnp.maximum(lr, 1e-12)
            return (g.astype(jnp.float32) / gap).astype(g.dtype)

        return state, jax.tree.map(leaf, grad, deltas)

    if method == "fisher":
        # One-shot: g + λ g⊙g⊙(θ^{t+τ} − θ^t); fixed λ, no iteration, no tuning.
        def leaf(g, d):
            total = jnp.sum(d.astype(jnp.float32), axis=0)
            g32 = g.astype(jnp.float32)
            return (g32 + cfg.lam0 * g32 * g32 * total).astype(g.dtype)

        return state, jax.tree.map(leaf, grad, deltas)

    if method == "iter_fisher":
        if cfg.eta_lambda > 0.0:
            state = _update_lambda(state, grad, deltas, cfg)
        # Eq. 9 with the λ just updated: a second pass over the whole tree.
        comp = ops.iter_fisher_compensate_tree(grad, deltas, state.lam)
        return state, comp

    raise ValueError(f"unknown compensation method {method!r}")


# ---------------------------------------------------------------------------
# Reference check utility (used by tests): exact gradient on quadratic loss
# ---------------------------------------------------------------------------


def quadratic_true_gradient(H: jax.Array, theta: jax.Array, b: jax.Array) -> jax.Array:
    """∇L for L(θ) = ½ θᵀHθ − bᵀθ, the closed-form testbed for compensation."""
    return H @ theta - b
