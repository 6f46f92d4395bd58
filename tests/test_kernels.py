"""Pallas kernel validation: hypothesis shape/dtype sweeps vs ref.py oracles.

Kernels execute under interpret=True on CPU (the TPU path is the same body).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import example, given, settings, strategies as st

from repro.kernels import iter_fisher, ref
from repro.kernels.iter_fisher import (
    iter_fisher_compensate_pallas,
    iter_fisher_leaf_stats_pallas,
)
from repro.kernels.ssd_scan import ssd_scan_pallas

# ---------------------------------------------------------------------------
# iter_fisher
# ---------------------------------------------------------------------------


# Leaves in their own layouts (collapsed to 2-D by the kernels): a stacked
# block weight, an MoE-like (experts, d, ff), a matrix, a norm scale, a
# short vector, a scalar, and a 1-D leaf longer than one row tile.
LEAVES = [(1, 64, 256), (3, 16, 384), (128, 128), (1, 96), (5,), (), (4500,)]


def _leaf_examples(name, values):
    def wrap(test):
        for shape in LEAVES:
            for v in values:
                test = example(**{"shape": shape, name: v, "dtype": "float32", "seed": 0})(test)
        return test
    return wrap


@_leaf_examples("tau", [0, 1, 2, 3])
@settings(max_examples=20, deadline=None)
@given(
    shape=st.sampled_from(LEAVES + [(33, 17), (2, 3, 130)]),
    tau=st.integers(0, 6),
    dtype=st.sampled_from(["float32", "bfloat16"]),
    seed=st.integers(0, 2**16),
)
def test_iter_fisher_compensate_matches_ref(shape, tau, dtype, seed):
    rng = np.random.default_rng(seed)
    g = jnp.asarray(rng.normal(size=shape), jnp.dtype(dtype))
    d = jnp.asarray(rng.normal(size=(tau, *shape)) * 0.01, jnp.dtype(dtype))
    lam = jnp.asarray(0.2, jnp.float32)
    want = ref.iter_fisher_compensate_ref(g, d, lam)
    got = iter_fisher_compensate_pallas(g, d, lam, interpret=True)
    assert got.shape == g.shape and got.dtype == g.dtype
    tol = 1e-6 if dtype == "float32" else 3e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=tol, atol=tol
    )


@_leaf_examples("depth", [0, 1, 2, 3])
@settings(max_examples=15, deadline=None)
@given(
    shape=st.sampled_from(LEAVES + [(128,), (513,), (32, 33), (4, 8, 130)]),
    depth=st.integers(0, 3),
    dtype=st.just("float32"),
    seed=st.integers(0, 2**16),
)
def test_iter_fisher_stats_matches_ref(shape, depth, dtype, seed):
    """depth 0: Δθ has the leaf's shape; else it is the newest row of a
    (depth, *shape) history, read in place."""
    rng = np.random.default_rng(seed)
    def mk(*lead):
        return jnp.asarray(rng.normal(size=(*lead, *shape)), jnp.dtype(dtype))

    g, vr, va = mk(), mk(), mk()
    d = mk(depth) if depth else mk()
    alpha = float(rng.uniform(0.5, 0.99))
    want = ref.iter_fisher_leaf_stats_ref(g, d[-1] if depth else d, vr, va, alpha)
    got = iter_fisher_leaf_stats_pallas(
        g, d, vr, va, alpha, interpret=True, row=-1 if depth else None
    )
    for a, b in zip(want, got):
        assert b.shape == a.shape
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("tile", [(8, 128), (24, 128), (16, 256), (64, 128)])
def test_iter_fisher_kernels_over_several_grid_steps(tile):
    """Tiles that split a leaf into several grid steps, ragged ones too:
    every element compensated once, s1/s2 the reference's sums to fp32
    rounding."""
    rows, cols = 100, 300
    rng = np.random.default_rng(sum(tile))
    g, vr, va = (jnp.asarray(rng.normal(size=(rows, cols)), jnp.float32) for _ in range(3))
    d = jnp.asarray(rng.normal(size=(2, rows, cols)) * 0.01, jnp.float32)
    lam = jnp.asarray(0.3, jnp.float32)
    got = iter_fisher.compensate_call(g, d, lam, interpret=True, tile=tile)
    want = ref.iter_fisher_compensate_ref(g, d, lam)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6)

    got = iter_fisher.stats_call(g, d, vr, va, 0.8, interpret=True, row=1, tile=tile)
    want = ref.iter_fisher_leaf_stats_ref(g, d[1], vr, va, 0.8)
    for a, b in zip(want[:2], got[:2]):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=1e-6, atol=1e-6)
    exact = [np.sum(np.float64(0.2) * (np.float64(g) - vr) * va), np.sum(np.float64(va) ** 2)]
    for s, e, w in zip(got[2:], exact, want[2:]):
        # both sums are fp32 sums of rows × cols terms: as close to the
        # float64 value as the reference's own
        scale = np.sum(np.abs(np.float64(va)) * (1 + np.abs(np.float64(g) - vr)))
        assert abs(float(s) - e) <= 4 * np.finfo(np.float32).eps * scale
        assert abs(float(w) - e) <= 4 * np.finfo(np.float32).eps * scale


@pytest.mark.parametrize("leaf", [(1536, 6144), (2048, 1536), (1, 1536), (16000, 2560),
                                  (3, 16, 384), (1600, 32001), (1, 100000)])
def test_tiles_fit_the_scoped_vmem(leaf):
    """The tile comes from the leaf's shape alone: legal TPU block dims
    (a multiple of the sublane count or the whole dim; a multiple of 128
    lanes or the whole dim), double-buffered within the budget."""
    rows, cols = iter_fisher.matrix_shape(leaf)
    for operands in (3, 4, 6):
        tm, tn = iter_fisher.tile_for(rows, cols, operands)
        assert tm == rows or tm % 8 == 0
        assert tn == cols or tn % 128 == 0
        assert 2 * operands * tm * tn * 4 <= iter_fisher.VMEM_BUDGET


def test_iter_fisher_zero_delta_is_identity():
    g = jnp.asarray(np.random.default_rng(0).normal(size=(300,)), jnp.float32)
    d = jnp.zeros((4, 300), jnp.float32)
    out = iter_fisher_compensate_pallas(g, d, jnp.asarray(0.5), interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(g), rtol=0, atol=0)


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------


@settings(max_examples=12, deadline=None)
@given(
    b=st.integers(1, 3),
    nc=st.integers(1, 4),
    h=st.integers(1, 4),
    p=st.sampled_from([8, 16, 64]),
    n=st.sampled_from([8, 16, 128]),
    Q=st.sampled_from([8, 16]),
    seed=st.integers(0, 2**16),
)
def test_ssd_kernel_matches_ref(b, nc, h, p, n, Q, seed):
    slen = nc * Q
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(b, slen, h, p)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.001, 0.1, size=(b, slen, h)), jnp.float32)
    A = jnp.asarray(-rng.uniform(0.5, 2.0, size=(h,)), jnp.float32)
    B = jnp.asarray(rng.normal(size=(b, slen, n)), jnp.float32)
    C = jnp.asarray(rng.normal(size=(b, slen, n)), jnp.float32)
    s0 = jnp.asarray(rng.normal(size=(b, h, p, n)) * 0.1, jnp.float32)
    y_ref, s_ref = ref.ssd_scan_ref(x, dt, A, B, C, Q, s0)
    y_k, s_k = ssd_scan_pallas(x, dt, A, B, C, Q, s0, interpret=True)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_ref), rtol=3e-5, atol=3e-5)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_ref), rtol=3e-5, atol=3e-5)


def test_ssd_matches_sequential_recurrence():
    """Chunked kernel == exact token-by-token recurrence (ground truth)."""
    b, slen, h, p, n, Q = 2, 32, 3, 8, 16, 8
    rng = np.random.default_rng(1)
    x = rng.normal(size=(b, slen, h, p))
    dt = rng.uniform(0.001, 0.2, size=(b, slen, h))
    A = -rng.uniform(0.5, 2.0, size=(h,))
    B = rng.normal(size=(b, slen, n))
    C = rng.normal(size=(b, slen, n))
    y_k, s_k = ssd_scan_pallas(
        *(jnp.asarray(a, jnp.float32) for a in (x, dt, A, B, C)), Q, None, interpret=True
    )
    s = np.zeros((b, h, p, n))
    ys = np.zeros((b, slen, h, p))
    for t in range(slen):
        dA = np.exp(dt[:, t] * A)
        s = s * dA[:, :, None, None] + np.einsum("bh,bhp,bn->bhpn", dt[:, t], x[:, t], B[:, t])
        ys[:, t] = np.einsum("bhpn,bn->bhp", s, C[:, t])
    np.testing.assert_allclose(np.asarray(y_k), ys, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s_k), s, rtol=1e-4, atol=1e-4)


def test_ssd_decode_step_continues_scan():
    """Prefill final state + decode step == scan over s+1 tokens."""
    b, slen, h, p, n, Q = 1, 16, 2, 8, 8, 8
    rng = np.random.default_rng(2)
    def mk(*s):
        return jnp.asarray(rng.normal(size=s), jnp.float32)

    x, B, C = mk(b, slen + 1, h, p), mk(b, slen + 1, n), mk(b, slen + 1, n)
    dt = jnp.asarray(rng.uniform(0.01, 0.1, size=(b, slen + 1, h)), jnp.float32)
    A = jnp.asarray(-rng.uniform(0.5, 1.5, size=(h,)), jnp.float32)
    y_all, s_all = ref.ssd_scan_ref(x, dt, A, B, C, chunk=slen + 1)
    _, s_pre = ref.ssd_scan_ref(x[:, :slen], dt[:, :slen], A, B[:, :slen], C[:, :slen], chunk=Q)
    y_dec, s_dec = ref.ssd_decode_step_ref(
        x[:, slen], dt[:, slen], A, B[:, slen], C[:, slen], s_pre
    )
    np.testing.assert_allclose(np.asarray(y_dec), np.asarray(y_all[:, slen]), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s_dec), np.asarray(s_all), rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# flash attention (custom VJP) — values AND gradients vs dense oracle
# ---------------------------------------------------------------------------


@settings(max_examples=10, deadline=None)
@given(
    b=st.integers(1, 2),
    s=st.sampled_from([32, 64, 96]),
    heads=st.sampled_from([(4, 2), (4, 4), (8, 2)]),
    d=st.sampled_from([8, 16]),
    window=st.sampled_from([None, 16, 32]),
    seed=st.integers(0, 2**16),
)
def test_flash_attention_fwd_bwd_matches_dense(b, s, heads, d, window, seed):
    from repro.models.flash import flash_gqa_attention
    from repro.models.layers import causal_mask_bias, gqa_scores_softmax_value

    h, kv = heads
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, kv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, kv, d)), jnp.float32)
    weff = jnp.asarray(window if window else s + 100, jnp.int32)
    probe = jnp.cos(jnp.arange(d, dtype=jnp.float32))

    def f_flash(q, k, v):
        return jnp.sum(flash_gqa_attention(q, k, v, weff, 32) * probe)

    def f_dense(q, k, v):
        return jnp.sum(gqa_scores_softmax_value(q, k, v, causal_mask_bias(s, window)) * probe)

    np.testing.assert_allclose(float(f_flash(q, k, v)), float(f_dense(q, k, v)), rtol=1e-4)
    gf = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(f_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=5e-4, atol=5e-4)
