"""Per-architecture smoke tests + decode/prefill consistency (deliverable f)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import smoke_cfg
from repro.models import transformer as T
from repro.models.registry import ARCHITECTURES, get_config

ALL_ARCHS = sorted(ARCHITECTURES)


def _batch(cfg, rng, b=2, s=16, extra_tok=0):
    if cfg.embed_inputs:
        toks = jax.random.randint(rng, (b, s + extra_tok), 0, cfg.vocab_size)
        out = {"tokens": toks[:, : s + extra_tok]}
    else:
        out = {
            "embeds": jax.random.normal(
                rng, (b, s + extra_tok, cfg.d_model), dtype=jnp.dtype(cfg.compute_dtype)
            )
        }
    out["labels"] = jax.random.randint(rng, (b, s + extra_tok), 0, cfg.vocab_size)
    return out


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_smoke_forward_and_train_step(arch, rng):
    """Reduced config: one forward + one train step, shapes + finiteness."""
    cfg = smoke_cfg(arch)
    params = T.init_params(cfg, rng)
    b, s = 2, 16
    batch = _batch(cfg, rng, b, s)
    logits, aux = T.forward(cfg, params, batch)
    assert logits.shape == (b, s, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())

    from repro.optim.optimizers import adamw

    opt = adamw(lr=1e-3)
    state = opt.init(params)
    loss, metrics = T.loss_fn(cfg, params, batch)
    assert bool(jnp.isfinite(loss))
    grads = jax.grad(lambda p: T.loss_fn(cfg, p, batch)[0])(params)
    new_params, _ = opt.update(params, grads, state)
    # parameters actually moved and stayed finite
    moved = jax.tree.map(lambda a, b_: float(jnp.max(jnp.abs(a - b_))), params, new_params)
    assert max(jax.tree.leaves(moved)) > 0
    finite = jax.tree.map(lambda a: bool(jnp.isfinite(a).all()), new_params)
    assert all(jax.tree.leaves(finite))


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_prefill_decode_matches_forward(arch, rng):
    """prefill(s) + decode(1) must equal forward(s+1) — exercises every cache
    type (full KV, ring SWA, grouped local:global, SSM conv+state)."""
    cfg = smoke_cfg(arch)
    params = T.init_params(cfg, rng)
    b, s = 2, 12
    full = _batch(cfg, rng, b, s, extra_tok=1)
    if cfg.embed_inputs:
        pre = {"tokens": full["tokens"][:, :s]}
        dec = {"tokens": full["tokens"][:, s : s + 1]}
    else:
        pre = {"embeds": full["embeds"][:, :s]}
        dec = {"embeds": full["embeds"][:, s : s + 1]}
    logits_full, _ = T.forward(cfg, params, full)
    logits_pre, cache = T.prefill(cfg, params, pre, max_len=s + 8)
    np.testing.assert_allclose(
        np.asarray(logits_full[:, :s]), np.asarray(logits_pre), rtol=3e-4, atol=3e-4
    )
    logits_dec, cache = T.decode_step(cfg, params, cache, dec)
    np.testing.assert_allclose(
        np.asarray(logits_full[:, s]), np.asarray(logits_dec), rtol=3e-4, atol=3e-4
    )
    assert int(cache["pos"]) == s + 1


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_param_specs_match_shapes(arch):
    """The PartitionSpec tree must mirror the parameter tree exactly and only
    shard divisible dims (GSPMD padding never needed)."""
    cfg = get_config(arch)  # FULL config: this is what the dry-run shards
    shapes = T.param_shapes(cfg)
    mesh_axes = {"pod": 2, "data": 16, "model": 16}
    specs = T.param_pspecs(cfg, mesh_axes, data_axes=("pod", "data"))
    flat_shapes = jax.tree.flatten_with_path(shapes, is_leaf=lambda s: isinstance(s, tuple))[0]
    sh_map = {tuple(p): v for p, v in flat_shapes}
    sp_flat = jax.tree.flatten_with_path(
        specs, is_leaf=lambda s: s.__class__.__name__ == "PartitionSpec"
    )[0]
    assert len(sh_map) == len(sp_flat)
    for path, spec in sp_flat:
        shape = sh_map[tuple(path)]
        assert len(spec) <= len(shape), (path, spec, shape)
        for dim, ax in enumerate(spec):
            if ax is None:
                continue
            axes = (ax,) if isinstance(ax, str) else ax
            size = 1
            for a in axes:
                size *= mesh_axes[a]
            assert shape[dim] % size == 0, (path, spec, shape, dim)


def test_param_count_matches_init():
    for arch in ALL_ARCHS:
        cfg = smoke_cfg(arch)
        params = T.init_params(cfg, jax.random.PRNGKey(0))
        n = sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(params))
        assert n == cfg.param_count(), arch


def test_stage_split_merge_roundtrip(rng):
    cfg = smoke_cfg("h2o-danube-1.8b", num_layers=4)
    params = T.init_params(cfg, rng)
    stages = T.split_stage_params(cfg, params, [0, 1, 3, 4])
    merged = T.merge_stage_params(cfg, stages)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(merged)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_stage_forward_composes_to_forward(rng):
    cfg = smoke_cfg("h2o-danube-1.8b", num_layers=4)
    params = T.init_params(cfg, rng)
    batch = _batch(cfg, rng, 2, 8)
    bounds = [0, 2, 4]
    stages = T.split_stage_params(cfg, params, bounds)
    x = None
    for j in range(2):
        x, _ = T.stage_forward(cfg, stages[j], x, j, 2, bounds, batch)
    ref, _ = T.forward(cfg, params, batch)
    np.testing.assert_allclose(np.asarray(x), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_moe_dispatch_drops_only_over_capacity():
    from repro.models.layers import moe_dispatch

    ids = jnp.asarray([[0], [0], [0], [1], [1], [2], [0], [0]], dtype=jnp.int32)
    dest, keep, order = moe_dispatch(ids, num_experts=4, capacity=3)
    # expert 0 got 5 tokens, capacity 3 -> exactly 2 dropped
    assert int(keep.sum()) == 6
    kept_dest = dest[keep]
    assert int(jnp.max(kept_dest)) < 4 * 3
    # destinations unique for kept tokens
    assert len(set(np.asarray(kept_dest).tolist())) == 6


def test_long_500k_applicability_flags():
    from repro.configs.common import SHAPES, shape_applicable

    runnable = {a for a in ALL_ARCHS if shape_applicable(get_config(a), SHAPES["long_500k"])}
    assert runnable == {
        "mamba2-780m", "h2o-danube-1.8b", "gemma3-12b", "hymba-1.5b", "mixtral-8x22b"
    }
