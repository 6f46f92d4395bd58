"""Unified decoder stack covering all assigned families.

dense / moe / vlm / audio : attention (+SwiGLU or MoE FFN)
ssm (mamba2)              : SSD mixer only (d_ff = 0)
hybrid (hymba)            : parallel attention ∥ SSD heads (+FFN)

Blocks are homogeneous and scanned. Architectures with a local:global
attention pattern (gemma3) use a *grouped* scan for serving so that the two
cache geometries (ring-window vs. full) stay separately allocated.
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.models import ssm as ssm_lib
from repro.models.config import ModelConfig
from repro.models.layers import (
    attention_decode,
    attention_train,
    cross_entropy_loss,
    embed_tokens,
    lm_head_logits,
    moe_block,
    rms_norm,
    swiglu_mlp,
)

# ---------------------------------------------------------------------------
# Parameter construction
# ---------------------------------------------------------------------------


def _block_param_shapes(cfg: ModelConfig) -> Dict:
    d, ff = cfg.d_model, cfg.d_ff
    hd = cfg.resolved_head_dim
    q, kv = cfg.num_heads * hd, cfg.num_kv_heads * hd
    shapes: Dict = {"pre_norm": (d,)}
    if cfg.uses_attention:
        shapes.update({"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d)})
        if cfg.qkv_bias:
            shapes.update({"bq": (q,), "bk": (kv,), "bv": (kv,)})
    if cfg.uses_ssm:
        shapes["ssm"] = ssm_lib.ssm_param_shapes(cfg)
    if ff > 0:
        shapes["mlp_norm"] = (d,)
        if cfg.uses_moe:
            shapes.update(
                {
                    "router": (d, cfg.num_experts),
                    "we_gate": (cfg.num_experts, d, ff),
                    "we_up": (cfg.num_experts, d, ff),
                    "we_down": (cfg.num_experts, ff, d),
                }
            )
        else:
            shapes.update({"w_gate": (d, ff), "w_up": (d, ff), "w_down": (ff, d)})
    return shapes


def param_shapes(cfg: ModelConfig) -> Dict:
    """Full parameter pytree of shapes (blocks stacked over num_layers)."""
    L = cfg.num_layers
    blocks = jax.tree.map(
        lambda s: (L, *s), _block_param_shapes(cfg), is_leaf=lambda s: isinstance(s, tuple)
    )
    shapes: Dict = {
        "embed": (cfg.vocab_size, cfg.d_model),
        "blocks": blocks,
        "final_norm": (cfg.d_model,),
    }
    if not cfg.tie_embeddings:
        shapes["lm_head"] = (cfg.d_model, cfg.vocab_size)
    return shapes


def _init_leaf(key, path: str, shape, dtype):
    """Fan-in scaled normal init; norms zero; special-cased SSM scalars."""
    name = path.split("/")[-1]
    if "norm" in name or name in ("bq", "bk", "bv", "conv_bx", "conv_bB", "conv_bC", "dt_bias"):
        return jnp.zeros(shape, dtype=dtype)
    if name == "A_log":
        # A in [1, 16) as in Mamba-2.
        return jnp.log(
            jax.random.uniform(key, shape, minval=1.0, maxval=16.0, dtype=jnp.float32)
        ).astype(dtype)
    if name == "D":
        return jnp.ones(shape, dtype=dtype)
    if name == "embed":
        return (jax.random.normal(key, shape, dtype=jnp.float32) * 0.02).astype(dtype)
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    scale = 1.0 / math.sqrt(max(fan_in, 1))
    return (jax.random.normal(key, shape, dtype=jnp.float32) * scale).astype(dtype)


def init_params(cfg: ModelConfig, rng: jax.Array) -> Dict:
    shapes = param_shapes(cfg)
    dtype = jnp.dtype(cfg.param_dtype)
    flat, treedef = jax.tree.flatten_with_path(shapes, is_leaf=lambda s: isinstance(s, tuple))
    leaves = []
    for i, (path, shape) in enumerate(flat):
        pathstr = "/".join(str(p.key) for p in path)
        leaves.append(_init_leaf(jax.random.fold_in(rng, i), pathstr, shape, dtype))
    return jax.tree.unflatten(treedef, leaves)


# ---------------------------------------------------------------------------
# Partition specs (2-D sharding: FSDP over data axes ⊗ TP over model axis)
# ---------------------------------------------------------------------------


def _axis_size(mesh_axes: Dict[str, int], axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        return mesh_axes.get(axes, 1)
    n = 1
    for a in axes:
        n *= mesh_axes.get(a, 1)
    return n


def param_pspecs(
    cfg: ModelConfig,
    mesh_axes: Dict[str, int],
    data_axes=("data",),
    model_axis: str = "model",
) -> Dict:
    """PartitionSpec pytree matching ``param_shapes``. A dim is sharded only

    when evenly divisible by the axis-product (GSPMD would pad otherwise)."""
    dsz = _axis_size(mesh_axes, data_axes)
    msz = _axis_size(mesh_axes, model_axis) if model_axis else 1
    da = tuple(data_axes) if not isinstance(data_axes, str) else (data_axes,)
    da_spec = da if len(da) > 1 else da[0]

    def rule(pathstr: str, shape) -> P:
        name = pathstr.split("/")[-1]

        def d_ok(dim):
            return shape[dim] % dsz == 0

        def m_ok(dim):
            # model_axis=None: pure-FSDP variant — never TP-shard anything
            return model_axis is not None and shape[dim] % msz == 0

        if "norm" in name or name in ("A_log", "D", "dt_bias", "conv_bx", "conv_bB", "conv_bC"):
            return P()
        if name == "embed":  # (V, d)
            return P(model_axis if m_ok(0) else None, da_spec if d_ok(1) else None)
        if name == "lm_head":  # (d, V)
            return P(da_spec if d_ok(0) else None, model_axis if m_ok(1) else None)
        if name in ("wq", "wk", "wv", "w_gate", "w_up", "in_z", "in_x", "in_B", "in_C", "in_dt"):
            # (L, in, out): FSDP on in, TP on out
            return P(None, da_spec if d_ok(1) else None, model_axis if m_ok(2) else None)
        if name in ("wo", "w_down", "out_proj"):
            # (L, in, out): TP on in, FSDP on out
            return P(None, model_axis if m_ok(1) else None, da_spec if d_ok(2) else None)
        if name in ("bq", "bk", "bv"):
            return P(None, model_axis if m_ok(1) else None)
        if name == "router":  # (L, d, E)
            return P(None, da_spec if d_ok(1) else None, None)
        if name in ("we_gate", "we_up"):  # (L, E, d, ff)
            return P(None, None, da_spec if d_ok(2) else None, model_axis if m_ok(3) else None)
        if name == "we_down":  # (L, E, ff, d)
            return P(None, None, model_axis if m_ok(2) else None, da_spec if d_ok(3) else None)
        if name in ("conv_x", "conv_B", "conv_C"):  # (L, K, ch)
            return P(None, None, model_axis if m_ok(2) else None)
        return P()

    shapes = param_shapes(cfg)
    flat, treedef = jax.tree.flatten_with_path(shapes, is_leaf=lambda s: isinstance(s, tuple))
    specs = []
    for path, shape in flat:
        pathstr = "/".join(str(p.key) for p in path)
        specs.append(rule(pathstr, shape))
    return jax.tree.unflatten(treedef, specs)


# ---------------------------------------------------------------------------
# Forward (training / scoring)
# ---------------------------------------------------------------------------

from repro.models.scan_util import scan_or_unroll as _layer_scan  # noqa: E402


def _embed_input(cfg: ModelConfig, params: Dict, batch: Dict) -> jax.Array:
    from repro.models import shard_hints

    cd = jnp.dtype(cfg.compute_dtype)
    if cfg.embed_inputs:
        x = embed_tokens(params["embed"], batch["tokens"], cd)
    else:
        x = batch["embeds"].astype(cd)
    # Without this, the vocab-sharded gather can emit a replicated (b, s, d)
    # and every scan residual downstream stays replicated (≈ L × b × s × d
    # per device). See EXPERIMENTS.md §Perf iteration 1.
    return shard_hints.constrain(x, shard_hints.current().activations)


def _positions(cfg: ModelConfig, batch: Dict, b: int, s: int, offset=0) -> jax.Array:
    if "positions" in batch:
        return batch["positions"]
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None, :] + offset, (b, s))
    if cfg.mrope_sections is not None:
        return jnp.broadcast_to(pos[None], (3, b, s))
    return pos


def _block_train(cfg: ModelConfig, p: Dict, x, kind, positions):
    from repro.models import shard_hints

    x = shard_hints.constrain(x, shard_hints.current().activations)
    aux = jnp.zeros((), jnp.float32)
    h = rms_norm(x, p["pre_norm"], cfg.norm_eps)
    if cfg.family == "hybrid":
        a = attention_train(cfg, p, h, kind, positions)
        s = ssm_lib.ssm_mixer_train(cfg, p["ssm"], h)
        x = x + 0.5 * (a + s)
    elif cfg.family == "ssm":
        x = x + ssm_lib.ssm_mixer_train(cfg, p["ssm"], h)
    else:
        x = x + attention_train(cfg, p, h, kind, positions)
    if cfg.d_ff > 0:
        h = rms_norm(x, p["mlp_norm"], cfg.norm_eps)
        if cfg.uses_moe:
            y, aux = moe_block(cfg, p, h)
        else:
            y = swiglu_mlp(p, h)
        x = x + y
    return x, aux


def forward(
    cfg: ModelConfig, params: Dict, batch: Dict, remat: bool = False
) -> Tuple[jax.Array, jax.Array]:
    """Returns (logits (b, s, V), moe_aux_loss)."""
    x = _embed_input(cfg, params, batch)
    b, s = x.shape[0], x.shape[1]
    positions = _positions(cfg, batch, b, s)
    kinds = jnp.asarray(cfg.layer_kinds(), dtype=jnp.int32)

    block = _block_train
    if remat:
        block = jax.checkpoint(_block_train, static_argnums=(0,), prevent_cse=False)

    def body(carry, xs):
        x, aux = carry
        p, kind = xs
        x, a = block(cfg, p, x, kind, positions)
        return (x, aux + a), None

    (x, aux), _ = _layer_scan(body, (x, jnp.zeros((), jnp.float32)), (params["blocks"], kinds))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return lm_head_logits(cfg, params, x), aux


MOE_AUX_WEIGHT = 0.01


def loss_fn(
    cfg: ModelConfig, params: Dict, batch: Dict, remat: bool = False
) -> Tuple[jax.Array, Dict]:
    logits, aux = forward(cfg, params, batch, remat=remat)
    ce = cross_entropy_loss(logits, batch["labels"], batch.get("mask"))
    loss = ce + MOE_AUX_WEIGHT * aux
    preds = jnp.argmax(logits, axis=-1)
    acc = jnp.mean((preds == batch["labels"]).astype(jnp.float32))
    return loss, {"ce": ce, "moe_aux": aux, "acc": acc}


# ---------------------------------------------------------------------------
# Decode caches
# ---------------------------------------------------------------------------


def _attn_cache_len(cfg: ModelConfig, kind: int, max_len: int) -> int:
    w = cfg.window_for_kind(kind)
    return min(w, max_len) if w is not None else max_len


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Dict:
    """Decode cache pytree. Layout depends on the family / attention pattern."""
    cd = jnp.dtype(cfg.compute_dtype)
    hd, kvh, L = cfg.resolved_head_dim, cfg.num_kv_heads, cfg.num_layers
    cache: Dict = {"pos": jnp.zeros((), jnp.int32)}
    if cfg.family == "ssm":
        cache["ssm"] = ssm_lib.init_ssm_cache(cfg, L, batch, cd)
        return cache
    if cfg.local_global_ratio > 0:
        r = cfg.local_global_ratio
        G = r + 1
        n_groups = L // G
        W = _attn_cache_len(cfg, 1, max_len)
        S = _attn_cache_len(cfg, 0, max_len)
        cache["k_local"] = jnp.zeros((n_groups, r, batch, W, kvh, hd), dtype=cd)
        cache["v_local"] = jnp.zeros((n_groups, r, batch, W, kvh, hd), dtype=cd)
        cache["k_global"] = jnp.zeros((n_groups, batch, S, kvh, hd), dtype=cd)
        cache["v_global"] = jnp.zeros((n_groups, batch, S, kvh, hd), dtype=cd)
        return cache
    S = _attn_cache_len(cfg, cfg.layer_kinds()[0], max_len)
    if cfg.kv_cache_dtype == "int8":
        cache["k"] = jnp.zeros((L, batch, S, kvh, hd), dtype=jnp.int8)
        cache["v"] = jnp.zeros((L, batch, S, kvh, hd), dtype=jnp.int8)
        cache["k_scale"] = jnp.zeros((L, batch, S, kvh), dtype=jnp.float32)
        cache["v_scale"] = jnp.zeros((L, batch, S, kvh), dtype=jnp.float32)
    else:
        cache["k"] = jnp.zeros((L, batch, S, kvh, hd), dtype=cd)
        cache["v"] = jnp.zeros((L, batch, S, kvh, hd), dtype=cd)
    if cfg.family == "hybrid":
        cache["ssm"] = ssm_lib.init_ssm_cache(cfg, L, batch, cd)
    return cache


def _ring(cfg: ModelConfig) -> bool:
    # Uniform-cache archs: ring iff every layer is windowed.
    return cfg.window is not None and cfg.local_global_ratio == 0


def _block_decode(cfg: ModelConfig, p: Dict, x, c: Dict, pos, positions, ring: bool):
    """One block, one token. c holds this layer's cache slices."""
    newc: Dict = {}
    h = rms_norm(x, p["pre_norm"], cfg.norm_eps)
    attn_keys = ("k", "v", "k_scale", "v_scale")
    attn_c = {k: c[k] for k in attn_keys if k in c}
    if cfg.family == "hybrid":
        a, attn_new = attention_decode(cfg, p, h, attn_c, pos, positions, 0, ring)
        s_out, nssm = ssm_lib.ssm_mixer_decode(cfg, p["ssm"], h, c["ssm"])
        x = x + 0.5 * (a + s_out)
        newc.update(attn_new)
        newc["ssm"] = nssm
    elif cfg.family == "ssm":
        s_out, nssm = ssm_lib.ssm_mixer_decode(cfg, p["ssm"], h, c["ssm"])
        x = x + s_out
        newc["ssm"] = nssm
    else:
        a, attn_new = attention_decode(cfg, p, h, attn_c, pos, positions, 0, ring)
        x = x + a
        newc.update(attn_new)
    if cfg.d_ff > 0:
        h = rms_norm(x, p["mlp_norm"], cfg.norm_eps)
        if cfg.uses_moe:
            y, _ = moe_block(cfg, p, h)
        else:
            y = swiglu_mlp(p, h)
        x = x + y
    return x, newc


def decode_step(cfg: ModelConfig, params: Dict, cache: Dict, batch: Dict):
    """One-token decode. batch: {'tokens': (b,1)} or {'embeds': (b,1,d)}.

    Returns (logits (b, V), new_cache)."""
    x = _embed_input(cfg, params, batch)
    b = x.shape[0]
    pos = cache["pos"]
    positions = _positions(cfg, batch, b, 1, offset=pos)

    if cfg.local_global_ratio > 0:
        x, new_cache = _decode_grouped(cfg, params, cache, x, pos, positions)
    else:
        layer_cache = {k: v for k, v in cache.items() if k != "pos"}

        def body(x, xs):
            p, c = xs
            x, newc = _block_decode(cfg, p, x, c, pos, positions, _ring(cfg))
            return x, newc

        x, new_layer_cache = _layer_scan(body, x, (params["blocks"], layer_cache))
        new_cache = dict(new_layer_cache)

    new_cache["pos"] = pos + 1
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = lm_head_logits(cfg, params, x)
    return logits[:, 0, :], new_cache


def _decode_grouped(cfg: ModelConfig, params: Dict, cache: Dict, x, pos, positions):
    """Grouped scan for local:global archs (two cache geometries)."""
    r = cfg.local_global_ratio
    G = r + 1
    n_groups = cfg.num_layers // G
    grouped = jax.tree.map(lambda a: a.reshape(n_groups, G, *a.shape[1:]), params["blocks"])

    def body(x, xs):
        p_g, kl, vl, kg, vg = xs
        new_kl, new_vl = [], []
        for i in range(r):
            p_i = jax.tree.map(lambda a: a[i], p_g)
            xi, ci = _block_decode(
                cfg, p_i, x, {"k": kl[i], "v": vl[i]}, pos, positions, ring=True
            )
            x = xi
            new_kl.append(ci["k"])
            new_vl.append(ci["v"])
        p_glob = jax.tree.map(lambda a: a[r], p_g)
        x, cg = _block_decode(cfg, p_glob, x, {"k": kg, "v": vg}, pos, positions, ring=False)
        return x, (jnp.stack(new_kl), jnp.stack(new_vl), cg["k"], cg["v"])

    x, (kl, vl, kg, vg) = _layer_scan(
        body, x, (grouped, cache["k_local"], cache["v_local"], cache["k_global"], cache["v_global"])
    )
    new_cache = {"k_local": kl, "v_local": vl, "k_global": kg, "v_global": vg}
    return x, new_cache


# ---------------------------------------------------------------------------
# Prefill (forward + cache construction)
# ---------------------------------------------------------------------------


def _ring_place(k: jax.Array, W: int) -> jax.Array:
    """Place the last W entries of k (b, s, ...) at slots (pos % W)."""
    s = k.shape[1]
    if s < W:
        pad = jnp.zeros((k.shape[0], W - s, *k.shape[2:]), dtype=k.dtype)
        return jnp.concatenate([k, pad], axis=1)
    tail = k[:, s - W :]
    slots = (np.arange(s - W, s) % W).astype(np.int32)
    inv = np.argsort(slots)
    return tail[:, inv]


def _full_place(k: jax.Array, S: int) -> jax.Array:
    s = k.shape[1]
    if s >= S:
        return k[:, :S]
    pad = jnp.zeros((k.shape[0], S - s, *k.shape[2:]), dtype=k.dtype)
    return jnp.concatenate([k, pad], axis=1)


def _attn_train_with_kv(cfg, p, x, kind, positions):
    """attention_train that also returns post-rope K/V for cache building."""
    from repro.models.layers import (
        BLOCKED_ATTN_THRESHOLD,
        _window_eff,
        apply_mrope,
        apply_rope,
        causal_mask_bias,
        gqa_scores_softmax_value,
    )

    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    h, kvh = cfg.num_heads, cfg.num_kv_heads
    cd = x.dtype
    q = jnp.einsum("bsd,dq->bsq", x, p["wq"].astype(cd))
    k = jnp.einsum("bsd,dq->bsq", x, p["wk"].astype(cd))
    v = jnp.einsum("bsd,dq->bsq", x, p["wv"].astype(cd))
    if cfg.qkv_bias:
        q, k, v = q + p["bq"].astype(cd), k + p["bk"].astype(cd), v + p["bv"].astype(cd)
    q = q.reshape(b, s, h, hd)
    k = k.reshape(b, s, kvh, hd)
    v = v.reshape(b, s, kvh, hd)
    if cfg.mrope_sections is not None:
        q = apply_mrope(q, positions, cfg.mrope_sections, cfg.rope_theta)
        k = apply_mrope(k, positions, cfg.mrope_sections, cfg.rope_theta)
    else:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    if s >= BLOCKED_ATTN_THRESHOLD:
        from repro.models.flash import flash_gqa_attention

        out = flash_gqa_attention(q, k, v, _window_eff(cfg, kind, s), 0)
    else:
        full_bias = causal_mask_bias(s, cfg.window_for_kind(0))
        if cfg.local_global_ratio > 0 or cfg.window is not None:
            local_bias = causal_mask_bias(s, cfg.window_for_kind(1))
            bias = jnp.where(kind == 1, local_bias, full_bias)
        else:
            bias = full_bias
        out = gqa_scores_softmax_value(q, k, v, bias)
    out = out.reshape(b, s, h * hd)
    return jnp.einsum("bsq,qd->bsd", out, p["wo"].astype(cd)), k, v


def _block_prefill(cfg: ModelConfig, p: Dict, x, kind_static: int, positions, max_len: int):
    newc: Dict = {}
    h = rms_norm(x, p["pre_norm"], cfg.norm_eps)
    if cfg.family == "hybrid":
        a, k, v = _attn_train_with_kv(cfg, p, h, jnp.int32(kind_static), positions)
        s_out, ssm_c = ssm_lib.ssm_mixer_prefill(cfg, p["ssm"], h)
        x = x + 0.5 * (a + s_out)
        newc["ssm"] = ssm_c
    elif cfg.family == "ssm":
        s_out, ssm_c = ssm_lib.ssm_mixer_prefill(cfg, p["ssm"], h)
        x = x + s_out
        newc["ssm"] = ssm_c
        k = v = None
    else:
        a, k, v = _attn_train_with_kv(cfg, p, h, jnp.int32(kind_static), positions)
        x = x + a
    if k is not None:
        C = _attn_cache_len(cfg, kind_static, max_len)
        w = cfg.window_for_kind(kind_static)
        place = _ring_place if (w is not None and C == w) else _full_place
        if cfg.kv_cache_dtype == "int8" and cfg.local_global_ratio == 0:
            from repro.models.layers import quantize_kv

            kq, ks = quantize_kv(k)
            vq, vs = quantize_kv(v)
            newc["k"], newc["v"] = place(kq, C), place(vq, C)
            newc["k_scale"], newc["v_scale"] = place(ks, C), place(vs, C)
        else:
            newc["k"], newc["v"] = place(k, C), place(v, C)
    if cfg.d_ff > 0:
        hh = rms_norm(x, p["mlp_norm"], cfg.norm_eps)
        y = moe_block(cfg, p, hh)[0] if cfg.uses_moe else swiglu_mlp(p, hh)
        x = x + y
    return x, newc


def prefill(cfg: ModelConfig, params: Dict, batch: Dict, max_len: int):
    """Run the full prompt, return (logits (b, s, V), decode cache)."""
    x = _embed_input(cfg, params, batch)
    b, s = x.shape[0], x.shape[1]
    positions = _positions(cfg, batch, b, s)

    if cfg.local_global_ratio > 0:
        r = cfg.local_global_ratio
        G = r + 1
        n_groups = cfg.num_layers // G
        grouped = jax.tree.map(lambda a: a.reshape(n_groups, G, *a.shape[1:]), params["blocks"])

        def body(x, p_g):
            kl, vl = [], []
            for i in range(r):
                p_i = jax.tree.map(lambda a: a[i], p_g)
                x, c = _block_prefill(cfg, p_i, x, 1, positions, max_len)
                kl.append(c["k"])
                vl.append(c["v"])
            p_glob = jax.tree.map(lambda a: a[r], p_g)
            x, cg = _block_prefill(cfg, p_glob, x, 0, positions, max_len)
            return x, (jnp.stack(kl), jnp.stack(vl), cg["k"], cg["v"])

        x, (kl, vl, kg, vg) = _layer_scan(body, x, grouped)
        cache = {"k_local": kl, "v_local": vl, "k_global": kg, "v_global": vg}
    else:
        kind = cfg.layer_kinds()[0]

        def body(x, p):
            x, c = _block_prefill(cfg, p, x, kind, positions, max_len)
            return x, c

        x, cache = _layer_scan(body, x, params["blocks"])
        cache = dict(cache)

    cache["pos"] = jnp.asarray(s, jnp.int32)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return lm_head_logits(cfg, params, x), cache


# ---------------------------------------------------------------------------
# Stage partitioning (consumed by the Ferret pipeline engine)
# ---------------------------------------------------------------------------


def split_stage_params(cfg: ModelConfig, params: Dict, boundaries) -> list:
    """Split into P stage subtrees. boundaries = partition scheme L (P+1 ints).

    Stage 0 owns the embedding; the last stage owns final_norm (+ lm_head).
    """
    P_ = len(boundaries) - 1
    stages = []
    for j in range(P_):
        lo, hi = boundaries[j], boundaries[j + 1]
        sp: Dict = {"blocks": jax.tree.map(lambda a: a[lo:hi], params["blocks"])}
        if j == 0:
            sp["embed"] = params["embed"]
        if j == P_ - 1:
            sp["final_norm"] = params["final_norm"]
            if not cfg.tie_embeddings:
                sp["lm_head"] = params["lm_head"]
        stages.append(sp)
    return stages


def merge_stage_params(cfg: ModelConfig, stages: list) -> Dict:
    """Inverse of split_stage_params."""
    blocks = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0), *[s["blocks"] for s in stages])
    params = {"embed": stages[0]["embed"], "blocks": blocks, "final_norm": stages[-1]["final_norm"]}
    if "lm_head" in stages[-1]:
        params["lm_head"] = stages[-1]["lm_head"]
    return params


def stage_forward(
    cfg: ModelConfig,
    stage_params: Dict,
    x_or_batch,
    stage_idx: int,
    num_stages: int,
    boundaries,
    batch: Dict,
    remat: bool = False,
):
    """Forward one pipeline stage. Stage 0 receives the batch (embeds);

    later stages receive activations. The last stage returns logits."""
    lo, hi = boundaries[stage_idx], boundaries[stage_idx + 1]
    if stage_idx == 0:
        x = _embed_input(
            cfg, {"embed": stage_params.get("embed")} if cfg.embed_inputs else {}, batch
        )
    else:
        x = x_or_batch
    b, s = x.shape[0], x.shape[1]
    positions = _positions(cfg, batch, b, s)
    kinds = jnp.asarray(cfg.layer_kinds()[lo:hi], dtype=jnp.int32)

    block = _block_train
    if remat:
        block = jax.checkpoint(_block_train, static_argnums=(0,), prevent_cse=False)

    def body(carry, xs):
        x, aux = carry
        p, kind = xs
        x, a = block(cfg, p, x, kind, positions)
        return (x, aux + a), None

    (x, aux), _ = _layer_scan(
        body, (x, jnp.zeros((), jnp.float32)), (stage_params["blocks"], kinds)
    )
    if stage_idx == num_stages - 1:
        x = rms_norm(x, stage_params["final_norm"], cfg.norm_eps)
        logits = lm_head_logits(cfg, stage_params, x)
        return logits, aux
    return x, aux
