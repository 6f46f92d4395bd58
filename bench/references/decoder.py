"""Plain reference of a pre-norm decoder language model, in jax.numpy.

The block every configuration with ``"reference": "decoder"`` states:

    h = rms(x) * (1 + w_norm)                     (float32 statistics)
    q, k, v = h Wq, h Wk, h Wv;  RoPE on q and k (halves rotated, theta)
    causal attention, grouped: query head i reads key/value head i // g,
        keys further back than ``window`` masked when a window is set
    x = x + attn Wo
    h = rms(x) * (1 + w_mlp)
    x = x + (silu(h Wg) * (h Wu)) Wd
    logits = rms(x) * (1 + w_final) W_lm;  loss = mean token cross-entropy

Everything is float32 at ``highest`` matmul precision. ``quant`` names a
lower precision for the control, held where the configuration's compute
type is held: every matmul operand and result, the residual stream and
the normalised activations are rounded to it (per-tensor scale to the
format's largest value), and so is every gradient flowing back through
them. Norm statistics, softmax and the loss stay float32.

Parameters are one pytree: ``embed (V, d)``, ``blocks`` with each leaf
stacked over layers, ``final_norm (d,)`` and ``lm_head (d, V)``. Nothing
here imports the system under test.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

QUANT_MAX = {"float8_e4m3fn": 448.0}


def _quantize(x: jax.Array, quant: str) -> jax.Array:
    top = QUANT_MAX[quant]
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    # clipped: a quotient rounded past the largest value converts to NaN
    q = jnp.clip(x / scale, -top, top).astype(jnp.dtype(quant))
    return q.astype(jnp.float32) * scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _operand(x: jax.Array, quant: str) -> jax.Array:
    """A matmul operand rounded to ``quant``; its gradient passes as is."""
    return _quantize(x, quant)


_operand.defvjp(lambda x, quant: (_quantize(x, quant), None), lambda quant, _, g: (g,))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _activation(y: jax.Array, quant: str) -> jax.Array:
    """An activation held in ``quant``: rounded going forward, and its
    gradient rounded going back."""
    return _quantize(y, quant)


_activation.defvjp(lambda y, quant: (_quantize(y, quant), None),
                   lambda quant, _, g: (_quantize(g, quant),))


def _act(y: jax.Array, quant: Optional[str]) -> jax.Array:
    return y if quant is None else _activation(y, quant)


def _mm(spec: str, a: jax.Array, b: jax.Array, quant: Optional[str]) -> jax.Array:
    if quant is None:
        return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)
    y = jnp.einsum(spec, _operand(a, quant), _operand(b, quant),
                   precision=jax.lax.Precision.HIGHEST)
    return _activation(y, quant)


def _rms(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w)


def _rope(x: jax.Array, theta: float) -> jax.Array:
    """x: (b, s, h, d); position t rotates pair (i, i + d/2) by t / theta^(2i/d)."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv  # (s, d/2)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(m: dict, p: dict, h: jax.Array, quant: Optional[str]) -> jax.Array:
    b, s, _ = h.shape
    nh, kvh = m["num_heads"], m["num_kv_heads"]
    hd = m["d_model"] // nh
    q = _mm("bsd,dq->bsq", h, p["wq"], quant).reshape(b, s, nh, hd)
    k = _mm("bsd,dq->bsq", h, p["wk"], quant).reshape(b, s, kvh, hd)
    v = _mm("bsd,dq->bsq", h, p["wv"], quant).reshape(b, s, kvh, hd)
    q, k = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"])
    g = nh // kvh
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    scores = _mm("bqhd,bkhd->bhqk", q, k, quant) / jnp.sqrt(jnp.float32(hd))
    qi, ki = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    allowed = ki <= qi
    if m.get("window") is not None:
        allowed &= ki > qi - m["window"]
    scores = jnp.where(allowed, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = _mm("bhqk,bkhd->bqhd", probs, v, quant).reshape(b, s, nh * hd)
    return _mm("bsq,qd->bsd", out, p["wo"], quant)


def _mlp(p: dict, h: jax.Array, quant: Optional[str]) -> jax.Array:
    gate = _mm("bsd,df->bsf", h, p["w_gate"], quant)
    up = _mm("bsd,df->bsf", h, p["w_up"], quant)
    return _mm("bsf,fd->bsd", jax.nn.silu(gate) * up, p["w_down"], quant)


def logits(m: dict, params: dict, tokens: jax.Array, quant: Optional[str] = None) -> jax.Array:
    eps = m["norm_eps"]
    x = _act(params["embed"][tokens], quant)
    for layer in range(m["num_layers"]):
        p = jax.tree.map(lambda a: a[layer], params["blocks"])
        x = _act(x + _attention(m, p, _act(_rms(x, p["pre_norm"], eps), quant), quant), quant)
        x = _act(x + _mlp(p, _act(_rms(x, p["mlp_norm"], eps), quant), quant), quant)
    x = _act(_rms(x, params["final_norm"], eps), quant)
    return _mm("bsd,dv->bsv", x, params["lm_head"], quant)


def loss(m: dict, params: dict, tokens: jax.Array, labels: jax.Array,
         quant: Optional[str] = None) -> jax.Array:
    z = logits(m, params, tokens, quant)
    logz = jax.nn.logsumexp(z, axis=-1)
    gold = jnp.take_along_axis(z, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)
