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
stacked over layers, ``final_norm (d,)`` and ``lm_head (d, V)``. The head
size is the configuration's ``head_dim`` where it gives one, else
``d_model / num_heads``. Nothing here imports the system under test.

Besides ``logits`` and ``loss``, a reference states its block to the
harness: ``param_shapes`` (the weights the harness makes from the seed),
``train_flops`` (the model FLOPs of a step, for ``step_mfu``),
``stage_sizes`` (parameters per pipeline stage, for the Iter-Fisher
kernels' bytes) and ``PROGRAM`` (the program's configuration of this
block). A block of another kind is another file that states the same.
"""

from __future__ import annotations

import functools
import math
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp

QUANT_MAX = {"float8_e4m3fn": 448.0}

# The program's ModelConfig for this block (``drive.program_model_config``):
# each key of ``fields`` and ``optional`` in the configuration's "model"
# sets the ModelConfig field of that name (an optional key left out sets
# None: the head size then follows d_model / num_heads, and no window
# means full attention); a key in neither is not this block. Every field
# or property in ``requires`` must then read the value given: the
# registry's architecture is otherwise another block than this one.
PROGRAM = {
    "fields": ("num_layers", "d_model", "num_heads", "num_kv_heads", "d_ff", "vocab_size",
               "rope_theta", "norm_eps", "param_dtype", "compute_dtype"),
    "optional": ("window", "head_dim"),
    "requires": {"uses_attention": True, "uses_ssm": False, "uses_moe": False,
                 "local_global_ratio": 0, "tie_embeddings": False, "qkv_bias": False,
                 "mrope_sections": None, "embed_inputs": True},
}


def head_dim(m: dict) -> int:
    return m.get("head_dim") or m["d_model"] // m["num_heads"]


def attention_shapes(m: dict) -> dict:
    """One layer's attention weights and the norm before it."""
    d, hd = m["d_model"], head_dim(m)
    q, kv = m["num_heads"] * hd, m["num_kv_heads"] * hd
    return {"pre_norm": (d,), "wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d)}


def stacked(m: dict, block: dict) -> dict:
    """The whole model's shapes around one layer's: each block leaf stacked
    over the layers, the embedding, the final norm and the LM head."""
    d, V, L = m["d_model"], m["vocab_size"], m["num_layers"]
    return {"embed": (V, d), "blocks": {k: (L, *s) for k, s in block.items()},
            "final_norm": (d,), "lm_head": (d, V)}


def param_shapes(m: dict) -> dict:
    d, ff = m["d_model"], m["d_ff"]
    block = dict(attention_shapes(m), mlp_norm=(d,), w_gate=(d, ff), w_up=(d, ff),
                 w_down=(ff, d))
    return stacked(m, block)


def stack_flops(m: dict, layer_weights: int, rows: int, seq: int) -> float:
    """Forward + backward FLOPs of one step on rows x seq tokens: 6 per
    weight per token for every matmul weight a token passes through (each
    layer's ``layer_weights``, the LM head; the embedding is a gather),
    plus 12 per (layer, head dim, key) for the full score and value
    products, as the step computes them (a window masks scores, it does
    not skip them)."""
    L, nh, hd = m["num_layers"], m["num_heads"], head_dim(m)
    tokens = rows * seq
    weights = L * layer_weights + m["d_model"] * m["vocab_size"]
    return 6.0 * weights * tokens + 12.0 * L * nh * hd * seq * tokens


def attention_weights(m: dict) -> int:
    """Matmul weights of one layer's attention."""
    return sum(math.prod(s) for k, s in attention_shapes(m).items() if k != "pre_norm")


def train_flops(m: dict, rows: int, seq: int) -> float:
    return stack_flops(m, attention_weights(m) + 3 * m["d_model"] * m["d_ff"], rows, seq)


def split_sizes(shapes: dict, bounds: List[int]) -> List[int]:
    """Parameters each pipeline stage holds, every leaf counted: the
    layers between its bounds, the embedding on the first stage, the
    final norm and LM head on the last."""
    layer = sum(math.prod(s[1:]) for s in shapes["blocks"].values())
    sizes = []
    for j in range(len(bounds) - 1):
        n = (bounds[j + 1] - bounds[j]) * layer
        if j == 0:
            n += math.prod(shapes["embed"])
        if j == len(bounds) - 2:
            n += math.prod(shapes["final_norm"]) + math.prod(shapes["lm_head"])
        sizes.append(n)
    return sizes


def stage_sizes(m: dict, bounds: List[int]) -> List[int]:
    return split_sizes(param_shapes(m), bounds)


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


def act(y: jax.Array, quant: Optional[str]) -> jax.Array:
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


def attention(m: dict, p: dict, h: jax.Array, quant: Optional[str],
              window: Optional[int]) -> jax.Array:
    b, s, _ = h.shape
    nh, kvh = m["num_heads"], m["num_kv_heads"]
    hd = head_dim(m)
    q = _mm("bsd,dq->bsq", h, p["wq"], quant).reshape(b, s, nh, hd)
    k = _mm("bsd,dq->bsq", h, p["wk"], quant).reshape(b, s, kvh, hd)
    v = _mm("bsd,dq->bsq", h, p["wv"], quant).reshape(b, s, kvh, hd)
    q, k = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"])
    g = nh // kvh
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    scores = _mm("bqhd,bkhd->bhqk", q, k, quant) / jnp.sqrt(jnp.float32(hd))
    qi, ki = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    allowed = ki <= qi
    if window is not None:
        allowed &= ki > qi - window
    scores = jnp.where(allowed, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = _mm("bhqk,bkhd->bqhd", probs, v, quant).reshape(b, s, nh * hd)
    return _mm("bsq,qd->bsd", out, p["wo"], quant)


def swiglu(h: jax.Array, w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array,
           quant: Optional[str]) -> jax.Array:
    gate = _mm("bsd,df->bsf", h, w_gate, quant)
    up = _mm("bsd,df->bsf", h, w_up, quant)
    return _mm("bsf,fd->bsd", jax.nn.silu(gate) * up, w_down, quant)


def _mlp(m: dict, p: dict, h: jax.Array, quant: Optional[str]) -> jax.Array:
    return swiglu(h, p["w_gate"], p["w_up"], p["w_down"], quant)


def stack_logits(m: dict, params: dict, tokens: jax.Array, quant: Optional[str], mlp,
                 windows: Sequence[Optional[int]]) -> jax.Array:
    """The pre-norm stack with layer i's attention under ``windows[i]``
    and ``mlp(m, p, h, quant)`` as every layer's MLP."""
    eps = m["norm_eps"]
    x = act(params["embed"][tokens], quant)
    for layer in range(m["num_layers"]):
        p = jax.tree.map(lambda a: a[layer], params["blocks"])
        h = act(_rms(x, p["pre_norm"], eps), quant)
        x = act(x + attention(m, p, h, quant, windows[layer]), quant)
        x = act(x + mlp(m, p, act(_rms(x, p["mlp_norm"], eps), quant), quant), quant)
    x = act(_rms(x, params["final_norm"], eps), quant)
    return _mm("bsd,dv->bsv", x, params["lm_head"], quant)


def logits(m: dict, params: dict, tokens: jax.Array, quant: Optional[str] = None) -> jax.Array:
    return stack_logits(m, params, tokens, quant, _mlp, [m.get("window")] * m["num_layers"])


def loss(m: dict, params: dict, tokens: jax.Array, labels: jax.Array,
         quant: Optional[str] = None) -> jax.Array:
    return cross_entropy(logits(m, params, tokens, quant), labels)


def cross_entropy(z: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean token cross-entropy of logits ``z``."""
    logz = jax.nn.logsumexp(z, axis=-1)
    gold = jnp.take_along_axis(z, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)
