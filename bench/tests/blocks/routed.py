"""Plain reference of a decoder with routed experts and windowed and full
attention layers, in jax.numpy.

The block of ``references/decoder.py`` (norms, RoPE, grouped attention,
the control's rounding, the parameter tree), with two changes:

- layers come in periods of ``local_global_ratio + 1``: the last layer of
  a period attends in full (under ``window`` when one is set), the others
  under ``local_window``;
- the MLP is routed: p = softmax(h W_router) over ``num_experts``
  (float32); the ``experts_per_token`` largest, renormalised to sum to 1,
  weight their experts' SwiGLU outputs, and the other experts add
  nothing. Every routed token is computed, so the configuration's
  capacity factor must let the program drop none. The router's
  load-balancing loss is not part of the training loss.

The head size is the configuration's ``head_dim``. Nothing here imports
the system under test.
"""

from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp

from references import decoder as dense

ROUTED = ("num_experts", "experts_per_token", "moe_capacity_factor", "local_global_ratio",
          "local_window")
PROGRAM = {
    "fields": dense.PROGRAM["fields"] + ROUTED,
    "optional": dense.PROGRAM["optional"],
    "requires": {"uses_attention": True, "uses_ssm": False, "uses_moe": True,
                 "tie_embeddings": False, "qkv_bias": False, "mrope_sections": None,
                 "embed_inputs": True},
}


def param_shapes(m: dict) -> dict:
    d, ff, E = m["d_model"], m["d_ff"], m["num_experts"]
    block = dict(dense.attention_shapes(m), mlp_norm=(d,), router=(d, E), we_gate=(E, d, ff),
                 we_up=(E, d, ff), we_down=(E, ff, d))
    return dense.stacked(m, block)


def train_flops(m: dict, rows: int, seq: int) -> float:
    """The router and the routed experts of each token, not all experts."""
    d, ff = m["d_model"], m["d_ff"]
    routed = d * m["num_experts"] + m["experts_per_token"] * 3 * d * ff
    return dense.stack_flops(m, dense.attention_weights(m) + routed, rows, seq)


def stage_sizes(m: dict, bounds: List[int]) -> List[int]:
    return dense.split_sizes(param_shapes(m), bounds)


def windows(m: dict) -> List[Optional[int]]:
    r = m["local_global_ratio"]
    return [m.get("window") if i % (r + 1) == r else m["local_window"]
            for i in range(m["num_layers"])]


def _routed_mlp(m: dict, p: dict, h: jax.Array, quant: Optional[str]) -> jax.Array:
    E, k = m["num_experts"], m["experts_per_token"]
    probs = jax.nn.softmax(
        jnp.einsum("bsd,de->bse", h, p["router"], precision=jax.lax.Precision.HIGHEST), axis=-1)
    top_w, top_ids = jax.lax.top_k(probs, k)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    gates = jnp.sum(jax.nn.one_hot(top_ids, E) * top_w[..., None], axis=-2)  # (b, s, E)
    y = sum(gates[..., e, None] * dense.swiglu(h, p["we_gate"][e], p["we_up"][e],
                                                p["we_down"][e], quant)
            for e in range(E))
    return dense.act(y, quant)


def logits(m: dict, params: dict, tokens: jax.Array, quant: Optional[str] = None) -> jax.Array:
    if m["moe_capacity_factor"] * m["experts_per_token"] < m["num_experts"]:
        raise ValueError("the capacity factor lets the program drop tokens; this block drops none")
    return dense.stack_logits(m, params, tokens, quant, _routed_mlp, windows(m))


def loss(m: dict, params: dict, tokens: jax.Array, labels: jax.Array,
         quant: Optional[str] = None) -> jax.Array:
    return dense.cross_entropy(logits(m, params, tokens, quant), labels)
