"""The yardstick's FLOP and byte counts against XLA's own cost analysis of
programs compiled for a described TPU v5e (no chip needed).

The topology is described inside a fixture, never at import: one process
at a time may load the TPU library."""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import costs
import spec as spec_lib
from conftest import BENCH, ROOT, WIDE_ROUTED_MODEL, load_block


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    jax.config.update("jax_enable_compilation_cache", False)
    return SingleDeviceSharding(topo.devices[0])


def _struct(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _block(name: str):
    """(reference, model at one layer) of each block the benchmark can
    state: the decoder at its configurations' widths, and the routed block
    of ``blocks/routed.py`` at MoE widths with every expert routed, the one
    size at which its reference (which computes every expert and weights
    the unrouted ones 0) does the routed work alone."""
    spec = spec_lib.Spec(ROOT, BENCH)
    if name == "routed":
        return load_block("routed"), dict(WIDE_ROUTED_MODEL, num_layers=1, num_experts=8,
                                          experts_per_token=8)
    model = json.loads((BENCH / "configs" / f"{name}.json").read_text())["model"]
    return spec.reference("decoder"), dict(model, num_layers=1)


@pytest.mark.parametrize("block", ["musicgen-medium", "h2o-danube-1.8b", "routed"])
def test_train_flops_match_xla(one_chip, block):
    """One layer, 2 rows x 512 tokens, forward and backward of the block's
    plain reference in bf16, against its ``train_flops``."""
    ref, model = _block(block)
    shapes = ref.param_shapes(model)
    params = jax.tree.map(lambda s: _struct(s, jnp.bfloat16, one_chip), shapes,
                          is_leaf=lambda s: isinstance(s, tuple))
    toks = _struct((2, 512), jnp.int32, one_chip)

    def loss(p, t, l):
        with jax.default_matmul_precision("default"):
            return ref.loss(model, p, t, l)

    compiled = jax.jit(jax.value_and_grad(loss)).lower(params, toks, toks).compile()
    xla = compiled.cost_analysis()["flops"]
    ours = ref.train_flops(model, 2, 512)
    print(f"{block}: ours {ours:.4e} FLOP, XLA {xla:.4e}")
    assert abs(xla / ours - 1.0) < 0.05


def test_iter_fisher_bytes_match_xla(one_chip):
    """The two kernels' math as jnp over 4M parameters, K = 2 Δθ rows given
    as K arrays, so that XLA fuses each kernel's math into one pass."""
    n, K, alpha = 1 << 22, 2, 0.9
    vec = _struct((n,), jnp.float32, one_chip)
    lam = _struct((), jnp.float32, one_chip)

    def comp(g, lam, *d):
        for d_i in d:
            g = g + lam * g * g * d_i
        return g

    def stats(g, d, vr, va):
        s1 = jnp.sum((1 - alpha) * (g - vr) * va)
        s2 = jnp.sum(va * va)
        return alpha * vr + (1 - alpha) * g, alpha * va + (1 - alpha) * g * g * d, s1, s2

    def xla_bytes(f, *args):
        return jax.jit(f).lower(*args).compile().cost_analysis()["bytes accessed"]

    comp_xla = xla_bytes(comp, vec, lam, *([vec] * K))
    stats_xla = xla_bytes(stats, vec, vec, vec, vec)
    comp_ours = 4.0 * n * (K + 2)
    stats_ours = costs.iter_fisher_bytes(n, K) - comp_ours
    print(f"compensation bytes: ours {comp_ours:.4e}, XLA {comp_xla:.4e}; "
          f"statistics: ours {stats_ours:.4e}, XLA {stats_xla:.4e}")
    assert abs(comp_xla / comp_ours - 1.0) < 0.01
    assert abs(stats_xla / stats_ours - 1.0) < 0.01
