"""The main path's Pallas kernels compile for a TPU v5e.

Each case lowers one Iter-Fisher kernel with ``interpret=False`` at
musicgen-medium width — the packed buffer of one full block (≈ 37.7 M fp32)
or its largest leaf, the (1536, 6144) MLP weight — and compiles it for one
chip of a *described* ``v5e:2x2`` topology. Nothing runs: the test proves
that the TPU compiler accepts the kernel (block layouts, SMEM/VMEM use) and
that the executable holds a Mosaic kernel, not an XLA fallback.

The topology is described inside a module fixture, never at import: one
process at a time may load the TPU library, and every xdist worker imports
this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import iter_fisher, packing
from repro.models import transformer as T
from repro.models.registry import get_config

LEAF = (1536, 6144)  # musicgen-medium w_up / w_gate
ALPHA = 0.9


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip are written to the persistent cache
    # but can never be read back without one: keep them out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _block_packed_len() -> int:
    cfg = get_config("musicgen-medium")
    block = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s, jnp.float32),
        T._block_param_shapes(cfg),
        is_leaf=lambda s: isinstance(s, tuple),
    )
    return packing.pack_spec(block).total


def _compile(fn, shapes, sharding) -> str:
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding) for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("tau", [1, 3])
def test_compensate_packed_compiles(one_chip, tau):
    n = _block_packed_len()
    assert 37_000_000 < n < 38_500_000, n
    text = _compile(
        lambda g, d, lam: packing.compensate_packed(g, d, lam, interpret=False),
        [(n,), (tau, n), ()], one_chip,
    )
    assert "tpu_custom_call" in text


def test_stats_packed_compiles(one_chip):
    n = _block_packed_len()
    text = _compile(
        lambda g, d, vr, va: packing.stats_packed(g, d, vr, va, ALPHA, interpret=False),
        [(n,)] * 4, one_chip,
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("tau", [1, 3])
def test_iter_fisher_compensate_leaf_compiles(one_chip, tau):
    text = _compile(
        lambda g, d, lam: iter_fisher.iter_fisher_compensate_pallas(g, d, lam, interpret=False),
        [LEAF, (tau, *LEAF), ()], one_chip,
    )
    assert "tpu_custom_call" in text


def test_iter_fisher_leaf_stats_compiles(one_chip):
    text = _compile(
        lambda g, d, vr, va: iter_fisher.iter_fisher_leaf_stats_pallas(
            g, d, vr, va, ALPHA, interpret=False
        ),
        [LEAF] * 4, one_chip,
    )
    assert "tpu_custom_call" in text


@pytest.fixture(scope="module")
def four_chip_mesh(one_chip):
    """A (data=4, model=1) mesh over the described chips: the layout of
    the data-parallel engine, whose carry — and so every kernel operand —
    is replicated."""
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    return Mesh(np.array(topo.devices[:4]).reshape(4, 1), ("data", "model"))


@pytest.mark.parametrize("kernel", ["compensate", "stats"])
def test_kernels_compile_under_a_four_chip_mesh(four_chip_mesh, kernel):
    """Mosaic kernels are not partitioned automatically: under a mesh each
    device must run its own copy (``iter_fisher._per_device``)."""
    from jax.sharding import NamedSharding, PartitionSpec

    rep = NamedSharding(four_chip_mesh, PartitionSpec())
    if kernel == "compensate":
        fn = lambda g, d, lam: iter_fisher.iter_fisher_compensate_pallas(  # noqa: E731
            g, d, lam, interpret=False)
        shapes = [LEAF, (3, *LEAF), ()]
    else:
        fn = lambda g, d, vr, va: iter_fisher.iter_fisher_leaf_stats_pallas(  # noqa: E731
            g, d, vr, va, ALPHA, interpret=False)
        shapes = [LEAF] * 4
    with jax.set_mesh(four_chip_mesh):
        text = _compile(fn, shapes, rep)
    assert "tpu_custom_call" in text
