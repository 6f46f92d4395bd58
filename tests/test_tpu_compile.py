"""The main path's Pallas kernels compile for a TPU v5e.

Each case lowers one Iter-Fisher kernel with ``interpret=False`` at
musicgen-medium width — the packed buffer of one full block (≈ 37.7 M fp32)
or a leaf in its own layout, or the Iter-Fisher step over one stage — and
compiles it for one chip of a *described* ``v5e:2x2`` topology. Nothing runs: the test proves
that the TPU compiler accepts the kernel (block layouts, SMEM/VMEM use) and
that the executable holds a Mosaic kernel, not an XLA fallback.

The topology is described inside a module fixture, never at import: one
process at a time may load the TPU library, and every xdist worker imports
this file.
"""

import dataclasses
import math
import os
import re

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


@pytest.mark.parametrize("kernel", ["compensate", "stats"])
def test_kernels_carry_their_names_for_the_chip(one_chip, kernel):
    """Each kernel's name rides its compiled op (``kernel_metadata``),
    which is the op's text in the profiler's trace."""
    if kernel == "compensate":
        fn = lambda g, d, lam: iter_fisher.iter_fisher_compensate_pallas(  # noqa: E731
            g, d, lam, interpret=False)
        shapes = [LEAF, (1, *LEAF), ()]
    else:
        fn = lambda g, d, vr, va: iter_fisher.iter_fisher_leaf_stats_pallas(  # noqa: E731
            g, d, vr, va, ALPHA, interpret=False)
        shapes = [LEAF] * 4
    text = _compile(fn, shapes, one_chip)
    assert re.search(r'kernel_metadata=\{\s*"kernel":"iter_fisher_%s"' % kernel, text)


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


# ---------------------------------------------------------------------------
# per-leaf kernels in each leaf's own layout
# ---------------------------------------------------------------------------

# musicgen-medium leaves as a stage holds them (one layer, stacked): an MLP
# weight, the embedding, a norm scale
STAGE_LEAVES = [(1, 1536, 6144), (2048, 1536), (1, 1536)]
K = 2  # the Δθ history of a P = 2 pipeline
COPIES = re.compile(r"= f32\[([\d,]+)\][^=]* (pad|slice|dynamic-update-slice)\(")


def _param_sized_copies(text: str, size: int) -> list:
    """pad / slice / dynamic-update-slice ops with an output of at least
    ``size`` elements: the relayout copies of a packed path."""
    out = []
    for dims, op in COPIES.findall(text):
        n = 1
        for d in dims.split(","):
            n *= int(d)
        if n >= size:
            out.append((op, dims))
    return out


@pytest.mark.parametrize("kernel", ["compensate", "stats"])
@pytest.mark.parametrize("leaf", STAGE_LEAVES)
def test_leaf_kernel_compiles_in_the_leafs_layout(one_chip, leaf, kernel):
    """One Mosaic kernel on the leaf as it is, results in its shape, and
    no pad or slice around it."""
    if kernel == "compensate":
        fn = lambda g, d, lam: iter_fisher.iter_fisher_compensate_pallas(  # noqa: E731
            g, d, lam, interpret=False)
        shapes = [leaf, (K, *leaf), ()]
    else:
        fn = lambda g, d, vr, va: iter_fisher.iter_fisher_leaf_stats_pallas(  # noqa: E731
            g, d, vr, va, ALPHA, interpret=False, row=-1)
        shapes = [leaf, (K, *leaf), leaf, leaf]
    text = _compile(fn, shapes, one_chip)
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert _param_sized_copies(text, min(1536, math.prod(leaf))) == []


def test_compensate_over_a_stage_runs_one_kernel_per_leaf(one_chip, monkeypatch):
    """``compensation.compensate`` (Iter-Fisher with λ tuning) over one
    musicgen-medium stage, compiled as the engine runs it on a TPU: one
    statistics and one compensation kernel per leaf, and no pad, slice or
    dynamic-update-slice of a parameter-sized array (no packing)."""
    from repro.core import compensation as comp
    from repro.kernels import ops

    monkeypatch.setattr(ops, "_backend", lambda: "tpu")
    monkeypatch.delenv("REPRO_PACK", raising=False)
    monkeypatch.delenv("REPRO_USE_PALLAS", raising=False)
    cfg = dataclasses.replace(get_config("musicgen-medium"), num_layers=2)
    params = jax.eval_shape(lambda: T.init_params(cfg, jax.random.PRNGKey(0)))
    stage = jax.eval_shape(lambda p: T.split_stage_params(cfg, p, [0, 1, 2])[0], params)
    ccfg = comp.CompensationConfig(method="iter_fisher", eta_lambda=1e-3)
    state = jax.eval_shape(lambda s: comp.init_state(s, ccfg), stage)
    deltas = jax.tree.map(lambda p: jax.ShapeDtypeStruct((K, *p.shape), p.dtype), stage)

    def placed(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), tree)

    def step(state, grad, deltas):
        return comp.compensate(ccfg, state, grad, deltas)

    text = jax.jit(step, donate_argnums=0).lower(
        placed(state), placed(stage), placed(deltas)).compile().as_text()
    leaves = jax.tree.leaves(stage)
    assert len(leaves) == 10
    assert text.count('custom_call_target="tpu_custom_call"') == 2 * len(leaves)
    for name in ("iter_fisher_stats", "iter_fisher_compensate"):
        assert len(re.findall(r"%%%s(?:\.\d+)? = " % name, text)) == len(leaves)
    assert _param_sized_copies(text, 1536 * 1536) == []
