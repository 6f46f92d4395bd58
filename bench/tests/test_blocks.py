"""The block contract of the references (``bench/references/decoder.py``
says what a reference states): musicgen-medium reads what it read before
the harness asked its reference, the routed block's weights are the
program's, and the program's configuration is the block its reference
implements (CPU, seconds)."""

from __future__ import annotations

import dataclasses
import hashlib
import json

import jax
import numpy as np
import pytest

import costs
import drive
import spec as spec_lib
from conftest import BENCH, ROOT, ROUTED_CONFIG, ROUTED_MODEL, load_block

MUSICGEN = json.loads((BENCH / "configs" / "musicgen-medium.json").read_text())
# the weights of musicgen-medium from seed 1, leaf by leaf in tree order, as
# the harness made them when its decoder shapes were its own (CPU)
MUSICGEN_TREE = [
    ("['blocks']['mlp_norm']", (2, 1536)), ("['blocks']['pre_norm']", (2, 1536)),
    ("['blocks']['w_down']", (2, 6144, 1536)), ("['blocks']['w_gate']", (2, 1536, 6144)),
    ("['blocks']['w_up']", (2, 1536, 6144)), ("['blocks']['wk']", (2, 1536, 1536)),
    ("['blocks']['wo']", (2, 1536, 1536)), ("['blocks']['wq']", (2, 1536, 1536)),
    ("['blocks']['wv']", (2, 1536, 1536)), ("['embed']", (2048, 1536)),
    ("['final_norm']", (1536,)), ("['lm_head']", (1536, 2048))]
MUSICGEN_SHA256 = "ada6a6b82240777801e6b803fc11c9c520c616d8def9f847f4e0e926d88352c4"


@pytest.fixture(scope="module")
def decoder():
    return spec_lib.Spec(ROOT, BENCH).reference("decoder")


@pytest.fixture(scope="module")
def routed():
    return load_block("routed")


def test_musicgen_weights_from_the_seed(decoder):
    params = drive.make_params(decoder.param_shapes(MUSICGEN["model"]), 1)
    flat, _ = jax.tree.flatten_with_path(params)
    assert [(jax.tree_util.keystr(k), a.shape) for k, a in flat] == MUSICGEN_TREE
    digest = hashlib.sha256()
    for _, a in flat:
        digest.update(np.asarray(a).tobytes())
    assert digest.hexdigest() == MUSICGEN_SHA256


def test_musicgen_counts(decoder):
    """What step_mfu and iter_fisher_roofline read on musicgen-medium.stream:
    the FLOPs of a round of 12 rows x 512, the parameters of its two
    stages, and the Iter-Fisher bytes of a round."""
    m = MUSICGEN["model"]
    assert decoder.train_flops(m, 12, 512) == 3_015_067_041_792
    sizes = decoder.stage_sizes(m, [0, 1, 2])
    assert sizes == [40_897_536, 40_899_072]
    assert costs.iter_fisher_bytes_per_round(sizes) == 3_271_864_320


def test_routed_flops_count_the_routed_experts(routed):
    """64 experts held, 8 routed: beside 8 of 64 only the router's width
    counts the experts a token is not routed to."""
    few = dict(ROUTED_MODEL, num_experts=8, experts_per_token=8, moe_capacity_factor=1.0)
    many = dict(few, num_experts=64, moe_capacity_factor=8.0)
    extra = 6.0 * 12 * 512 * ROUTED_MODEL["num_layers"] * ROUTED_MODEL["d_model"] * 56
    assert routed.train_flops(many, 12, 512) == routed.train_flops(few, 12, 512) + extra
    shapes = routed.param_shapes(many)
    assert sum(routed.stage_sizes(many, [0, 1, 4])) == sum(
        int(np.prod(s)) for s in jax.tree.leaves(shapes, is_leaf=lambda s: isinstance(s, tuple)))


@pytest.mark.parametrize("block", ["decoder", "routed"])
def test_shapes_are_the_programs(decoder, routed, block):
    """The weights the harness makes are the program's tree, leaf for leaf."""
    from repro.models import transformer as T

    ref, config = (decoder, MUSICGEN) if block == "decoder" else (routed, ROUTED_CONFIG)
    pcfg = drive.program_model_config(config, ref)
    program = jax.eval_shape(lambda: T.init_params(pcfg, jax.random.key(0)))
    ours = ref.param_shapes(config["model"])
    assert jax.tree.structure(program) == jax.tree.structure(
        ours, is_leaf=lambda s: isinstance(s, tuple))
    assert [a.shape for a in jax.tree.leaves(program)] == jax.tree.leaves(
        ours, is_leaf=lambda s: isinstance(s, tuple))
    assert {a.dtype for a in jax.tree.leaves(program)} == {np.dtype("float32")}


def test_program_model_config_is_the_reference_block(decoder, routed):
    from repro.models.registry import get_config

    m = MUSICGEN["model"]
    # as the harness set it when it knew only the decoder
    assert drive.program_model_config(MUSICGEN, decoder) == dataclasses.replace(
        get_config("musicgen-medium"), num_layers=2, d_model=1536, num_heads=24,
        num_kv_heads=24, d_ff=6144, vocab_size=2048, window=None, rope_theta=10000.0,
        norm_eps=1e-6, head_dim=None, param_dtype="float32", compute_dtype="bfloat16")
    pcfg = drive.program_model_config(ROUTED_CONFIG, routed)
    assert (pcfg.resolved_head_dim, pcfg.layer_kinds(), pcfg.num_experts,
            pcfg.experts_per_token, pcfg.window_for_kind(1), pcfg.window_for_kind(0)) == \
        (32, (1, 1, 1, 0), 4, 2, 8, None)
    assert pcfg.param_dtype == m["param_dtype"] and pcfg.d_model == 64


@pytest.mark.parametrize("config, ref, why", [
    (ROUTED_CONFIG, "decoder", "implements no"),  # fields the decoder has not
    (dict(ROUTED_CONFIG, reference="decoder", model=MUSICGEN["model"]), "decoder",
     "is not the block"),  # the registry's experts, which the file does not set
    (dict(ROUTED_CONFIG, registry_name="mamba2-780m"), "routed", "is not the block"),
    (dict(ROUTED_CONFIG, registry_name="hymba-1.5b"), "routed", "is not the block"),
])
def test_program_model_config_refuses_another_block(decoder, routed, config, ref, why):
    with pytest.raises(ValueError, match=why):
        drive.program_model_config(config, {"decoder": decoder, "routed": routed}[ref])
