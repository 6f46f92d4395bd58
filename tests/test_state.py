"""The unified engine-state plane: ``repro.state``.

Property tests for the invariants the elastic runtime leans on:

(a) ``EngineState`` is tuple-compatible (legacy positional unpacking) and
    a registered keyed pytree whose metadata survives ``jax.tree.map``;
(b) merge → re-split is the identity on the whole-model view for *any*
    pair of partitions (hypothesis over the cut-point bitmask) — the
    property that makes cross-partition switches lossless;
(c) ring trees remap slot-wise: an A→B→A round-trip is bit-exact;
(d) the in-flight accounting (``pending_groups`` / ``rounds_in_flight`` /
    ``applied_updates``) is conservative against the schedule arrays;
(e) ``StateRemapper`` flushes every pending accumulation group through
    the optimizer on a schedule-restarting switch (bit-compared against a
    manual replay), and ``carry_rings=False`` drops the rings but
    *reports* the in-flight rounds it discarded;
(f) ``retime_deltas`` re-indexes Δθ history onto a new ring depth with
    newest-first alignment and zero padding.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import compensation as comp_lib
from repro.core import schedule as sched_lib
from repro.core.compensation import CompensationConfig
from repro.core.cost_model import PipelineConfig, StageKnobs, WorkerConfig
from repro.models import transformer as T
from repro.models.registry import get_config
from repro.optim.optimizers import adamw
from repro.state import (
    StateRemapper,
    applied_updates,
    pending_groups,
    remap_ring_trees,
    remap_stage_params,
    retime_deltas,
    rounds_in_flight,
)
from repro.state.engine_state import EngineState

pytestmark = pytest.mark.state

L = 4  # layers in the test model → partition bounds over [0, 4]


@functools.lru_cache(maxsize=1)
def _cfg():
    return dataclasses.replace(
        get_config("h2o-danube-1.8b", smoke=True),
        compute_dtype="float32", num_layers=L, vocab_size=32,
    )


@functools.lru_cache(maxsize=1)
def _params():
    return T.init_params(_cfg(), jax.random.PRNGKey(0))


def _bounds_from_mask(mask: int):
    """Interior cut points of [0, L] from a bitmask — every partition of
    the layer range is reachable, which is what the property quantifies
    over (bit i set → a stage boundary after layer i+1)."""
    return [0] + [i + 1 for i in range(L - 1) if (mask >> i) & 1] + [L]


def _pipe_config(P: int, workers: int = 2, accum: int = 2) -> PipelineConfig:
    return PipelineConfig(workers=[
        WorkerConfig(delay=0, stages=[StageKnobs(accum=accum) for _ in range(P)])
        for _ in range(workers)
    ])


def _tree_equal(a, b) -> bool:
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb)
    )


# ---------------------------------------------------------------------------
# (a) EngineState: tuple compatibility + pytree registration
# ---------------------------------------------------------------------------


def _dummy_state() -> EngineState:
    sp = T.split_stage_params(_cfg(), _params(), [0, 2, L])
    rings = tuple(
        jax.tree.map(lambda p: jnp.zeros((3, *p.shape), jnp.float32), s) for s in sp
    )
    return EngineState(
        stage_params=tuple(sp), rings=rings, deltas=None,
        opt_states=None, comp_states=None,
        bounds=(0, 2, L),
        geometry=sched_lib.RingGeometry(ring_size=3, delta_ring=2),
        sched_origin=7,
    )


def test_engine_state_tuple_compat():
    state = _dummy_state()
    assert len(state) == 5
    sp, rings, deltas, opts, comps = state  # 5-way unpacking
    assert sp is state.stage_params and rings is state.rings
    assert deltas is None and opts is None and comps is None
    assert state[0] is state.stage_params and state[1] is state.rings
    assert state.as_tuple() == (sp, rings, None, None, None)
    rt = EngineState.from_tuple(
        state.as_tuple(), bounds=state.bounds,
        geometry=state.geometry, sched_origin=state.sched_origin,
    )
    assert rt.bounds == state.bounds and rt.sched_origin == 7
    assert _tree_equal(rt.stage_params, state.stage_params)


def test_engine_state_is_keyed_pytree():
    state = _dummy_state()
    # identity map preserves the static metadata (it rides as aux data)
    mapped = jax.tree.map(lambda x: x * 2.0, state)
    assert isinstance(mapped, EngineState)
    assert mapped.bounds == state.bounds
    assert mapped.geometry == state.geometry
    assert mapped.sched_origin == state.sched_origin
    np.testing.assert_array_equal(
        np.asarray(jax.tree.leaves(mapped)[0]),
        2.0 * np.asarray(jax.tree.leaves(state)[0]),
    )
    # key paths name the fields (checkpoint key paths depend on this)
    paths = {
        str(path[0]) for path, _ in jax.tree_util.tree_flatten_with_path(state)[0]
    }
    assert {".stage_params", ".rings"} <= paths


# ---------------------------------------------------------------------------
# (b) merge → re-split identity over all partition pairs
# ---------------------------------------------------------------------------


@settings(max_examples=30, deadline=None)
@given(
    mask_a=st.integers(0, 2 ** (L - 1) - 1),
    mask_b=st.integers(0, 2 ** (L - 1) - 1),
)
def test_merge_resplit_identity(mask_a, mask_b):
    cfg, params = _cfg(), _params()
    bounds_a, bounds_b = _bounds_from_mask(mask_a), _bounds_from_mask(mask_b)
    sp_a = T.split_stage_params(cfg, params, bounds_a)
    sp_b = remap_stage_params(cfg, sp_a, bounds_b)
    assert len(sp_b) == len(bounds_b) - 1
    # the whole-model view is invariant under any remap
    assert _tree_equal(T.merge_stage_params(cfg, list(sp_b)), params)
    # and the round-trip restores the per-stage split bit-exactly
    assert _tree_equal(remap_stage_params(cfg, sp_b, bounds_a), sp_a)


@settings(max_examples=15, deadline=None)
@given(
    mask_b=st.integers(0, 2 ** (L - 1) - 1),
    num_slots=st.integers(1, 4),
)
def test_ring_remap_roundtrip_is_bit_exact(mask_b, num_slots):
    cfg = _cfg()
    bounds_a, bounds_b = [0, 1, 2, L], _bounds_from_mask(mask_b)
    sp_a = T.split_stage_params(cfg, _params(), bounds_a)
    rng = np.random.default_rng(num_slots)
    rings_a = tuple(
        jax.tree.map(
            lambda p: jnp.asarray(
                rng.standard_normal((num_slots, *p.shape)), jnp.float32
            ),
            sp,
        )
        for sp in sp_a
    )
    rings_b = remap_ring_trees(cfg, rings_a, bounds_b, num_slots)
    assert len(rings_b) == len(bounds_b) - 1
    rings_rt = remap_ring_trees(cfg, rings_b, bounds_a, num_slots)
    assert _tree_equal(rings_rt, rings_a)


# ---------------------------------------------------------------------------
# (d) in-flight accounting against the schedule arrays
# ---------------------------------------------------------------------------


def test_pending_groups_sync_schedule_exact():
    """The synchronous schedule makes the in-flight count closed-form:
    every stage accumulates K items then applies, so after ``upto``
    rounds exactly ``upto % K`` grads are pending."""
    K, P = 4, 2
    sched = sched_lib.build_schedule(_pipe_config(P), P, 32, sync_period=K)
    for upto in range(33):
        assert rounds_in_flight(sched, upto) == upto % K, upto


def test_pending_groups_conservation_async():
    """Per stage, every pushed backward round is either applied by a pop
    within the prefix or still pending — nothing vanishes."""
    P = 2
    config = _pipe_config(P, workers=3, accum=2)
    sched = sched_lib.build_schedule(config, P, 48)
    for upto in (0, 1, 5, 13, 24, 48):
        pending = pending_groups(sched, upto)
        for j in range(P):
            pushed = int(np.sum(sched.push_slot[:upto, j] >= 0))
            pops = [
                round(1.0 / sched.pop_scale[m, j])
                for m in range(upto) if sched.pop_slot[m, j] >= 0
            ]
            assert pushed == sum(pops) + sum(pending[j].values()), (upto, j)
    assert rounds_in_flight(sched, 0) == 0
    # full-schedule update count agrees with the schedule's own stats
    assert sum(applied_updates(sched, 48)) == sched.stats()["updates"]


# ---------------------------------------------------------------------------
# (e) StateRemapper: flush correctness + the carry_rings escape hatch
# ---------------------------------------------------------------------------


def _live_state(bounds, config, upto):
    """A mid-schedule EngineState whose ring contents are random but whose
    geometry/schedule coordinates are real."""
    cfg = _cfg()
    P = len(bounds) - 1
    sp = T.split_stage_params(cfg, _params(), bounds)
    opt = adamw(lr=1e-2)
    opts = tuple(opt.init(s) for s in sp)
    comps = tuple(
        comp_lib.init_state(s, CompensationConfig(method="iter_fisher")) for s in sp
    )
    geom = sched_lib.ring_geometry(config, P)
    rng = np.random.default_rng(upto)
    rings = tuple(
        jax.tree.map(
            lambda p: jnp.asarray(
                rng.standard_normal((geom.ring_size, *p.shape)), jnp.float32
            ),
            s,
        )
        for s in sp
    )
    deltas = tuple(
        jax.tree.map(
            lambda p: jnp.asarray(
                rng.standard_normal((geom.delta_ring, *p.shape)), jnp.float32
            ),
            s,
        )
        for s in sp
    )
    state = EngineState(
        stage_params=tuple(sp), rings=rings, deltas=deltas,
        opt_states=opts, comp_states=comps,
        bounds=tuple(bounds), geometry=geom, sched_origin=0,
    )
    return state, opt


def test_restart_switch_flushes_pending_groups():
    """A schedule-restarting remap applies every in-flight accumulation
    group through the optimizer — bit-compared against a manual replay of
    ``pending_groups`` on the old schedule prefix."""
    bounds_a, bounds_b = [0, 2, L], [0, L]
    config_a = _pipe_config(2, workers=2, accum=2)
    upto = 9
    sched = sched_lib.build_schedule(config_a, 2, 16)
    state, opt = _live_state(bounds_a, config_a, upto)
    pending = pending_groups(sched, upto)
    assert any(g for g in pending), "prefix must leave groups in flight"

    remapper = StateRemapper(_cfg(), opt)
    new_geom = sched_lib.ring_geometry(_pipe_config(1), 1)
    out, lost = remapper.remap(
        state, bounds_b, new_geometry=new_geom, same_schedule=False,
        old_schedule=sched, rounds_into_schedule=upto,
    )
    assert lost == 0
    assert out.rings is None  # nothing in flight after the flush
    assert out.sched_origin is None  # the schedule restarts

    # manual replay: apply each pending mean gradient, then merge/re-split
    sp = list(state.stage_params)
    opts = list(state.opt_states)
    for j, groups in enumerate(pending):
        for slot, count in groups.items():
            g = jax.tree.map(lambda a: a[slot] / count, state.rings[j])
            sp[j], opts[j] = opt.update(sp[j], g, opts[j])
    expect_sp = remap_stage_params(_cfg(), sp, bounds_b)
    assert _tree_equal(out.stage_params, expect_sp)
    # flushed Δθ history is carried at the *destination* ring depth
    assert out.deltas is not None
    for d in out.deltas:
        for leaf in jax.tree.leaves(d):
            assert leaf.shape[0] == new_geom.delta_ring


def test_carry_rings_false_drops_and_reports():
    bounds_a = [0, 2, L]
    config_a = _pipe_config(2, workers=2, accum=2)
    upto = 9
    sched = sched_lib.build_schedule(config_a, 2, 16)
    state, opt = _live_state(bounds_a, config_a, upto)
    remapper = StateRemapper(_cfg(), opt)
    out, lost = remapper.remap(
        state, [0, L], new_geometry=sched_lib.ring_geometry(_pipe_config(1), 1),
        same_schedule=False, old_schedule=sched, rounds_into_schedule=upto,
        carry_rings=False,
    )
    assert lost == rounds_in_flight(sched, upto) > 0
    assert out.rings is None and out.deltas is None
    # the weights were NOT flushed: pure merge/re-split of the old params
    assert _tree_equal(
        out.stage_params, remap_stage_params(_cfg(), state.stage_params, [0, L])
    )


def test_topology_shrink_remap_is_lossless():
    """The device-loss path: a replan under the survivors' topology remaps
    mid-schedule state with ``rounds_lost == 0`` on the default
    ``carry_rings`` path — in-flight accumulation groups are flushed
    through the optimizer, never dropped — while the shrunken topology
    re-keys the engine cache (distinct fingerprint)."""
    from repro.runtime.topology import DeviceTopology

    topo = DeviceTopology(device_count=4, mesh_shape=(4, 1))
    shrunk = topo.shrink(1)
    assert shrunk.mesh_shape == (3, 1)
    assert shrunk.fingerprint() != topo.fingerprint()

    bounds_a, bounds_b = [0, 2, L], [0, L]
    config_a = _pipe_config(2, workers=2, accum=2)
    upto = 9
    sched = sched_lib.build_schedule(config_a, 2, 16)
    state, opt = _live_state(bounds_a, config_a, upto)
    assert rounds_in_flight(sched, upto) > 0  # the shrink hits live state

    remapper = StateRemapper(_cfg(), opt)
    out, lost = remapper.remap(
        state, bounds_b, new_geometry=sched_lib.ring_geometry(_pipe_config(1), 1),
        same_schedule=False, old_schedule=sched, rounds_into_schedule=upto,
        carry_rings=True,
    )
    assert lost == 0
    assert out.bounds == tuple(bounds_b)


def test_same_schedule_switch_carries_rings_and_origin():
    bounds_a, bounds_b = [0, 1, L], [0, 3, L]
    config = _pipe_config(2, workers=2, accum=2)
    state, opt = _live_state(bounds_a, config, upto=5)
    remapper = StateRemapper(_cfg(), opt)
    out, lost = remapper.remap(state, bounds_b, same_schedule=True)
    assert lost == 0
    assert out.sched_origin == state.sched_origin  # schedule continues
    assert out.geometry == state.geometry
    # slot-wise lossless: remapping back restores the ring contents
    assert _tree_equal(
        remap_ring_trees(_cfg(), out.rings, bounds_a, state.geometry.ring_size),
        state.rings,
    )
    assert _tree_equal(
        remap_stage_params(_cfg(), out.stage_params, bounds_a), state.stage_params
    )


# ---------------------------------------------------------------------------
# (f) Δθ re-time-indexing
# ---------------------------------------------------------------------------


def test_retime_deltas_alignment():
    k_old, upd = 3, 5
    # fill slot u % k_old with 1+u (latest write wins), mirroring how the
    # engine writes Δθ slots round-robin; 0 marks never-written
    arr = np.zeros((k_old, 2), np.float32)
    for u in range(upd):
        arr[u % k_old] = 1 + u
    ring = {"w": jnp.asarray(arr)}

    # newest carried entry lands at slot k_new-1, older ones walk back
    shrunk = retime_deltas([ring], [upd], k_old, 2)[0]["w"]
    np.testing.assert_array_equal(np.asarray(shrunk), [[4, 4], [5, 5]])

    grown = retime_deltas([ring], [upd], k_old, 5)[0]["w"]
    np.testing.assert_array_equal(
        np.asarray(grown), [[0, 0], [0, 0], [3, 3], [4, 4], [5, 5]]
    )

    # fewer updates than slots: only written entries are carried
    one = np.zeros((k_old, 2), np.float32)
    one[0] = 1
    fresh = retime_deltas([{"w": jnp.asarray(one)}], [1], k_old, 2)[0]["w"]
    np.testing.assert_array_equal(np.asarray(fresh), [[0, 0], [1, 1]])
