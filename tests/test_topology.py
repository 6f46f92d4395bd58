"""DeviceTopology plane: discovery, budgets, meshes, sharded-engine parity.

In-process tests are device-count agnostic — they pass whether the host
exposes 1 device (bare ``pytest``) or 8 (``scripts/test.sh`` and the CI
multidevice job). Anything that *needs* a guaranteed multi-device world
(sharded parity vs single-device, shrink-on-device-loss) runs in a
subprocess that sets ``XLA_FLAGS`` before jax init — the
``test_stage_parallel.py`` idiom.
"""

import dataclasses
import json
import math
import os
import subprocess
import sys
import textwrap

import pytest
from jax.sharding import PartitionSpec as P

from repro.core.compensation import CompensationConfig
from repro.core.ferret import FerretConfig
from repro.core.planner import default_data_interval, plan
from repro.core.profiler import analytic_profile
from repro.core.stage_parallel import mesh_for_topology
from repro.launch.mesh import make_production_mesh
from repro.launch.shardings import stream_batch_pspec
from repro.models.registry import get_config
from repro.models.shard_hints import ShardHints
from repro.models.shard_hints import for_topology as hints_for_topology
from repro.profile.bridge import for_topology
from repro.runtime import ElasticStreamTrainer
from repro.runtime.topology import DeviceTopology, as_topology


def _cfg():
    return dataclasses.replace(
        get_config("h2o-danube-1.8b", smoke=True),
        compute_dtype="float32", num_layers=4, vocab_size=32,
    )


def _ferret_cfg():
    return FerretConfig(
        budget_bytes=math.inf, lr=5e-3,
        compensation=CompensationConfig(method="iter_fisher", eta_lambda=1e-4),
        max_workers=3, max_stages=4,
    )


# ---------------------------------------------------------------------------
# DeviceTopology units (no jax device state needed beyond what's visible)
# ---------------------------------------------------------------------------


def test_trivial_and_validation():
    t = DeviceTopology.trivial()
    assert t.is_trivial and t.data_parallel == 1 and t.model_parallel == 1
    assert t.is_main()
    with pytest.raises(ValueError):
        DeviceTopology(device_count=4, mesh_shape=(2, 1))


def test_discover_reads_the_jax_world():
    import jax

    n = len(jax.devices())
    t = DeviceTopology.discover()
    assert t.device_count == n and t.mesh_shape == (n, 1)
    assert t.device_kind == str(jax.devices()[0].device_kind)
    assert t.process_count == 1 and t.is_main()
    one = DeviceTopology.discover(max_devices=1)
    assert one.is_trivial
    with pytest.raises(ValueError):
        DeviceTopology.discover(model_axis=n + 1)


@dataclasses.dataclass
class _FakeDevice:
    platform: str
    device_kind: str
    stats: dict = dataclasses.field(default_factory=dict)

    def memory_stats(self):
        return self.stats


@pytest.mark.parametrize("device, want", [
    (_FakeDevice("tpu", "TPU v5 lite", {"bytes_limit": 123 * 2**20}), 123 * 2**20),
    (_FakeDevice("tpu", "TPU v5 lite"), RuntimeError),
    (_FakeDevice("cpu", "cpu"), 16 * 2**30),
])
def test_device_memory_is_reported_never_guessed_on_tpu(device, want, monkeypatch):
    from repro.runtime.topology import _device_memory

    monkeypatch.delenv("REPRO_DEVICE_MEM_BYTES", raising=False)
    if want is RuntimeError:
        with pytest.raises(RuntimeError, match="bytes_limit"):
            _device_memory(device, None)
    else:
        assert _device_memory(device, None) == want
    assert _device_memory(device, 7) == 7


@pytest.mark.parametrize("device, want", [
    (_FakeDevice("tpu", "TPU v5 lite"), "TPU v5 lite"),
    (_FakeDevice("cpu", "cpu"), "TPU v5 lite"),
    (_FakeDevice("tpu", "TPU v9 imaginary"), ValueError),
])
def test_chip_spec_is_keyed_by_device_kind(device, want):
    from repro.core.chips import chip_spec

    if want is ValueError:
        with pytest.raises(ValueError, match="TPU v9 imaginary"):
            chip_spec(device)
    else:
        assert chip_spec(device).device_kind == want


def test_shrink_keeps_model_axis_only_when_divisible():
    t = DeviceTopology(device_count=8, mesh_shape=(4, 2))
    assert t.shrink(2).mesh_shape == (3, 2)  # 6 % 2 == 0: model axis survives
    assert t.shrink(1).mesh_shape == (7, 1)  # 7 % 2 != 0: collapses to data
    assert t.shrink(7).mesh_shape == (1, 1)
    with pytest.raises(ValueError):
        t.shrink(8)


def test_plan_budget_scales_with_model_axis_not_data():
    mem = 100
    tp = DeviceTopology(device_count=8, mesh_shape=(4, 2), memory_per_device=mem)
    dp = DeviceTopology(device_count=8, mesh_shape=(8, 1), memory_per_device=mem)
    assert tp.plan_budget(memory_fraction=0.5) == 0.5 * mem * 2
    # data-parallel replicas hold the full footprint: no extra budget
    assert dp.plan_budget(memory_fraction=1.0) == mem
    assert dp.total_memory_bytes == 8 * mem


def test_fingerprint_and_describe_are_stable():
    t = DeviceTopology(device_count=8, mesh_shape=(4, 2))
    assert t.fingerprint() == ("topo", 8, "cpu", 1, (4, 2))
    assert t.fingerprint() == dataclasses.replace(t).fingerprint()
    d = t.describe()
    assert d["device_count"] == 8 and d["mesh_shape"] == [4, 2]
    json.dumps(d)  # JSON-ready for bench payloads / manifests


def test_as_topology_normalization():
    t = DeviceTopology.trivial()
    assert as_topology(None) is None
    assert as_topology(t) is t
    assert isinstance(as_topology("discover"), DeviceTopology)
    with pytest.raises(TypeError):
        as_topology(42)


def test_memory_per_device_overrides(monkeypatch):
    monkeypatch.setenv("REPRO_DEVICE_MEM_BYTES", "12345")
    assert DeviceTopology.discover().memory_per_device == 12345
    # explicit argument beats the env
    assert DeviceTopology.discover(memory_per_device=777).memory_per_device == 777


# ---------------------------------------------------------------------------
# mesh construction
# ---------------------------------------------------------------------------


def test_make_production_mesh_derives_from_topology():
    import jax

    mesh = make_production_mesh()
    assert mesh.devices.size == len(jax.devices())
    assert mesh.axis_names == ("data", "model")
    one = make_production_mesh(DeviceTopology.discover(max_devices=1))
    assert one.devices.size == 1


def test_make_production_mesh_preset_errors_clearly():
    import jax

    if len(jax.devices()) >= 256:  # pragma: no cover — not a CI shape
        pytest.skip("host actually has a pod's worth of devices")
    with pytest.raises(ValueError, match="256 devices"):
        make_production_mesh(preset="pod")
    with pytest.raises(ValueError, match="512 devices"):
        make_production_mesh(multi_pod=True)
    with pytest.raises(ValueError, match="unknown mesh preset"):
        make_production_mesh(preset="nope")


def test_mesh_for_topology_requires_matching_stage_axis():
    t = DeviceTopology(device_count=4, mesh_shape=(2, 2))
    with pytest.raises(ValueError, match="model_axis"):
        mesh_for_topology(t, num_stages=4)


# ---------------------------------------------------------------------------
# planner / profile / sharding integration
# ---------------------------------------------------------------------------


def test_plan_caps_budget_and_stamps_topology_fingerprint():
    cfg = _cfg()
    profile = analytic_profile(cfg, 2, 16)
    t_d = default_data_interval(profile)
    topo = DeviceTopology(
        device_count=2, mesh_shape=(2, 1), memory_per_device=64 * 2**20
    )
    p = plan(profile, t_d, budget=math.inf, max_workers=3, topology=topo)
    assert p.topology == topo.fingerprint()
    assert p.memory <= topo.plan_budget() * (1 + 1e-9)
    legacy = plan(profile, t_d, budget=math.inf, max_workers=3)
    assert legacy.topology is None


def test_profile_for_topology_scales_time_not_weights():
    cfg = _cfg()
    prof = analytic_profile(cfg, 2, 16)
    topo = DeviceTopology(device_count=4, mesh_shape=(4, 1))
    eff = for_topology(prof, topo)
    for raw, scaled in zip(prof.layers, eff.layers):
        assert scaled.t_fwd == pytest.approx(raw.t_fwd / 4)
        assert scaled.t_bwd == pytest.approx(raw.t_bwd / 4)
        assert scaled.a_bytes == raw.a_bytes // 4
        # weights replicate across data-parallel devices: bytes unchanged
        assert scaled.w_bytes == raw.w_bytes
    assert eff.embed_bytes == prof.embed_bytes
    # no topology / no data axis: the exact same object, no rescale
    assert for_topology(prof, None) is prof
    assert for_topology(prof, DeviceTopology.trivial()) is prof


def test_stream_batch_pspec_shards_batch_dim_when_divisible():
    axes = {"data": 2, "model": 1}
    assert stream_batch_pspec((40,), axes) == P()  # rank<2: replicate
    assert stream_batch_pspec((40, 4, 16), axes) == P(None, "data", None)
    # indivisible batch: replicate rather than crash
    assert stream_batch_pspec((40, 3, 16), axes) == P(None, None, None)


def test_shard_hints_for_topology():
    assert hints_for_topology(None) == ShardHints()
    assert hints_for_topology(DeviceTopology.trivial()) == ShardHints()
    h = hints_for_topology(DeviceTopology(device_count=2, mesh_shape=(2, 1)))
    assert h.logits == P("data", None, None)
    assert h.activations == P("data", None, None)


def test_trainer_cache_scope_gains_topology_fingerprint():
    cfg, fc = _cfg(), _ferret_cfg()
    legacy = ElasticStreamTrainer(cfg, fc, batch=2, seq=16)
    topo = ElasticStreamTrainer(
        cfg, fc, batch=2, seq=16, topology=DeviceTopology.trivial()
    )
    # legacy trainers keep byte-identical cache keys (serve-layer sharing);
    # topology-aware trainers append the fingerprint so a shrink re-keys
    assert topo._cache_scope[:-1] == legacy._cache_scope
    assert topo._cache_scope[-1] == DeviceTopology.trivial().fingerprint()
    with pytest.raises(RuntimeError, match="request_budget"):
        legacy.request_shrink()


# ---------------------------------------------------------------------------
# sharded-engine parity (subprocess: guaranteed 8 fake devices)
# ---------------------------------------------------------------------------

PARITY_CODE = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses, json, math
    import jax, numpy as np
    from repro.core.compensation import CompensationConfig
    from repro.core.ferret import FerretConfig, FerretTrainer
    from repro.models import transformer as T
    from repro.models.registry import get_config
    from repro.ocl.streams import StreamConfig, make_stream
    from repro.runtime.topology import DeviceTopology

    cfg = dataclasses.replace(get_config("h2o-danube-1.8b", smoke=True),
                              compute_dtype="float32", num_layers=4, vocab_size=32)
    fc = FerretConfig(budget_bytes=math.inf, lr=5e-3,
                      compensation=CompensationConfig(method="iter_fisher",
                                                      eta_lambda=1e-4),
                      max_workers=3, max_stages=4)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    stream = make_stream(StreamConfig(kind="drift", modality="tokens",
                                      length=16, batch=4, vocab=32, seq=16))

    base = FerretTrainer(cfg, fc, batch=4, seq=16).run_stream(params, stream)

    # a trivial topology degenerates to the legacy path: bit-identical
    triv = FerretTrainer(cfg, fc, batch=4, seq=16,
                         topology=DeviceTopology.trivial()
                         ).run_stream(params, stream)
    np.testing.assert_array_equal(np.asarray(base.losses),
                                  np.asarray(triv.losses))

    # 4-way data-parallel over the fake devices: same math, different
    # reduction geometry -> numerical tolerance, not bit-exactness
    topo = DeviceTopology.discover(max_devices=4)
    assert topo.mesh_shape == (4, 1), topo
    shard = FerretTrainer(cfg, fc, batch=4, seq=16,
                          topology=topo).run_stream(params, stream)
    np.testing.assert_allclose(np.asarray(base.losses),
                               np.asarray(shard.losses),
                               rtol=1e-5, atol=1e-6)
    assert shard.online_acc == base.online_acc
    print(json.dumps({"ok": True}))
    """
)


def _run_sub(code: str) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)  # the subprocess pins its own device count
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=600, cwd=root, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_sharded_engine_matches_single_device():
    assert _run_sub(PARITY_CODE)["ok"]
