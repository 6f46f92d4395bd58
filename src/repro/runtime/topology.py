"""Discovered device topology: the one description of the hardware world.

Every layer that used to pretend the world is one device — the mesh
builder (``launch/mesh.py``), the sharding rules (``launch/shardings.py``),
the engine's jitted scan (``core/pipeline.py``), the planner budget
(``core/planner.py`` / ``runtime/elastic.py``) and the elastic trainer's
device-loss handling — now consumes a ``DeviceTopology``:

- **discovery**: ``DeviceTopology.discover()`` reads ``jax.devices()`` /
  ``jax.process_index()`` once and freezes the result (device count and
  kind, process count/index, a ``(data, model)`` mesh shape, per-device
  memory). Nothing here touches jax at *import* time — the dry-run sets
  ``XLA_FLAGS`` before first jax init and only then discovers.
- **planning**: ``plan_budget()`` is the per-device memory bound the
  planner uses instead of a scalar cluster total — data-parallel replicas
  do not add budget (each device holds the full pipeline footprint); only
  the model axis spans devices.
- **elasticity**: ``shrink(lost_devices)`` is the topology-shrink event a
  ``DeviceLossError`` escalates into — a new topology over the surviving
  devices, which the elastic trainer re-plans and re-meshes around.
- **multi-host**: ``is_main()`` is the HomebrewNLP/olmax gating idiom —
  exactly one process writes checkpoints/benchmarks; all processes
  participate in collectives.

A topology of size 1 (``is_trivial``) degenerates to the historical
single-device path everywhere: no mesh is built, no array is re-placed,
and results are bit-identical to a run that never heard of topologies.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional, Tuple

import numpy as np

from repro.core.chips import TPU_V5E

# Per-device memory of a non-TPU device, which reports none (CPU fake
# devices): the v5e row of repro.core.chips. Override with
# REPRO_DEVICE_MEM_BYTES or the memory_per_device= argument.
DEFAULT_MEMORY_PER_DEVICE = TPU_V5E.hbm_bytes

# Fraction of per-device memory handed to the planner (headroom for XLA
# scratch, collectives buffers, host transfers) — matches the historical
# ElasticPlanner.memory_fraction default.
DEFAULT_MEMORY_FRACTION = 0.9


def _device_memory(device, override: Optional[int]) -> int:
    if override is not None:
        return int(override)
    env = os.environ.get("REPRO_DEVICE_MEM_BYTES", "").strip()
    if env:
        return int(env)
    if device.platform != "tpu":
        return DEFAULT_MEMORY_PER_DEVICE
    limit = (device.memory_stats() or {}).get("bytes_limit")
    if not limit:
        raise RuntimeError(
            f"{device.device_kind} reports no memory_stats()['bytes_limit']; "
            "pass memory_per_device= or set REPRO_DEVICE_MEM_BYTES"
        )
    return int(limit)


@dataclasses.dataclass(frozen=True)
class DeviceTopology:
    """A frozen description of the devices a run executes on.

    ``mesh_shape`` is ``(data, model)``: the data axis shards the batch
    (pure replication of weights), the model axis is the spatial pipeline /
    tensor axis (``core/stage_parallel.py``). ``data * model`` must equal
    ``device_count``.
    """

    device_count: int
    device_kind: str = "cpu"
    process_count: int = 1
    process_index: int = 0
    mesh_shape: Tuple[int, int] = (1, 1)
    memory_per_device: int = DEFAULT_MEMORY_PER_DEVICE

    def __post_init__(self):
        d, m = self.mesh_shape
        if d * m != self.device_count:
            raise ValueError(
                f"mesh_shape {self.mesh_shape} does not cover "
                f"device_count={self.device_count}"
            )
        if self.device_count < 1:
            raise ValueError("device_count must be >= 1")

    # -- discovery ---------------------------------------------------------
    @classmethod
    def discover(
        cls,
        *,
        model_axis: int = 1,
        max_devices: Optional[int] = None,
        memory_per_device: Optional[int] = None,
    ) -> "DeviceTopology":
        """Read the world from jax: one call, at run start.

        ``model_axis`` devices are grouped along the model/stage axis
        (default 1: pure data parallelism); the rest form the data axis.
        ``max_devices`` restricts discovery to a prefix of
        ``jax.devices()`` — how tests carve a 4-device topology out of an
        8-fake-device host. The device count is rounded *down* to a
        multiple of ``model_axis`` so the mesh always covers it.
        """
        import jax

        devices = jax.devices()
        n = len(devices) if max_devices is None else min(max_devices, len(devices))
        model_axis = max(1, int(model_axis))
        if model_axis > n:
            raise ValueError(
                f"model_axis={model_axis} exceeds the {n} visible devices"
            )
        n -= n % model_axis
        return cls(
            device_count=n,
            device_kind=str(devices[0].device_kind),
            process_count=int(jax.process_count()),
            process_index=int(jax.process_index()),
            mesh_shape=(n // model_axis, model_axis),
            memory_per_device=_device_memory(devices[0], memory_per_device),
        )

    @classmethod
    def trivial(cls, device_kind: str = "cpu") -> "DeviceTopology":
        """The single-device topology: degenerates to the legacy path."""
        return cls(device_count=1, device_kind=device_kind)

    # -- derived views -----------------------------------------------------
    @property
    def data_parallel(self) -> int:
        return self.mesh_shape[0]

    @property
    def model_parallel(self) -> int:
        return self.mesh_shape[1]

    @property
    def is_trivial(self) -> bool:
        return self.device_count == 1 and self.process_count == 1

    @property
    def total_memory_bytes(self) -> int:
        return self.device_count * self.memory_per_device

    def is_main(self) -> bool:
        """The multi-host gating idiom: exactly one process does host-side
        I/O (checkpoints, bench artifacts); every process computes."""
        return self.process_index == 0

    def fingerprint(self) -> Tuple:
        """Hashable identity for compile/engine caches: two topologies with
        the same fingerprint lower to the same partitioned executable."""
        return (
            "topo", self.device_count, self.device_kind,
            self.process_count, self.mesh_shape,
        )

    def describe(self) -> dict:
        """JSON-ready summary (bench payloads, manifests)."""
        return {
            "device_count": self.device_count,
            "device_kind": self.device_kind,
            "process_count": self.process_count,
            "mesh_shape": list(self.mesh_shape),
            "memory_per_device": int(self.memory_per_device),
        }

    # -- planning ----------------------------------------------------------
    def plan_budget(self, memory_fraction: float = DEFAULT_MEMORY_FRACTION) -> float:
        """The memory bound M the planner gets under this topology.

        Per-device memory bounds the plan: a data-parallel replica holds
        the *whole* pipeline footprint, so extra data-parallel devices add
        throughput, never budget. Only the model axis — stages spread
        across devices — multiplies the bound.
        """
        return memory_fraction * self.memory_per_device * self.model_parallel

    # -- elasticity --------------------------------------------------------
    def shrink(self, lost_devices: int = 1) -> "DeviceTopology":
        """The topology after losing ``lost_devices`` devices.

        The surviving devices re-mesh: the model axis is kept when it
        still divides the survivor count, otherwise it collapses to 1
        (stage span cannot straddle a hole); the data axis takes the rest.
        Shrinking below one device raises — there is nothing to replan on.
        """
        survivors = self.device_count - int(lost_devices)
        if survivors < 1:
            raise ValueError(
                f"cannot shrink {self.device_count} devices by {lost_devices}"
            )
        model = self.model_parallel if survivors % self.model_parallel == 0 else 1
        return dataclasses.replace(
            self,
            device_count=survivors,
            mesh_shape=(survivors // model, model),
        )

    # -- mesh construction -------------------------------------------------
    def mesh(self, axis_names: Tuple[str, str] = ("data", "model")):
        """A jax ``Mesh`` over the first ``device_count`` visible devices.

        Built lazily (never at import, never in ``discover``) so topology
        objects stay cheap, picklable metadata; a shrunken topology meshes
        over the surviving prefix of ``jax.devices()``.
        """
        import jax

        devices = jax.devices()
        if len(devices) < self.device_count:
            raise RuntimeError(
                f"topology wants {self.device_count} devices but only "
                f"{len(devices)} are visible"
            )
        arr = np.array(devices[: self.device_count]).reshape(self.mesh_shape)
        return jax.sharding.Mesh(arr, axis_names)


def as_topology(value) -> Optional[DeviceTopology]:
    """Normalize a topology argument: ``None`` stays ``None`` (legacy
    single-device path), ``"discover"`` runs discovery, a ``DeviceTopology``
    passes through."""
    if value is None or isinstance(value, DeviceTopology):
        return value
    if value == "discover":
        return DeviceTopology.discover()
    raise TypeError(
        f"topology= accepts None, 'discover' or a DeviceTopology, got {value!r}"
    )
