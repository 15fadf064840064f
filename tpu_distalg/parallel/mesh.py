"""Device-mesh runtime core.

Replaces the reference's ``SparkSession.builder...getOrCreate()`` + executor
topology (e.g. ``/root/reference/optimization/ssgd.py:78-81`` and the
``n_slices`` partition-count globals) with a ``jax.sharding.Mesh`` over the
available TPU chips. Where Spark runs ``local[*]`` threads as fake executors
for single-machine testing (SURVEY.md §4), we run N virtual CPU devices via
``XLA_FLAGS=--xla_force_host_platform_device_count=N``.

Two mesh axes by default:
  * ``data``  — data parallelism: rows of an RDD-like array live here.
  * ``model`` — model parallelism: factor matrices / feature blocks can be
    sharded here (used by the ALS workload; size 1 by default).
"""

from __future__ import annotations

import dataclasses
import os
import sys
from typing import Mapping, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

from tpu_distalg.telemetry import events as tevents

DATA_AXIS = "data"
MODEL_AXIS = "model"


class NoAcceleratorError(RuntimeError):
    """The default backend is not a TPU and nobody asked for the CPU."""


def emulate_devices(n: int = 8, platform: str = "cpu") -> None:
    """Request ``n`` virtual host devices. Must run before JAX is initialised.

    The JAX analogue of Spark ``local[*]`` (no master URL set anywhere in the
    reference, e.g. ``/root/reference/optimization/ssgd.py:78-81``).
    """
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}"
        ).strip()
    os.environ.setdefault("JAX_PLATFORMS", platform)
    # the env var was read when jax was imported (above); the config
    # update is what a not-yet-initialised backend actually honours
    jax.config.update("jax_platforms", platform)


def local_device_count() -> int:
    """Devices attached to THIS process (differs from the global count on
    multi-host slices)."""
    return jax.local_device_count()


def multihost_initialize(**kwargs) -> None:
    """Initialise the multi-host runtime (DCN-connected TPU slices).

    Must run before anything initialises an XLA backend (same contract as
    ``jax.distributed.initialize``, which it wraps). Idempotent: a no-op if
    the distributed client is already up.
    """
    if jax.distributed.is_initialized():
        return
    jax.distributed.initialize(**kwargs)


def cpu_requested() -> bool:
    """Whether the CPU backend was ASKED for: ``JAX_PLATFORMS`` naming
    ``cpu`` (what the tier-1 tests export) or :func:`emulate_devices`
    (the CLI's ``--emulate N``) — both land in ``jax_platforms``,
    whose first entry is the default backend."""
    return (jax.config.jax_platforms or "").split(",")[0] == "cpu"


def mesh_on_tpu(mesh: Mesh) -> bool:
    """Whether ``mesh`` runs on TPU chips — the one question every
    Pallas call site asks to pick compiled Mosaic (``True``) over
    ``interpret=True``. Each answer leaves a ``device`` telemetry event
    (platform, ``device_kind``, device count, ``pallas:
    compiled|interpret``) so a run's log says which way its kernels
    were built; ``chip_smoke.py`` asserts on it."""
    dev = next(iter(mesh.devices.flat))
    tpu = dev.platform == "tpu"
    tevents.emit("device", platform=dev.platform,
                 device_kind=dev.device_kind,
                 n_devices=int(mesh.devices.size),
                 pallas="compiled" if tpu else "interpret")
    return tpu


def get_mesh(
    data: int | None = None,
    model: int = 1,
    *,
    devices: Sequence[jax.Device] | None = None,
) -> Mesh:
    """Build a 2-D ``(data, model)`` mesh.

    ``data=None`` uses every available device on the data axis (after
    dividing out ``model``). This is the stand-in for the per-script
    ``n_slices`` globals (``ssgd.py:17``): partition count == mesh data size.

    Topology awareness (TPU, all devices used, none pinned explicitly):

      * multi-slice (devices spanning >1 ``slice_index``): a DCN-hybrid
        mesh via ``mesh_utils.create_hybrid_device_mesh`` — the data
        axis spans slices over DCN (one gradient AllReduce per step
        tolerates DCN latency) while the model axis stays inside a
        slice so its per-matmul collectives ride ICI;
      * single slice, >1 chip (covers multi-host pods too):
        ``mesh_utils.create_device_mesh`` orders devices along the
        physical ICI torus so neighbouring mesh coordinates are
        neighbouring chips (ring collectives stay nearest-neighbour);
      * otherwise (CPU emulation, one chip, explicit ``devices``, or a
        shape the topology helpers cannot express): a plain row-major
        grid — deterministic ordering for tests.

    The CPU is used only when asked for: with the default devices on a
    non-TPU backend and neither ``JAX_PLATFORMS`` naming ``cpu`` nor
    :func:`emulate_devices`, this raises :class:`NoAcceleratorError`
    instead of quietly interpreting every kernel on the host.
    """
    devs = list(devices) if devices is not None else jax.devices()
    if (devices is None and devs[0].platform != "tpu"
            and not cpu_requested()):
        raise NoAcceleratorError(
            f"no TPU: the default jax backend is "
            f"{devs[0].platform!r}. To run on host devices on purpose "
            f"pass --emulate N (library: parallel.mesh.emulate_devices) "
            f"or set JAX_PLATFORMS=cpu")
    n = len(devs)
    if data is None:
        if n % model != 0:
            raise ValueError(f"{n} devices not divisible by model={model}")
        data = n // model
    need = data * model
    if need > n:
        raise ValueError(f"mesh {data}x{model} needs {need} devices, have {n}")
    grid = _topology_grid(devs, data, model, explicit=devices is not None)
    return Mesh(grid, (DATA_AXIS, MODEL_AXIS))


def _topology_grid(devs, data: int, model: int, *, explicit: bool):
    """Arrange ``devs`` into the ``(data, model)`` grid per the topology
    policy in :func:`get_mesh`'s docstring. Pure device-list → grid
    function so the DCN-hybrid / ICI-torus / fallback branches are unit-
    testable with fake device objects (no TPU hardware required)."""
    need = data * model
    grid, branch = None, "row_major"
    if (not explicit and need == len(devs) and len(devs) > 1
            and devs[0].platform == "tpu"):
        from jax.experimental import mesh_utils

        n_slices = len({getattr(d, "slice_index", 0) for d in devs})
        try:
            if n_slices > 1 and data % n_slices == 0:
                grid = mesh_utils.create_hybrid_device_mesh(
                    (data // n_slices, model), (n_slices, 1), devices=devs
                )
                branch = "dcn_hybrid"
            elif n_slices == 1:
                grid = mesh_utils.create_device_mesh(
                    (data, model), devices=devs
                )
                branch = "ici_torus"
        except (NotImplementedError, ValueError) as e:
            # the topology helpers can't express the shape: the mesh is
            # still correct row-major, but neighbouring coordinates are
            # no longer neighbouring chips — say so, ring collectives
            # pay for it
            branch = "row_major_fallback"
            print(f"[mesh] {data}x{model}: topology-aware layout "
                  f"unavailable ({type(e).__name__}: {e}); using the "
                  f"row-major device order", file=sys.stderr)
    if grid is None:
        grid = np.array(devs[:need]).reshape(data, model)
    tevents.emit("mesh", branch=branch, data=data, model=model)
    return grid


@dataclasses.dataclass(frozen=True)
class MeshContext:
    """A mesh plus the axis names workloads shard over.

    The one runtime object workloads receive — the role SparkSession plays in
    every reference script.
    """

    mesh: Mesh

    @classmethod
    def create(cls, data: int | None = None, model: int = 1) -> "MeshContext":
        return cls(mesh=get_mesh(data=data, model=model))

    @property
    def n_data(self) -> int:
        return self.mesh.shape[DATA_AXIS]

    @property
    def n_model(self) -> int:
        return self.mesh.shape[MODEL_AXIS]

    @property
    def axis_sizes(self) -> Mapping[str, int]:
        return dict(self.mesh.shape)
