"""Device-mesh construction for TPU slices.

The framework's parallelism vocabulary (SPMD over a named
:class:`jax.sharding.Mesh`, collectives inserted by XLA — the scaling-book
recipe) uses five axes:

- ``dp``   — pure data parallel (gradient all-reduce over ICI/DCN)
- ``pp``   — pipeline stages (GPipe microbatch loop, parallel/pipeline.py)
- ``fsdp`` — data parallel with parameter/optimizer sharding (ZeRO-3:
  all-gather params, reduce-scatter grads)
- ``tp``   — tensor (megatron-style) parallelism inside a layer
- ``sp``   — sequence/context parallelism (ring attention over ICI)
- ``ep``   — expert parallelism for MoE layers (all_to_all dispatch)

On a real slice, axis order maps the fastest-varying axis (``tp``) onto
the densest ICI neighborhood; ``dp`` rides DCN across slices
(multislice). There is no NCCL anywhere: this is the TPU-native
replacement for the reference's rendezvous-env + torchrun pattern
(reference runner executor.go:237-246).
"""

import math
from dataclasses import dataclass, field
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

AXES = ("dp", "pp", "fsdp", "ep", "sp", "tp")


@dataclass(frozen=True)
class MeshConfig:
    """Logical mesh shape. ``-1`` on one axis means "absorb the rest"."""

    dp: int = 1
    pp: int = 1
    fsdp: int = -1
    ep: int = 1
    sp: int = 1
    tp: int = 1

    def resolved(self, n_devices: int) -> dict[str, int]:
        sizes = {
            "dp": self.dp,
            "pp": self.pp,
            "fsdp": self.fsdp,
            "ep": self.ep,
            "sp": self.sp,
            "tp": self.tp,
        }
        fixed = math.prod(v for v in sizes.values() if v != -1)
        wild = [k for k, v in sizes.items() if v == -1]
        if len(wild) > 1:
            raise ValueError("at most one mesh axis may be -1")
        if wild:
            if n_devices % fixed != 0:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes product {fixed}"
                )
            sizes[wild[0]] = n_devices // fixed
        if sizes["pp"] > 1 and sizes["sp"] > 1:
            # checked AFTER wildcard resolution (a -1 axis could land on
            # pp/sp): ring attention runs in its own sp shard_map, which
            # cannot nest inside the pipeline's partial-manual pp
            # shard_map — reject at CONFIG time, not when jit trips
            raise ValueError(
                "pp and sp cannot compose (pipeline's shard_map cannot "
                "nest ring attention's); pick one, or use fsdp for the "
                "memory axis alongside pp"
            )
        if math.prod(sizes.values()) != n_devices:
            raise ValueError(
                f"mesh {sizes} does not cover {n_devices} devices"
            )
        return sizes


def make_mesh(
    config: Optional[MeshConfig] = None,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a named mesh over ``devices`` (default: all local devices).

    Device order: ``mesh_utils.create_device_mesh`` when available (it
    optimizes for ICI nearest-neighbor torus placement on real TPU
    slices); plain reshape otherwise (CPU virtual devices).
    """
    devices = list(devices if devices is not None else jax.devices())
    config = config or MeshConfig()
    fixed_axes = (config.dp, config.pp, config.fsdp, config.ep, config.sp, config.tp)
    if -1 not in fixed_axes:
        # All axes fixed: allow using a leading subset of the devices.
        need = math.prod(fixed_axes)
        if need <= len(devices):
            devices = devices[:need]
    sizes = config.resolved(len(devices))
    shape = tuple(sizes[a] for a in AXES)
    try:
        from jax.experimental import mesh_utils

        if devices[0].platform == "tpu":
            dev_array = mesh_utils.create_device_mesh(shape, devices=devices)
        else:
            dev_array = np.asarray(devices).reshape(shape)
    except Exception:
        dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, AXES)


def single_device_mesh(device: Optional[jax.Device] = None) -> Mesh:
    """A mesh over one device (default: the first)."""
    return make_mesh(
        MeshConfig(dp=1, fsdp=1, ep=1, sp=1, tp=1),
        devices=[device or jax.devices()[0]],
    )


def mesh_shape(mesh: Mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))
