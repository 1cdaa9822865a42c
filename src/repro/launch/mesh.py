"""Production meshes and logical-axis rules.

Target hardware: TPU v5e.  Single pod = 16x16 = 256 chips with axes
("data", "model"); multi-pod = 2 pods = 512 chips with ("pod", "data",
"model") — the pod axis is pure data parallelism (gradient all-reduce over
DCN in production; here it lowers like a third mesh axis, which is what the
multi-pod dry-run must prove shards correctly).

Functions, not module constants: importing this module never touches jax
device state (required so smoke tests see the 1-device CPU backend).
"""
from __future__ import annotations

from typing import Mapping

import jax
from jax.sharding import AbstractMesh, AxisType, Mesh


def make_mesh(shape, axes) -> Mesh:
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_abstract_mesh(shape, axes):
    """Device-less mesh for spec inference."""
    return AbstractMesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh() -> Mesh:
    """1-device mesh for CPU smoke runs of the distributed code path."""
    return make_mesh((1, 1), ("data", "model"))


def make_fleet_mesh(n_devices: int | None = None) -> Mesh:
    """1-D ("dev",) mesh for the fleet simulator's embarrassingly-parallel
    device axis (`repro.fleet` / `repro.adapt`): every backend simulates an
    independent slice of the candidate × harvester × seed population.
    Defaults to all visible devices."""
    n = len(jax.devices()) if n_devices is None else n_devices
    return make_mesh((n,), ("dev",))


def logical_rules(mesh: Mesh) -> Mapping[str, object]:
    """Logical-axis -> mesh-axis mapping used by ``models.common.shard``."""
    has_pod = "pod" in mesh.axis_names
    batch = ("pod", "data") if has_pod else ("data",)
    return {
        "batch": batch,
        # FSDP dim for weights/optimizer state; on the multi-pod mesh the
        # shard extends across pods (ZeRO over DCN) — this is what brings
        # the 132B/235B optimizer state under 16 GiB/chip (see §Roofline)
        "embed": (("pod", "data") if has_pod else ("data",)),
        "heads": ("model",),
        "kv_heads": ("model",),
        "ff": ("model",),
        "vocab": ("model",),
        "experts": ("model",),
        # NOTE (§Perf P2-H2, refuted): mapping "seq" -> ("model",) enables
        # Megatron-SP-style residual sharding; measured on this GSPMD
        # version it cut the memory term 2.6x but grew the collective bound
        # (involuntary resharding around attention / the recurrent scan),
        # so the default keeps the sequence replicated.
        "seq": None,
        "qseq": None,
    }
