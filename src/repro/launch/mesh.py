"""Production meshes.

``make_production_mesh`` is the mandated serving/dry-run mesh: one v5e pod
(16x16 = 256 chips, axes ("data","model")) or two pods (2x16x16 = 512,
axes ("pod","data","model")).

``make_training_mesh`` re-views the same chips for decentralized training:
axes ("pod","agent","fsdp","model") where agent x fsdp = 16 (the pod's data
dimension). Each decentralized agent owns an fsdp x model slice and holds a
full model replica (FSDP-sharded); the agent (+pod) axes are the paper's
communication graph. ``make_host_mesh`` lays the same axes over one host's
chips (agent rows across the chips, e.g. 4 on a v5e 2x2 host).

Every mesh uses ``AxisType.Auto`` axes: the panel engine places its state
with sharding constraints inside jit, which explicit-axis meshes (the
``jax.make_mesh`` default) reject outside a ``jax.set_mesh`` context.
Functions, not module constants — importing this module never touches jax
device state.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType

MODEL_AXIS = 16
DATA_AXIS = 16
PODS = 2
TRAIN_AXES = ("pod", "agent", "fsdp", "model")


def _mesh(shape, axes):
    n = int(np.prod(shape))
    return jax.make_mesh(shape, axes, devices=jax.devices()[:n],
                         axis_types=(AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_training_mesh(agents_per_pod: int, *, multi_pod: bool = False):
    if DATA_AXIS % agents_per_pod:
        raise ValueError(f"agents_per_pod={agents_per_pod} must divide 16")
    fsdp = DATA_AXIS // agents_per_pod
    pods = PODS if multi_pod else 1
    return _mesh((pods, agents_per_pod, fsdp, MODEL_AXIS), TRAIN_AXES)


def make_host_mesh(chips: int | None = None):
    """One host's chips as a training mesh: the agent axis spans ``chips``
    devices (default: all of them), fsdp and model are 1."""
    chips = chips or jax.device_count()
    return _mesh((1, chips, 1, 1), TRAIN_AXES)


def num_agents(mesh) -> int:
    m = 1
    for ax in ("pod", "agent"):
        if ax in mesh.axis_names:
            m *= mesh.shape[ax]
    return m


def make_debug_mesh(agents: int = 2, fsdp: int = 1, model: int = 2):
    """Small mesh for CPU tests (requires xla_force_host_platform_device_count)."""
    return _mesh((1, agents, fsdp, model), TRAIN_AXES)
