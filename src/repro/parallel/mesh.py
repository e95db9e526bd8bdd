"""Mesh construction and axis conventions.

Axes:
  pod   -- data-parallel over DCN (multislice); gradient all-reduce only
  data  -- data-parallel over ICI; also sequence-parallel for long context
  model -- tensor/expert parallel over ICI

``("pod", "data")`` together form the batch axis; sharding rules refer to the
logical axis names below and are legalized against the concrete mesh by
:mod:`repro.parallel.sharding`.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import numpy as np

BATCH_AXES: Tuple[str, ...] = ("pod", "data")
MODEL_AXIS = "model"
DATA_AXIS = "data"
POD_AXIS = "pod"


def make_mesh(shape: Sequence[int], axes: Sequence[str]) -> jax.sharding.Mesh:
    """An Auto-typed mesh over the first ``prod(shape)`` devices, so a mesh
    smaller than the host (one chip of four) can be built."""
    n = int(np.prod(shape))
    return jax.make_mesh(tuple(shape), tuple(axes),
                         devices=jax.devices()[:n],
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1, pod: int = 1) -> jax.sharding.Mesh:
    """Small mesh over whatever devices exist (tests / smoke runs)."""
    n = pod * data * model
    devs = jax.devices()
    if len(devs) < n:
        raise ValueError(f"need {n} devices, have {len(devs)}")
    if pod > 1:
        return make_mesh((pod, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))


def single_device_mesh() -> jax.sharding.Mesh:
    return make_mesh((1, 1), ("data", "model"))


def dp_submeshes(dp: int, tp: int = 1) -> list:
    """Slice the first ``dp * tp`` devices into ``dp`` independent
    ``(1, tp)`` (data, model) meshes — one per serving replica.

    Serving replicas never communicate through a collective (the router
    moves requests, not activations), so each replica gets its OWN mesh
    over its device row instead of a slice of one global mesh: its
    shard_map steps compile against exactly tp devices and the ``data``
    axis stays size 1 inside every replica.  Device rows follow the same
    row-major (data, model) order ``make_host_mesh(dp, tp)`` would use,
    so replica ``i`` owns the devices global-mesh row ``i`` would."""
    dp, tp = int(dp), int(tp)
    if dp < 1 or tp < 1:
        raise ValueError(f"dp_submeshes({dp}, {tp}): axes must be >= 1")
    devs = jax.devices()
    need = dp * tp
    if len(devs) < need:
        raise ValueError(f"need {need} devices, have {len(devs)}")
    rows = np.asarray(devs[:need], dtype=object).reshape(dp, 1, tp)
    return [jax.sharding.Mesh(rows[i], ("data", "model"))
            for i in range(dp)]


def mesh_axis_sizes(mesh: jax.sharding.Mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def batch_shards(mesh: jax.sharding.Mesh) -> int:
    sizes = mesh_axis_sizes(mesh)
    n = 1
    for a in BATCH_AXES:
        n *= sizes.get(a, 1)
    return n
