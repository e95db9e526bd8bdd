"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state.  Single pod: 16x16 = 256 chips (data, model).  Multi-pod: 2 pods of
256 = 512 chips with a leading DCN ``pod`` axis.

When the process exposes more devices than a mesh needs (the dry-run forces
512 host devices and then builds the single-pod 256-chip mesh), the first
``prod(shape)`` devices are used (parallel.mesh.make_mesh).
"""

from __future__ import annotations

from repro.parallel.mesh import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def mesh_name(mesh) -> str:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if sizes.get("pod", 1) > 1:
        return "multipod"
    return "pod"
