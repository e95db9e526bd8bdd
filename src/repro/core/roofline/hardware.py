"""Hardware descriptions for roofline construction.

The paper builds rooflines for three NUMA *scopes* of a 2-socket Xeon
(single thread / single socket / two sockets), each with its own peak compute
``pi`` and peak bandwidth ``beta``.  Our target is a TPU v5e fleet, whose
analogous hierarchy is  chip -> pod (ICI-connected 16x16) -> multi-pod
(DCN-connected).  Each scope carries the three roofline ceilings used by
:mod:`repro.core.roofline.model`:

* ``peak_flops``      -- aggregate compute ceiling of the scope [FLOP/s]
* ``hbm_bw``          -- aggregate HBM bandwidth [B/s]
* ``interconnect_bw`` -- aggregate bandwidth of the *slowest interconnect
                         crossed inside the scope* [B/s] (ICI within a pod,
                         DCN across pods).  This is the distributed analogue
                         of the paper's cross-socket UPI concern.

Constants per the assignment: 197 TFLOP/s bf16 per chip, 819 GB/s HBM,
~50 GB/s/link ICI.  DCN per-chip egress is an explicit, documented assumption
(v5e-era multislice deployments budget ~12.5 GB/s/chip); it only affects the
multi-pod scope, never the single-pod roofline table.

Hierarchical extension (arXiv 2009.05257): each chip also carries a beta
for the two levels that bracket HBM — on-chip VMEM above it and the host
link below it — so the time-based ledger can place every byte a serving
phase moves on exactly one level of

    VMEM  <->  HBM  <->  ICI  <->  DCN  <->  host

``vmem_bw`` and ``host_bw`` are documented assumptions like ``dcn_bw``:
v5e VMEM streams roughly an order of magnitude faster than HBM (we budget
~22x HBM, the load/store fabric behind the 8 MXU passes/cycle), and the
host link is a PCIe-attached DMA path budgeted at 16 GB/s/chip.  The
microbench (microbench.py) *measures* every level it can reach on the
live platform; these constants are the deterministic analytic fallback.
"""

from __future__ import annotations

import dataclasses
from typing import Mapping

# Memory levels of the hierarchical roofline, fastest first.  Every byte a
# serving phase moves is attributed to exactly one of these; a level that
# moves zero bytes is "unbound" (it contributes no roof and no time).
MEMORY_LEVELS = ("vmem", "hbm", "ici", "dcn", "host")


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    """Peak capabilities of one accelerator chip."""

    name: str
    peak_flops: float            # FLOP/s at the benchmark dtype
    peak_flops_by_dtype: Mapping[str, float]
    hbm_bw: float                # bytes/s
    hbm_bytes: int               # capacity, bytes
    ici_bw: float                # bytes/s per link
    ici_links: int               # usable links per chip in a 2D torus
    dcn_bw: float                # bytes/s per chip, cross-pod egress
    vmem_bytes: int              # on-chip vector memory
    mxu_dim: int = 128           # systolic array edge
    vmem_bw: float = 0.0         # bytes/s through on-chip vector memory
    host_bw: float = 0.0         # bytes/s on the host DMA link (swap path)

    def flops_for(self, dtype: str) -> float:
        return float(self.peak_flops_by_dtype.get(dtype, self.peak_flops))

    def level_bw(self, level: str) -> float:
        """Beta of one memory level of the hierarchy (B/s).  Levels this
        chip spec does not describe (bw == 0) return 0.0 — callers treat
        a zero-beta level with traffic as unpriceable, and a zero-byte
        level as unbound regardless of beta."""
        if level not in MEMORY_LEVELS:
            raise ValueError(f"unknown memory level {level!r}")
        return float(getattr(self, "hbm_bw" if level == "hbm"
                             else f"{level}_bw"))


TPU_V5E = ChipSpec(
    name="tpu_v5e",
    peak_flops=197e12,
    peak_flops_by_dtype={
        "bfloat16": 197e12,
        "float32": 98.5e12,   # bf16 inputs / f32 accumulate path, half rate for f32 ops
        "int8": 394e12,
        "float16": 197e12,
    },
    hbm_bw=819e9,
    hbm_bytes=16 * 1024**3,
    ici_bw=50e9,
    ici_links=4,
    dcn_bw=12.5e9,
    vmem_bytes=128 * 1024**2,
    vmem_bw=22 * 819e9,          # ~22x HBM, documented assumption (see above)
    host_bw=16e9,                # PCIe-attached host DMA, assumption
)


# Peaks of the chips a measurement may run on, keyed by the name JAX
# reports as ``device.device_kind``.  A device missing here is an error:
# its peaks are unknown, and no other chip's stand in for them.
CHIPS_BY_DEVICE_KIND: Mapping[str, ChipSpec] = {
    "TPU v5 lite": TPU_V5E,
}


def chip_for_device_kind(device_kind: str) -> ChipSpec:
    """The :class:`ChipSpec` of a device as JAX names it; raises for a kind
    with no recorded peaks."""
    try:
        return CHIPS_BY_DEVICE_KIND[device_kind]
    except KeyError:
        raise ValueError(
            f"no peaks recorded for device kind {device_kind!r} "
            f"(known: {sorted(CHIPS_BY_DEVICE_KIND)})") from None


# The host this container runs on.  ``microbench.py`` *measures* the real
# numbers with the paper's protocol; these are fallbacks so analysis is
# deterministic when the microbench hasn't been run.
HOST_CPU_FALLBACK = ChipSpec(
    name="host_cpu",
    peak_flops=50e9,
    peak_flops_by_dtype={"float32": 50e9},
    hbm_bw=10e9,
    hbm_bytes=32 * 1024**3,
    ici_bw=10e9,
    ici_links=1,
    dcn_bw=1e9,
    vmem_bytes=32 * 1024**2,
    vmem_bw=50e9,                # cache-resident streaming fallback
    host_bw=10e9,                # "host" DMA == same DRAM on a CPU platform
)


@dataclasses.dataclass(frozen=True)
class ScopeSpec:
    """A resource scope = the paper's thread/socket/two-socket rung.

    ``n_chips`` chips act as one roofline platform.  ``interconnect_bw`` is
    aggregate: chips x per-chip attainable bandwidth on the scope's weakest
    crossed link class.
    """

    name: str
    chip: ChipSpec
    n_chips: int
    interconnect: str            # "none" | "ici" | "dcn"

    @property
    def peak_flops(self) -> float:
        return self.chip.peak_flops * self.n_chips

    def peak_flops_for(self, dtype: str) -> float:
        return self.chip.flops_for(dtype) * self.n_chips

    @property
    def hbm_bw(self) -> float:
        return self.chip.hbm_bw * self.n_chips

    @property
    def hbm_bytes(self) -> int:
        return self.chip.hbm_bytes * self.n_chips

    @property
    def interconnect_bw(self) -> float:
        if self.interconnect == "none":
            return float("inf")
        if self.interconnect == "ici":
            return self.chip.ici_bw * self.n_chips
        if self.interconnect == "dcn":
            return self.chip.dcn_bw * self.n_chips
        raise ValueError(f"unknown interconnect {self.interconnect!r}")

    def per_chip_link_bw(self, kind: str) -> float:
        return self.chip.ici_bw if kind == "ici" else self.chip.dcn_bw


def chip_scope(chip: ChipSpec = TPU_V5E) -> ScopeSpec:
    """Single chip — the paper's 'single thread' rung."""
    return ScopeSpec("chip", chip, 1, "none")


def tp_scope(chip: ChipSpec = TPU_V5E, n_chips: int = 1) -> ScopeSpec:
    """Tensor-parallel serving scope: ``n_chips`` ICI-connected chips
    acting as ONE decode platform (weights and KV sharded, activations
    all-reduced every block).  The paper's NUMA analogue: one socket's
    threads sharing a working set through the cross-socket link — the
    scope where the interconnect ceiling can out-bind the HBM ceiling
    (see RooflineTerms.binding_roof)."""
    if n_chips <= 1:
        return chip_scope(chip)
    return ScopeSpec(f"tp{n_chips}", chip, n_chips, "ici")


def pod_scope(chip: ChipSpec = TPU_V5E, n_chips: int = 256) -> ScopeSpec:
    """One ICI-connected pod — the paper's 'single socket' rung."""
    return ScopeSpec("pod", chip, n_chips, "ici")


def multipod_scope(chip: ChipSpec = TPU_V5E, n_pods: int = 2,
                   chips_per_pod: int = 256) -> ScopeSpec:
    """DCN-connected multislice — the paper's 'two sockets' rung."""
    return ScopeSpec("multipod", chip, n_pods * chips_per_pod, "dcn")


def scope_for_mesh(mesh_shape: Mapping[str, int], chip: ChipSpec = TPU_V5E) -> ScopeSpec:
    """Pick the scope that matches a mesh: a ``pod`` axis implies DCN."""
    n = 1
    for v in mesh_shape.values():
        n *= int(v)
    if mesh_shape.get("pod", 1) > 1:
        return ScopeSpec("multipod", chip, n, "dcn")
    if n == 1:
        return chip_scope(chip)
    return ScopeSpec("pod", chip, n, "ici")


DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1,
    "s8": 1, "u8": 1, "f8e4m3": 1, "f8e5m2": 1, "f8e4m3fn": 1, "f8e4m3b11fnuz": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
    "token": 0, "opaque": 0,
}
