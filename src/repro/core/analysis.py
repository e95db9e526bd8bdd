"""High-level analysis API: compile a step, characterize it, emit a roofline.

This is the "program to benchmark computing platforms and evaluate Deep
Learning operators" the paper describes, as a library call:

    report = analyze_step(train_step, args=input_specs(cfg),
                          mesh=mesh, in_shardings=..., out_shardings=...,
                          model_flops=model_flops(cfg, shape))
    print(report.render())
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax

from .roofline import (
    RooflineTerms,
    ScopeSpec,
    StepCharacter,
    characterize,
    character_as_dict,
    render_report,
    scope_for_mesh,
    terms_from_character,
)
from .roofline.hardware import TPU_V5E, ChipSpec


@dataclasses.dataclass
class AnalysisReport:
    label: str
    character: StepCharacter
    terms: RooflineTerms
    compile_seconds: float
    mesh_shape: Dict[str, int]

    def render(self) -> str:
        extra = []
        top = self.character.collectives.top_ops[:5]
        if top:
            extra.append("top collectives (per-device wire bytes):")
            for op in top:
                extra.append(
                    f"  {op.kind:<20} {op.wire_bytes / 1e6:>10.2f} MB"
                    f"  axes={'+'.join(op.axes) or '?'} x{op.group_size}"
                )
        if self.character.scopes:
            extra.append("per-scope (named_scope) breakdown:")
            for tag, sb in sorted(self.character.scopes.items(),
                                  key=lambda kv: -kv[1]["bytes"]):
                extra.append(
                    f"  {tag:<18} flops={sb['flops'] / 1e12:8.2f} TF"
                    f"  bytes={sb['bytes'] / 2**30:9.2f} GiB"
                )
        extra.append(
            f"memory/device: args={self.character.memory.argument_bytes / 2**30:.2f} GiB"
            f" temps={self.character.memory.temp_bytes / 2**30:.2f} GiB"
            f" out={self.character.memory.output_bytes / 2**30:.2f} GiB"
        )
        return render_report(self.label, self.terms, extra)

    def as_dict(self) -> Dict[str, Any]:
        d = character_as_dict(self.character)
        d.update(
            label=self.label,
            mesh_shape=self.mesh_shape,
            compile_seconds=self.compile_seconds,
            scope=self.terms.scope,
            n_chips=self.terms.n_chips,
            dtype=self.terms.dtype,
            compute_s=self.terms.compute_s,
            memory_s=self.terms.memory_s,
            ici_s=self.terms.ici_s,
            dcn_s=self.terms.dcn_s,
            dominant=self.terms.dominant,
            bound=self.terms.bound_class(),
            t_lower_s=self.terms.t_lower,
            t_upper_s=self.terms.t_upper,
            arithmetic_intensity=self.terms.arithmetic_intensity,
            model_flops_total=self.terms.model_flops_total,
            useful_ratio=self.terms.useful_ratio,
            roofline_fraction=self.terms.roofline_fraction,
            hardware_fraction=self.terms.hardware_fraction,
        )
        return d


def analyze_compiled(
    compiled,
    mesh,
    *,
    label: str = "step",
    scope: Optional[ScopeSpec] = None,
    chip: ChipSpec = TPU_V5E,
    dtype: str = "bfloat16",
    model_flops: Optional[float] = None,
    compile_seconds: float = 0.0,
) -> AnalysisReport:
    mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    if scope is None:
        scope = scope_for_mesh(mesh_shape, chip)
    char = characterize(compiled, mesh)
    terms = terms_from_character(char, scope, dtype=dtype, model_flops_total=model_flops)
    return AnalysisReport(
        label=label,
        character=char,
        terms=terms,
        compile_seconds=compile_seconds,
        mesh_shape=mesh_shape,
    )


def analyze_step(
    fn: Callable,
    *,
    args: Sequence[Any],
    mesh,
    in_shardings: Any = None,
    out_shardings: Any = None,
    donate_argnums: Tuple[int, ...] = (),
    label: str = "step",
    scope: Optional[ScopeSpec] = None,
    chip: ChipSpec = TPU_V5E,
    dtype: str = "bfloat16",
    model_flops: Optional[float] = None,
) -> Tuple[AnalysisReport, Any]:
    """Lower + compile ``fn`` under ``mesh`` and characterize it.

    Returns (report, compiled) so callers can reuse the executable.
    """
    jit_kwargs: Dict[str, Any] = {}
    if in_shardings is not None:
        jit_kwargs["in_shardings"] = in_shardings
    if out_shardings is not None:
        jit_kwargs["out_shardings"] = out_shardings
    if donate_argnums:
        jit_kwargs["donate_argnums"] = donate_argnums
    t0 = time.time()
    with jax.set_mesh(mesh):
        lowered = jax.jit(fn, **jit_kwargs).lower(*args)
        compiled = lowered.compile()
    dt = time.time() - t0
    report = analyze_compiled(
        compiled, mesh, label=label, scope=scope, chip=chip,
        dtype=dtype, model_flops=model_flops, compile_seconds=dt,
    )
    return report, compiled


def kernel_character(fn: Callable, *args, static_argnums=()) -> Dict[str, float]:
    """Single-device W/Q/AI for a kernel (benchmarks' measurement channel).

    Uses the module cost walk (same conventions as the distributed path),
    so max/min/data-movement report ~0 FLOPs — the paper's §3.5 semantics.
    """
    from .roofline import hlo_cost
    compiled = jax.jit(fn, static_argnums=static_argnums).lower(*args).compile()
    mc = hlo_cost.module_cost(compiled.as_text())
    return {
        "W_flops": mc.flops,
        "Q_bytes": mc.bytes,
        "transcendentals": mc.transcendentals,
        "AI": mc.flops / mc.bytes if mc.bytes else 0.0,
    }
