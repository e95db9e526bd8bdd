"""Reduce a profiler trace of the window to device numbers.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with nothing but
``jax.profiler.ProfileData``.  On a TPU v5e the planes that matter are:

- ``/device:TPU:<n>``: line ``XLA Ops`` holds one event per operation
  that ran, named by its HLO text (``%paged_attention.9 = bf16[...]
  custom-call(...)``), with start and duration in nanoseconds on the
  host's clock; control-flow ops (``while``, ``conditional``, ``call``)
  span the ops of their bodies.  Line ``XLA Modules`` holds one event per
  program run (``jit__decode_sample(<hash>)``).
- ``/host:CPU``: the harness's ``TraceAnnotation`` spans (``window``,
  ``engine.step``, ``generator.submit``, ``generator.wait``).

From them:

- busy: the union of a chip's operation intervals inside the window;
- a kernel's time: summed durations of the ops whose name carries it
  (``paged_attention``);
- collective time: summed durations of the ops whose HLO opcode is
  all-reduce, all-gather, reduce-scatter, all-to-all or
  collective-permute (a ``psum`` is named ``%psum.14`` and is an
  ``all-reduce``), in the decode step's program (``*decode*`` modules) or
  in all programs;
- breakdown: the ops that took the most time (containers left out, so
  no time counts twice), and the longest idle stretches of chip 0, each
  named by the harness span the host was in.
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
import re
from pathlib import Path
from typing import Dict, List, NamedTuple, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "window"
HOST_SPANS = ("engine.step", "generator.submit", "generator.wait")
CONTAINERS = ("while", "conditional", "call")
COLLECTIVE = re.compile(r"^(all-reduce|all-gather|reduce-scatter|all-to-all"
                        r"|collective-permute)")
OPCODE = re.compile(r"\s([a-z][a-z0-9-]*)\(")
TOP = 10


class Op(NamedTuple):
    start: float            # ns
    end: float
    name: str               # HLO op name, e.g. "paged_attention.9"
    module: str             # program name, e.g. "jit__decode_sample"
    opcode: str = ""        # HLO opcode, e.g. "custom-call"

    @property
    def kind(self) -> str:
        """The op name without its numeric suffix."""
        return re.sub(r"\.\d+$", "", self.name)


@functools.lru_cache(maxsize=1 << 16)       # a trace repeats its op names
def op_name(hlo_text: str) -> str:
    return hlo_text.split(" = ", 1)[0].lstrip("%").strip()


@functools.lru_cache(maxsize=1 << 16)
def op_code(hlo_text: str) -> str:
    """The opcode of an op's HLO text: the first word followed by ``(``
    after its shape (``all-reduce`` in ``%psum.14 = bf16[64,1,5120]{...}
    all-reduce(%fusion.162), ...``); "" for a name with no HLO text."""
    if " = " not in hlo_text:
        return ""
    m = OPCODE.search(hlo_text.split(" = ", 1)[1])
    return m.group(1) if m else ""


def union_ns(intervals: List[Tuple[float, float]], lo: float,
             hi: float) -> float:
    """Length of the union of ``intervals`` clipped to [lo, hi]."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_ns(intervals: List[Tuple[float, float]], lo: float, hi: float
            ) -> List[Tuple[float, float]]:
    """The idle stretches of [lo, hi] that no interval covers."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(a, b) for a, b in out if b > a]


@dataclasses.dataclass
class TraceSummary:
    window: Tuple[float, float]                 # ns, host span "window"
    ops: Dict[int, List[Op]]                    # chip -> ops in the window
    host: List[Tuple[float, float, str]]        # harness spans

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def chip_busy_s(self, chip: int) -> float:
        return union_ns([(o.start, o.end) for o in self.ops[chip]],
                        *self.window) * 1e-9

    @property
    def busy_s(self) -> float:
        """Busy seconds averaged over the chips traced."""
        return (sum(self.chip_busy_s(c) for c in self.ops)
                / max(len(self.ops), 1))

    def kernel_time_s(self, kernel: str, chip: int = 0) -> float:
        return sum(o.end - o.start for o in self.ops[chip]
                   if kernel in o.kind) * 1e-9

    def collective_time_s(self, chip: int = 0,
                          module: Optional[str] = None) -> float:
        """Collective op time on ``chip``, in programs whose name holds
        ``module`` (all programs when None)."""
        return sum(o.end - o.start for o in self.ops[chip]
                   if COLLECTIVE.match(o.opcode)
                   and (module is None or module in o.module)) * 1e-9

    def host_span_at(self, t: float) -> str:
        """The innermost harness span around host time ``t``."""
        best, best_len = "harness", None
        for s, e, name in self.host:
            if s <= t <= e and (best_len is None or e - s < best_len):
                best, best_len = name, e - s
        return best

    def breakdown(self) -> Dict[str, List[List]]:
        by_op: Dict[str, float] = {}
        for o in self.ops[0]:
            if o.kind in CONTAINERS:
                continue
            k = f"{o.module}:{o.name}"
            by_op[k] = by_op.get(k, 0.0) + (o.end - o.start) * 1e-9
        top_ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:TOP]
        gaps = gaps_ns([(o.start, o.end) for o in self.ops[0]],
                       *self.window)
        gaps.sort(key=lambda g: g[0] - g[1])
        idle = [[self.host_span_at((a + b) / 2), (b - a) * 1e-9]
                for a, b in gaps[:TOP]]
        return {"device_ops": [[k, v] for k, v in top_ops],
                "idle_gaps": idle}


def _modules_of(ops_events, module_events) -> List[str]:
    """The program each op ran in: the module event that started last at
    or before the op."""
    mods = sorted((float(m.start_ns), m.name.split("(")[0])
                  for m in module_events)
    starts = [s for s, _ in mods]
    out = []
    for ev in ops_events:
        i = bisect.bisect_right(starts, float(ev.start_ns)) - 1
        out.append(mods[i][1] if i >= 0 else "")
    return out


def summarize(profile, chips: int) -> TraceSummary:
    """Reduce a ``jax.profiler.ProfileData`` over its first ``chips`` TPU
    planes and the harness's window span."""
    host: List[Tuple[float, float, str]] = []
    window = None
    ops: Dict[int, List[Op]] = {}
    for plane in profile.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    s = float(ev.start_ns)
                    if ev.name == WINDOW_SPAN:
                        window = (s, s + float(ev.duration_ns))
                    elif ev.name in HOST_SPANS:
                        host.append((s, s + float(ev.duration_ns), ev.name))
            continue
        m = DEVICE_PLANE.match(plane.name)
        if not m or int(m.group(1)) >= chips:
            continue
        lines = {line.name: list(line.events) for line in plane.lines}
        evs = lines.get(OPS_LINE, [])
        mods = _modules_of(evs, lines.get(MODULES_LINE, []))
        chip = ops[int(m.group(1))] = []
        for ev, mod in zip(evs, mods):
            s, text = float(ev.start_ns), ev.name
            chip.append(Op(s, s + float(ev.duration_ns), op_name(text), mod,
                           op_code(text)))
    if window is None:
        raise ValueError("the trace holds no 'window' span")
    if sorted(ops) != list(range(chips)):
        raise ValueError(f"the trace holds ops of chips {sorted(ops)}, "
                         f"want 0..{chips - 1}")
    lo, hi = window
    ops = {c: [o for o in v if o.end > lo and o.start < hi]
           for c, v in ops.items()}
    return TraceSummary(window, ops, host)


def load(trace_dir: Path):
    """The newest ``.xplane.pb`` under ``trace_dir`` as ProfileData."""
    import jax
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return jax.profiler.ProfileData.from_file(str(files[-1]))
