#!/usr/bin/env python3
"""The program's own spans in a profiler trace of the window.

The serving program opens a ``jax.profiler.TraceAnnotation`` at every
boundary inside ``Engine.step`` (``repro.obs.trace.span``): ``sched.admit``;
``engine.prefill`` around ``kv.grow``, ``engine.prefill.launch``,
``kv.write_prefill``, ``engine.prefill.fence`` and ``engine.sample_first``;
``engine.decode`` around ``kv.grow``, ``engine.decode.stage``,
``engine.decode.launch``, ``engine.decode.fence`` and
``engine.decode.commit``.  They lie in the ``/host:CPU`` plane on the
clock of the device's ``XLA Ops``, nested inside the harness's
``engine.step``, and carry stats: ``request`` on ``engine.prefill``,
``batch`` on ``engine.decode``.

This module keeps them (every host event whose name starts with one of
``PREFIXES``, but for the harness's own spans) and does the arithmetic the span metrics share:

- idle by span: each idle stretch of chip 0 in the window is put down to
  the innermost span, harness or program, the host was in;
- prefill stalls: for each decode step, the ``engine.prefill`` time
  since the previous decode step ended;
- prefill tails: per prefilled request, ``kv.write_prefill`` plus
  ``engine.sample_first``: dispatching the page writes, and the
  first-token sample that waits on the device behind them;
- decode batches: the ``batch`` stat of each decode step.

A program without these spans (an older commit) gives none, and each
reader then returns None.

    python3 bench/spans.py [--trace-dir DIR] [--cut OUT.textproto.gz]

prints these numbers for the newest trace under DIR (the harness's trace
directory by default) as one JSON object; ``--cut`` also writes 0.9 s of
that trace, from just before the window's first prefill, as an XSpace
text proto for tests.
"""

from __future__ import annotations

import dataclasses
import functools
import gzip
import json
import sys
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

if __name__ == "__main__":
    sys.path[:0] = [str(Path(__file__).resolve().parent.parent)]

from bench import harness  # noqa: E402
from bench import trace as trace_mod  # noqa: E402

PREFIXES = ("sched.", "engine.", "kv.")
HARNESS = "harness"             # idle outside every span (trace.host_span_at)
CUT_NS = 0.9e9


@dataclasses.dataclass(frozen=True)
class HostSpan:
    start: float                # ns, host clock
    end: float
    name: str
    stats: Dict[str, float]


def is_program(name: str) -> bool:
    """A span of the program; the harness's ``engine.step`` is not."""
    return name.startswith(PREFIXES) and name not in trace_mod.HOST_SPANS


def program_spans(profile, window: Tuple[float, float]) -> List[HostSpan]:
    """The program's spans that start inside ``window`` (ns), in start
    order, from a ``jax.profiler.ProfileData``."""
    lo, hi = window
    out = []
    for plane in profile.planes:
        if plane.name != trace_mod.HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                s = float(ev.start_ns)
                if is_program(ev.name) and lo <= s <= hi:
                    out.append(HostSpan(s, s + float(ev.duration_ns),
                                        ev.name, dict(ev.stats)))
    out.sort(key=lambda sp: (sp.start, -sp.end))
    return out


@functools.lru_cache(maxsize=1)
def _load(path: str, mtime_ns: int,
          window: Tuple[float, float]) -> List[HostSpan]:
    import jax
    return program_spans(jax.profiler.ProfileData.from_file(path), window)


def spans_of(run) -> List[HostSpan]:
    """The program spans of a ``--trace 1`` run: read once from the
    newest trace in the harness's trace directory (the file the run's
    ``TraceSummary`` came from); none without a trace."""
    if run.trace is None:
        return []
    files = sorted(Path(harness.TRACE_DIR).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime_ns)
    if not files:
        return []
    return _load(str(files[-1]), files[-1].stat().st_mtime_ns,
                 tuple(run.trace.window))


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def exclusive(spans: Sequence[Tuple[float, float, str]]
              ) -> List[Tuple[float, float, str]]:
    """Tile the union of properly nested spans ``(start, end, name)``:
    each stretch belongs to the innermost span over it."""
    out: List[Tuple[float, float, str]] = []
    stack: List[Tuple[float, float, str]] = []
    t = 0.0
    for s, e, name in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][1] <= s:
            top = stack.pop()
            out.append((t, top[1], top[2]))
            t = top[1]
        if stack:
            out.append((t, s, stack[-1][2]))
            e = min(e, stack[-1][1])
        stack.append((s, e, name))
        t = s
    while stack:
        top = stack.pop()
        out.append((t, top[1], top[2]))
        t = top[1]
    return [seg for seg in out if seg[1] > seg[0]]


def overlap_by_name(gaps: Sequence[Tuple[float, float]],
                    segments: Sequence[Tuple[float, float, str]]
                    ) -> Dict[str, float]:
    """How much of the sorted, disjoint ``gaps`` each name's segments
    (sorted, disjoint) cover; the rest goes to ``HARNESS``."""
    out: Dict[str, float] = {}
    j = 0
    for a, b in gaps:
        covered = 0.0
        while j < len(segments) and segments[j][1] <= a:
            j += 1
        k = j
        while k < len(segments) and segments[k][0] < b:
            s, e, name = segments[k]
            d = min(b, e) - max(a, s)
            if d > 0:
                out[name] = out.get(name, 0.0) + d
                covered += d
            k += 1
        if b - a > covered:
            out[HARNESS] = out.get(HARNESS, 0.0) + (b - a - covered)
    return out


def idle_by_span(summary, spans: Sequence[HostSpan]) -> Dict[str, float]:
    """Chip-0 idle ns of the window, by the innermost harness or program
    span the host was in."""
    gaps = trace_mod.gaps_ns([(o.start, o.end) for o in summary.ops[0]],
                             *summary.window)
    segs = exclusive(list(summary.host)
                     + [(sp.start, sp.end, sp.name) for sp in spans])
    return overlap_by_name(gaps, segs)


def is_decode_step(sp: HostSpan) -> bool:
    """An ``engine.decode`` span that stepped at least one request."""
    return sp.name == "engine.decode" and sp.stats.get("batch", 0) > 0


def prefill_stalls_ns(spans: Sequence[HostSpan]) -> List[float]:
    """For each decode step, the summed ``engine.prefill`` time since
    the previous decode step."""
    out, acc = [], 0.0
    for sp in spans:
        if sp.name == "engine.prefill":
            acc += sp.end - sp.start
        elif is_decode_step(sp):
            out.append(acc)
            acc = 0.0
    return out


def prefill_tails_ns(spans: Sequence[HostSpan]) -> List[float]:
    """Per request that sampled its first token in the window,
    ``kv.write_prefill`` plus ``engine.sample_first`` inside its
    ``engine.prefill`` spans."""
    tails: Dict[int, float] = {}
    sampled = set()
    parent: Optional[HostSpan] = None
    for sp in spans:
        if sp.name == "engine.prefill":
            parent = sp
        elif (parent is not None and parent.start <= sp.start <= parent.end
              and sp.name in ("kv.write_prefill", "engine.sample_first")):
            req = parent.stats.get("request")
            tails[req] = tails.get(req, 0.0) + sp.end - sp.start
            if sp.name == "engine.sample_first":
                sampled.add(req)
    return [tails[r] for r in sorted(sampled)]


# ---------------------------------------------------------------------------
# the metrics (bench/metrics/<metric>.py reads each from spans_of(run))
# ---------------------------------------------------------------------------

def host_idle_ms_per_step(summary, spans: Sequence[HostSpan]
                          ) -> Optional[float]:
    """Chip-0 idle time of the window inside the program's spans, ms, over
    the window's decode steps: the device waiting on the engine's host
    work, per step."""
    steps = sum(map(is_decode_step, spans))
    if not steps:
        return None
    idle = idle_by_span(summary, spans)
    return 1e-6 * sum(v for k, v in idle.items() if is_program(k)) / steps


def prefill_stall_p90_ms(spans: Sequence[HostSpan]) -> Optional[float]:
    """90th percentile over the window's decode steps of the prefill time
    that ran since the previous decode step, ms: how long a clump of
    admissions holds every live slot's next token."""
    v = harness.percentile(prefill_stalls_ns(spans), 90)
    return None if v is None else 1e-6 * v


def prefill_tail_ms(spans: Sequence[HostSpan]) -> Optional[float]:
    """Mean over prefilled requests of ``kv.write_prefill`` +
    ``engine.sample_first``, ms."""
    tails = prefill_tails_ns(spans)
    return 1e-6 * sum(tails) / len(tails) if tails else None


def decode_batch_mean(spans: Sequence[HostSpan]) -> Optional[float]:
    """Mean live slots per decode step."""
    batches = [sp.stats["batch"] for sp in spans if is_decode_step(sp)]
    return sum(batches) / len(batches) if batches else None


# ---------------------------------------------------------------------------
# the whole breakdown, and a cut for tests
# ---------------------------------------------------------------------------

def report(summary, spans: Sequence[HostSpan]) -> Dict:
    """The metrics above and where chip 0's idle time went, by span
    (seconds), and each span's count and host seconds."""
    idle = idle_by_span(summary, spans)
    program = sum(v for k, v in idle.items() if is_program(k))
    in_step = program + idle.get("engine.step", 0.0)
    host: Dict[str, List[float]] = {}
    for sp in spans:
        h = host.setdefault(sp.name, [0, 0.0])
        h[0] += 1
        h[1] += (sp.end - sp.start) * 1e-9
    return {
        "window_s": summary.window_s, "busy_s": summary.chip_busy_s(0),
        "idle_by_span_s": {k: v * 1e-9 for k, v in
                           sorted(idle.items(), key=lambda kv: -kv[1])},
        "idle_in_engine_step_s": in_step * 1e-9,
        "program_share_of_step_idle": program / in_step if in_step else None,
        "decode_steps": sum(map(is_decode_step, spans)),
        "host_idle_ms_per_step": host_idle_ms_per_step(summary, spans),
        "prefill_stall_p90_ms": prefill_stall_p90_ms(spans),
        "prefill_tail_ms": prefill_tail_ms(spans),
        "decode_batch_mean": decode_batch_mean(spans),
        "host_spans": {k: {"count": n, "seconds": v}
                       for k, (n, v) in sorted(host.items())},
    }


def _stat_text(sid: int, value: float) -> str:
    kind = "int64_value" if isinstance(value, int) else "double_value"
    return f"stats {{ metadata_id: {sid} {kind}: {value} }}"


def text_proto(planes: Sequence[Tuple[str, Dict[str, list]]]) -> str:
    """An XSpace text proto (``ProfileData.from_text_proto`` reads it) of
    ``[(plane name, {line name: [(event name, start_ns, duration_ns,
    stats)]})]``."""
    out = []
    for pid, (pname, lines) in enumerate(planes, 1):
        names: Dict[str, int] = {}
        stat_ids: Dict[str, int] = {}
        out.append(f"planes {{ id: {pid} name: {json.dumps(pname)}")
        for lid, (lname, events) in enumerate(lines.items(), 1):
            out.append(f"  lines {{ id: {lid} name: {json.dumps(lname)} "
                       "timestamp_ns: 0")
            for name, start, dur, stats in events:
                mid = names.setdefault(name, len(names) + 1)
                st = " ".join(
                    _stat_text(stat_ids.setdefault(k, len(stat_ids) + 1), v)
                    for k, v in stats.items())
                out.append(f"    events {{ metadata_id: {mid} offset_ps: "
                           f"{int(round(start * 1e3))} duration_ps: "
                           f"{int(round(dur * 1e3))} {st}}}")
            out.append("  }")
        for name, mid in names.items():
            out.append(f"  event_metadata {{ key: {mid} value {{ id: {mid} "
                       f"name: {json.dumps(name)} }} }}")
        for name, sid in stat_ids.items():
            out.append(f"  stat_metadata {{ key: {sid} value {{ id: {sid} "
                       f"name: {json.dumps(name)} }} }}")
        out.append("}")
    return "\n".join(out) + "\n"


def cut(profile, summary, spans: Sequence[HostSpan]) -> str:
    """0.9 s of the trace from 5 ms before the first ``engine.step`` that
    prefills, as a text proto with ``window`` set to the cut: the host
    spans of the harness and the program, and chip 0's modules and ops."""
    first = min(sp.start for sp in spans if sp.name == "engine.prefill")
    step = max((s for s, e, n in summary.host
                if n == "engine.step" and s <= first), default=first)
    lo = step - 5e6
    hi = lo + CUT_NS
    keep = set(trace_mod.HOST_SPANS)
    planes = []
    for plane in profile.planes:
        if plane.name == trace_mod.HOST_PLANE:
            events = [(trace_mod.WINDOW_SPAN, lo, CUT_NS, {})]
            for line in plane.lines:
                events += [(ev.name, float(ev.start_ns),
                            float(ev.duration_ns), dict(ev.stats))
                           for ev in line.events
                           if (ev.name in keep or is_program(ev.name))
                           and float(ev.start_ns) < hi
                           and float(ev.start_ns + ev.duration_ns) > lo]
            planes.append((plane.name, {"python3": sorted(
                events, key=lambda x: x[1])}))
        elif plane.name == "/device:TPU:0":
            lines = {}
            for line in plane.lines:
                if line.name in (trace_mod.MODULES_LINE, trace_mod.OPS_LINE):
                    lines[line.name] = [
                        (ev.name, float(ev.start_ns), float(ev.duration_ns),
                         {}) for ev in line.events
                        if float(ev.start_ns) < hi
                        and float(ev.start_ns + ev.duration_ns) > lo]
            planes.append((plane.name, lines))
    return text_proto(planes)


def main() -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--trace-dir", default=str(harness.TRACE_DIR))
    ap.add_argument("--cut", help="write a 0.9 s cut here (.textproto.gz)")
    args = ap.parse_args()
    profile = trace_mod.load(Path(args.trace_dir))
    summary = trace_mod.summarize(profile, chips=1)
    spans = program_spans(profile, summary.window)
    print(json.dumps(report(summary, spans)), flush=True)
    if args.cut:
        with gzip.open(args.cut, "wt") as f:
            f.write(cut(profile, summary, spans))
    return 0


if __name__ == "__main__":
    sys.exit(main())
