"""Host-side span tracer with Chrome trace-event export.

The serve stack already fences and stamps every interesting edge — the
prefill and decode windows bracket ``jax.block_until_ready`` with
monotonic stamps, preemption/swap/migration measure their DMAs, the
router stamps submit and dispatch.  This tracer does nothing but record
those existing stamps as structured events (a list append per edge; no
device interaction, no extra fences), so tracing is observation-only by
construction: token streams are byte-identical with it on or off, the
same rule the roofline ledger obeys.

Durations go through :func:`span`, which also opens a
``jax.profiler.TraceAnnotation`` of the same name and stats: with the
profiler running, every span lands in its trace on the host clock the
device's operations share, so an idle stretch of the chip can be put
down to the host work around it.  Span names are ``<layer>.<what>``
(``sched.admit``, ``engine.prefill``, ``engine.decode.launch``,
``kv.write_prefill``, ...).

Export is the Chrome trace-event JSON format, loadable in
``chrome://tracing`` or https://ui.perfetto.dev: one *process* per
serving replica (pid = replica index; the router front door gets its own
pid), one *thread* per track — the engine's packed-step track, a
request-lifecycle track, and one track per decode slot — so a run opens
as a timeline with prefill chunks and decode windows as duration slices,
migrations as flow arrows between replica processes, and pool/attainment
counters charted above them.

Event vocabulary (kept deliberately small so the validator can be
strict):

* ``X`` duration slices for the serial host work of a step and the
  device windows inside it — admission, prefill and its page writes,
  decode staging/launch/fence/commit, verify/propose steps, swap/migrate
  DMAs.  On one track these never partially overlap (they nest), which
  :func:`validate_trace` enforces.
* ``b``/``e`` async pairs (per request id) for request lifetimes —
  allowed to overlap arbitrarily.
* ``i`` instants for point edges: submit, dispatch, placement, first
  token, preemption.
* ``s``/``f`` flow pairs linking a migration's export on the source
  replica to its restore on the destination.
* ``C`` counters (pool pages in use, live roofline attainment).
* ``M`` metadata naming every process and thread.
"""

from __future__ import annotations

import json
import math
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation

from . import clock

# Track (tid) layout inside each replica process.  Slot tracks start at
# SLOT_TID0 so engine/lifecycle tracks sort above them in the viewer.
ENGINE_TID = 0          # admission and the packed decode/verify step
LIFECYCLE_TID = 1       # request instants + async request spans
SLOT_TID0 = 10          # per-slot prefill/swap/migrate spans
ROUTER_PID = 999        # the front door is its own process


class Tracer:
    """Append-only event recorder over the shared monotonic clock.

    All ``t``/``t0``/``t1`` arguments are raw :func:`repro.obs.clock.now`
    stamps; the tracer subtracts its ``epoch`` (set at construction, or
    shared explicitly so multi-replica timelines align) and renders
    microseconds, the trace-event unit."""

    def __init__(self, epoch: Optional[float] = None):
        self.epoch = clock.now() if epoch is None else epoch
        self.events: List[Dict[str, Any]] = []
        self._named: set = set()          # de-dup (kind, pid, tid) metadata

    # -- time ------------------------------------------------------------

    def _us(self, t: float) -> float:
        return max((t - self.epoch) * 1e6, 0.0)

    # -- metadata --------------------------------------------------------

    def process(self, pid: int, name: str) -> None:
        key = ("process", pid)
        if key in self._named:
            # re-announce (e.g. a sharded engine learns its tp width
            # after construction): last metadata event wins in the viewer
            self.events.append({"ph": "M", "name": "process_name",
                                "pid": pid, "tid": 0, "ts": 0,
                                "args": {"name": name}})
            return
        self._named.add(key)
        self.events.append({"ph": "M", "name": "process_name", "pid": pid,
                            "tid": 0, "ts": 0, "args": {"name": name}})

    def thread(self, pid: int, tid: int, name: str) -> None:
        key = ("thread", pid, tid)
        if key in self._named:
            return
        self._named.add(key)
        self.events.append({"ph": "M", "name": "thread_name", "pid": pid,
                            "tid": tid, "ts": 0, "args": {"name": name}})

    # -- events ----------------------------------------------------------

    def span(self, name: str, pid: int, tid: int, t0: float, t1: float,
             **args) -> None:
        self.events.append({"ph": "X", "name": name, "pid": pid,
                            "tid": tid, "ts": self._us(t0),
                            "dur": max((t1 - t0) * 1e6, 0.0),
                            "args": args})

    def instant(self, name: str, pid: int, tid: int, t: float,
                **args) -> None:
        self.events.append({"ph": "i", "name": name, "pid": pid,
                            "tid": tid, "ts": self._us(t), "s": "t",
                            "args": args})

    def counter(self, name: str, pid: int, t: float,
                values: Dict[str, float]) -> None:
        self.events.append({"ph": "C", "name": name, "pid": pid, "tid": 0,
                            "ts": self._us(t), "args": dict(values)})

    def async_begin(self, name: str, pid: int, tid: int, id_: int,
                    t: float, **args) -> None:
        self.events.append({"ph": "b", "cat": "serve", "name": name,
                            "pid": pid, "tid": tid, "id": id_,
                            "ts": self._us(t), "args": args})

    def async_end(self, name: str, pid: int, tid: int, id_: int,
                  t: float, **args) -> None:
        self.events.append({"ph": "e", "cat": "serve", "name": name,
                            "pid": pid, "tid": tid, "id": id_,
                            "ts": self._us(t), "args": args})

    def flow_start(self, name: str, pid: int, tid: int, id_: int,
                   t: float, **args) -> None:
        self.events.append({"ph": "s", "cat": "serve", "name": name,
                            "pid": pid, "tid": tid, "id": id_,
                            "ts": self._us(t), "args": args})

    def flow_finish(self, name: str, pid: int, tid: int, id_: int,
                    t: float, **args) -> None:
        self.events.append({"ph": "f", "cat": "serve", "name": name,
                            "pid": pid, "tid": tid, "id": id_, "bp": "e",
                            "ts": self._us(t), "args": args})

    # -- export ----------------------------------------------------------

    def export(self, path: Optional[str] = None) -> Dict[str, Any]:
        """The Chrome trace-event document; written to ``path`` when
        given.  Exports a copy — the tracer keeps recording."""
        doc = {"displayTimeUnit": "ms",
               "traceEvents": list(self.events)}
        if path is not None:
            with open(path, "w") as f:
                json.dump(doc, f)
        return doc


class _Span:
    """One host span, written to two traces under one name and one set of
    stats: always a ``jax.profiler.TraceAnnotation`` (so it lands in any
    profiler trace on the host clock the device's ``XLA Ops`` share), and
    a Chrome ``X`` event when a :class:`~repro.obs.Telemetry` bundle is
    attached.  ``t0``/``t1`` are the :func:`clock.now` stamps at entry and
    exit, for callers whose phase walls must be the span's own interval.
    ``set`` adds stats known only inside the span; ``tid`` may be set
    before exit when the Chrome track is known only then; ``drop`` keeps
    the Chrome event out when the work the span names did not happen."""

    __slots__ = ("obs", "name", "pid", "tid", "stats", "t0", "t1", "_ann")

    def __init__(self, obs, name: str, pid: int, tid: int, stats):
        self.obs, self.name, self.pid, self.tid = obs, name, pid, tid
        self.stats = stats
        self.t0 = self.t1 = 0.0

    def set(self, **stats) -> None:
        self.stats.update(stats)
        self._ann.set_metadata(**stats)

    def drop(self) -> None:
        self.obs = None

    def __enter__(self) -> "_Span":
        self._ann = TraceAnnotation(self.name, **self.stats)
        self._ann.__enter__()
        self.t0 = clock.now()
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = clock.now()
        self._ann.__exit__(*exc)
        if self.obs is not None:
            self.obs.tracer.span(self.name, self.pid, self.tid, self.t0,
                                 self.t1, **self.stats)


def span(obs, name: str, pid: int = 0, tid: int = ENGINE_TID,
         **stats) -> _Span:
    """``with span(engine.obs, "engine.decode", pid, batch=n):`` — see
    :class:`_Span`.  Costs about a microsecond with no profiler running
    and ``obs`` None.  Names are ``<layer>.<what>``."""
    return _Span(obs, name, pid, tid, stats)


_REQUIRED = ("ph", "name", "pid", "tid")


def validate_trace(doc: Dict[str, Any]) -> List[str]:
    """Schema check for an exported trace — the CI gate.

    Returns a list of human-readable problems (empty = valid):

    * top-level shape (``traceEvents`` list + ``displayTimeUnit``),
    * every event carries ph/name/pid/tid and a finite ``ts >= 0``,
    * duration slices have finite ``dur >= 0`` and, per track, never
      *partially* overlap (proper nesting is fine — that is the
      trace-viewer stacking contract; a partial overlap means two
      "serial" device windows claimed the same wall time),
    * every pid/tid that carries events is named by ``M`` metadata,
    * async ``b``/``e`` pairs balance per (name, id) with ``e`` no
      earlier than ``b``; flow ``s``/``f`` ids pair up with ``f`` no
      earlier than ``s`` — no orphan ids anywhere.
    """
    errors: List[str] = []
    if not isinstance(doc, dict) or "traceEvents" not in doc:
        return ["trace document must be a dict with a 'traceEvents' list"]
    if "displayTimeUnit" not in doc:
        errors.append("missing displayTimeUnit")
    events = doc["traceEvents"]
    if not isinstance(events, list) or not events:
        return errors + ["traceEvents must be a non-empty list"]

    named_p, named_t = set(), set()
    used_p, used_t = set(), set()
    spans: Dict[tuple, List[tuple]] = {}
    asyncs: Dict[tuple, List[tuple]] = {}
    flows: Dict[Any, Dict[str, List[float]]] = {}
    for i, ev in enumerate(events):
        missing = [k for k in _REQUIRED if k not in ev]
        if missing:
            errors.append(f"event {i}: missing keys {missing}")
            continue
        ph, name = ev["ph"], ev["name"]
        pid, tid = ev["pid"], ev["tid"]
        if ph == "M":
            if name == "process_name":
                named_p.add(pid)
            elif name == "thread_name":
                named_t.add((pid, tid))
            continue
        ts = ev.get("ts")
        if not isinstance(ts, (int, float)) or not math.isfinite(ts) \
                or ts < 0:
            errors.append(f"event {i} ({name!r}): bad ts {ts!r}")
            continue
        used_p.add(pid)
        used_t.add((pid, tid))
        if ph == "X":
            dur = ev.get("dur")
            if not isinstance(dur, (int, float)) or not math.isfinite(dur) \
                    or dur < 0:
                errors.append(f"event {i} ({name!r}): bad dur {dur!r}")
                continue
            spans.setdefault((pid, tid), []).append((ts, ts + dur, name))
        elif ph in ("b", "e"):
            asyncs.setdefault((name, ev.get("id")), []).append((ts, ph))
        elif ph in ("s", "f"):
            flows.setdefault(ev.get("id"), {"s": [], "f": []})[ph].append(ts)
        elif ph not in ("i", "C"):
            errors.append(f"event {i} ({name!r}): unknown phase {ph!r}")

    for pid in sorted(used_p):
        if pid not in named_p:
            errors.append(f"pid {pid} has events but no process_name")
    for pid, tid in sorted(used_t):
        if (pid, tid) not in named_t:
            errors.append(f"pid {pid} tid {tid} has events but no "
                          "thread_name")

    # monotone-span check: per track, sorted slices must nest like a
    # call stack — a slice starting inside its predecessor must also end
    # inside it
    for (pid, tid), sl in spans.items():
        sl.sort()
        stack: List[tuple] = []
        for t0, t1, name in sl:
            while stack and t0 >= stack[-1][1]:
                stack.pop()
            if stack and t1 > stack[-1][1] + 1e-6:
                errors.append(
                    f"pid {pid} tid {tid}: span {name!r} "
                    f"[{t0:.1f}, {t1:.1f}]us partially overlaps "
                    f"{stack[-1][2]!r} ending at {stack[-1][1]:.1f}us")
            stack.append((t0, t1, name))

    for (name, id_), evs in asyncs.items():
        n_b = sum(1 for _, ph in evs if ph == "b")
        n_e = len(evs) - n_b
        if n_b != n_e:
            errors.append(f"async {name!r} id {id_}: {n_b} begins vs "
                          f"{n_e} ends (orphan id)")
        elif evs and max(ts for ts, ph in evs if ph == "e") < \
                min(ts for ts, ph in evs if ph == "b"):
            errors.append(f"async {name!r} id {id_}: end precedes begin")
    for id_, ends in flows.items():
        if not ends["s"] or not ends["f"]:
            errors.append(f"flow id {id_}: orphan "
                          f"({len(ends['s'])} starts, "
                          f"{len(ends['f'])} finishes)")
        elif min(ends["f"]) < min(ends["s"]) - 1e-6:
            errors.append(f"flow id {id_}: finish precedes start")
    return errors
