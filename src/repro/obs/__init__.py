"""Serve-stack observability: span tracing, metrics, live attainment.

One :class:`Telemetry` bundle ties the three pieces together:

* :class:`~repro.obs.trace.Tracer` — Chrome trace-event spans for every
  lifecycle edge the stack already stamps (Perfetto/chrome://tracing);
  :func:`~repro.obs.trace.span` writes each span to it and, as a
  ``jax.profiler.TraceAnnotation``, to any running profiler trace,
* :class:`~repro.obs.metrics.Registry` — counters/gauges/histograms
  projected from the ledgers/pool stats/latency traces the stack
  already keeps, with Prometheus text exposition,
* :class:`~repro.obs.attainment.AttainmentTracker` — windowed roofline
  attainment ("what fraction of which roof, right now") from ledger
  deltas.

An ``Engine`` owns a private bundle when ``EngineConfig.telemetry`` is
on; a ``Cluster`` builds one shared bundle and attaches it to every
replica so all replicas land on one timeline (pid = replica index) and
one registry.  Everything in this package is observation-only: hooks
are host-side list appends/dict updates behind ``if obs is not None``,
never a device op or an extra fence — token streams are byte-identical
with telemetry on or off.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from . import clock
from .attainment import AttainmentTracker, AttainmentWindow
from .metrics import Registry, harvest_serve
from .trace import (ENGINE_TID, LIFECYCLE_TID, ROUTER_PID, SLOT_TID0,
                    Tracer, span, validate_trace)

__all__ = [
    "Telemetry", "Tracer", "span", "validate_trace", "Registry",
    "harvest_serve",
    "AttainmentTracker", "AttainmentWindow", "clock",
    "ENGINE_TID", "LIFECYCLE_TID", "SLOT_TID0", "ROUTER_PID",
]


class Telemetry:
    """The bundle an engine/cluster threads through its hooks.

    ``on_step`` is the per-step hot(ish) path: a pool-occupancy counter
    sample plus an attainment tick; everything else happens on lifecycle
    edges or at harvest time.
    """

    def __init__(self, window_steps: int = 4,
                 epoch: Optional[float] = None):
        self.tracer = Tracer(epoch=epoch)
        self.registry = Registry()
        self.attainment = AttainmentTracker(window_steps=window_steps)
        self._seen: set = set()        # request ids already observed

    # -- per-step ---------------------------------------------------------

    def on_step(self, engine) -> None:
        pid = getattr(engine, "_obs_pid", 0)
        t = clock.now()
        kv = getattr(engine, "_kv", None)
        if kv is not None:
            self.tracer.counter(
                "pool_pages", pid, t,
                {"in_use": kv.pool.num_pages - 1 - kv.pool.free_page_count})
        w = self.attainment.tick(engine, pid)
        if w is not None:
            self._publish(w)

    def _publish(self, w: AttainmentWindow) -> None:
        self.tracer.counter(
            "roofline_attainment", w.pid, w.t_end,
            {"fraction_of_binding": w.fraction})
        self.attainment.publish(self.registry, w)

    # -- harvest / export -------------------------------------------------

    def harvest(self, source) -> None:
        """Fold a serving source (Engine or Cluster) into the registry,
        closing any partial attainment windows first so short runs still
        report at least one."""
        from .metrics import _engines
        for i, eng in enumerate(_engines(source)):
            w = self.attainment.flush(eng, getattr(eng, "_obs_pid", i))
            if w is not None:
                self._publish(w)
        harvest_serve(self.registry, source, seen=self._seen)

    def export_trace(self, path: Optional[str] = None) -> Dict[str, Any]:
        return self.tracer.export(path)

    def snapshot(self, path: Optional[str] = None) -> str:
        text = self.registry.expose()
        if path is not None:
            with open(path, "w") as f:
                f.write(text)
        return text
