"""repro.obs — off-path serving telemetry (DESIGN.md §12).

One facade object threads through the serving stack::

    obs = Obs()
    eng = VisionEngine(cfg, params, backend="pallas", obs=obs)
    for out in eng.stream(batches):
        ...
    obs.export_jsonl("serve.jsonl")
    print(obs.exposition())

Everything is opt-in and host-side: engines take ``obs=None`` by default
and guard each instrument call with a single ``is None`` check, so the
disabled path has zero cost — bit-identical outputs, unchanged jit
caches, unchanged op census (all three are tested). Submodules:

* :mod:`repro.obs.clock` — the single-sourced wall clock and the
  deferred-readiness :class:`~repro.obs.clock.WallProbe` that moves
  latency syncs off the dispatch path.
* :mod:`repro.obs.metrics` — counters / gauges / log-bucket streaming
  histograms (p50/p95/p99 without storing samples).
* :mod:`repro.obs.trace` — span tracing + structured events in Chrome
  trace format, mirrored to ``jax.profiler.TraceAnnotation``.
* :mod:`repro.obs.compiles` — ``jax_compiles_total``, the programs JAX
  loaded (compiled or read from the persistent cache) while the ``Obs``
  lived.
* :mod:`repro.obs.export` — JSONL sink, Prometheus-style exposition,
  and the shared ``BENCH_*.json`` meta block.
"""
from __future__ import annotations

import contextlib
from typing import Any, ContextManager, Dict, List, Optional

from repro.obs import clock, compiles, export, metrics, trace
from repro.obs.clock import ProbeSet, WallProbe
from repro.obs.export import bench_meta
from repro.obs.metrics import Counter, Gauge, Histogram, MetricsRegistry
from repro.obs.trace import Tracer

__all__ = ["Obs", "bench_meta", "clock", "compiles", "export",
           "metrics", "trace",
           "Counter", "Gauge", "Histogram", "MetricsRegistry",
           "ProbeSet", "Tracer", "WallProbe"]


class Obs:
    """Facade bundling one metrics registry and one tracer.

    ``tracing=False`` keeps metrics but makes spans/events no-ops;
    ``device_annotations=False`` keeps host spans but skips
    ``jax.profiler.TraceAnnotation``. Every ``Obs`` counts JAX's program
    loads into ``jax_compiles_total`` (:mod:`repro.obs.compiles`).
    """

    def __init__(self, tracing: bool = True,
                 device_annotations: bool = True):
        self.registry = MetricsRegistry()
        compiles.watch(self.registry)
        self.tracer: Optional[Tracer] = (
            Tracer(device_annotations=device_annotations) if tracing
            else None)

    # -- metrics ------------------------------------------------------------
    def counter(self, name: str) -> Counter:
        return self.registry.counter(name)

    def gauge(self, name: str) -> Gauge:
        return self.registry.gauge(name)

    def histogram(self, name: str, **kwargs: Any) -> Histogram:
        return self.registry.histogram(name, **kwargs)

    # -- tracing ------------------------------------------------------------
    def span(self, name: str, **args: Any) -> ContextManager[None]:
        if self.tracer is None:
            return contextlib.nullcontext()
        return self.tracer.span(name, **args)

    def mark(self, name: str) -> None:
        if self.tracer is not None:
            self.tracer.mark(name)

    def event(self, name: str, **args: Any) -> None:
        if self.tracer is not None:
            self.tracer.event(name, **args)

    # -- export -------------------------------------------------------------
    def records(self, meta: Optional[Dict[str, Any]] = None
                ) -> List[Dict[str, Any]]:
        """Everything as JSONL-ready records: meta, then trace, then
        one ``metric`` record per instrument."""
        out: List[Dict[str, Any]] = [
            {"ph": "M", "cat": "meta",
             "meta": meta if meta is not None else bench_meta("obs")}]
        if self.tracer is not None:
            out.extend(self.tracer.records)
        for name, snap in self.registry.snapshot().items():
            out.append({"ph": "C", "cat": "metric", "name": name, **snap})
        return out

    def export_jsonl(self, path: str,
                     meta: Optional[Dict[str, Any]] = None) -> int:
        return export.write_jsonl(path, self.records(meta))

    def exposition(self) -> str:
        return export.prometheus_text(self.registry)

    def summary(self) -> Dict[str, Any]:
        """Metrics snapshot + span/event counts, for quick inspection."""
        out: Dict[str, Any] = {"metrics": self.registry.snapshot()}
        if self.tracer is not None:
            spans: Dict[str, int] = {}
            events: Dict[str, int] = {}
            for r in self.tracer.records:
                bucket = spans if r["ph"] == "X" else events
                bucket[r["name"]] = bucket.get(r["name"], 0) + 1
            out["spans"] = spans
            out["events"] = events
        return out
