"""Span tracing + structured events, Chrome-trace compatible.

A :class:`Tracer` records two record kinds:

* **Spans** — ``with tracer.span("microbatch", frames=8):`` blocks with a
  start timestamp and duration. Nesting is tracked host-side (a span
  stack), and each span also enters ``jax.profiler.TraceAnnotation`` (its
  args as the annotation's stats) so a device profile
  (``jax.profiler.trace``) carries the *same* names as the host trace, on
  the profiler's clock — one vocabulary for both. Device intervals come
  from that profile, never from host-side guesses.
* **Events** — instantaneous structured facts (``recalibration``,
  ``drift_guard_fallback``, ``fleet_join`` ...) with chip_id attribution
  in their args.

Export is Chrome Trace Event Format (one JSON object per JSONL line,
phase ``"X"`` complete spans / ``"i"`` instants, timestamps in µs since
the tracer epoch) — loadable in ``chrome://tracing`` / Perfetto after
wrapping in ``{"traceEvents": [...]}``, which ``python -m repro.obs
chrome`` does.
"""
from __future__ import annotations

import contextlib
from typing import Any, Dict, Iterator, List, Optional

from repro.obs import clock

try:                                    # jax always present in this repo;
    from jax.profiler import TraceAnnotation as _TraceAnnotation
except Exception:                       # keep the tracer importable anyway
    _TraceAnnotation = None


class Tracer:
    """Host-side span/event recorder with a fixed epoch.

    ``device_annotations=False`` skips ``jax.profiler.TraceAnnotation``
    (it is cheap, but tests that count host work want the tracer inert).
    """

    def __init__(self, device_annotations: bool = True):
        self.epoch = clock.now()
        self.records: List[Dict[str, Any]] = []
        self._stack: List[str] = []
        self._device_annotations = (device_annotations
                                    and _TraceAnnotation is not None)

    # -- helpers ------------------------------------------------------------
    def _us(self, t: float) -> float:
        return (t - self.epoch) * 1e6

    @property
    def depth(self) -> int:
        return len(self._stack)

    # -- spans --------------------------------------------------------------
    @contextlib.contextmanager
    def span(self, name: str, **args: Any) -> Iterator[None]:
        t0 = clock.now()
        self._stack.append(name)
        ann = (_TraceAnnotation(name, **args) if self._device_annotations
               else contextlib.nullcontext())
        try:
            with ann:
                yield
        finally:
            self._stack.pop()
            t1 = clock.now()
            self.records.append({
                "ph": "X", "name": name, "cat": "span",
                "ts": self._us(t0), "dur": (t1 - t0) * 1e6,
                "pid": 0, "tid": "host", "depth": len(self._stack),
                "args": args,
            })

    def mark(self, name: str) -> None:
        """A zero-length annotation on the profiler's clock, and nothing in
        ``records``: a device trace counts the marks inside its window."""
        if self._device_annotations:
            with _TraceAnnotation(name):
                pass

    # -- events -------------------------------------------------------------
    def event(self, name: str, **args: Any) -> None:
        """Record an instantaneous structured event."""
        self.records.append({
            "ph": "i", "name": name, "cat": "event", "s": "p",
            "ts": self._us(clock.now()),
            "pid": 0, "tid": "host", "depth": len(self._stack),
            "args": args,
        })

    # -- queries ------------------------------------------------------------
    def spans(self, name: Optional[str] = None) -> List[Dict[str, Any]]:
        return [r for r in self.records
                if r["ph"] == "X" and (name is None or r["name"] == name)]

    def events(self, name: Optional[str] = None) -> List[Dict[str, Any]]:
        return [r for r in self.records
                if r["ph"] == "i" and (name is None or r["name"] == name)]
