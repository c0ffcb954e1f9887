"""Program loads counted into every live ``Obs``: ``jax_compiles_total``.

JAX reports each program it loads through ``jax.monitoring``:
``/jax/core/compile/backend_compile_duration`` times the backend step of a
jit's first call for a shape, whether XLA compiles afresh or the persistent
compilation cache supplies the executable (that lookup happens inside the
timed step, where ``/jax/compilation_cache/cache_hits`` also fires). So one
such event is one program loaded at that moment, and a serving window with
every shape warmed up should see none.

One listener is registered per process, the first time an ``Obs`` is
made, and forwards to the registries of the ``Obs`` objects still alive
(held weakly): making many ``Obs`` never stacks listeners. Each load also
leaves a ``jax_compile`` mark (``jax.profiler.TraceAnnotation``) on the
profiler's clock, so a device trace tells which loads fell inside it.
"""
from __future__ import annotations

import weakref

import jax

from repro.obs.metrics import MetricsRegistry

COUNTER = "jax_compiles_total"
EVENT = "/jax/core/compile/backend_compile_duration"
MARK = "jax_compile"

_live: "weakref.WeakSet[MetricsRegistry]" = weakref.WeakSet()
_listening = False


def _on_duration(event: str, duration: float, **kwargs) -> None:
    if event == EVENT:
        registries = list(_live)
        for registry in registries:
            registry.counter(COUNTER).inc()
        if registries:
            with jax.profiler.TraceAnnotation(MARK):
                pass


def watch(registry: MetricsRegistry) -> None:
    """Count every program load from now on into ``registry``'s
    ``jax_compiles_total`` (made at the first load) for as long as the
    registry lives."""
    global _listening
    _live.add(registry)
    if not _listening:
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _listening = True
