"""Retrace sanitizer: name the argument that forced a recompile.

The zero-recompile serving invariants (DESIGN.md §8-§10) used to be
enforced as bare ``fn._cache_size() == 1`` asserts — a failure told you the
count grew but not *why*. The PR 4 weak-type flip (a solved trim came back
``weak_type=True`` and silently forced one extra trace of the whole serving
step) took a debugging session to localize. This module turns that class of
bug into a one-line error:

    with tracecheck.capture() as rec:
        eng = VisionEngine(...)
        list(eng.stream(batches))
        tracecheck.assert_jit_cache(eng._step, 1, recorder=rec)

On failure the assert names the offending argument by its jit debug path::

    RetraceError: eng._step traced 2x (expected 1). Trace #2 differs from
    trace #1 in 1 of 37 arguments:
      params['p2m']['cal_trim']: f32[32] (weak_type False -> True)

Implementation: while a :class:`TraceRecorder` is active, every fresh jit
trace (a miss of the C++ fast-path cache) is recorded with the function
identity, the jit debug-info argument names, and the input avals. The hook
point is ``jax._src.interpreters.partial_eval.trace_to_jaxpr`` — the cached
function every jit trace funnels through; a call counts as a fresh trace
when it raised the cache's miss count. The recorder restores the original
on exit and is reentrant (nested captures share one patch).
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from jax._src.interpreters import partial_eval as _pe


class RetraceError(AssertionError):
    """A jitted function compiled more often than the invariant allows."""


@dataclasses.dataclass(frozen=True)
class TraceEvent:
    """One fresh trace of one jitted callable."""
    fun: Callable                 # the callable under the jit wrapper
    name: str                     # debug_info func_src_info ("f at file:ln")
    arg_names: Tuple[str, ...]    # per-flat-argument jit debug paths
    avals: Tuple                  # matching flat input avals

    @property
    def is_jax_internal(self) -> bool:
        """Traces of jax's own api-level jits (jnp.add etc.) — noise for
        repo invariants, filtered from ``no_retrace`` enforcement."""
        return "/jax/_src/" in self.name or "/jax/experimental/" in self.name


def _aval_str(a) -> str:
    s = a.str_short() if hasattr(a, "str_short") else str(a)
    if getattr(a, "weak_type", False):
        s += "{weak}"
    return s


def diff_avals(prev: TraceEvent, new: TraceEvent) -> List[str]:
    """Human-readable per-argument diff between two traces' input avals.

    Arguments are matched by jit debug path (``params['p2m']['w']``-style),
    so a pytree-structure change shows up as added/removed names rather
    than a misaligned positional diff.
    """
    lines: List[str] = []
    pv = dict(zip(prev.arg_names, prev.avals))
    nv = dict(zip(new.arg_names, new.avals))
    for name in prev.arg_names:
        if name not in nv:
            lines.append(f"{name}: removed (was {_aval_str(pv[name])})")
    for name in new.arg_names:
        if name not in pv:
            lines.append(f"{name}: added ({_aval_str(nv[name])})")
            continue
        a, b = pv[name], nv[name]
        if a == b:
            continue
        detail = []
        if getattr(a, "shape", None) != getattr(b, "shape", None):
            detail.append(f"shape {getattr(a, 'shape', '?')} -> "
                          f"{getattr(b, 'shape', '?')}")
        if getattr(a, "dtype", None) != getattr(b, "dtype", None):
            detail.append(f"dtype {getattr(a, 'dtype', '?')} -> "
                          f"{getattr(b, 'dtype', '?')}")
        if getattr(a, "weak_type", None) != getattr(b, "weak_type", None):
            detail.append(f"weak_type {getattr(a, 'weak_type', '?')} -> "
                          f"{getattr(b, 'weak_type', '?')}")
        if not detail:            # some other aval field (sharding, vma...)
            detail.append(f"{_aval_str(a)} -> {_aval_str(b)}")
        lines.append(f"{name}: " + ", ".join(detail))
    if not lines:
        lines.append("(avals identical — the retrace was forced by a "
                     "static argument, a new donate/sharding spec, or a "
                     "jax config flag change)")
    return lines


# one process-wide patch shared by nested recorders
_LOCK = threading.Lock()
_ACTIVE: List["TraceRecorder"] = []
_ORIG = None


def _install() -> None:
    global _ORIG
    if _ORIG is not None:
        return
    _ORIG = orig = _pe.trace_to_jaxpr

    def recording_trace_to_jaxpr(fun, in_avals, debug_info, *rest):
        misses = orig.cache_info().misses
        out = orig(fun, in_avals, debug_info, *rest)
        # a hit runs nothing; a miss traced ``fun`` (and any jit nested in
        # it, which recorded itself first)
        if orig.cache_info().misses == misses:
            return out
        try:
            ev = TraceEvent(fun=fun,
                            name=getattr(debug_info, "func_src_info", None)
                            or getattr(fun, "__name__", repr(fun)),
                            arg_names=tuple(getattr(debug_info, "arg_names",
                                                    ()) or ()),
                            avals=tuple(in_avals))
        except Exception:           # never let telemetry break tracing
            return out
        for rec in list(_ACTIVE):
            rec._record(ev)         # no_retrace enforcement may raise here
        return out

    # jit internals call attributes of this symbol (cache_clear /
    # evict_weakref, e.g. from jit.clear_cache) — forward them
    for attr in ("cache_clear", "cache_info", "evict_weakref"):
        setattr(recording_trace_to_jaxpr, attr, getattr(orig, attr))
    _pe.trace_to_jaxpr = recording_trace_to_jaxpr


def _uninstall() -> None:
    global _ORIG
    if _ORIG is not None and not _ACTIVE:
        _pe.trace_to_jaxpr = _ORIG
        _ORIG = None


class TraceRecorder:
    """Records every fresh jit trace between ``__enter__``/``__exit__``.

    ``on_retrace`` (optional) is called with ``(prev, new)`` TraceEvents the
    moment a non-jax-internal callable traces a second time — this is how
    :func:`no_retrace` raises at the offending call instead of at the end.
    """

    def __init__(self, on_retrace: Optional[Callable[[TraceEvent,
                                                      TraceEvent],
                                                     None]] = None):
        self.events: List[TraceEvent] = []
        self._by_fun: Dict[int, List[TraceEvent]] = {}
        self._funs: Dict[int, Callable] = {}   # keep identity keys alive
        self._on_retrace = on_retrace

    # -- recording ----------------------------------------------------------
    def _record(self, ev: TraceEvent) -> None:
        self.events.append(ev)
        key = id(ev.fun)
        self._funs[key] = ev.fun
        hist = self._by_fun.setdefault(key, [])
        hist.append(ev)
        if (self._on_retrace is not None and len(hist) > 1
                and not ev.is_jax_internal):
            self._on_retrace(hist[-2], ev)

    def __enter__(self) -> "TraceRecorder":
        with _LOCK:
            _install()
            _ACTIVE.append(self)
        return self

    def __exit__(self, *exc) -> None:
        with _LOCK:
            if self in _ACTIVE:
                _ACTIVE.remove(self)
            _uninstall()

    # -- queries ------------------------------------------------------------
    @staticmethod
    def _unwrap(fn) -> Callable:
        """The callable identity a jitted function's traces are keyed by."""
        return getattr(fn, "__wrapped__", fn)

    def traces_of(self, fn) -> List[TraceEvent]:
        """All trace events of ``fn`` (a jitted function or the raw
        callable under it) seen while this recorder was active."""
        return list(self._by_fun.get(id(self._unwrap(fn)), []))

    def explain_retraces(self, fn) -> Optional[str]:
        """Per-retrace aval diff for ``fn``; None if it traced <= 1 time."""
        hist = self.traces_of(fn)
        if len(hist) <= 1:
            return None
        out = [f"{hist[0].name} traced {len(hist)}x while recording:"]
        for i in range(1, len(hist)):
            diff = diff_avals(hist[i - 1], hist[i])
            out.append(f"  trace #{i + 1} vs #{i} "
                       f"({len(diff)} of {len(hist[i].arg_names)} "
                       "arguments differ):")
            out.extend("    " + d for d in diff)
        return "\n".join(out)


def capture() -> TraceRecorder:
    """``with tracecheck.capture() as rec:`` — record traces for later
    :func:`assert_jit_cache` / :meth:`TraceRecorder.explain_retraces`."""
    return TraceRecorder()


@contextlib.contextmanager
def no_retrace(allow: Sequence[Callable] = ()):
    """Context manager: every distinct callable may trace AT MOST once.

    A second trace of any non-jax-internal function raises
    :class:`RetraceError` at the offending call site, with the aval diff
    naming the argument that changed. ``allow`` lists callables (jitted or
    raw) that are expected to retrace (e.g. a deliberate warm/cold pair).
    """
    allowed = {id(TraceRecorder._unwrap(f)) for f in allow}

    def on_retrace(prev: TraceEvent, new: TraceEvent) -> None:
        if id(new.fun) in allowed:
            return
        diff = diff_avals(prev, new)
        raise RetraceError(
            f"unexpected retrace of {new.name}: "
            f"{len(diff)} argument(s) changed since the previous trace:\n"
            + "\n".join("  " + d for d in diff))

    with TraceRecorder(on_retrace=on_retrace) as rec:
        yield rec


def assert_jit_cache(fn, expected: int = 1, *, le: bool = False,
                     recorder: Optional[TraceRecorder] = None,
                     what: Optional[str] = None) -> None:
    """Assert a jitted function's cache size — with a *why* on failure.

    ``expected`` is the exact cache size (or an upper bound with
    ``le=True``). When the assert fails and a :class:`TraceRecorder` that
    was active around the calls is passed as ``recorder``, the error names
    which argument's aval changed between the traces (the PR 4 weak-type
    flip class); without one it still reports the count plus instructions.

    ``what`` labels the function in the message (defaults to its jit debug
    name).
    """
    size = fn._cache_size()
    ok = size <= expected if le else size == expected
    if ok:
        return
    label = what or getattr(fn, "__name__", None) or repr(fn)
    rel = "<=" if le else "=="
    msg = [f"jit cache of {label} is {size}, expected {rel} {expected}."]
    explained = recorder.explain_retraces(fn) if recorder is not None \
        else None
    if explained is not None:
        msg.append(explained)
    else:
        msg.append(
            "No trace recording available for the offending traces — rerun "
            "the failing calls inside `with tracecheck.capture() as rec:` "
            "and pass `recorder=rec` to see which argument changed.")
    raise RetraceError("\n".join(msg))
