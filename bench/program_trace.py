"""From a profiler trace of the window to the program's own stages.

``trace_reduce`` names device time by kernel and idle gaps by whatever the
host thread was doing; this module names both by the program's stages, as
the configuration's kind (``kinds/``) names them:

scope_s   device seconds of the operations under each of the model's
          device scopes (the kind's ``SCOPES``: for ``p2m_vgg``
          ``p2m_frontend``, each ``backbone/<layer>``, ``head``), a stage's
          seconds (``backbone``) including its layers'; ``""`` for
          operations under none
span_s    host seconds inside each of the engine's spans (the kind's
          ``SPANS``): which spans the program has at all
span_n    how many of each span the window holds
idle_s    the idle gaps of ``trace_reduce.reduce`` (the same gaps), each put
          down to the innermost engine span that covers it, or to
          ``harness`` when none does
top_gaps  the longest gaps: [seconds, engine span, innermost host event]

A TPU's "XLA Ops" events carry no op name, only the HLO instruction's
name, which is unique within its program. So an operation's scope is
looked up in the optimized HLO text of the program it ran in (the
``op_name`` metadata of that instruction, ``jit(_forward)/backbone/conv3/
...``), the program being the "XLA Modules" event that covers it on the
same device. A program without these scopes or spans (an older one) gives
empty tables, never an error. Checked on synthetic events and on a
recorded chip trace in ``tests/``.

``of_window`` is what ``trace_reduce.Window.stop`` calls, once per traced
run, on the events it has already loaded and the served engine's own step
programs (the kind server's ``step_programs``). It adds, for each of the
kind's ``MARKS`` (``compiles``: a mark per program load; ``syncs``: a mark
per host sync), how many the window holds, under ``marks`` (None where
the program makes no such mark). The metric readers find the result with
``of_run``.
"""
from __future__ import annotations

import bisect
import heapq
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from bench import trace_reduce

HARNESS = "harness"
_HLO_MODULE = re.compile(r"^HloModule ([^\s,]+)", re.M)
_HLO_OP = re.compile(
    r'^\s*(?:ROOT )?%?([\w.\-]+) = [^\n]*?op_name="([^"]*)"', re.M)
_RUN_ID = re.compile(r"\(.*\)$")


def scope_of(op_name: str, pattern: re.Pattern) -> str:
    """The innermost model scope ``pattern`` finds in an op name, or
    ``""``."""
    found = pattern.findall(op_name)
    return found[-1] if found else ""


def scopes(texts: Iterable[str], pattern: re.Pattern
           ) -> Dict[str, Dict[str, str]]:
    """{program: {instruction: scope}} from optimized HLO texts."""
    out: Dict[str, Dict[str, str]] = {}
    for text in texts:
        m = _HLO_MODULE.search(text)
        if m:
            out[m.group(1)] = {name: scope_of(op, pattern)
                               for name, op in _HLO_OP.findall(text)}
    return out


def of_window(events: Dict, window_s: float, kind,
              texts: Sequence[str]) -> Dict:
    """The traced window's reduction by program stage (``reduce``), with
    the count of each of the kind's marks in it."""
    out = reduce(events, window_s, scopes(texts, kind.SCOPES), kind.SPANS)
    names = [e[0] for e in events["host"]]
    out["marks"] = {reading: names.count(mark) if mark else None
                    for reading, mark in kind.MARKS.items()}
    return out


def of_run(ctx: Dict) -> Optional[Dict]:
    """The traced run's reduction by program stage, as ``Window.stop``
    made it; None where the window ran nothing on a device (no device
    plane, as on a CPU) or was not traced."""
    return (ctx["rec"].traced or {}).get("program")


def _cover(events: Sequence[Tuple[str, float, float, str]],
           starts: List[float], t: float, back: int) -> str:
    """The name of the latest-started event still open at ``t`` (events
    nest on one line), looking at most ``back`` events back, else ""."""
    i = bisect.bisect_right(starts, t) - 1
    for j in range(i, max(i - back, -1), -1):
        name, s, d, _ = events[j]
        if s + d >= t:
            return name
    return ""


def reduce(events: Dict, window_s: float,
           programs: Dict[str, Dict[str, str]], span_names: Sequence[str],
           top: int = 5) -> Dict:
    """scope_s, span_s, span_n, idle_s and top_gaps from ``trace_reduce``'s
    events, the programs' scope tables (``scopes``) and the engine's span
    names."""
    scope_s: Dict[str, float] = {}
    for i, evs in events["device"].items():
        runs = sorted(events.get("modules", {}).get(i, []),
                      key=lambda e: e[1])
        run_starts = [e[1] for e in runs]
        for name, s, d, _ in evs:
            program = _RUN_ID.sub("", _cover(runs, run_starts, s, 1))
            scope = programs.get(program, {}).get(name, "")
            scope_s[scope] = scope_s.get(scope, 0.0) + d * 1e-9
            if "/" in scope:        # a layer counts in its stage too
                stage = scope.split("/", 1)[0]
                scope_s[stage] = scope_s.get(stage, 0.0) + d * 1e-9
    host = sorted(events["host"], key=lambda e: e[1])
    spans = [e for e in host if e[0] in span_names]
    span_s: Dict[str, float] = {}
    span_n: Dict[str, int] = {}
    for name, _, d, _ in spans:
        span_s[name] = span_s.get(name, 0.0) + d * 1e-9
        span_n[name] = span_n.get(name, 0) + 1
    span_starts = [e[1] for e in spans]
    host_starts = [e[1] for e in host]
    idle_s: Dict[str, float] = {}
    gaps: List[Tuple[float, str, str]] = []
    for s, e in _gaps(events["device"]):
        mid = 0.5 * (s + e)
        # an item opens far fewer than 256 spans
        span = _cover(spans, span_starts, mid, 256) or HARNESS
        idle_s[span] = idle_s.get(span, 0.0) + (e - s) * 1e-9
        gaps.append(((e - s) * 1e-9, span,
                     trace_reduce._host_label(host, host_starts, mid)))
    return {"scope_s": scope_s, "span_s": span_s, "span_n": span_n,
            "idle_s": idle_s,
            "window_s": window_s,
            "top_gaps": [list(g) for g in heapq.nlargest(
                top, gaps, key=lambda g: g[0])]}


def _gaps(device: Dict[int, Sequence]) -> List[Tuple[float, float]]:
    """The idle gaps between the first device's operations, as
    ``trace_reduce.reduce`` takes them."""
    if not device:
        return []
    evs = device[min(device)]
    merged = trace_reduce._union([(s, s + d) for _, s, d, _ in evs])
    return [(a[1], b[0]) for a, b in zip(merged, merged[1:]) if b[0] > a[1]]


def report(program: Dict) -> List[str]:
    """Lines for the traced run's stderr: device seconds per scope, idle
    seconds per engine span, the longest gaps."""
    scope_s, idle_s = program["scope_s"], program["idle_s"]
    out = ["device s by scope: " + ", ".join(
        f"{k or '(none)'} {v:.6f}" for k, v in sorted(scope_s.items()))]
    out.append("idle s by engine span: " + ", ".join(
        f"{k} {v:.6f}" for k, v in sorted(idle_s.items(),
                                           key=lambda kv: -kv[1])))
    for secs, span, host in program["top_gaps"]:
        out.append(f"idle gap {secs * 1e3:.4f} ms under span {span}, "
                   f"host event {host}")
    out.append("marks in the window: " + ", ".join(
        f"{k} {v}" for k, v in sorted(program.get("marks", {}).items()))
               + "; spans: " + ", ".join(
                   f"{k} {v}" for k, v in sorted(program["span_n"].items())))
    return out
