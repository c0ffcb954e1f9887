"""From a profiler trace of the window to the program's own stages.

``trace_reduce`` names device time by kernel and idle gaps by whatever the
host thread was doing; this module names both by the program's stages:

scope_s   device seconds of the operations under each named scope of the
          model (``models/vision.py``): ``p2m_frontend``, ``backbone`` (all
          of it) and each ``backbone/<layer>`` (``conv{i}``, or a ResNet
          block), ``head``; ``""`` for operations under none
span_s    host seconds inside each engine span (``serving/vision.py``):
          which spans the program has at all
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

``of_run`` is what the metric readers call: once per traced run it reads
the window's ``.xplane.pb`` (which ``trace_reduce.Window`` leaves under
``bench/traces``), the step programs' HLO (``step_programs``) and the
program's own counts, reduces them, prints the tables on standard error
and keeps the result in the readers' shared context. It adds

compiles     the program's ``jax_compile`` marks inside the window: each
             increment of its ``jax_compiles_total`` leaves one on the
             profiler's clock (None where the program makes none)
syncs        the same of ``host_sync``, the mark of each increment of
             ``serving_host_syncs_total``
microbatches the engine's ``microbatch`` spans inside the window: one per
             microbatch served
"""
from __future__ import annotations

import bisect
import glob
import heapq
import os
import re
import sys
import time
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from bench import trace_reduce

# the engine's spans, outermost first
SPANS = ("stream", "microbatch", "key_fold", "theta_sync", "merge", "drain")
HARNESS = "harness"
# the program's marks of a program load and of a host sync
COMPILE_MARK = "jax_compile"
SYNC_MARK = "host_sync"
_SCOPE = re.compile(
    r"(?:^|/)(p2m_frontend|head|backbone(?:/(?:conv\d+|s\d+b\d+))?)(?=/|$)")
_HLO_MODULE = re.compile(r"^HloModule ([^\s,]+)", re.M)
_HLO_OP = re.compile(
    r'^\s*(?:ROOT )?%?([\w.\-]+) = [^\n]*?op_name="([^"]*)"', re.M)
_RUN_ID = re.compile(r"\(.*\)$")


def scope_of(op_name: str) -> str:
    """The model scope of an op name: the innermost of ``p2m_frontend``,
    ``backbone[/<layer>]``, ``head``, or ``""``."""
    found = _SCOPE.findall(op_name)
    return found[-1] if found else ""


def scopes(texts: Iterable[str]) -> Dict[str, Dict[str, str]]:
    """{program: {instruction: scope}} from optimized HLO texts."""
    out: Dict[str, Dict[str, str]] = {}
    for text in texts:
        m = _HLO_MODULE.search(text)
        if m:
            out[m.group(1)] = {name: scope_of(op)
                               for name, op in _HLO_OP.findall(text)}
    return out


def load_events(path: str, device_ids: Sequence[int]) -> Dict:
    """``trace_reduce.load_events``'s tables, and "modules": {id:
    [Event]}, each device's program runs (its "XLA Modules" line)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device: Dict[int, List] = {}
    modules: Dict[int, List] = {}
    host: List = []
    for plane in data.planes:
        m = trace_reduce._DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) in device_ids:
            i = int(m.group(1))
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules.setdefault(i, []).extend(
                        (e.name, e.start_ns, e.duration_ns, "")
                        for e in line.events)
                elif line.name == "XLA Ops":
                    device.setdefault(i, []).extend(
                        (trace_reduce._short(e.name), e.start_ns,
                         e.duration_ns, trace_reduce._scope_of(e.name,
                                                               e.stats))
                        for e in line.events)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                if line.name.startswith("python"):
                    host.extend((e.name, e.start_ns, e.duration_ns, "")
                                for e in line.events)
    return {"device": device, "host": host, "modules": modules}


def step_programs(cfg: Dict) -> List[str]:
    """The optimized HLO text of the engine's step programs at the
    configuration's microbatch, for an engine built on weights' shapes
    alone: the served engine's programs, found in the compile caches. A
    program without the model's scopes is left out uncompiled."""
    from bench import inputs, system
    params = jax.eval_shape(lambda: inputs.weights(cfg, 0))
    eng = system.engine(cfg, params, 0)
    frames = jax.ShapeDtypeStruct(
        (cfg["microbatch"], cfg["in_hw"], cfg["in_hw"],
         cfg["p2m"]["in_channels"]), jnp.float32)
    key = jax.random.PRNGKey(0)
    lowered = [eng._step.lower(params, frames, key)]
    if eng.backend == "pallas":
        lowered.append(eng._fused_step.lower(
            params, frames, key, jax.ShapeDtypeStruct((), jnp.float32)))
    return [low.compile().as_text() for low in lowered
            if _SCOPE.search(low.as_text(debug_info=True))]


def _marks() -> Tuple[bool, bool]:
    """Whether the program marks its program loads and its host syncs
    (an older one does neither)."""
    try:
        from repro.obs import compiles
        from repro.serving import vision
    except ImportError:
        return False, False
    return (getattr(compiles, "MARK", None) == COMPILE_MARK,
            getattr(vision, "HOST_SYNC_MARK", None) == SYNC_MARK)


def of_run(ctx: Dict, reader_file: str) -> Optional[Dict]:
    """The traced run's reduction by program stage (``reduce``, with
    ``compiles``, ``syncs``, ``microbatches`` and ``reduce_s``), made at
    the first call and kept in ``ctx`` for the other readers; None where
    the window ran nothing on a device (no device plane, as on a CPU).
    ``reader_file`` is the calling reader's ``__file__``, in
    ``bench/metrics`` of the checkout whose ``bench/traces`` holds the
    window's trace."""
    if "program" not in ctx:
        ctx["program"] = _of_run(ctx, os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(reader_file)))))
    return ctx["program"]


def _of_run(ctx: Dict, root: str) -> Optional[Dict]:
    traced = ctx["rec"].traced
    if not traced or not traced.get("busy_s"):
        return None
    paths = sorted(glob.glob(os.path.join(
        root, "bench", "traces", "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        return None
    t = time.perf_counter()
    ids = [d.id for d in jax.devices()[:ctx["chips"]]]
    events = load_events(paths[-1], ids)
    out = reduce(events, traced["window_s"],
                 scopes(step_programs(ctx["config"])))
    names = [e[0] for e in events["host"]]
    loads, syncs = _marks()
    out["compiles"] = names.count(COMPILE_MARK) if loads else None
    out["syncs"] = names.count(SYNC_MARK) if syncs else None
    out["microbatches"] = names.count("microbatch")
    out["reduce_s"] = time.perf_counter() - t
    for line in report(out):
        print(line, file=sys.stderr)
    return out


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
           programs: Dict[str, Dict[str, str]], top: int = 5) -> Dict:
    """scope_s, span_s, idle_s and top_gaps from ``trace_reduce``'s
    events and the programs' scope tables (``scopes``)."""
    scope_s: Dict[str, float] = {}
    for i, evs in events["device"].items():
        runs = sorted(events.get("modules", {}).get(i, []),
                      key=lambda e: e[1])
        run_starts = [e[1] for e in runs]
        for name, s, d, _ in evs:
            program = _RUN_ID.sub("", _cover(runs, run_starts, s, 1))
            scope = programs.get(program, {}).get(name, "")
            scope_s[scope] = scope_s.get(scope, 0.0) + d * 1e-9
            if scope.startswith("backbone/"):
                scope_s["backbone"] = scope_s.get("backbone", 0.0) + d * 1e-9
    host = sorted(events["host"], key=lambda e: e[1])
    spans = [e for e in host if e[0] in SPANS]
    span_s: Dict[str, float] = {}
    for name, _, d, _ in spans:
        span_s[name] = span_s.get(name, 0.0) + d * 1e-9
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
    return {"scope_s": scope_s, "span_s": span_s, "idle_s": idle_s,
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
    if "reduce_s" in program:
        out.append(f"program trace reduced in {program['reduce_s']:.3f} s; "
                   f"programs loaded in the window: {program['compiles']}; "
                   f"host syncs: {program['syncs']} over {program['microbatches']} "
                   "microbatches")
    return out
