"""From a profiler trace of the window to device metrics.

``Window`` runs ``jax.profiler`` around the measured window (python tracer
off, host TraceMe annotations on, so the program's spans and JAX's
dispatches land on the same clock as the device's operations).
``load_events`` reads the ``.xplane.pb`` into plain tuples once; ``reduce``
turns those into the numbers below, and ``program_trace.of_window`` the
same events into the program's stages (``program``):

busy_s      the union of the device's operation intervals ("XLA Ops"
            line of each ``/device:TPU:<n>`` plane), averaged over chips
window_s    the traced window's length on the host clock
kernel_s    device seconds of the operations whose name or metadata
            carries one of the kind's kernel names (``KERNELS``; for
            ``p2m_vgg``, ``p2m_kernel`` covers every frontend kernel: A, B,
            fused, f32 and int8)
breakdown   the ten device operations that took most time, and the idle
            gaps between device operations summed by what the host's
            python thread (line ``python*``) was doing in them (the innermost annotation or
            dispatch that covers the gap)

The reduction is plain Python over those tuples, checked on a small
recorded trace in ``tests/data``.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import shutil
import time
from typing import Dict, List, Sequence, Tuple

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")

# (name, start_ns, duration_ns, scope) -- scope: the kernel scope the event
# belongs to, or ""
Event = Tuple[str, float, float, str]


def _short(name: str) -> str:
    """An XLA op's name without its HLO text: ``%fusion.12 = (...)`` ->
    ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


def _scope_of(name: str, stats, kernels: Sequence[str]) -> str:
    for scope in kernels:
        if scope in name:
            return scope
    for _, value in stats:
        if isinstance(value, str):
            for scope in kernels:
                if scope in value:
                    return scope
    return ""


def load_events(path: str, device_ids: Sequence[int],
                kernels: Sequence[str] = ()) -> Dict:
    """{"device": {id: [Event]}, "modules": {id: [Event]}, "host":
    [Event]} from an xplane file: each device's operations (its "XLA Ops"
    line, each tagged with the kernel of ``kernels`` it belongs to) and
    program runs (its "XLA Modules" line), and the host's python thread."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device: Dict[int, List[Event]] = {}
    modules: Dict[int, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) in device_ids:
            i = int(m.group(1))
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules.setdefault(i, []).extend(
                        (e.name, e.start_ns, e.duration_ns, "")
                        for e in line.events)
                elif line.name == "XLA Ops":
                    device.setdefault(i, []).extend(
                        (_short(e.name), e.start_ns, e.duration_ns,
                         _scope_of(e.name, e.stats, kernels))
                        for e in line.events)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                if line.name.startswith("python"):
                    host.extend((e.name, e.start_ns, e.duration_ns, "")
                                for e in line.events)
    return {"device": device, "modules": modules, "host": host}


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _host_label(host: List[Event], starts: List[float], t: float) -> str:
    """The innermost host event that covers time ``t``: on one thread the
    latest-started event still open at ``t`` (events nest)."""
    i = bisect.bisect_right(starts, t) - 1
    for j in range(i, max(i - 256, -1), -1):
        name, s, d, _ = host[j]
        if s + d >= t:
            return name
    return "host idle"


def reduce(events: Dict, window_s: float, top: int = 10) -> Dict:
    """busy_s, window_s, kernel_s and breakdown from loaded events."""
    device = events["device"]
    if not device:
        return {"busy_s": 0.0, "window_s": window_s, "kernel_s": {},
                "breakdown": {"device_ops": [], "idle_gaps": []}}
    busy, kernel, op_time = [], {}, {}
    gaps: List[Tuple[float, float]] = []
    for i, evs in sorted(device.items()):
        merged = _union([(s, s + d) for _, s, d, _ in evs])
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        for name, _, d, scope in evs:
            key = f"{scope}:{name}" if scope else name
            op_time[key] = op_time.get(key, 0.0) + d * 1e-9
            if scope:
                kernel[scope] = kernel.get(scope, 0.0) + d * 1e-9
        if i == min(device):
            gaps = [(a[1], b[0]) for a, b in zip(merged, merged[1:])
                    if b[0] > a[1]]
    host = sorted(events["host"], key=lambda e: e[1])
    starts = [e[1] for e in host]
    by_label: Dict[str, float] = {}
    for s, e in gaps:
        label = _host_label(host, starts, 0.5 * (s + e))
        by_label[label] = by_label.get(label, 0.0) + (e - s) * 1e-9
    ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(by_label.items(), key=lambda kv: -kv[1])[:top]
    return {"busy_s": sum(busy) / len(busy), "window_s": window_s,
            "kernel_s": kernel,
            "breakdown": {"device_ops": [[k, v] for k, v in ops],
                          "idle_gaps": [[k, v] for k, v in idle]}}


class Window:
    """The profiler around one measured window, written under ``out_dir``
    (emptied first). When it stops, the trace is read once and reduced
    twice: by device operation and host event (``reduce``) and, where a
    device ran anything, by the program's stages (``program``), with the
    kind's names and the served engine's own step programs
    (``server.step_programs``)."""

    def __init__(self, out_dir: str, devs, kind, server):
        self.out_dir, self.kind, self.server = out_dir, kind, server
        self.ids = [d.id for d in devs]

    def start(self) -> None:
        import jax
        shutil.rmtree(self.out_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.out_dir, profiler_options=opts)

    def stop(self, window_s: float) -> Dict:
        import jax

        from bench import program_trace
        jax.profiler.stop_trace()
        t = time.perf_counter()
        paths = sorted(glob.glob(os.path.join(
            self.out_dir, "plugins", "profile", "*", "*.xplane.pb")))
        events = load_events(paths[-1], self.ids, self.kind.KERNELS)
        out = reduce(events, window_s)
        out["program"] = (program_trace.of_window(
            events, window_s, self.kind, self.server.step_programs())
            if out["busy_s"] > 0 else None)
        out["reduce_s"] = time.perf_counter() - t
        return out
