"""Exporters: JSONL sink, Prometheus-style exposition, bench metadata.

Three consumers, three formats:

* ``chrome://tracing`` / ad-hoc scripts → :func:`write_jsonl` (one JSON
  object per line: tracer records verbatim plus one ``metric`` record per
  instrument snapshot and one ``meta`` header line).
* Scrape-style monitoring → :func:`prometheus_text`: counters/gauges as
  plain samples, histograms as Prometheus *summaries* (``quantile``
  labels + ``_sum``/``_count``). Names must already follow Prometheus
  conventions (the registry's contract).
* ``BENCH_*.json`` → :func:`bench_meta`: the shared ``meta`` block every
  benchmark stamps into its results file, so all bench outputs carry one
  schema (jax version, backend, hostname, schema version) instead of
  five divergent shapes.
"""
from __future__ import annotations

import json
import platform
import socket
import sys
from typing import Any, Dict, Iterable, List

import jax

from repro.obs.metrics import MetricsRegistry

#: Bump when the shape of bench JSON / obs JSONL records changes.
BENCH_SCHEMA_VERSION = 1


def bench_meta(bench: str, **extra: Any) -> Dict[str, Any]:
    """The shared ``meta`` block stamped into every ``BENCH_*.json``."""
    meta: Dict[str, Any] = {
        "bench": bench,
        "schema_version": BENCH_SCHEMA_VERSION,
        "jax_version": jax.__version__,
        "backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "device_count": jax.device_count(),
        "hostname": socket.gethostname(),
        "platform": platform.platform(),
        "python": sys.version.split()[0],
    }
    meta.update(extra)
    return meta


# -- JSONL -------------------------------------------------------------------

def write_jsonl(path: str, records: Iterable[Dict[str, Any]]) -> int:
    """Write records one-JSON-object-per-line; returns the line count."""
    n = 0
    with open(path, "w") as fh:
        for rec in records:
            fh.write(json.dumps(rec, sort_keys=True))
            fh.write("\n")
            n += 1
    return n


def read_jsonl(path: str) -> List[Dict[str, Any]]:
    out: List[Dict[str, Any]] = []
    with open(path) as fh:
        for line in fh:
            line = line.strip()
            if line:
                out.append(json.loads(line))
    return out


# -- Prometheus-style text exposition ----------------------------------------

def _fmt(v: Any) -> str:
    if v is None:
        return "NaN"
    return repr(float(v))


def prometheus_text(registry: MetricsRegistry) -> str:
    """Text exposition of every instrument in the registry."""
    lines: List[str] = []
    for inst in registry:
        snap = inst.snapshot()
        kind = snap["type"]
        if kind == "counter":
            lines.append(f"# TYPE {inst.name} counter")
            lines.append(f"{inst.name} {_fmt(snap['value'])}")
        elif kind == "gauge":
            lines.append(f"# TYPE {inst.name} gauge")
            lines.append(f"{inst.name} {_fmt(snap['value'])}")
        else:                # histogram -> buckets + quantile summary
            # real Prometheus histogram series: cumulative _bucket{le=}
            # samples straight off the occupied log-bucket edges (sparse
            # emission of a cumulative series is lossless), terminated by
            # the mandatory le="+Inf" == _count
            lines.append(f"# TYPE {inst.name} histogram")
            for edge, cum in inst.cumulative_buckets():
                le = "+Inf" if edge == float("inf") else _fmt(edge)
                lines.append(f'{inst.name}_bucket{{le="{le}"}} {cum}')
            # the pre-existing summary view rides along (same name — this
            # exposition is self-scraped, not fed to a strict parser)
            for q in (0.5, 0.95, 0.99):
                lines.append(f'{inst.name}{{quantile="{q}"}} '
                             f"{_fmt(inst.quantile(q))}")
            lines.append(f"{inst.name}_sum {_fmt(snap['sum'])}")
            lines.append(f"{inst.name}_count {_fmt(snap['count'])}")
    return "\n".join(lines) + ("\n" if lines else "")
