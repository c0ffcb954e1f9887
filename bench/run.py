"""One run of one benchmark cell on the machine it is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found by name: the cell in
``BENCHMARK.json``, its configuration in ``bench/configs/<config>.json``,
its traffic in ``bench/traffic/<traffic>.json`` (overridden by
``bench/cells/<cell>.json`` where that exists), and each metric's reader in
``bench/metrics/<metric>.py``. A run builds weights and frames from the
seed, warms up every shape the window uses (set-up), serves for
``--seconds``, then checks a seeded sample of what it served against the
plain reference. ``--trace 1`` runs the window under the profiler and
reports the cell's per-layer metrics instead of its end-to-end ones.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` when
traced), with the compared numbers beside their limits last under
``checks``. Without a TPU, or with fewer chips than the cell asks for, it
exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from typing import Callable, Dict, List, Optional  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402

# pool of frames the closed loop cycles through / requests draw from,
# in microbatches
POOL_BLOCKS = 8
# microbatches the correctness check recomputes
SAMPLE_STEPS = 24


def _load(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_cell(root: str, workload: str) -> Dict:
    """The cell's entry, configuration, traffic and metric lists."""
    bench = _load(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"run.py: no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    d = os.path.join(root, "bench")
    traffic = _load(os.path.join(d, "traffic", cell["traffic"] + ".json"))
    over = os.path.join(d, "cells", workload + ".json")
    if os.path.exists(over):
        traffic = {**traffic, **_load(over)}

    def mine(m):
        return "workloads" not in m or workload in m["workloads"]
    return {"cell": cell, "traffic": traffic,
            "config": _load(os.path.join(d, "configs",
                                         cell["config"] + ".json")),
            "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
            "per_layer": [m for m in bench["per_layer"] if mine(m)]}


def require_chips(count: int) -> List:
    """The devices, or exit non-zero when JAX finds no TPU or too few."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"run.py: JAX found no TPU (platform "
                         f"{devs[0].platform!r}); nothing was run")
    if len(devs) < count:
        raise SystemExit(f"run.py: the cell needs {count} chips, "
                         f"found {len(devs)}")
    return devs[:count]


def use_compile_cache(root: str) -> str:
    """JAX's persistent cache: ``$JAX_COMPILATION_CACHE_DIR`` when set,
    else the fixed ``bench/.jax_cache`` of this checkout; every program is
    cached, however quickly it compiled."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, "bench", ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def read_metric(root: str, name: str) -> Callable:
    """``bench/metrics/<name>.py``'s ``read(ctx)``."""
    path = os.path.join(root, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peak_of(root: str, kind: str) -> Dict:
    """The device's row of ``bench/peaks.json``; an unknown kind is an
    error, never a default."""
    table = _load(os.path.join(root, "bench", "peaks.json"))["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def memory_peak(devs) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def serve(spec: Dict, seed: int, seconds: float, trace: bool, root: str,
          devs) -> Dict:
    """Set-up, the window and the trace: everything the program runs."""
    from bench import inputs, loops, system, trace_reduce
    cfg, traffic = spec["config"], spec["traffic"]
    mb = cfg["microbatch"]
    params = inputs.weights(cfg, seed)
    pool = inputs.frame_pool(cfg, POOL_BLOCKS * mb, seed)
    obs = system.make_obs() if trace else None
    eng = system.engine(cfg, params, inputs.engine_seed(seed), obs)
    server = loops.Server(eng, pool, mb)
    tracer = (trace_reduce.Window(os.path.join(root, "bench", "traces"),
                                  devs) if trace else None)
    rec = loops.LOOPS[traffic["loop"]](server, traffic, seconds, seed,
                                       SAMPLE_STEPS, tracer=tracer,
                                       t_origin=T_START)
    if obs is not None:
        rec.counters = {k: system.counter(obs, k)
                        for k in ("serving_fused_steps_total",
                                  "serving_fused_fallback_total")}
    return {"rec": rec, "params": params, "pool": pool}


def main(argv=None, root: str = ROOT,
         require: Callable = require_chips) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = load_cell(root, args.workload)
    devs = require(spec["cell"]["chips"])
    peak = peak_of(root, devs[0].device_kind)
    use_compile_cache(root)
    from bench import correct
    ran = serve(spec, args.seed, args.seconds, bool(args.trace), root, devs)
    rec = ran["rec"]
    mem = memory_peak(devs)
    t_check = time.perf_counter()
    read = correct.readings(spec["config"], ran["params"], ran["pool"],
                            args.seed, rec.samples)
    print(f"reference check: {len(rec.samples)} microbatches in "
          f"{time.perf_counter() - t_check:.3f} s", file=sys.stderr)
    ok, checks = correct.judge(read, correct.limits(spec["config"]))
    ctx = {"rec": rec, "config": spec["config"], "peak": peak,
           "chips": len(devs)}
    wanted = spec["per_layer"] if args.trace else spec["end_to_end"]
    metrics = {}
    for m in wanted:
        value = read_metric(root, m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": mem}
    result = {"correct": ok, "attempted": rec.attempted,
              "failed": rec.failed, "metrics": metrics, "device": device}
    if args.trace and rec.traced is not None:
        device["busy_s"] = rec.traced["busy_s"]
        device["window_s"] = rec.traced["window_s"]
        result["breakdown"] = rec.traced["breakdown"]
    if rec.late_s:
        late = sorted(rec.late_s)
        print(f"generator late: p50 {late[len(late) // 2] * 1e3:.4f} ms, "
              f"max {late[-1] * 1e3:.4f} ms over {len(late)} windows",
              file=sys.stderr)
    if rec.service_s:
        slow = sum(x > 0.0333 for x in rec.latency_s)
        print(f"service: max {max(rec.service_s) * 1e3:.4f} ms; latency "
              f"over 33.3 ms: {slow} of {len(rec.latency_s)} requests",
              file=sys.stderr)
    for c in checks:
        print(f"check {c['name']}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    result["checks"] = checks
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
