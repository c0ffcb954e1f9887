"""Find the open loop's knee once, on the chip, to fix a cell's rate.

    python3 bench/sweep.py --workload <open cell> --seed <n> --seconds <s> \
        --rates <frames/s> ...

Serves the cell's open loop at each offered rate in the order given (one process, one
server) and prints, per rate, the latency percentiles and whether the
backlog grew: the median latency of the window's last quarter of requests
against its first. The knee is the highest rate whose p95 stays within
``SLO_MS`` (one frame period of a 30 frames/s camera) with no growing
backlog; the cell's rate is ``SHARE`` of it, written into
``cells/<cell>.json`` by hand.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import run  # noqa: E402

SLO_MS = 33.3
SHARE = 0.8


def grew(latency_s) -> bool:
    q = max(len(latency_s) // 4, 1)
    first = statistics.median(latency_s[:q])
    last = statistics.median(latency_s[-q:])
    return last > 2.0 * first + 2e-3


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    spec = run.load_cell(run.ROOT, args.workload)
    devs = run.require_chips(spec["cell"]["chips"])
    run.use_compile_cache(run.ROOT)
    from bench import loops, stats
    traffic = spec["traffic"]
    server = spec["kind"].Server(spec["config"], args.seed)
    knee = None
    for rate in args.rates:
        rec = loops.open_loop(server, {**traffic, "rate_per_s": rate},
                              args.seconds, args.seed, 0)
        p95 = stats.percentile(rec.latency_s, 95)
        row = {"rate_per_s": rate, "requests": rec.attempted,
               "served_per_s": rec.frames / rec.window_s,
               "p50_ms": stats.percentile(rec.latency_s, 50) * 1e3,
               "p95_ms": p95 * 1e3,
               "queue_p95_ms": stats.percentile(rec.queue_s, 95) * 1e3,
               "service_p95_ms": stats.percentile(rec.service_s, 95) * 1e3,
               "late_max_ms": max(rec.late_s) * 1e3,
               "backlog_grew": grew(rec.latency_s)}
        print(json.dumps(row), flush=True)
        if p95 * 1e3 <= SLO_MS and not row["backlog_grew"]:
            knee = rate if knee is None else max(knee, rate)
    print(json.dumps({"knee_per_s": knee,
                      "cell_rate_per_s": None if knee is None
                      else SHARE * knee}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
