"""Readings for setting the limits of ``correct``, many seeds in one process.

    python3 bench/readings.py --workload <cell> --seconds <s> --seeds <n> ...

For each seed: one short window of the cell exactly as ``run.py`` serves
it, then the compared numbers of the program's sample and of the control
(the reference in the program's place, one precision step below the
configuration's: bf16 for f32, fp8 for bf16). Prints one JSON line per
seed. Limits sit above every program reading and below every control
reading (``PERF.md`` lists both); benchmark runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    spec = run.load_cell(run.ROOT, args.workload)
    devs = run.require_chips(spec["cell"]["chips"])
    run.use_compile_cache(run.ROOT)
    from bench import correct
    cfg = spec["config"]
    for seed in args.seeds:
        ran = run.serve(spec, seed, args.seconds, False, run.ROOT, devs)
        rec = ran["rec"]
        row = {"seed": seed, "steps": rec.steps, "frames": rec.frames,
               "window_s": rec.window_s}
        for side in ("program", "control"):
            row[side] = correct.readings(cfg, ran["params"], ran["pool"],
                                         seed, rec.samples,
                                         control=side == "control")
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
