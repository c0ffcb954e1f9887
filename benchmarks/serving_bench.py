"""Serving harness benchmark -> BENCH_serving.json.

Every other bench in this repo times one jitted step in steady state; the
paper's system claims (front-end energy, communication energy, bandwidth)
are about a pipeline *under load*. This bench closes the loop: a
deterministic virtual-time load generator (``repro.serving.loadgen`` —
seeded counter-hash arrivals, no host RNG, no wall clock) assembles
requests into admission windows under a batching deadline, the windows are
dispatched through the REAL engines (``VisionEngine.stream`` /
``FleetEngine.serve``, obs-enabled), and the measured probe-derived
service walls feed the work-conserving queueing simulation whose
per-request latency decomposition (queue-wait / service / TTFA) lands in
``repro.obs`` log-bucket histograms. The curves:

    latency vs offered load      p50/p95/p99 + time-to-first-activation at
                                 loads straddling the measured capacity,
                                 for BOTH engines, with the saturation
                                 knee (loadgen.find_knee)
    throughput vs microbatch     frames/s per admission-window size,
                                 fused vs exact streaming — each window
                                 shape first fed through the
                                 kernels/autotune search so the TileChoice
                                 is picked per operating point (table
                                 persisted next to this JSON, the same
                                 schema as BENCH_frontend_tiles.json)
    fleet size sweep             frames/s serving G concurrent chip
                                 streams through the harness

Usage:
    PYTHONPATH=src python benchmarks/serving_bench.py [--smoke|--quick] \
        [--out BENCH_serving.json] [--warnings-as-errors]

``--quick`` (CI, runs BEFORE tier-1): census-not-wallclock gates — the
harness-driven obs-enabled ``VisionEngine._step`` / ``FleetEngine._step``
jaxpr censuses must equal the pinned ``stream.exact`` / ``fleet.g2``
budgets in ANALYSIS_BUDGETS.json; a two-round same-load harness drive
must add zero retraces (``tracecheck.assert_jit_cache``); the obs=None
dispatch path must be bit-identical to the obs-enabled one; and the
deterministic request trace must reproduce. It still writes
BENCH_serving.json (a minimal measured sweep + the byte-reproducible
``request_trace`` section). Exits non-zero on any gate failure.

``--smoke``: fewer loads / window sizes / repeats — same JSON schema.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import warnings
from typing import Dict, List, Optional

# ---------------------------------------------------------------------------
# the deterministic request trace (the --quick byte-identity surface)
# ---------------------------------------------------------------------------

# pinned config: this section of BENCH_serving.json is a pure function of
# these constants (virtual time + synthetic service model, nothing measured)
TRACE_SEED = 7
TRACE_OFFERED_FPS = 2000.0
TRACE_REQUESTS = 24
TRACE_WINDOW_FRAMES = 8
TRACE_DEADLINE_MS = 4.0
TRACE_SLO_MS = 10.0


def _service_model(batch) -> float:
    """Deterministic synthetic service wall (seconds) for the trace."""
    return 1e-3 + 2.5e-4 * batch.n_frames


def deterministic_trace() -> Dict:
    """The byte-reproducible request trace: schedule -> admission plan ->
    simulated SLO decomposition, entirely in virtual time."""
    from repro.serving import loadgen
    cfg = loadgen.LoadgenConfig(seed=TRACE_SEED,
                                offered_fps=TRACE_OFFERED_FPS,
                                n_requests=TRACE_REQUESTS)
    sched = loadgen.make_schedule(cfg)
    plan = loadgen.plan_microbatches(sched, TRACE_WINDOW_FRAMES,
                                     TRACE_DEADLINE_MS / 1e3)
    sim = loadgen.simulate(plan, _service_model, slo_ms=TRACE_SLO_MS)
    return {"config": dataclasses.asdict(cfg),
            "window_frames": TRACE_WINDOW_FRAMES,
            "deadline_ms": TRACE_DEADLINE_MS,
            "slo_ms": TRACE_SLO_MS,
            "schedule": [r.to_json() for r in sched],
            "microbatches": [b.to_json() for b in plan],
            "simulated": sim}


# ---------------------------------------------------------------------------
# engine drivers: dispatch an admission plan, return measured service walls
# ---------------------------------------------------------------------------

def _setup(pool_frames: int = 16):
    import jax

    from repro.models import vision
    cfg = vision.VisionConfig(name="serving_bench", arch="vgg_tiny",
                              num_classes=10)
    params = vision.init_params(jax.random.PRNGKey(0), cfg)
    pool = jax.random.uniform(jax.random.PRNGKey(1),
                              (pool_frames, 32, 32, 3))
    return cfg, params, pool


class VisionDriver:
    """Drives one warm ``VisionEngine`` operating point (window = mb).

    Every admission window dispatches the full ``mb``-frame array (a
    global-shutter readout reads the whole pixel array; padding the tail
    windows keeps the jit cache at one entry per operating point), so a
    window's measured wall is its honest probe-derived service time.
    """

    def __init__(self, cfg, params, pool, mb: int,
                 fused: Optional[bool] = None, obs=None, seed: int = 0):
        from repro.serving import VisionEngine
        self.mb = mb
        self.frames = pool[:mb]
        self.eng = VisionEngine(cfg, params, backend="pallas", seed=seed,
                                microbatch=mb, fused_stream=fused, obs=obs)
        self.warm()

    def warm(self, rounds: int = 2) -> None:
        list(self.eng.stream([self.frames] * rounds))

    def drive(self, plan) -> List[float]:
        """Measured service wall (s) per admission window, plan order."""
        outs = list(self.eng.stream([self.frames] * len(plan)))
        return [o["wall_ms"] / 1e3 for o in outs]


class FleetDriver:
    """Drives one warm ``FleetEngine`` operating point (G chips/window).

    An admission window becomes one ``serve()`` of G per-chip requests
    (missing chips padded with pool frames so every step packs the same
    (G, mb) shape); its service wall is the sum of the probe-derived
    per-item wall shares — the batch's total step wall.
    """

    def __init__(self, cfg, params, pool, mb: int, g: int,
                 obs=None, seed: int = 0):
        from repro.serving import FleetEngine
        self.mb, self.g = mb, g
        self.frames = pool[:mb]
        self.eng = FleetEngine(cfg, params, backend="pallas", seed=seed,
                               chips_per_step=g, microbatch=mb,
                               fused_stream=False, obs=obs)
        for c in range(g):
            self.eng.add_chip(c)
        self.warm()

    def _reqs(self):
        return [(c, self.frames) for c in range(self.g)]

    def warm(self, rounds: int = 2) -> None:
        for _ in range(rounds):
            self.eng.serve(self._reqs())

    def drive(self, plan) -> List[float]:
        walls = []
        for _ in plan:
            outs = self.eng.serve(self._reqs())
            walls.append(sum(o["wall_ms"] for o in outs) / 1e3)
        return walls


# ---------------------------------------------------------------------------
# the sweep
# ---------------------------------------------------------------------------

def _calibrate(driver, repeats: int) -> float:
    """Min measured service wall (s) of one full window on a warm engine."""
    walls = []
    for _ in range(repeats):
        walls.extend(driver.drive([None]))
    return min(walls)


def _latency_sweep(driver, window_frames: int, capacity_fps: float,
                   loads_rel, n_requests: int, seed: int,
                   frames_per_request: int = 1, chips: int = 1,
                   slo_ms: Optional[float] = None) -> List[Dict]:
    """latency-vs-offered-load rows for one operating point.

    Offered loads are relative to the measured capacity (so the sweep
    straddles saturation on any host); the arrival schedule itself stays
    a pure function of (seed, offered_fps). SLO quantiles are read back
    from fresh log-bucket histograms per row.
    """
    import repro.obs as obs_mod
    from repro.serving import loadgen
    if slo_ms is None:
        slo_ms = 4.0 * window_frames / capacity_fps * 1e3
    rows = []
    # the batching deadline is a property of the OPERATING POINT, not the
    # offered load (a deadline that stretched with sparse arrivals would
    # dominate light-load latency and invert the curve): one service time
    # at capacity — windows fill under pressure, tail out when sparse
    deadline_s = window_frames / capacity_fps
    for rel in loads_rel:
        offered = rel * capacity_fps
        lcfg = loadgen.LoadgenConfig(seed=seed, offered_fps=offered,
                                     n_requests=n_requests,
                                     frames_per_request=frames_per_request,
                                     chips=chips)
        sched = loadgen.make_schedule(lcfg)
        plan = loadgen.plan_microbatches(sched, window_frames, deadline_s)
        walls = driver.drive(plan)
        sim = loadgen.simulate(plan, walls, slo_ms=slo_ms)
        obs = obs_mod.Obs(tracing=False)
        summ = loadgen.record_slo(obs, sim, slo_ms)
        rows.append({"offered_fps": offered, "offered_rel": rel,
                     "n_windows": len(plan),
                     "achieved_fps": sim["achieved_fps"],
                     "slowdown": sim["slowdown"],
                     "makespan_ms": sim["makespan_ms"], **summ})
    return rows


def _autotune_point(cfg, params, pool, mb: int, repeats: int) -> Dict:
    """Feed one (load, shape) operating point through the tile autotuner;
    the stored winner is what the engines built afterwards resolve to."""
    import jax

    from repro.core import p2m
    from repro.kernels import autotune
    pcfg = cfg.p2m
    wq = p2m.quantize_weights(params["p2m"]["w"], pcfg.weight_bits)
    choice, _ = autotune.autotune_frontend(
        pool[:mb], wq, params["p2m"]["v_th"], jax.random.PRNGKey(3),
        kernel=pcfg.kernel_size, stride=pcfg.stride,
        pixel_params=pcfg.pixel, mtj_params=pcfg.mtj,
        repeats=repeats, store=True)
    return choice.to_json()


def run(smoke: bool = False, quick: bool = False) -> Dict:
    # the overloaded point needs enough requests to BUILD a queue: with
    # only ~2 admission windows the tail window's deadline close masks
    # the per-window service deficit and slowdown never leaves 1.0
    if quick:
        loads_rel = (0.3, 0.9, 1.6)
        n_requests, mbs, fleet_gs, repeats = 40, (8,), (2,), 1
        fused_modes = (False,)
    elif smoke:
        loads_rel = (0.3, 0.9, 1.6)
        n_requests, mbs, fleet_gs, repeats = 48, (4, 8), (1, 2), 1
        fused_modes = (False, True)
    else:
        loads_rel = (0.3, 0.6, 0.9, 1.3, 1.6)
        n_requests, mbs, fleet_gs, repeats = 64, (4, 8, 16), (1, 2, 4), 2
        fused_modes = (False, True)
    seed = 11
    cfg, params, pool = _setup(pool_frames=max(mbs))
    results: Dict = {"quick": quick, "smoke": smoke,
                     "loads_rel": list(loads_rel),
                     "n_requests": n_requests, "seed": seed}

    # --- operating-point autotune: one search per window shape ------------
    results["operating_points"] = {
        str(mb): _autotune_point(cfg, params, pool, mb, repeats)
        for mb in mbs}

    # --- throughput vs microbatch x fused-vs-exact ------------------------
    from repro.serving import loadgen
    tput = []
    for mb in mbs:
        for fused in fused_modes:
            d = VisionDriver(cfg, params, pool, mb, fused=fused)
            svc = _calibrate(d, max(repeats, 2))
            tput.append({"microbatch": mb, "fused": fused,
                         "service_ms": svc * 1e3,
                         "frames_per_s": mb / svc})
    results["throughput_vs_microbatch"] = tput

    # --- latency vs offered load: VisionEngine ----------------------------
    import repro.obs as obs_mod
    mb = 8
    obs_v = obs_mod.Obs()
    dv = VisionDriver(cfg, params, pool, mb, fused=False, obs=obs_v)
    cap_v = mb / _calibrate(dv, max(repeats, 2))
    rows_v = _latency_sweep(dv, mb, cap_v, loads_rel, n_requests, seed)
    results["vision"] = {
        "microbatch": mb, "capacity_fps": cap_v,
        "latency_vs_load": rows_v,
        "knee": loadgen.find_knee(rows_v),
    }

    # --- latency vs offered load + size sweep: FleetEngine ----------------
    g = max(fleet_gs)
    obs_f = obs_mod.Obs()
    df = FleetDriver(cfg, params, pool, mb, g, obs=obs_f)
    cap_f = g * mb / _calibrate(df, max(repeats, 2))
    rows_f = _latency_sweep(df, g * mb, cap_f, loads_rel, n_requests,
                            seed, frames_per_request=mb, chips=g)
    results["fleet"] = {
        "microbatch": mb, "fleet_size": g, "capacity_fps": cap_f,
        "latency_vs_load": rows_f,
        "knee": loadgen.find_knee(rows_f),
    }
    size_rows = []
    for gg in fleet_gs:
        dg = df if gg == g else FleetDriver(cfg, params, pool, mb, gg)
        svc = _calibrate(dg, max(repeats, 2))
        size_rows.append({"fleet_size": gg, "service_ms": svc * 1e3,
                          "frames_per_s": gg * mb / svc})
    results["fleet_size_sweep"] = size_rows

    # --- the deterministic request trace (byte-identical across runs) ----
    results["request_trace"] = deterministic_trace()
    return results


# ---------------------------------------------------------------------------
# --quick gates (census-not-wallclock, per the PR 8 standard)
# ---------------------------------------------------------------------------

def _fail(msg: str) -> None:
    print(f"FAIL: {msg}", file=sys.stderr)


def quick_gates() -> int:
    """The CI gates: unchanged op census, zero added retraces, obs=None
    bit-identity, reproducible request trace. No timing assertions."""
    import jax
    import numpy as np

    import repro.obs as obs_mod
    from repro.analysis import census, tracecheck
    failed = False

    root = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        os.pardir)
    with open(os.path.join(root, census.BUDGETS_BASENAME)) as fh:
        budgets = json.load(fh)["census"]
    fields = ("conv", "dot_general", "eqn_count", "host_callback")
    cfg, params, pool = _setup(pool_frames=census.STREAM_BATCH)
    mb = census.STREAM_BATCH

    # 1. two same-load harness rounds over an obs-enabled VisionEngine:
    #    zero added retraces, and the harness-driven step census must equal
    #    the pinned stream.exact budget.
    obs = obs_mod.Obs()
    dv = VisionDriver(cfg, params, pool, mb, fused=False, obs=obs)
    with tracecheck.capture() as rec:
        walls_a = dv.drive([None] * 3)
        walls_b = dv.drive([None] * 3)
    try:
        tracecheck.assert_jit_cache(dv.eng._step, 1, recorder=rec,
                                    what="harness-driven VisionEngine._step")
    except tracecheck.RetraceError as e:
        _fail(str(e))
        failed = True
    if not (len(walls_a) == len(walls_b) == 3
            and all(w > 0 for w in walls_a + walls_b)):
        _fail("harness drive produced no positive service walls")
        failed = True
    got = census.jaxpr_census(dv.eng._step, dv.eng.params, pool[:mb],
                              jax.random.PRNGKey(2))
    budget = budgets["stream.exact"]["jaxpr"]
    for f in fields:
        if got[f] != budget[f]:
            _fail(f"stream.exact jaxpr {f} = {got[f]} under the harness, "
                  f"budget pins {budget[f]}")
            failed = True

    # 2. the same two gates for the harness-driven fleet step at G=2.
    df = FleetDriver(cfg, params, pool, mb, 2, obs=obs_mod.Obs())
    with tracecheck.capture() as rec:
        df.drive([None] * 2)
        df.drive([None] * 2)
    try:
        tracecheck.assert_jit_cache(df.eng._step, 1, recorder=rec,
                                    what="harness-driven FleetEngine._step")
    except tracecheck.RetraceError as e:
        _fail(str(e))
        failed = True
    idx = jax.numpy.arange(2, dtype=jax.numpy.int32)
    chips = jax.tree.map(lambda a: a[idx], df.eng.state.chips0)
    trims = df.eng.state.trim[idx]
    gf = jax.numpy.stack([pool[:mb]] * 2)
    keys = jax.random.split(jax.random.PRNGKey(0), 2)
    got = census.jaxpr_census(df.eng._step, params, chips, trims, gf, keys)
    budget = budgets["fleet.g2"]["jaxpr"]
    for f in fields:
        if got[f] != budget[f]:
            _fail(f"fleet.g2 jaxpr {f} = {got[f]} under the harness, "
                  f"budget pins {budget[f]}")
            failed = True

    # 3. obs=None dispatch path: bit-identical labels/probs under the same
    #    harness drive (PR 8 standard), jit cache unchanged.
    d_obs = VisionDriver(cfg, params, pool, mb, fused=False,
                         obs=obs_mod.Obs(), seed=5)
    d_none = VisionDriver(cfg, params, pool, mb, fused=False, seed=5)
    outs_obs = list(d_obs.eng.stream([pool[:mb]] * 2))
    outs_none = list(d_none.eng.stream([pool[:mb]] * 2))
    for o_a, o_b in zip(outs_obs, outs_none):
        for k in ("labels", "probs"):
            if not np.array_equal(np.asarray(o_a[k]), np.asarray(o_b[k])):
                _fail(f"obs=None harness drive diverged on {k!r}")
                failed = True
    if (d_obs.eng._step._cache_size()
            != d_none.eng._step._cache_size()):
        _fail("obs=None harness drive changed the jit cache size")
        failed = True

    # 4. the deterministic request trace must reproduce in-process (the
    #    cross-process byte-identity is asserted in tests/test_loadgen.py).
    t1 = json.dumps(deterministic_trace(), sort_keys=True)
    t2 = json.dumps(deterministic_trace(), sort_keys=True)
    if t1 != t2:
        _fail("deterministic request trace did not reproduce")
        failed = True
    print(f"serving_bench --quick gates: {'FAIL' if failed else 'ok'}")
    return 1 if failed else 0


# ---------------------------------------------------------------------------

def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="CI gates (census/retrace/obs-parity/trace "
                         "determinism) + a minimal measured sweep")
    ap.add_argument("--smoke", action="store_true",
                    help="fewer loads / window sizes / repeats (CI)")
    ap.add_argument("--out", default="BENCH_serving.json")
    ap.add_argument("--warnings-as-errors", action="store_true",
                    help="fail on any warning raised from repro.serving")
    args = ap.parse_args()
    if args.warnings_as_errors:
        warnings.filterwarnings("error", module=r"repro\.serving.*")
    rc = 0
    if args.quick:
        rc = quick_gates()
    results = run(smoke=args.smoke or args.quick, quick=args.quick)
    from repro.kernels import autotune
    from repro.obs.export import bench_meta
    tiles_path = os.path.splitext(args.out)[0] + "_tiles.json"
    autotune.save_table(tiles_path)
    results["tile_table"] = tiles_path
    results["meta"] = bench_meta("serving", smoke=args.smoke,
                                 quick=args.quick)
    with open(args.out, "w") as f:
        json.dump(results, f, indent=2, sort_keys=True)
    print(f"wrote {args.out}")
    for name in ("vision", "fleet"):
        r = results[name]
        print(f"  {name}: capacity {r['capacity_fps']:.1f} fps")
        for row in r["latency_vs_load"]:
            print(f"    load {row['offered_rel']:>4.2f}x "
                  f"({row['offered_fps']:8.1f} fps): "
                  f"p50 {row['latency_p50_ms']:8.2f} ms  "
                  f"p99 {row['latency_p99_ms']:8.2f} ms  "
                  f"ttfa p95 {row['ttfa_p95_ms']:8.2f} ms  "
                  f"viol {row['slo_violations']:.0f}")
        knee = r["knee"]
        print(f"    knee: " + (f"{knee['offered_fps']:.1f} fps offered "
                               f"(p99 {knee['latency_p99_ms']:.2f} ms)"
                               if knee else "not reached"))
    sys.exit(rc)


def bench_rows():
    """(name, value, derived) rows for benchmarks/run.py (smoke scale)."""
    r = run(smoke=True)
    for name in ("vision", "fleet"):
        rows = r[name]["latency_vs_load"]
        yield f"serving_{name}_capacity_fps", r[name]["capacity_fps"], False
        yield (f"serving_{name}_p99_ms_light", rows[0]["latency_p99_ms"],
               True)
        yield (f"serving_{name}_p99_ms_heavy", rows[-1]["latency_p99_ms"],
               True)
        knee = r[name]["knee"]
        yield (f"serving_{name}_knee_fps",
               knee["offered_fps"] if knee else float("nan"), True)
    for row in r["throughput_vs_microbatch"]:
        yield (f"serving_tput_mb{row['microbatch']}_"
               f"{'fused' if row['fused'] else 'exact'}",
               row["frames_per_s"], False)


if __name__ == "__main__":
    main()
