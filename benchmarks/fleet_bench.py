"""Fleet-serving benchmark -> BENCH_fleet.json.

The production question behind serving/fleet.py (DESIGN.md §10): a
deployment is not one chip but a POPULATION of distinct aging sensors
streaming concurrently. ``FleetEngine`` batches frames across chips in one
vmapped jitted step and maintains the fleet with amortized background
recalibration sweeps. This benchmark writes the curves that justify it:

    throughput vs fleet size     frames/s serving F concurrent chip streams
                                 (fixed per-chip microbatch), F = 1..8 —
                                 the chip axis rides the kernel grid, so
                                 fps should grow, not flatline
    throughput vs chips/step     the packing knob at a fixed fleet
    recal amortization           sweep wall overhead + maintenance energy
                                 per frame vs refresh period (tester pJ
                                 amortized over served frames)
    single-chip parity           a 1-chip fleet is bit-identical to
                                 VisionEngine (asserted, recorded)
    fused frontend parity        the fleet fused frontend at G=1 vs the
                                 single-chip fps recorded in
                                 BENCH_frontend.json at the same batch —
                                 the fleet wrapper must be within 10%

Usage:
    PYTHONPATH=src python benchmarks/fleet_bench.py [--smoke|--quick] \
        [--out BENCH_fleet.json] [--warnings-as-errors]

``--quick`` (CI): static HLO census gate only — the vmapped fleet step at
G = 2 must run the SAME pallas dot/conv census as the single-chip step
(the chip axis must batch the kernel, never duplicate it). Exits 1 on
drift, no timing.

``--smoke`` (CI): fewer fleet sizes / repeats — same JSON schema.
``--warnings-as-errors`` promotes warnings from ``repro.serving`` to
errors (ci.sh sets it).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import warnings

FRONTEND_JSON = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             os.pardir, "BENCH_frontend.json")

# the aging/mismatch profiles mirror lifetime_bench's reference deployment
VARIATION_PROFILE = dict(sigma_logit_offset=0.4, sigma_pixel_offset=0.25,
                         sigma_pixel_gain=0.05)
DRIFT_PROFILE = dict(sigma_pixel_offset=0.12, sigma_logit_offset=0.20,
                     tau_frames=1.0e3)


def _setup(batch: int = 16):
    import jax

    from repro.models import vision

    cfg = vision.VisionConfig(name="fleet_bench", arch="vgg_tiny",
                              num_classes=10)
    params = vision.init_params(jax.random.PRNGKey(0), cfg)
    frames = jax.random.uniform(jax.random.PRNGKey(1), (batch, 32, 32, 3))
    return cfg, params, frames


def _time_ms(fn, repeats: int = 10) -> float:
    import jax
    jax.block_until_ready(fn())                       # compile + warm
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best * 1e3


def quick_check() -> int:
    """CI census gate: delegates to ``repro.analysis.census``, the single
    census implementation — identical rule/thresholds to the pre-refactor
    private copy (G=2 fleet step must run the SAME dot/conv census as G=1,
    with <= 2.05x the matmul flops: vmap batches the grid, never
    duplicates it)."""
    from repro.analysis import census
    return census.quick_fleet_gate()


def _single_chip_parity(cfg, params, frames) -> bool:
    """A 1-chip fleet reproduces VisionEngine draw for draw."""
    import numpy as np

    from repro.serving import FleetEngine, VisionEngine

    ve = VisionEngine(cfg, params, backend="pallas", seed=0, microbatch=8)
    fe = FleetEngine(cfg, params, backend="pallas", seed=0, microbatch=8)
    batches = [frames, frames[::-1]]
    ok = True
    for ov, (of,) in zip(ve.stream(batches),
                         fe.stream([[(0, b)] for b in batches])):
        ok &= np.array_equal(np.asarray(ov["labels"]),
                             np.asarray(of["labels"]))
        ok &= np.array_equal(np.asarray(ov["probs"]),
                             np.asarray(of["probs"]))
    return bool(ok)


def run(smoke: bool = False) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.core import energy, p2m
    from repro.kernels import blocking, ops
    from repro.lifetime import DriftConfig, SchedulePolicy
    from repro.models import vision
    from repro.platform import pallas_interpret
    from repro.serving import FleetEngine, FleetSweepPolicy
    from repro.variation import VariationConfig

    mb = 16
    repeats = 3 if smoke else 10
    rounds = 2 if smoke else 5
    fleet_sizes = (1, 2, 4) if smoke else (1, 2, 4, 8)
    cfg, params, frames = _setup(batch=mb)
    vcfg = VariationConfig(**VARIATION_PROFILE)
    cfgv = vision.VisionConfig(name="fleet_bench", arch="vgg_tiny",
                               num_classes=10, variation=vcfg)
    dcfg = DriftConfig(**DRIFT_PROFILE)
    cal_frames = jax.random.uniform(jax.random.PRNGKey(7),
                                    (8 if smoke else 16, 32, 32, 3))

    results = {"smoke": smoke, "microbatch": mb, "hw": 32,
               "repeats": repeats,
               "interpret": pallas_interpret(),
               "variation_profile": VARIATION_PROFILE,
               "drift_profile": DRIFT_PROFILE}

    # --- throughput vs fleet size (all chips packed into one step) --------
    def reqs(fe, fsize, seed):
        return [(c, jax.random.uniform(
            jax.random.fold_in(jax.random.PRNGKey(seed), c),
            (mb, 32, 32, 3))) for c in range(fsize)]

    curve = []
    for fsize in fleet_sizes:
        fe = FleetEngine(cfgv, params, backend="pallas", seed=0,
                         chips_per_step=fsize, drift=dcfg,
                         calibration_frames=cal_frames)
        fe.serve(reqs(fe, fsize, 0))                   # register + compile
        fe.serve(reqs(fe, fsize, 1))                   # warm the fused step
        best = float("inf")
        for r in range(repeats):
            t0 = time.perf_counter()
            for s in range(rounds):
                fe.serve(reqs(fe, fsize, 2 + r * rounds + s))
            best = min(best, time.perf_counter() - t0)
        fps = fsize * mb * rounds / best
        curve.append({"fleet_size": fsize, "frames_per_s": fps,
                      "wall_ms_per_round": best * 1e3 / rounds,
                      "exact_cache": fe._step._cache_size(),
                      "fused_cache": fe._fused_step._cache_size()})
    results["throughput_vs_fleet_size"] = curve
    base_fps = curve[0]["frames_per_s"]
    results["fleet_speedup_at_max"] = curve[-1]["frames_per_s"] / base_fps

    # --- throughput vs chips_per_step at a fixed fleet --------------------
    fsize = max(fleet_sizes)
    packing = []
    for g in (1, 2, fsize):
        fe = FleetEngine(cfgv, params, backend="pallas", seed=0,
                         chips_per_step=g, drift=dcfg,
                         calibration_frames=cal_frames)
        fe.serve(reqs(fe, fsize, 0))
        fe.serve(reqs(fe, fsize, 1))
        best = float("inf")
        for r in range(repeats):
            t0 = time.perf_counter()
            for s in range(rounds):
                fe.serve(reqs(fe, fsize, 50 + r * rounds + s))
            best = min(best, time.perf_counter() - t0)
        packing.append({"chips_per_step": g,
                        "frames_per_s": fsize * mb * rounds / best})
    results["throughput_vs_chips_per_step"] = packing

    # --- recalibration amortization ---------------------------------------
    # the sweep refreshes K chips per serve() out of an F-chip fleet: the
    # tester cost is recal_energy_pj per refresh, amortized over the frames
    # the fleet served since — plus the measured sweep wall overhead
    spec = energy.FrameSpec(h_in=32, w_in=32, c_in=3, h_out=8, w_out=8,
                            c_out=cfg.p2m.out_channels,
                            kernel=cfg.p2m.kernel_size,
                            stride=cfg.p2m.stride,
                            n_mtj=cfg.p2m.mtj.n_redundant)
    e_frame = energy.frontend_energy_ours(spec)
    amort = []
    for period in (64, 256, 1024):
        sweep = FleetSweepPolicy(policy=SchedulePolicy(period_frames=period),
                                 refresh_per_sweep=2, auto=False)
        fe = FleetEngine(cfgv, params, backend="pallas", seed=0,
                         chips_per_step=4, drift=dcfg, sweep=sweep,
                         calibration_frames=cal_frames)
        fe.serve(reqs(fe, 4, 0))
        fe.serve(reqs(fe, 4, 1))
        # drive every chip past the refresh period, then time one sweep
        need = period // (mb * 2) + 1
        for s in range(need):
            fe.serve(reqs(fe, 4, 100 + s))
        t0 = time.perf_counter()
        report = fe.run_sweep()
        sweep_ms = (time.perf_counter() - t0) * 1e3
        recal_pj = fe._scheduler.recal_energy_pj
        e_maint = recal_pj / period                    # pJ/frame amortized
        amort.append({
            "recal_period_frames": period,
            "refreshed": len(report["refreshed"]),
            "sweep_wall_ms": sweep_ms,
            "recalibration_pj": recal_pj,
            "maintenance_per_frame_pj": e_maint,
            "maintenance_overhead_fraction": e_maint / e_frame,
        })
    results["recal_amortization"] = amort

    # --- single-chip parity (bit-exactness, recorded as a gate) -----------
    results["single_chip_parity"] = _single_chip_parity(cfg, params, frames)

    # --- fused frontend: fleet wrapper at G=1 vs BENCH_frontend.json ------
    pcfg = cfg.p2m
    wq = p2m.quantize_weights(params["p2m"]["w"], pcfg.weight_bits)
    v_th = params["p2m"]["v_th"]
    key = jax.random.PRNGKey(3)
    out = ops.p2m_frontend(frames, wq, v_th, key,
                           kernel=pcfg.kernel_size, stride=pcfg.stride,
                           pixel_params=pcfg.pixel, mtj_params=pcfg.mtj)
    theta = jnp.asarray(out[1]["theta"], jnp.float32)
    gf, gk = frames[None], key[None]
    gtheta = theta[None]

    # measured EXACTLY the way frontend_bench measures its headline pallas
    # number: a jitted activations-only wrapper (aux pruned by XLA), min of
    # alternating single-shot runs so host drift cannot bias the pair
    single_step = jax.jit(lambda im, th, k: ops.p2m_frontend_fused(
        im, wq, v_th, th, k, kernel=pcfg.kernel_size, stride=pcfg.stride,
        pixel_params=pcfg.pixel, mtj_params=pcfg.mtj)[0])
    fleet_step = jax.jit(lambda im, th, k: ops.p2m_frontend_fused_fleet(
        im, wq, v_th, th, k, kernel=pcfg.kernel_size, stride=pcfg.stride,
        pixel_params=pcfg.pixel, mtj_params=pcfg.mtj)[0])
    jax.block_until_ready(single_step(frames, theta, key))
    jax.block_until_ready(fleet_step(gf, gtheta, gk))
    best_single = best_fleet = float("inf")
    # same round count as frontend_bench's interleaved headline timing —
    # a min over too few rounds reads high on a noisy host and the
    # vs-BENCH_frontend ratio drifts with it
    for _ in range(max(4 * repeats, 20)):
        t0 = time.perf_counter()
        jax.block_until_ready(single_step(frames, theta, key))
        best_single = min(best_single, time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.block_until_ready(fleet_step(gf, gtheta, gk))
        best_fleet = min(best_fleet, time.perf_counter() - t0)
    ms, single_ms = best_fleet * 1e3, best_single * 1e3
    fleet_fps = mb / (ms / 1e3)
    results["fleet_fused_frontend"] = {
        "batch": mb, "wall_ms": ms, "frames_per_s": fleet_fps,
        "single_chip_wall_ms": single_ms,
        "single_chip_frames_per_s": mb / (single_ms / 1e3),
        # the chip-axis wrapper's own overhead, host-drift-free
        "fleet_vs_single_inprocess": single_ms / ms,
    }
    if os.path.exists(FRONTEND_JSON):
        with open(FRONTEND_JSON) as f:
            ref_fps = json.load(f)["backends"]["pallas"]["frames_per_s"]
        results["frontend_bench_frames_per_s"] = ref_fps
        results["fleet_fused_fps_ratio"] = fleet_fps / ref_fps
    else:
        results["frontend_bench_frames_per_s"] = None
        results["fleet_fused_fps_ratio"] = None
    return results


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="static census gate only (CI): the vmapped fleet "
                         "step must not change the pallas kernel census")
    ap.add_argument("--smoke", action="store_true",
                    help="fewer fleet sizes / repeats (CI)")
    ap.add_argument("--out", default="BENCH_fleet.json")
    ap.add_argument("--warnings-as-errors", action="store_true",
                    help="fail on any warning raised from repro.serving")
    args = ap.parse_args()
    if args.warnings_as_errors:
        warnings.filterwarnings("error", module=r"repro\.serving.*")
    if args.quick:
        sys.exit(quick_check())
    results = run(smoke=args.smoke)
    from repro.obs.export import bench_meta
    results["meta"] = bench_meta("fleet", smoke=args.smoke)
    with open(args.out, "w") as f:
        json.dump(results, f, indent=2, sort_keys=True)
    print(f"wrote {args.out}")
    for row in results["throughput_vs_fleet_size"]:
        print(f"  fleet {row['fleet_size']:2d}: "
              f"{row['frames_per_s']:8.1f} frames/s "
              f"(caches {row['exact_cache']}+{row['fused_cache']})")
    print(f"  speedup at max fleet: "
          f"{results['fleet_speedup_at_max']:.2f}x")
    print(f"  single-chip parity: {results['single_chip_parity']}")
    ratio = results["fleet_fused_fps_ratio"]
    if ratio is not None:
        print(f"  fleet fused frontend vs BENCH_frontend: {ratio:.2f}x")
    if not results["single_chip_parity"]:
        sys.exit(1)


def bench_rows():
    """(name, value, derived) rows for benchmarks/run.py (smoke scale)."""
    r = run(smoke=True)
    for row in r["throughput_vs_fleet_size"]:
        yield (f"fleet_fps_F{row['fleet_size']}", row["frames_per_s"],
               False)
    yield "fleet_speedup_at_max", r["fleet_speedup_at_max"], True
    yield "fleet_single_chip_parity", float(r["single_chip_parity"]), False
    yield ("fleet_maintenance_overhead_p1024",
           r["recal_amortization"][-1]["maintenance_overhead_fraction"],
           True)
    if r["fleet_fused_fps_ratio"] is not None:
        yield "fleet_fused_fps_ratio", r["fleet_fused_fps_ratio"], True


if __name__ == "__main__":
    main()
