import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (deliverable e).

For every (architecture x input shape) cell, on both the single-pod 16x16
mesh and the 2x16x16 multi-pod mesh:

    with mesh:
        lowered = jax.jit(step, ...).lower(**input_specs(arch))
        compiled = lowered.compile()
        print(compiled.memory_analysis())
        print(compiled.cost_analysis())

plus the collective-traffic parse of the per-device HLO, which feeds
EXPERIMENTS.md §Roofline. Results are cached as JSON under
``experiments/dryrun/``.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-34b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
    PYTHONPATH=src python -m repro.launch.dryrun --all --both-meshes
"""
import argparse
import dataclasses
import functools
import json
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro import sharding
from repro.configs import ARCHS, get_arch, get_shape, shapes_for
from repro.configs.base import ArchConfig, OptimizerConfig, ShapeSpec
from repro.launch import hlo_analysis
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import (abstract_cache_sharded,
                                abstract_opt_state,
                                abstract_params_sharded, input_specs)
from repro.models import lm
from repro.obs import clock
from repro.serving.engine import make_decode_step, make_prefill_step
from repro.train.loop import make_train_step

RESULTS_DIR = os.path.join(os.path.dirname(__file__),
                           "..", "..", "..", "experiments", "dryrun")


def optimizer_for(cfg: ArchConfig) -> OptimizerConfig:
    """Big-MoE archs need memory-reduced optimizer state to fit 16 GB/chip."""
    if cfg.num_experts >= 160:
        return OptimizerConfig(factored_second_moment=True,
                               momentum_dtype="bfloat16")
    return OptimizerConfig()


# --- §Perf hillclimb variants: tag -> (cfg_fn, opt_fn, rules_overrides) ----
# Each is one hypothesis -> change iteration; see EXPERIMENTS.md §Perf.
VARIANTS = {
    # sequence-sharded KV cache: shard the 32k cache over "model" when
    # kv_heads can't use that axis (GQA kv=8 vs 16-way TP)
    "seqkv": (None, None, {"cache_seq": "model"}),
    # seqkv + the token-gather MoE serving path (iteration 2 of the kimi
    # decode cell; the path switch itself lives in blocks.moe_apply)
    "seqkv_tokmoe": (None, None, {"cache_seq": "model"}),
    # pure Adafactor (no first moment) — 1T-params fit a single pod
    "nomom": (None, lambda o: dataclasses.replace(o, use_momentum=False),
              None),
    # MoE capacity factor 1.25 -> 1.05: -16% expert FLOPs, small drop risk
    "cap105": (lambda c: dataclasses.replace(c, capacity_factor=1.05),
               None, None),
    "nomom_cap105": (
        lambda c: dataclasses.replace(c, capacity_factor=1.05),
        lambda o: dataclasses.replace(o, use_momentum=False), None),
    # prefill: shard the sequence over "model" instead of TP-ing activations
    "seqshard": (None, None, {"seq": "model"}),
    "seqshard_seqkv": (None, None, {"seq": "model", "cache_seq": "model"}),
    # int8 gradient compression (hypothesis test: does it cut ICI bytes?)
    "gradcomp": (None, lambda o: dataclasses.replace(o, grad_compression=True),
                 None),
    # no remat: trade activation memory for -fwd recompute FLOPs
    "noremat": (lambda c: dataclasses.replace(c, remat="none"), None, None),
    # FSDP-via-rules: shard every weight's embed dim over "data" (ZeRO
    # storage; GSPMD inserts the per-layer gathers) + sequence sharding for
    # the compute: the yi-34b fix (56 heads can't use the 16-way model axis)
    "seqshard_fsdp": (None, None, {"seq": "model", "embed": "data"}),
}


def build_cell(cfg: ArchConfig, shape: ShapeSpec, mesh,
               rules: Optional[sharding.ShardingRules] = None,
               opt_cfg: Optional[OptimizerConfig] = None):
    """Returns (jitted_fn, abstract_args tuple)."""
    rules = rules or sharding.ShardingRules.make(dict(cfg.rule_overrides))
    params = abstract_params_sharded(cfg, mesh, rules)
    ins = input_specs(cfg, shape, mesh)

    if shape.kind == "train":
        opt_cfg = opt_cfg or optimizer_for(cfg)
        step = make_train_step(cfg, opt_cfg, mesh, rules)
        opt = abstract_opt_state(cfg, opt_cfg, mesh, rules)
        # pin output shardings to the input ones: otherwise GSPMD is free to
        # replicate updated params/opt state (measured +28 GB/step of
        # all-reduce on kimi without the momentum anchor — §Perf K2)
        sh_of = lambda t: jax.tree.map(lambda s: s.sharding, t,
                                       is_leaf=lambda x: isinstance(
                                           x, jax.ShapeDtypeStruct))
        fn = jax.jit(step, donate_argnums=(0, 1),
                     out_shardings=(sh_of(params), sh_of(opt), None))
        return fn, (params, opt, ins)

    if shape.kind == "prefill":
        # the served prefill, growing the cache by the one token it picks
        pf = make_prefill_step(cfg, mesh, rules, max_len=shape.seq_len + 1)
        enc = ins["encoder_embeddings"] if cfg.is_encdec else None
        return (jax.jit(functools.partial(pf, new_tokens=1)),
                (params, ins["tokens"], None, enc, None))

    # decode: one token against a seq_len-deep cache, in the served decode
    # state (the cache, a buffer holding the token in and the token out, the
    # step)
    dec = make_decode_step(cfg, mesh, rules)
    cache = abstract_cache_sharded(cfg, shape.global_batch, shape.seq_len,
                                   mesh, rules)
    tok = ins["tokens"]
    state = {"cache": cache,
             "tokens": jax.ShapeDtypeStruct((tok.shape[0], 2), tok.dtype,
                                            sharding=tok.sharding),
             "step": jax.ShapeDtypeStruct((), jnp.int32)}
    fn = jax.jit(dec, donate_argnums=(1,))
    return fn, (params, state)


def _with_layers(cfg: ArchConfig, periods: int) -> ArchConfig:
    """Prefix + N periods, fully unrolled (for cost extrapolation)."""
    n = cfg.first_dense_layers + periods * len(cfg.block_pattern)
    # whisper-style enc-dec has encoder depth == decoder depth, so scaling
    # encoder layers with the same period count keeps the delta aligned
    enc = periods if cfg.encoder_layers else 0
    return dataclasses.replace(cfg, num_layers=n, force_unroll=True,
                               encoder_layers=enc)


def _analytic_xlstm_costs(cfg: ArchConfig, shape: ShapeSpec, mesh,
                          raw_cost, raw_coll) -> Dict[str, float]:
    """xLSTM flops analytically (the chunked mLSTM cannot be unrolled at 32k+
    without trace explosion; its math is simple enough to count directly).

    Collectives: xlstm is DP-only (weights replicated), so the only traffic is
    the end-of-step gradient all-reduce, which sits OUTSIDE the layer scan and
    is therefore already counted correctly by the raw HLO parse.
    """
    d, h, dh = cfg.d_model, cfg.num_heads, cfg.resolved_head_dim
    chunk = 256      # mlstm_apply default
    n_batch = 1
    for a in ("pod", "data"):
        n_batch *= mesh.shape.get(a, 1)
    if shape.kind == "decode":
        tokens = max(shape.global_batch // n_batch, 1) * 1
    else:
        tokens = max(shape.global_batch // n_batch, 1) * shape.seq_len

    def layer_flops(mixer: str) -> float:
        if mixer == "mlstm":
            proj = 2 * d * (4 * h * dh + 2 * h) + 2 * h * dh * d
            intra = 2 * min(chunk, tokens) * h * 2 * dh
            inter = 8 * h * dh * dh
            return proj + intra + inter
        # slstm
        return 2 * d * 4 * h * dh + 8 * h * dh * dh + 2 * h * dh * d

    fwd = sum(layer_flops(mx) for mx, _ in cfg.layer_kinds()) * tokens
    fwd += 2 * 2 * cfg.vocab_size * d * tokens      # embed + logits
    mult = (4.0 if cfg.remat != "none" else 3.0) \
        if shape.kind == "train" else 1.0
    return {"flops": mult * fwd,
            "bytes": float(raw_cost.get("bytes accessed", 0.0)),
            "collective_bytes": float(raw_coll["total_bytes"]),
            "analytic": True}


def corrected_costs(cfg: ArchConfig, shape: ShapeSpec, mesh,
                    rules: Optional[sharding.ShardingRules] = None,
                    opt_cfg: Optional[OptimizerConfig] = None,
                    raw_cost=None, raw_coll=None) -> Dict[str, float]:
    """XLA cost_analysis counts while-loop bodies ONCE (scan-over-layers,
    flash kv-chunk scans). Extrapolate true per-device cost from two small
    FULLY-UNROLLED configs: cost(L) ~= cost(1 period) + (P-1)*delta, where
    delta = cost(2 periods) - cost(1 period). Collective traffic is corrected
    the same way. (sLSTM's per-timestep scan stays a loop — its flops are
    added analytically below.)"""
    if any(mx in ("mlstm", "slstm") for mx, _ in cfg.layer_kinds()):
        return _analytic_xlstm_costs(cfg, shape, mesh, raw_cost or {},
                                     raw_coll or {"total_bytes": 0})
    period = len(cfg.block_pattern)
    reps = (cfg.num_layers - cfg.first_dense_layers) / period

    out = {}
    for p_n in (1, 2):
        c = _with_layers(cfg, p_n)
        with mesh:
            fn, args = build_cell(c, shape, mesh, rules, opt_cfg)
            lowered = fn.lower(*args)
            compiled = lowered.compile()
            cost = compiled.cost_analysis()
            coll = hlo_analysis.collective_stats(compiled.as_text())
        out[p_n] = {"flops": float(cost.get("flops", 0.0)),
                    "bytes": float(cost.get("bytes accessed", 0.0)),
                    "coll": float(coll["total_bytes"])}

    scale = reps - 1.0
    corrected = {}
    for k in ("flops", "bytes", "coll"):
        delta = out[2][k] - out[1][k]
        corrected[k] = out[1][k] + scale * delta

    # analytic sLSTM correction (its seq scan cannot be unrolled)
    n_slstm = sum(1 for mx, _ in cfg.layer_kinds() if mx == "slstm")
    if n_slstm and shape.kind == "train":
        d, h, dh = cfg.d_model, cfg.num_heads, cfg.resolved_head_dim
        tokens = shape.global_batch * shape.seq_len / \
            (mesh.devices.size / mesh.shape.get("model", 1))
        per_tok = 2 * 4 * h * dh * dh      # recurrent h @ R, 4 gates
        corrected["flops"] += 3.0 * n_slstm * per_tok * tokens  # fwd+bwd
    return {"flops": corrected["flops"], "bytes": corrected["bytes"],
            "collective_bytes": corrected["coll"],
            "one_period": out[1], "two_period": out[2]}


def run_cell(arch_name: str, shape_name: str, multi_pod: bool,
             rules: Optional[sharding.ShardingRules] = None,
             tag: str = "", verbose: bool = True) -> Dict[str, Any]:
    cfg = get_arch(arch_name)
    shape = get_shape(shape_name)
    opt_cfg = None
    if tag in VARIANTS:
        cfg_fn, opt_fn, rule_over = VARIANTS[tag]
        if cfg_fn:
            cfg = cfg_fn(cfg)
        if opt_fn:
            opt_cfg = opt_fn(optimizer_for(cfg))
        if rule_over:
            merged = dict(cfg.rule_overrides)
            merged.update(rule_over)
            rules = sharding.ShardingRules.make(merged)
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = 512 if multi_pod else 256
    t0 = clock.now()
    record: Dict[str, Any] = {
        "arch": arch_name, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16", "tag": tag,
    }
    try:
        with mesh:
            fn, args = build_cell(cfg, shape, mesh, rules, opt_cfg)
            lowered = fn.lower(*args)
            t_lower = clock.now() - t0
            compiled = lowered.compile()
            t_compile = clock.now() - t0 - t_lower
            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis()
            coll = hlo_analysis.collective_stats(compiled.as_text())
        # scan-corrected per-device costs (see corrected_costs docstring)
        corr = corrected_costs(cfg, shape, mesh, rules, opt_cfg,
                               raw_cost=cost, raw_coll=coll)
        mf = hlo_analysis.model_flops_estimate(cfg, shape)
        arg_b = getattr(mem, "argument_size_in_bytes", 0) or 0
        out_b = getattr(mem, "output_size_in_bytes", 0) or 0
        ana_bytes = hlo_analysis.analytic_memory_bytes(
            cfg, shape, dict(mesh.shape), float(arg_b), float(out_b))
        rf = hlo_analysis.roofline(
            {"flops": corr["flops"], "bytes accessed": corr["bytes"]},
            {"total_bytes": int(corr["collective_bytes"]),
             "count": coll["count"]},
            n_chips, model_flops=mf, analytic_bytes=ana_bytes)
        record.update({
            "ok": True,
            "lower_s": round(t_lower, 1),
            "compile_s": round(t_compile, 1),
            "memory": {
                "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
                "output_bytes": getattr(mem, "output_size_in_bytes", None),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
                "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
            },
            "cost_raw": {k: cost.get(k) for k in
                         ("flops", "bytes accessed") if k in cost},
            "cost_corrected": {k: corr[k] for k in
                               ("flops", "bytes", "collective_bytes")},
            "collectives_raw": coll,
            "roofline": rf,
            "model_flops_global": mf,
        })
        if verbose:
            print(f"[OK] {arch_name} x {shape_name} on {record['mesh']}"
                  f" lower={t_lower:.0f}s compile={t_compile:.0f}s"
                  f" dominant={rf['dominant']}"
                  f" frac={rf.get('roofline_fraction', 0):.3f}")
            print(f"     mem: {record['memory']}")
            print(f"     coll: total={coll['total_bytes']/1e6:.1f}MB "
                  f"count={coll['count']}")
    except Exception as e:  # a failing cell is a bug — record it loudly
        record.update({"ok": False, "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-2000:]})
        if verbose:
            print(f"[FAIL] {arch_name} x {shape_name} on {record['mesh']}: "
                  f"{record['error']}")
    return record


def save_record(record: Dict[str, Any]) -> str:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    tag = f"_{record['tag']}" if record.get("tag") else ""
    path = os.path.join(
        RESULTS_DIR,
        f"{record['arch']}_{record['shape']}_{record['mesh'].replace('x','-')}"
        f"{tag}.json")
    with open(path, "w") as f:
        json.dump(record, f, indent=1)
    return path


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    cells = []
    if args.all:
        for name, cfg in sorted(ARCHS.items()):
            for shp in shapes_for(cfg):
                cells.append((name, shp.name))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells.append((args.arch, args.shape))

    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]

    failures = 0
    for mp in meshes:
        for arch, shp in cells:
            mesh_name = "2-16-16" if mp else "16-16"
            tag = f"_{args.tag}" if args.tag else ""
            path = os.path.join(RESULTS_DIR,
                                f"{arch}_{shp}_{mesh_name}{tag}.json")
            if args.skip_existing and os.path.exists(path):
                with open(path) as f:
                    if json.load(f).get("ok"):
                        continue
            rec = run_cell(arch, shp, mp, tag=args.tag)
            save_record(rec)
            failures += 0 if rec["ok"] else 1
    print(f"\n{len(cells) * len(meshes) - failures} passed, {failures} failed")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
