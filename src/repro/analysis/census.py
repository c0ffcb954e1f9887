"""Declarative jaxpr/HLO census of every public entry point.

The repo's op-structure invariants (DESIGN.md §11) — "the ADC-less pallas
frontend contains zero convolution ops and exactly one dot", "a fleet step
batches the kernel instead of duplicating it", "no f64 creeps into a jitted
step" — used to live as private census loops inside
``benchmarks/frontend_bench.py`` and ``benchmarks/fleet_bench.py``. This
module is the single implementation: a registry of *entry points* (the four
frontend backends, the exact/fused serving steps, the fleet step at two
fleet sizes, the vision train step), each traced **without executing** into

  * a jaxpr primitive census (dot_general / conv / gather / scatter /
    f64 converts / host callbacks / rng primitives / pallas_call), and
  * an HLO census of the compiled module
    (``launch.hlo_analysis.matmul_stats``: dot/conv counts + flop model),

checked two ways:

  * **structural rules** — the hard paper invariants with their historical
    thresholds (pallas dot==1/conv==0, pallas flops <= 1.2x ideal census,
    fleet G=2 census == G=1 with <= 2.05x flops). The bench ``--quick``
    gates call these.
  * **budgets** — every census field pinned exactly in the repo-root
    ``ANALYSIS_BUDGETS.json`` (regenerate with ``python -m repro.analysis
    --update-budgets``; named waivers skip individual fields). Any drift in
    either direction fails CI with the per-field diff — a stale budget file
    is a failure, not a silent pass.
"""
from __future__ import annotations

import json
import os
from typing import Callable, Dict, List, Optional, Sequence, Tuple

BUDGETS_BASENAME = "ANALYSIS_BUDGETS.json"

UPDATE_INSTRUCTIONS = (
    "If this drift is intentional, regenerate the budget file:\n"
    "    PYTHONPATH=src python -m repro.analysis --update-budgets\n"
    "then review the ANALYSIS_BUDGETS.json diff as part of the PR (the\n"
    "diff IS the reviewable claim — e.g. a new dot in the pallas step)."
)

# --- structural rules: the paper invariants with their historical gates -----
# (identical thresholds to the pre-refactor bench --quick gates)
EXPECTED_FRONTEND_CENSUS = {
    "frontend.pallas": {"dot_count": 1, "conv_count": 0},  # ONE packed dot
    "frontend.analog": {"dot_count": 0, "conv_count": 1},  # packed 2-phase
    "frontend.device": {"dot_count": 0, "conv_count": 1},
    "frontend.ideal": {"dot_count": 0, "conv_count": 1},
}
# the quantized fused step (DESIGN.md §14): exactly ONE dot, both operands
# int8, zero f32-operand dots, and the accumulator dtype pinned per mode —
# f32 in interpret mode (exact: products < 2^14, K=27 keeps sums < 2^24),
# int32 on the real-MXU trace. Checked against the JAXPR census because
# XLA:CPU rewrites s8 dots into f32 GEMMs in optimized HLO.
EXPECTED_QUANT_JAXPR = {
    "quant.fused_q8": {"dot_i8": 1, "dot_f32": 0, "acc": "float32"},
    "quant.fused_q8_mxu": {"dot_i8": 1, "dot_f32": 0, "acc": "int32"},
}
PALLAS_MATMUL_BUDGET = 1.2     # flops vs ideal census  # analysis: waive=physics-constants (threshold, not the 1.2 V pixel constant)
FLEET_FLOP_BUDGET = 2.05       # G=2 flops vs G=1 (chip axis must batch)

# shapes the censuses are taken at (must stay fixed: budgets pin absolute
# flop numbers at these shapes)
FRONTEND_BATCH = 16
STREAM_BATCH = 8
FLEET_BATCH = 8
TRAIN_BATCH = 8


# --- jaxpr census -----------------------------------------------------------

_RNG_PRIMS = ("threefry2x32", "random_seed", "random_bits", "random_wrap",
              "random_unwrap", "random_fold_in", "random_gamma",
              "random_clone", "prng_seed", "prng_random_bits")


def _classify_prim(name: str) -> Optional[str]:
    if name == "dot_general":
        return "dot_general"
    if name == "conv_general_dilated":
        return "conv"
    if name == "gather":
        return "gather"
    if name.startswith("scatter"):
        return "scatter"
    if name == "pallas_call":
        return "pallas_call"
    if name in _RNG_PRIMS:
        return "rng"
    if "callback" in name:
        return "host_callback"
    return None


def _sub_jaxprs(value):
    from jax.extend import core as jex_core
    if isinstance(value, jex_core.Jaxpr):
        yield value
    elif isinstance(value, jex_core.ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, (list, tuple)):
        for v in value:
            yield from _sub_jaxprs(v)


def _walk_jaxpr(jaxpr, counts: Dict[str, int],
                i8_sigs: List[str]) -> None:
    import jax.numpy as jnp
    for eqn in jaxpr.eqns:
        counts["eqn_count"] += 1
        kind = _classify_prim(eqn.primitive.name)
        if kind is not None:
            counts[kind] += 1
        if eqn.primitive.name == "dot_general":
            # operand-dtype split of the dots (DESIGN.md §14): the quantized
            # path is pinned at the JAXPR level — XLA:CPU rewrites an
            # s8 x s8 -> f32 dot into an f32 GEMM in optimized HLO, so an
            # HLO-level gate would never see the int8 operands.
            avals = [v.aval for v in eqn.invars]
            dts = [str(a.dtype) for a in avals]
            if all(d == "int8" for d in dts):
                counts["dot_i8"] += 1
                out_dt = str(eqn.outvars[0].aval.dtype)
                i8_sigs.append(
                    "x".join(f"{'x'.join(map(str, a.shape))}:{d}"
                             for a, d in zip(avals, dts)) + f"->{out_dt}")
            elif any(d == "float32" for d in dts):
                counts["dot_f32"] += 1
        if (eqn.primitive.name == "convert_element_type"
                and eqn.params.get("new_dtype") == jnp.float64):
            counts["f64_convert"] += 1
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                _walk_jaxpr(sub, counts, i8_sigs)


def jaxpr_census(fn: Callable, *args, **kwargs) -> Dict[str, object]:
    """Trace ``fn`` (without executing) and count primitives of interest.

    Counts are *static* — an op inside a scan/while body counts once
    (matching the HLO census semantics in ``hlo_analysis.matmul_stats``);
    sub-jaxprs (pjit bodies, cond branches, pallas kernel bodies) are
    walked recursively. ``dot_i8`` / ``dot_f32`` split ``dot_general`` by
    operand dtype, and ``dot_i8_sig`` pins each int8 dot's full
    shape/dtype signature (operands and accumulator) as a string.
    """
    import jax
    counts: Dict[str, object] = {
        k: 0 for k in ("eqn_count", "dot_general", "conv", "gather",
                       "scatter", "pallas_call", "rng",
                       "host_callback", "f64_convert", "dot_i8", "dot_f32")}
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    i8_sigs: List[str] = []
    _walk_jaxpr(closed.jaxpr, counts, i8_sigs)
    counts["dot_i8_sig"] = ";".join(i8_sigs)
    return counts


# --- HLO census -------------------------------------------------------------

def hlo_census(jitted_fn, *args, **kwargs) -> Tuple[Dict, object]:
    """Compile ``jitted_fn`` at the example arguments (no execution) and
    return ``(matmul_stats census, compiled)``."""
    from repro.launch import hlo_analysis
    compiled = jitted_fn.lower(*args, **kwargs).compile()
    return hlo_analysis.matmul_stats(compiled.as_text()), compiled


# --- entry-point registry ---------------------------------------------------
#
# A *group builder* constructs the engines once and yields
# (entry_name, jitted_fn, args) triples; ``collect`` runs both censuses on
# each. Builders must be deterministic (fixed seeds/shapes) so budgets pin
# exact numbers.

def _frontend_setup(batch: int = FRONTEND_BATCH):
    import jax

    from repro import frontend
    from repro.core import p2m
    cfg = p2m.P2MConfig()
    fe_cfg = frontend.FrontendConfig(p2m=cfg, global_shutter=False)
    fe = frontend.SensorFrontend(fe_cfg)
    params = fe.init(jax.random.PRNGKey(0))
    frames = jax.random.uniform(jax.random.PRNGKey(1), (batch, 32, 32, 3))
    key = jax.random.PRNGKey(2)
    return fe, params, frames, key


def _frontend_entries(batch: int = FRONTEND_BATCH):
    import jax

    from repro import frontend
    fe, params, frames, key = _frontend_setup(batch)
    for mode in frontend.list_backends():
        step = jax.jit(lambda p, x, k, m=mode: fe(p, x, key=k, mode=m)[0])
        yield f"frontend.{mode}", step, (params, frames, key)


def _stream_entries():
    import jax
    import jax.numpy as jnp

    from repro.models import vision
    from repro.serving.vision import VisionEngine
    cfg = vision.VisionConfig(name="census", arch="vgg_tiny", num_classes=10)
    params = vision.init_params(jax.random.PRNGKey(0), cfg)
    frames = jax.random.uniform(jax.random.PRNGKey(1),
                                (STREAM_BATCH, 32, 32, 3))
    key = jax.random.PRNGKey(2)
    eng = VisionEngine(cfg, params, backend="pallas", seed=0)
    yield "stream.exact", eng._step, (eng.params, frames, key)
    theta = jnp.asarray(0.7, jnp.float32)
    yield "stream.fused", eng._fused_step, (eng.params, frames, key, theta)


def _fleet_entries():
    import jax

    from repro.models import vision
    from repro.serving import FleetEngine
    cfg = vision.VisionConfig(name="census", arch="vgg_tiny", num_classes=10)
    params = vision.init_params(jax.random.PRNGKey(0), cfg)
    frames = jax.random.uniform(jax.random.PRNGKey(1),
                                (FLEET_BATCH, 32, 32, 3))
    for g in (1, 2):
        fe = FleetEngine(cfg, params, backend="pallas", seed=0,
                         chips_per_step=g, fused_stream=False)
        for c in range(g):
            fe.add_chip(c)
        idx = jax.numpy.arange(g, dtype=jax.numpy.int32)
        chips = jax.tree.map(lambda a: a[idx], fe.state.chips0)
        trims = fe.state.trim[idx]
        gf = jax.numpy.stack([frames] * g)
        keys = jax.random.split(jax.random.PRNGKey(0), g)
        yield f"fleet.g{g}", fe._step, (params, chips, trims, gf, keys)


def _train_entries():
    import jax
    import jax.numpy as jnp

    from repro.models import vision
    from repro.train.vision import make_step
    cfg = vision.VisionConfig(name="census", arch="vgg_tiny", num_classes=10)
    params = vision.init_params(jax.random.PRNGKey(0), cfg)
    batch = {"image": jax.random.uniform(jax.random.PRNGKey(1),
                                         (TRAIN_BATCH, 32, 32, 3)),
             "label": jnp.zeros((TRAIN_BATCH,), jnp.int32)}
    step = make_step(cfg, lr=3e-3)
    yield "train.step", step, (params, batch, jax.random.PRNGKey(2))


def _quant_entries():
    import functools

    import jax
    import jax.numpy as jnp

    from repro.core import p2m
    from repro.kernels import ops
    cfg = p2m.P2MConfig()
    params = p2m.init_params(jax.random.PRNGKey(0), cfg)
    wq = p2m.quantize_weights(params["w"], cfg.weight_bits)
    v_th = params["v_th"]
    frames = jax.random.uniform(jax.random.PRNGKey(1),
                                (FRONTEND_BATCH, 32, 32, 3))
    key = jax.random.PRNGKey(2)
    theta = jnp.asarray(0.7, jnp.float32)
    # the int8 fused streaming step as the CPU validation path runs it:
    # interpret-mode pallas, f32 accumulator (exact — DESIGN.md §14)
    step = jax.jit(functools.partial(
        ops.p2m_frontend_fused, kernel=cfg.kernel_size, stride=cfg.stride,
        precision="int8", interpret=True))
    yield "quant.fused_q8", step, (frames, wq, v_th, theta, key)
    # the SAME step the way a real TPU serves it: interpret=False (int32
    # MXU accumulator) + on-device RNG. Jaxpr-only — Mosaic lowering needs
    # TPU hardware, but make_jaxpr traces the kernel body fine, which is
    # all the int8-dot-shape pin needs.
    mxu = jax.jit(functools.partial(
        ops.p2m_frontend_fused, kernel=cfg.kernel_size, stride=cfg.stride,
        precision="int8", interpret=False, on_device_rng=True))
    yield ("quant.fused_q8_mxu", mxu, (frames, wq, v_th, theta, key),
           {"hlo": False})


ENTRY_GROUPS: Dict[str, Callable] = {
    "frontend": _frontend_entries,
    "stream": _stream_entries,
    "fleet": _fleet_entries,
    "train": _train_entries,
    "quant": _quant_entries,
}


def collect(groups: Optional[Sequence[str]] = None,
            hlo: bool = True) -> Dict[str, Dict[str, Dict]]:
    """Census every entry point of the requested groups (default: all).

    Returns ``{entry_name: {"jaxpr": {...}, "hlo": {...}}}`` (the "hlo"
    block is omitted with ``hlo=False`` — jaxpr-only is much faster when a
    caller only needs primitive counts).
    """
    names = list(ENTRY_GROUPS) if groups is None else list(groups)
    out: Dict[str, Dict[str, Dict]] = {}
    for g in names:
        if g not in ENTRY_GROUPS:
            raise KeyError(f"unknown census group {g!r}; "
                           f"known: {sorted(ENTRY_GROUPS)}")
        for item in ENTRY_GROUPS[g]():
            # builders yield (name, fn, args) or (name, fn, args, opts);
            # opts={"hlo": False} marks jaxpr-only entries (e.g. the
            # interpret=False pallas trace, which cannot compile off-TPU)
            name, fn, args = item[:3]
            opts = item[3] if len(item) > 3 else {}
            entry: Dict[str, Dict] = {"jaxpr": jaxpr_census(fn, *args)}
            if hlo and opts.get("hlo", True):
                entry["hlo"], _ = hlo_census(fn, *args)
            out[name] = entry
    return out


# --- structural rules -------------------------------------------------------

def structural_failures(results: Dict[str, Dict]) -> List[str]:
    """The hard invariants, at their historical bench-gate thresholds.

    Only checks rules whose entries are present in ``results`` — a caller
    that collected just the "frontend" group gets just the frontend rules.
    """
    fails: List[str] = []
    for entry, want in EXPECTED_FRONTEND_CENSUS.items():
        got = results.get(entry, {}).get("hlo")
        if got is None:
            continue
        for field, val in want.items():
            if got[field] != val:
                fails.append(f"{entry}.hlo.{field}: expected {val}, "
                             f"got {got[field]}")
    for entry, want in EXPECTED_QUANT_JAXPR.items():
        got = results.get(entry, {}).get("jaxpr")
        if got is None:
            continue
        for field in ("dot_i8", "dot_f32"):
            if got[field] != want[field]:
                fails.append(f"{entry}.jaxpr.{field}: expected "
                             f"{want[field]}, got {got[field]}")
        sig = got.get("dot_i8_sig", "")
        if want["dot_i8"] and not sig.endswith("->" + want["acc"]):
            fails.append(f"{entry}.jaxpr.dot_i8_sig: accumulator must be "
                         f"{want['acc']}, got {sig!r}")
    ideal = results.get("frontend.ideal", {}).get("hlo")
    pallas = results.get("frontend.pallas", {}).get("hlo")
    if ideal is not None and pallas is not None:
        ratio = pallas["matmul_flops"] / ideal["matmul_flops"]
        if ratio > PALLAS_MATMUL_BUDGET:
            fails.append(
                f"frontend.pallas.hlo.matmul_flops: "
                f"{pallas['matmul_flops']:.0f} is {ratio:.2f}x the ideal "
                f"census ({ideal['matmul_flops']:.0f}); budget is "
                f"{PALLAS_MATMUL_BUDGET}x")
    one = results.get("fleet.g1", {}).get("hlo")
    two = results.get("fleet.g2", {}).get("hlo")
    if one is not None and two is not None:
        for field in ("dot_count", "conv_count"):
            if one[field] != two[field]:
                fails.append(f"fleet.{field}: G=1 has {one[field]}, "
                             f"G=2 has {two[field]} — the chip axis must "
                             "batch the kernel, not duplicate it")
        if two["matmul_flops"] > FLEET_FLOP_BUDGET * one["matmul_flops"]:
            fails.append(
                f"fleet.matmul_flops: G=2 ({two['matmul_flops']:.0f}) "
                f"exceeds {FLEET_FLOP_BUDGET}x G=1 "
                f"({one['matmul_flops']:.0f}) — the chip axis is "
                "duplicating work, not batching it")
    return fails


# --- budgets ----------------------------------------------------------------

def default_budgets_path(start: Optional[str] = None) -> str:
    """Walk up from ``start`` (default cwd) to the repo-root budget file."""
    d = os.path.abspath(start or os.getcwd())
    while True:
        cand = os.path.join(d, BUDGETS_BASENAME)
        if os.path.exists(cand):
            return cand
        parent = os.path.dirname(d)
        if parent == d:
            # not found: return the conventional location (callers get a
            # clear "missing file" error with the update instruction)
            return os.path.join(os.getcwd(), BUDGETS_BASENAME)
        d = parent


def load_budgets(path: Optional[str] = None) -> Dict:
    path = path or default_budgets_path()
    if not os.path.exists(path):
        raise FileNotFoundError(
            f"{path} not found — generate it with\n"
            "    PYTHONPATH=src python -m repro.analysis --update-budgets")
    with open(path) as f:
        return json.load(f)


def update_budgets(results: Dict[str, Dict],
                   path: Optional[str] = None) -> str:
    """Write ``results`` as the new budget file, preserving waivers."""
    path = path or default_budgets_path()
    prev: Dict = {}
    if os.path.exists(path):
        with open(path) as f:
            prev = json.load(f)
    doc = {
        "_readme": [
            "Static-analysis budgets (DESIGN.md §11). 'census' pins the",
            "jaxpr/HLO op census of every traced entry point; any drift",
            "fails scripts/lint.sh. Regenerate with",
            "  PYTHONPATH=src python -m repro.analysis --update-budgets",
            "and REVIEW THE DIFF — it is the op-structure claim of the PR.",
            "'waivers.census' skips {entry, field} pairs; 'waivers.ast'",
            "skips {rule, path} pairs of the AST pass. Every waiver needs",
            "a reason.",
        ],
        "census": results,
        "waivers": prev.get("waivers", {"census": [], "ast": []}),
    }
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
    return path


def _flatten(d: Dict, prefix: str = "") -> Dict[str, object]:
    out: Dict[str, object] = {}
    for k, v in d.items():
        key = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(_flatten(v, key + "."))
        else:
            out[key] = v
    return out


def _values_differ(a, b) -> bool:
    if isinstance(a, float) or isinstance(b, float):
        fa, fb = float(a), float(b)
        return abs(fa - fb) > 1e-6 * max(abs(fa), abs(fb), 1.0)
    return a != b


def budget_failures(results: Dict[str, Dict], budgets: Dict) -> List[str]:
    """Exact per-field diff of the collected census vs the budget file.

    Any mismatch — in either direction — is a failure: a *regression* means
    the code grew ops the paper claims it does not have; an *improvement*
    means the checked-in budget is stale and must be regenerated so the
    next regression is caught at the new baseline.
    """
    fails: List[str] = []
    budget_census: Dict[str, Dict] = budgets.get("census", {})
    waived = {(w.get("entry"), w.get("field"))
              for w in budgets.get("waivers", {}).get("census", [])}

    def is_waived(entry: str, field: str) -> bool:
        return ((entry, field) in waived or (entry, None) in waived
                or (entry, "*") in waived)

    for entry, want in sorted(budget_census.items()):
        if entry not in results:
            continue                      # caller collected a subset
        got_flat = _flatten(results[entry])
        want_flat = _flatten(want)
        for field, val in sorted(want_flat.items()):
            if is_waived(entry, field):
                continue
            if field not in got_flat:
                fails.append(f"{entry}.{field}: in budget ({val!r}) but "
                             "missing from the census — stale budget")
            elif _values_differ(got_flat[field], val):
                fails.append(f"{entry}.{field}: budget {val!r}, "
                             f"current {got_flat[field]!r}")
        for field in sorted(set(got_flat) - set(want_flat)):
            if not is_waived(entry, field):
                fails.append(f"{entry}.{field}: censused "
                             f"({got_flat[field]!r}) but absent from the "
                             "budget — stale budget")
    for entry in sorted(set(results) - set(budget_census)):
        fails.append(f"{entry}: traced entry point has no budget — stale "
                     "budget file")
    return fails


def check(results: Dict[str, Dict],
          budgets: Optional[Dict] = None) -> List[str]:
    """Structural rules + (when ``budgets`` given) the budget diff; the
    returned failure list already carries the regeneration instructions."""
    fails = structural_failures(results)
    if budgets is not None:
        fails += budget_failures(results, budgets)
    if fails:
        fails.append(UPDATE_INSTRUCTIONS)
    return fails


# --- bench-facing helpers (the --quick gates call these) --------------------

def frontend_step_info(batch: int = FRONTEND_BATCH) -> Dict[str, Dict]:
    """Census + cost + jitted step per frontend backend (the shape the
    benches time): ``{mode: {"census", "cost", "step", "args"}}``."""
    out: Dict[str, Dict] = {}
    for name, fn, args in _frontend_entries(batch):
        mode = name.split(".", 1)[1]
        census, compiled = hlo_census(fn, *args)
        out[mode] = {"census": census, "cost": compiled.cost_analysis(),
                     "step": fn, "args": args}
    return out


def _gate(results: Dict[str, Dict], header: str) -> int:
    import sys
    fails = check(results)
    for entry in sorted(results):
        c = results[entry].get("hlo")
        if c is None:                     # jaxpr-only entry (no HLO off-TPU)
            j = results[entry]["jaxpr"]
            print(f"  {entry:16s} dot_i8={j['dot_i8']} "
                  f"dot_f32={j['dot_f32']} sig={j['dot_i8_sig'] or '-'}")
            continue
        print(f"  {entry:16s} dot={c['dot_count']} conv={c['conv_count']} "
              f"matmul_flops={c['matmul_flops']:.3g}")
    if fails:
        print(f"REGRESSION — {header} census drifted:", file=sys.stderr)
        for f in fails:
            print(f"  {f}", file=sys.stderr)
        return 1
    print("quick census gate: OK")
    return 0


def quick_frontend_gate() -> int:
    """frontend_bench --quick: structural frontend invariants plus the
    quantized-dot pin (no timing, no budget file — the budget diff runs in
    scripts/lint.sh)."""
    return _gate(collect(["frontend", "quant"]), "frontend")


def quick_fleet_gate() -> int:
    """fleet_bench --quick: the G=1-vs-G=2 fleet batching invariant."""
    return _gate(collect(["fleet"]), "fleet step")
