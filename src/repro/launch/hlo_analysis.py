"""HLO-level analysis of compiled dry-run artifacts.

``collective_stats`` parses the (post-SPMD, per-device) HLO text and sums the
traffic of every collective op; ``roofline`` combines it with
``cost_analysis()`` into the three-term roofline of EXPERIMENTS.md §Roofline.

Hardware constants: TPU v5e — 197 TFLOP/s bf16/chip, 819 GB/s HBM,
~50 GB/s/link ICI (per the brief).
"""
from __future__ import annotations

import re
from typing import Dict, Iterator, List, Optional, Tuple

PEAK_FLOPS = 197e12          # bf16 / chip
HBM_BW = 819e9               # bytes/s / chip
ICI_BW = 50e9                # bytes/s / link

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1,
    "u64": 8, "u32": 4, "u16": 2, "u8": 1, "pred": 1, "c64": 8,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

# any shape literal on an op line:  bf16[8,128]
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


# the opcode: the first lower-case word directly followed by "(" after the
# result type (layouts such as {1,0:T(8,128)} never put a space before "(")
_OPCODE_RE = re.compile(r"(?:^|\s)([a-z][a-z0-9\-]*)\(")


def _split_operands(args: str) -> List[str]:
    """The top-level comma-separated operands of ``op(...)...`` text that
    starts just after the opening parenthesis."""
    out, depth, cur = [], 1, []
    for ch in args:
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
            if depth == 0:
                break
        if ch == "," and depth == 1:
            out.append("".join(cur))
            cur = []
        else:
            cur.append(ch)
    out.append("".join(cur))
    return [o.strip() for o in out if o.strip()]


def _instructions(hlo_text: str) -> Iterator[Tuple[str, List, List, str]]:
    """``(opcode, result shapes, operand shapes, line)`` per instruction.

    Operand shapes are read inline where the text carries them
    (``dot(f32[8,16]{1,0} %a, ...)``) and otherwise looked up from the
    instruction that defined the operand (``dot(%a, %b)``).
    """
    defined: Dict[str, List] = {}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if " = " not in stripped:
            continue
        lhs, rhs = stripped.split(" = ", 1)
        m = _OPCODE_RE.search(rhs)
        if m is None:
            continue
        result = _SHAPE_RE.findall(rhs[:m.start(1)])
        defined[lhs.split()[-1]] = result
        operands = []
        for tok in _split_operands(rhs[m.end():]):
            inline = _SHAPE_RE.findall(tok)
            operands.extend(inline[:1] or defined.get(tok.split()[-1], [])[:1])
        yield m.group(1), result, operands, stripped


def collective_stats(hlo_text: str) -> Dict[str, int]:
    """Per-device collective traffic (bytes) by op kind.

    Volume model (ring algorithms): all-reduce moves ~2x its buffer per
    device; all-gather / reduce-scatter / all-to-all / permute ~1x the larger
    of (operand, result). '-start/-done' async pairs are counted once (start).
    """
    out = {k: 0 for k in _COLLECTIVES}
    out["count"] = 0
    for op, result, operands, _ in _instructions(hlo_text):
        base = op[:-len("-start")] if op.endswith("-start") else op
        if base not in _COLLECTIVES:
            continue
        sizes = [_shape_bytes(d, dims) for d, dims in result + operands]
        if not sizes:
            continue
        nbytes = max(sizes)
        factor = 2 if base == "all-reduce" else 1
        out[base] += factor * nbytes
        out["count"] += 1
    out["total_bytes"] = sum(out[c] for c in _COLLECTIVES)
    return out


_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")


def matmul_stats(hlo_text: str) -> Dict[str, float]:
    """Static matmul/conv census of an HLO module.

    Counts every ``dot`` and ``convolution`` op in the text and estimates
    its flops (2 x output elements x contracted extent; convolutions use
    2 x output x kernel-spatial x input-features, recovered from the operand
    shapes). Ops inside loop bodies are counted ONCE — this is a *static*
    census for asserting op-structure claims (e.g. "the frontend performs
    the patch matmul exactly once": the single-pass pipeline must contain no
    convolution ops and strictly fewer matmul flops than the double-conv
    path), not a dynamic execution profile.
    """
    out = {"dot_count": 0, "dot_flops": 0.0,
           "conv_count": 0, "conv_flops": 0.0}
    for op, result, operands, line in _instructions(hlo_text):
        if not result:
            continue
        out_elems = 1
        for d in result[0][1].split(","):
            if d:
                out_elems *= int(d)
        if op == "dot" and operands:
            lhs_dims = [int(d) for d in operands[0][1].split(",") if d]
            m = _CONTRACT_RE.search(line)
            contracted = 1
            if m and m.group(1):
                for i in m.group(1).split(","):
                    contracted *= lhs_dims[int(i)]
            out["dot_count"] += 1
            out["dot_flops"] += 2.0 * out_elems * contracted
        elif op == "convolution" and len(operands) >= 2:
            # rhs (kernel) shape: contracted extent = all dims but the
            # output-feature one, located via the dim_labels 'o' position
            # (e.g. dim_labels=b01f_01io->b01f); fall back to the last dim
            rhs_dims = [int(d) for d in operands[1][1].split(",") if d]
            m = re.search(r"dim_labels=[^_]+_([^-]+)->", line)
            o_pos = m.group(1).index("o") if m else len(rhs_dims) - 1
            contracted = 1
            for i, d in enumerate(rhs_dims):
                if i != o_pos:
                    contracted *= d
            out["conv_count"] += 1
            out["conv_flops"] += 2.0 * out_elems * contracted
    out["matmul_flops"] = out["dot_flops"] + out["conv_flops"]
    return out


def analytic_memory_bytes(cfg, shape, mesh_shape: Dict[str, int],
                          arg_bytes: float, out_bytes: float) -> float:
    """Fusion-aware HBM-traffic estimate per device per step.

    XLA:CPU's ``bytes accessed`` counts every unfused op's operands (we
    measured ~30-60x inflation vs a fused TPU execution), so the memory
    roofline term uses this analytic model instead (the raw number is still
    reported as ``hlo_bytes_unfused``):

      train:   read args + write outputs (params+opt, = arg+out bytes from
               memory_analysis) + activation traffic ~ 4x the remat-saved
               layer inputs (fwd write, bwd read + recompute stream);
      prefill: args + cache write + 4x layer activations;
      decode:  args (params + whole KV cache read) + outputs — decode is
               pure streaming.
    """
    n_model = mesh_shape.get("model", 1)
    n_batch = 1
    for a in ("pod", "data"):
        n_batch *= mesh_shape.get(a, 1)
    b_loc = max(shape.global_batch // n_batch, 1)
    dt = 2  # bf16 activations
    if shape.kind == "decode":
        return arg_bytes + out_bytes
    act = cfg.num_layers * b_loc * shape.seq_len * cfg.d_model * dt * 4.0
    if shape.kind == "train":
        return arg_bytes + out_bytes + act
    return arg_bytes + out_bytes + act  # prefill


def roofline(cost: Dict[str, float], coll: Dict[str, int], n_chips: int,
             model_flops: Optional[float] = None,
             analytic_bytes: Optional[float] = None) -> Dict[str, float]:
    """Three roofline terms (seconds) from a compiled cell.

    cost_analysis flops/bytes are for the per-device module already (SPMD),
    so we do NOT divide by n_chips again.
    """
    flops = float(cost.get("flops", 0.0))
    bytes_hbm = float(analytic_bytes if analytic_bytes is not None
                      else cost.get("bytes accessed", 0.0))
    t_compute = flops / PEAK_FLOPS
    t_memory = bytes_hbm / HBM_BW
    t_collective = coll["total_bytes"] / ICI_BW
    dominant = max(
        (("compute", t_compute), ("memory", t_memory),
         ("collective", t_collective)), key=lambda kv: kv[1])[0]
    out = {
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "t_collective_s": t_collective,
        "dominant": dominant,
        "device_flops": flops,
        "device_bytes": bytes_hbm,
        "hlo_bytes_unfused": float(cost.get("bytes accessed", 0.0)),
        "collective_bytes": coll["total_bytes"],
        "collective_count": coll["count"],
    }
    if model_flops:
        # useful-compute ratio: 'model flops' (6ND-style) vs compiled flops
        out["model_flops_per_device"] = model_flops / n_chips
        out["useful_flops_ratio"] = (model_flops / n_chips) / max(flops, 1.0)
        t_star = max(t_compute, t_memory, t_collective)
        out["roofline_fraction"] = (model_flops / n_chips / PEAK_FLOPS) \
            / max(t_star, 1e-30)
    return out


def model_flops_estimate(cfg, shape) -> float:
    """6*N_active*D for train, 2*N_active*D for inference (global, all chips).

    N counts active (dense-equivalent) parameters per token; D = tokens
    processed by the step.
    """
    d, L = cfg.d_model, cfg.num_layers
    dh = cfg.resolved_head_dim
    n_attn_per_layer = 0
    for mixer, mlp in cfg.layer_kinds():
        if mixer in ("attn", "local_attn", "enc_attn"):
            n_attn_per_layer += d * dh * (cfg.num_heads * 2
                                          + cfg.num_kv_heads * 2)
        elif mixer == "mla":
            r = cfg.kv_lora_rank
            q_in = cfg.q_lora_rank or d
            n_attn_per_layer += (d * r + d * cfg.rope_head_dim
                                 + (d * cfg.q_lora_rank if cfg.q_lora_rank
                                    else 0)
                                 + q_in * cfg.num_heads * (dh + cfg.rope_head_dim)
                                 + r * cfg.num_heads * dh * 2
                                 + cfg.num_heads * dh * d)
        elif mixer in ("rglru",):
            r = d
            n_attn_per_layer += d * r * 2 + r * r * 2 + r * d
        elif mixer in ("mlstm", "slstm"):
            n_attn_per_layer += d * cfg.num_heads * dh * 5
        if mlp == "dense":
            ff = cfg.dense_d_ff or cfg.d_ff
            n_attn_per_layer += d * ff * (3 if cfg.mlp_gated else 2)
        elif mlp == "moe":
            active = cfg.top_k + cfg.num_shared_experts
            n_attn_per_layer += d * cfg.d_ff * 3 * active + d * cfg.num_experts
    n_active = n_attn_per_layer + 2 * cfg.vocab_size * d
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    tokens = shape.global_batch * 1
    return 2.0 * n_active * tokens
