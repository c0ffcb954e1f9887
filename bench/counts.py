"""Operations and bytes of the served model, counted from its shapes.

The yardstick for ``step_mfu`` and ``frontend_roofline_share``: nothing here
reads the program. A configuration file gives the frame size, the P2M layer
and the backbone plan (channel counts, ``"M"`` for a 2x2 max-pool); the
counts follow from SAME convolutions.
"""
from __future__ import annotations

from typing import Dict


def _out_hw(h: int, stride: int) -> int:
    return -(-h // stride)


def frontend_macs(cfg: Dict) -> int:
    """MACs of the P2M layer per frame: one signed k x k x C_in -> C
    convolution at its stride (the two integration phases are one signed
    MAC each; the kernels' packed dot is an implementation detail)."""
    p = cfg["p2m"]
    ho = _out_hw(cfg["in_hw"], p["stride"])
    return ho * ho * p["kernel_size"] ** 2 * p["in_channels"] * p["out_channels"]


def backbone_macs(cfg: Dict) -> int:
    """MACs of the 3x3 conv stack per frame (the head is apart)."""
    hw = _out_hw(cfg["in_hw"], cfg["p2m"]["stride"])
    cin, macs = cfg["p2m"]["out_channels"], 0
    for item in cfg["plan"]:
        if item == "M":
            if hw > 1:
                hw = _out_hw(hw, 2)
            continue
        macs += hw * hw * 9 * cin * item
        cin = item
    return macs


def head_macs(cfg: Dict) -> int:
    feat = [c for c in cfg["plan"] if c != "M"][-1]
    return feat * cfg["num_classes"]


def model_flops_per_frame(cfg: Dict) -> int:
    """FLOPs (2 per MAC) of one frame through frontend, backbone and head:
    the numerator of ``step_mfu``."""
    return 2 * (frontend_macs(cfg) + backbone_macs(cfg) + head_macs(cfg))


def frontend_bytes_per_frame(cfg: Dict) -> int:
    """HBM bytes the frontend must move per frame: the f32 frame in, the
    f32 activation map out and one uint16 draw word per activation."""
    p = cfg["p2m"]
    ho = _out_hw(cfg["in_hw"], p["stride"])
    acts = ho * ho * p["out_channels"]
    return cfg["in_hw"] ** 2 * p["in_channels"] * 4 + acts * 4 + acts * 2


def frontend_min_seconds(cfg: Dict, frames: int, peak: Dict):
    """(least seconds for ``frames`` frames of frontend work, bound) where
    bound names the roofline side that sets it: "compute" or "memory"."""
    t_ops = 2 * frontend_macs(cfg) * frames / peak["bf16_flops_per_s"]
    t_bytes = frontend_bytes_per_frame(cfg) * frames / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")
