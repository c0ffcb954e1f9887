"""The system under test, built from a configuration file.

The only module of the benchmark that imports the program: it turns
``configs/<name>.json`` into the program's ``VisionConfig`` and a
``VisionEngine`` on the benchmark's own weights.
"""
from __future__ import annotations

from typing import Dict, List, Optional

from repro.core import mtj, p2m, pixel
from repro.models import vision
from repro.serving import VisionEngine


def vision_config(cfg: Dict) -> vision.VisionConfig:
    px, m, p = cfg["pixel"], cfg["mtj"], cfg["p2m"]
    pcfg = p2m.P2MConfig(
        in_channels=p["in_channels"], out_channels=p["out_channels"],
        kernel_size=p["kernel_size"], stride=p["stride"],
        weight_bits=p["weight_bits"],
        pixel=pixel.PixelCircuitParams(
            vdd=px["vdd"], v_sw=px["v_sw"], norm_range=px["norm_range"],
            curve=px["curve"], saturation=px["saturation"]),
        mtj=mtj.MTJParams(
            n_redundant=m["n_redundant"], write_pulse_ps=m["write_pulse_ps"],
            precession_period_ps=m["precession_period_ps"],
            measured_voltages=tuple(m["measured_voltages"]),
            measured_p_sw=tuple(m["measured_p_sw"])))
    return vision.VisionConfig(
        name=cfg["name"], arch=cfg["arch"], num_classes=cfg["num_classes"],
        in_hw=cfg["in_hw"], p2m=pcfg, frontend_backend=cfg["backend"],
        weight_bits=cfg["weight_bits"])


def engine(cfg: Dict, params: Dict, seed: int,
           obs: Optional[object] = None) -> VisionEngine:
    e = cfg["engine"]
    return VisionEngine(vision_config(cfg), params, backend=cfg["backend"],
                        seed=seed, microbatch=cfg["microbatch"],
                        fused_theta_tol=e["fused_theta_tol"],
                        fused_theta_ema=e["fused_theta_ema"], obs=obs)


def record_microbatches(eng: VisionEngine) -> List[Dict]:
    """A list to which the engine's stream appends each microbatch's own
    outputs as it makes them, before it merges one item's microbatches
    into the result it yields: the check needs each microbatch's threshold
    and channel rates, which the merge averages. Costs one append per
    microbatch; the caller clears it per item."""
    seen: List[Dict] = []
    inner = eng._classify

    def classify(*args, **kwargs):
        out = inner(*args, **kwargs)
        seen.append(out)
        return out
    eng._classify = classify
    return seen


def make_obs():
    """The program's counters and spans, for traced runs."""
    from repro.obs import Obs
    return Obs()


def counter(obs, name: str) -> Optional[float]:
    if obs is None:
        return None
    snap = obs.registry.snapshot().get(name)
    return None if snap is None else float(snap.get("value", 0.0))
