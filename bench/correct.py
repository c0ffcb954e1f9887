"""The comparison that decides ``correct``.

After the window has closed, each microbatch of the seeded sample
(``loops.Sample``) is recomputed by the plain reference (``reference.py``)
from the benchmark's own weights and frames, the engine's rng key for it
(``fold_in(PRNGKey(engine seed), stream index)``, and the microbatch's
place folded in where an item has several) and the threshold its draws
ran at (``theta_used``, the engine's carried value, as an LM check
conditions on the served tokens). The answers compared are the rows of the
item's served result, after the engine merged its microbatches. Four
numbers are compared, each against its own limit from the configuration
file (``limits``):

rate_flips      frontend: max over sampled microbatches and channels of
                |rate - reference rate| x rows per channel, i.e. the net
                number of activations of one channel that differ from the
                oracle's draws on the same words
theta_gap       frontend: max relative gap of the fresh Hoyer threshold
theta_guard     engine: max relative gap between the threshold a fused step
                drew at and the reference's fresh one; the configuration
                states the guard (``engine.fused_theta_tol``)
frames_off      backbone: share of the sampled requests whose log-
                probabilities differ from the reference's anywhere by more
                than ``LOGP_TOL``

``control`` computes what the reference gives, in the program's place, one
precision step below the configuration's (bf16 frontend operands, fp8
backbone operands): the check has to call it incorrect.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference
from bench.inputs import engine_seed

# a request is "off" when a log-probability moves by more than this; a
# request whose spikes all agree with the reference is ~1e-6 away
LOGP_TOL = 1e-3

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
           "float8_e4m3fn": jnp.float8_e4m3fn}
# one precision step below each stated operand precision
_BELOW = {"float32": "bfloat16", "bfloat16": "float8_e4m3fn"}


def _precisions(cfg: Dict, control: bool) -> Tuple:
    p = cfg["precision"]
    fe, bb = p["frontend"], p["backbone_operands"]
    if control:
        fe, bb = _BELOW[fe], _BELOW[bb]
    return _DTYPES[fe], _DTYPES[bb]


def _reference_fn(cfg: Dict, control: bool):
    """The reference for one microbatch as one jitted program (compiled
    once per run and shape, and kept in the persistent cache)."""
    fe_dt, bb_dt = _precisions(cfg, control)

    def step(params, frames, key, theta_used):
        acts, fa = reference.frontend(params, frames, key, theta_used, cfg,
                                      fe_dt)
        return (fa["theta"], fa["channel_rates"],
                jax.nn.log_softmax(reference.backbone(params, acts, cfg,
                                                      bb_dt)))
    return jax.jit(step)


def reference_step(cfg: Dict, params: Dict, frames: jax.Array,
                   key: jax.Array, theta_used: jax.Array,
                   control: bool = False, fn=None) -> Dict:
    """The reference's outputs for one microbatch: fresh theta, channel
    rates and log-probabilities (float64 on the host)."""
    fn = fn or _reference_fn(cfg, control)
    theta, rates, logp = fn(params, frames, key, theta_used)
    out_hw = -(-cfg["in_hw"] // cfg["p2m"]["stride"])
    return {"theta": float(theta),
            "channel_rates": np.asarray(rates, np.float64),
            "logp": np.asarray(logp, np.float64),
            "rows": frames.shape[0] * out_hw * out_hw}


def step_key(seed: int, index: int, part: int = 0, parts: int = 1
             ) -> jax.Array:
    """The engine's key for microbatch ``part`` of stream item ``index``:
    it folds its item counter into ``PRNGKey(seed)``, and an item of more
    than one microbatch folds in each one's place."""
    key = jax.random.fold_in(jax.random.PRNGKey(engine_seed(seed)), index)
    return key if parts == 1 else jax.random.fold_in(key, part)


def _program_view(sample) -> Dict:
    out = sample.out
    rows = slice(sample.row0, sample.row0 + len(sample.frames))
    return {"theta": float(out["theta"]),
            "theta_used": float(out.get("theta_used", out["theta"])),
            "fused": float(out.get("stream_fused", 0.0)) > 0.5,
            "channel_rates": np.asarray(out["channel_rates"], np.float64),
            "logp": np.log(np.asarray(out["probs"], np.float64)[rows])}


def readings(cfg: Dict, params: Dict, pool: jax.Array, seed: int,
             samples: Sequence, control: bool = False) -> Dict[str, float]:
    """The four compared numbers over ``samples``. With ``control`` the
    program's outputs are replaced by the control's."""
    rate_flips = theta_gap = theta_guard = 0.0
    off = total = 0
    ref_fn = _reference_fn(cfg, False)
    ctl_fn = _reference_fn(cfg, True) if control else None
    with jax.default_matmul_precision("highest"):
        for s in samples:
            frames = jnp.take(pool, jnp.asarray(s.frames, jnp.int32), axis=0)
            prog = _program_view(s)
            key = step_key(seed, s.index, s.part, s.parts)
            th_used = jnp.asarray(prog["theta_used"], jnp.float32)
            ref = reference_step(cfg, params, frames, key, th_used,
                                 fn=ref_fn)
            if control:
                ctl = reference_step(cfg, params, frames, key, th_used,
                                     fn=ctl_fn)
                prog = {**prog, "theta": ctl["theta"],
                        "channel_rates": ctl["channel_rates"],
                        "logp": ctl["logp"]}
            rate_flips = max(rate_flips, float(np.max(np.abs(
                prog["channel_rates"] - ref["channel_rates"]))) * ref["rows"])
            theta_gap = max(theta_gap, abs(prog["theta"] - ref["theta"])
                            / abs(ref["theta"]))
            if prog["fused"]:
                theta_guard = max(theta_guard,
                                  abs(prog["theta_used"] - ref["theta"])
                                  / abs(prog["theta_used"]))
            gap = np.max(np.abs(prog["logp"][:s.real]
                                - ref["logp"][:s.real]), axis=1)
            off += int(np.sum(~(gap <= LOGP_TOL)))
            total += s.real
    return {"rate_flips": rate_flips, "theta_gap": theta_gap,
            "theta_guard": theta_guard,
            "frames_off": off / max(total, 1)}


def limits(cfg: Dict) -> Dict[str, float]:
    lim = dict(cfg["limits"])
    lim["theta_guard"] = cfg["engine"]["fused_theta_tol"]
    return lim


def judge(read: Dict[str, float], lim: Dict[str, float]
          ) -> Tuple[bool, List[Dict]]:
    """(correct, [{name, value, limit}]): every reading at or under its
    limit (a NaN reading fails)."""
    rows = [{"name": k, "value": read[k], "limit": lim[k]} for k in lim]
    return all(r["value"] <= r["limit"] for r in rows), rows
