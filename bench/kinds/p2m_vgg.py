"""Kind ``p2m_vgg``: a VGG behind the P2M sensor layer, served by
``VisionEngine`` (see ``bench/kinds/__init__.py`` for what a kind provides).

The benchmark's weights and frames, made on the device from the seed; the
program's ``VisionConfig`` and engine built on them; the server the shared
loops drive, which keeps each microbatch's own outputs for the check; the
served engine's step programs and counters for the traced run; the work of
one frame for the roofline and MFU readers. The comparison and its plain
reference, which import nothing of the program, are in
``p2m_vgg_reference.py`` beside this file.
"""
from __future__ import annotations

import functools
import re
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import loops
from bench.kinds import p2m_vgg_reference as reference
from repro.core import mtj, p2m, pixel
from repro.models import vision
from repro.obs import Obs, compiles
from repro.serving import VisionEngine
from repro.serving import vision as serving_vision

# pool of frames the closed loop cycles through / requests draw from,
# in microbatches
POOL_BLOCKS = 8

# what the traced window is read by: the frontend kernels' name, the
# model's device scopes (``models/vision.py``; the innermost one names an
# op), the engine's host spans outermost first (``serving/vision.py``) and
# the marks the program leaves on the profiler's clock for each program
# load and each host sync (None where it leaves none)
KERNELS = ("p2m_kernel",)
SCOPES = re.compile(
    r"(?:^|/)(p2m_frontend|head|backbone(?:/(?:conv\d+|s\d+b\d+))?)(?=/|$)")
SPANS = ("stream", "microbatch", "key_fold", "theta_sync", "merge", "drain")
MARKS = {"compiles": getattr(compiles, "MARK", None),
         "syncs": getattr(serving_vision, "HOST_SYNC_MARK", None)}

# counters read after a traced window
COUNTERS = ("serving_fused_steps_total", "serving_fused_fallback_total")

limits = reference.limits


# --- weights and frames, made on the device from the run's seed -------------

def root_key(seed: int) -> jax.Array:
    """A key from any non-negative seed, including ones past 32 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


def _conv_leaf(key, cin: int, cout: int) -> Dict:
    w = jax.random.normal(key, (3, 3, cin, cout), jnp.float32)
    return {"w": w / (9 * cin) ** 0.5,
            "bn_scale": jnp.ones((cout,)), "bn_bias": jnp.zeros((cout,)),
            "bn_mean": jnp.zeros((cout,)), "bn_var": jnp.ones((cout,)),
            "v_th": jnp.ones(())}


@functools.partial(jax.jit, static_argnums=(1,))
def _weights(key, spec):
    plan, cin, p2m_shape, ncls = spec
    keys = jax.random.split(key, len(plan) + 2)
    fan = p2m_shape[0] * p2m_shape[1] * p2m_shape[2]
    p2m = {"w": jax.random.normal(keys[0], p2m_shape) * (2.0 / fan) ** 0.5,
           "v_th": jnp.ones(())}
    layers, i = {}, 0
    for item in plan:
        if item == "M":
            continue
        layers[f"conv{i}"] = _conv_leaf(keys[1 + i], cin, item)
        cin, i = item, i + 1
    head = {"w": jax.random.normal(keys[-1], (cin, ncls)) / cin ** 0.5,
            "b": jnp.zeros((ncls,))}
    return {"p2m": p2m, "layers": layers, "head": head}


def weights(cfg: Dict, seed: int) -> Dict:
    """The served model's parameters in the tree the engine takes: the
    P2M layer (He-normal, v_th 1), the convs of the plan (normal /
    sqrt(fan_in), BatchNorm at its initial running statistics) and the
    linear head. One jitted call; every seed gives the same shapes."""
    p = cfg["p2m"]
    spec = (tuple(cfg["plan"]), p["out_channels"],
            (p["kernel_size"], p["kernel_size"], p["in_channels"],
             p["out_channels"]), cfg["num_classes"])
    return _weights(jax.random.fold_in(root_key(seed), 1), spec)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _pool(key, n: int, hw: int, cin: int):
    k1, k2 = jax.random.split(key)
    side = max(hw // 8, 2)
    coarse = jax.random.uniform(k1, (n, side, side, cin))
    scene = jax.image.resize(coarse, (n, hw, hw, cin), "linear")
    grain = jax.random.uniform(k2, (n, hw, hw, cin))
    return jnp.clip(0.8 * scene + 0.2 * grain, 0.0, 1.0)


def frame_pool(cfg: Dict, n: int, seed: int) -> jax.Array:
    """``n`` frames (n, H, W, C) in [0, 1]: smooth random scenes (bilinear
    upsampling of an 8x coarser grid) with 20% pixel grain."""
    return _pool(jax.random.fold_in(root_key(seed), 2), n, cfg["in_hw"],
                 cfg["p2m"]["in_channels"])


# --- the system under test ---------------------------------------------------

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
           obs: Optional[Obs] = None) -> VisionEngine:
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


class Feed:
    """The iterable ``engine.stream()`` pulls from: the server sets the
    next item before asking for its result."""

    def __init__(self):
        self.frames: Optional[jax.Array] = None

    def __iter__(self):
        return self

    def __next__(self):
        frames, self.frames = self.frames, None
        if frames is None:
            raise StopIteration
        return frames


# the outputs of a microbatch the check reads, beside the item's served
# probabilities
_KEEP = ("theta", "theta_used", "channel_rates", "stream_fused")


def item_samples(index: int, idx: np.ndarray, real: int, out: Dict,
                 parts: List[Dict]) -> List[loops.Sample]:
    """The samples of one served item: one per microbatch, each with its
    own outputs (``parts``, in order) and the item's served probabilities
    (``out``), of which rows ``row0 ..`` are its answers."""
    return loops.item_samples(index, idx, real, [
        {"probs": out["probs"], **{k: p[k] for k in _KEEP if k in p}}
        for p in parts])


class Server:
    """The engine behind ONE long-lived ``engine.stream()``, so that its
    carried threshold persists across the window as in deployment, on the
    benchmark's weights and a pool of ``pool_blocks`` microbatches of
    frames. The pool is kept whole (the closed loop cuts it, once, into
    batch-sized blocks) and as one row per frame (what the open loop
    gathers a window from), so an item adds no device work of the
    harness's own beyond a gather of its own frames. With ``trace`` the
    engine reports to an ``Obs`` (counters, spans, marks)."""

    def __init__(self, cfg: Dict, seed: int, trace: bool = False,
                 pool_blocks: int = POOL_BLOCKS):
        mb = cfg["microbatch"]
        self.cfg, self.mb = cfg, mb
        self.params = weights(cfg, seed)
        self.pool = frame_pool(cfg, pool_blocks * mb, seed)
        self.obs = Obs() if trace else None
        self.engine = engine(cfg, self.params, reference.engine_seed(seed),
                             self.obs)
        self.pool_n, self.frame_shape = self.pool.shape[0], self.pool.shape[1:]
        self.rows = self.pool.reshape(self.pool_n, -1)
        self.parts = record_microbatches(self.engine)
        self.feed = Feed()
        self.outs = self.engine.stream(self.feed)
        self.index = 0           # stream items served
        self.microbatches = 0    # microbatches the stream served
        shape = (mb,) + tuple(self.frame_shape)
        self._gather = jax.jit(
            lambda rows, i: jnp.take(rows, i, axis=0).reshape(shape))

    def blocks(self, n: int) -> List[jax.Array]:
        """The pool cut into consecutive blocks of ``n`` frames."""
        return [self.pool[i:i + n] for i in range(0, self.pool_n, n)]

    def window(self, idx: np.ndarray) -> jax.Array:
        """Pool frames ``idx`` (length mb) as one microbatch."""
        return self._gather(self.rows, jnp.asarray(idx, jnp.int32))

    def serve(self, frames: jax.Array):
        """Serve one stream item; returns (labels on the host, the served
        result, each microbatch's own outputs, stream index)."""
        self.feed.frames = frames
        self.parts.clear()
        out = next(self.outs)
        labels = np.asarray(out["labels"])
        parts = list(self.parts)
        i, self.index = self.index, self.index + 1
        self.microbatches += len(parts)
        return labels, out, parts, i

    samples = staticmethod(item_samples)

    def counters(self) -> Dict[str, Optional[float]]:
        """The engine's fused-path counters (None where never counted)."""
        snap = self.obs.registry.snapshot() if self.obs is not None else {}
        return {k: None if k not in snap
                else float(snap[k].get("value", 0.0)) for k in COUNTERS}

    def step_programs(self) -> List[str]:
        """The optimized HLO text of the served engine's own step programs
        at the configuration's microbatch (the exact step and, on
        ``pallas``, the fused one), found in the compile caches. A program
        without the model's scopes is left out uncompiled."""
        cfg, eng = self.cfg, self.engine
        params = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), self.params)
        frames = jax.ShapeDtypeStruct(
            (cfg["microbatch"], cfg["in_hw"], cfg["in_hw"],
             cfg["p2m"]["in_channels"]), jnp.float32)
        key = jax.random.PRNGKey(0)
        lowered = [eng._step.lower(params, frames, key)]
        if eng.backend == "pallas":
            lowered.append(eng._fused_step.lower(
                params, frames, key, jax.ShapeDtypeStruct((), jnp.float32)))
        return [low.compile().as_text() for low in lowered
                if SCOPES.search(low.as_text(debug_info=True))]


def readings(cfg: Dict, server: Server, seed: int, samples,
             control: bool = False) -> Dict[str, float]:
    """The compared numbers of ``samples`` (``p2m_vgg_reference``)."""
    return reference.readings(cfg, server.params, server.pool, seed, samples,
                              control)


# --- work of one frame, counted from the configuration's shapes -------------

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


def frontend_bytes_per_frame(cfg: Dict) -> int:
    """HBM bytes the frontend must move per frame: the f32 frame in, the
    f32 activation map out and one uint16 draw word per activation."""
    p = cfg["p2m"]
    ho = _out_hw(cfg["in_hw"], p["stride"])
    acts = ho * ho * p["out_channels"]
    return cfg["in_hw"] ** 2 * p["in_channels"] * 4 + acts * 4 + acts * 2


def work(cfg: Dict) -> Dict[str, Dict[str, int]]:
    """The work of one item (one frame) by part of the model: FLOPs (2 per
    MAC) of the whole forward pass (``model``: frontend, backbone and
    head), of the ``backbone`` conv stack, and of the ``p2m_frontend`` with
    the HBM bytes it must move."""
    fe, bb = frontend_macs(cfg), backbone_macs(cfg)
    return {"model": {"flops": 2 * (fe + bb + head_macs(cfg))},
            "backbone": {"flops": 2 * bb},
            "p2m_frontend": {"flops": 2 * fe,
                             "bytes": frontend_bytes_per_frame(cfg)}}
