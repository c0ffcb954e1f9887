"""The paper's model zoo: VGG16 / ResNet sparse-BNNs with the P2M first layer.

First layer = the in-pixel P2MConv (paper's technique: hardware conv + VC-MTJ
binary activation); every later conv uses BN + the same Hoyer binary spike
(the "sparse BNN" of §2.3, Table 1). Weights are 4-bit fake-quantized.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import frontend
from repro.core import hoyer, p2m
from repro.models.params import ParamSpec, abstract_tree, axes_tree, init_tree
from repro.variation.chip import VariationConfig


@dataclasses.dataclass(frozen=True)
class VisionConfig:
    name: str = "vgg16_cifar10"
    arch: str = "vgg16"       # vgg16 | vgg_tiny | resnet18 | resnet20
    num_classes: int = 10
    in_hw: int = 32
    p2m: p2m.P2MConfig = p2m.P2MConfig()
    frontend_backend: str = "analog"     # default SensorFrontend backend
    # None = per-shape autotuner table (kernels/autotune.py); ints pin tiles
    frontend_block_n: Optional[int] = None      # kernel-A patch-row block
    frontend_block_n_elem: Optional[int] = None  # kernel-B row-block cap
    weight_bits: int = 4
    remove_first_maxpool: bool = False   # paper's Model* variants
    hoyer_coeff: float = 1e-8
    bn_momentum: float = 0.9             # EMA decay of the BN running stats
    # device-variation handle (repro/variation): the sampled chip this
    # model's sensor frontend simulates; None = the nominal chip
    variation: Optional[VariationConfig] = None
    chip_id: int = 0

    @property
    def frontend(self) -> frontend.FrontendConfig:
        return frontend.FrontendConfig(p2m=self.p2m,
                                       backend=self.frontend_backend,
                                       block_n=self.frontend_block_n,
                                       block_n_elem=self.frontend_block_n_elem,
                                       variation=self.variation,
                                       chip_id=self.chip_id)


_VGG_PLANS = {
    "vgg16": [64, 64, "M", 128, 128, "M", 256, 256, 256, "M",
              512, 512, 512, "M", 512, 512, 512, "M"],
    # benchmark-scale variant: same structure (P2M front + binary conv
    # stack + pools), CPU-trainable in minutes
    "vgg_tiny": [32, "M", 64, "M", 64, "M"],
}
_RESNET_PLAN = {"resnet18": (2, 2, 2, 2), "resnet20": (3, 3, 3)}


def _conv_spec(cin: int, cout: int, k: int = 3) -> Dict[str, Any]:
    return {
        "w": ParamSpec((k, k, cin, cout), (None, None, "channels", "channels")),
        "bn_scale": ParamSpec((cout,), ("channels",), init="ones"),
        "bn_bias": ParamSpec((cout,), ("channels",), init="zeros"),
        # BN running stats (EMA; non-trainable — they never enter the loss
        # with a gradient path, so SGD leaves them untouched and the train
        # loop overwrites them from aux["bn_state"] after each step)
        "bn_mean": ParamSpec((cout,), ("channels",), init="zeros"),
        "bn_var": ParamSpec((cout,), ("channels",), init="ones"),
        "v_th": ParamSpec((), (), init="ones"),
    }


def _conv_apply(params: Dict, x: jax.Array, stride: int, bits: int,
                binary: bool = True, train: bool = False,
                bn_momentum: float = 0.9
                ) -> Tuple[jax.Array, jax.Array, Optional[Dict]]:
    """One quantized conv + BN + Hoyer-spike layer.

    ``train=True`` normalizes with the live batch statistics and returns the
    updated EMA running stats; ``train=False`` (eval/serving) consumes the
    stored running stats AND computes the dynamic Hoyer spike threshold per
    example (deployment semantics: each frame thresholds on its own
    statistics), so a frame's prediction cannot depend on its batchmates
    (the seed used live BN stats and a whole-batch spike threshold
    unconditionally, which made ``VisionEngine`` outputs batch-composition
    dependent).
    """
    w = p2m.quantize_weights(params["w"], bits)
    y = jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    new_stats: Optional[Dict] = None
    if train:
        mu = jnp.mean(y, axis=(0, 1, 2))
        var = jnp.var(y, axis=(0, 1, 2))
        m = bn_momentum
        new_stats = {
            "bn_mean": jax.lax.stop_gradient(
                m * params["bn_mean"] + (1.0 - m) * mu),
            "bn_var": jax.lax.stop_gradient(
                m * params["bn_var"] + (1.0 - m) * var),
        }
    else:
        mu, var = params["bn_mean"], params["bn_var"]
    y = (y - mu) / jnp.sqrt(var + 1e-5)
    y = y * params["bn_scale"] + params["bn_bias"]
    if not binary:
        return jax.nn.relu(y), jnp.zeros(()), new_stats
    if train:
        o, hl = hoyer.hoyer_spike(y, params["v_th"])
        return o, hl, new_stats
    # eval: per-example dynamic threshold (batch-independent predictions);
    # no gradients needed, so the spike is a plain comparison
    z = y / jnp.maximum(params["v_th"], 1e-6)
    zc = hoyer.clip01(z)
    thr = hoyer.hoyer_extremum(zc, axis=tuple(range(1, z.ndim)),
                               keepdims=True)
    o = (z >= thr).astype(y.dtype)
    return o, hoyer.hoyer_regularizer(zc), new_stats


def _maxpool(x: jax.Array) -> jax.Array:
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                 (1, 2, 2, 1), (1, 2, 2, 1), "SAME")


def model_spec(cfg: VisionConfig) -> Dict[str, Any]:
    spec: Dict[str, Any] = {
        "p2m": {
            "w": ParamSpec((cfg.p2m.kernel_size, cfg.p2m.kernel_size,
                            cfg.p2m.in_channels, cfg.p2m.out_channels),
                           ("pixel", "pixel", "channels", "channels")),
            "v_th": ParamSpec((), (), init="ones"),
        },
    }
    c_in = cfg.p2m.out_channels
    layers: Dict[str, Any] = {}
    if cfg.arch.startswith("vgg"):
        i = 0
        for item in _VGG_PLANS[cfg.arch]:
            if item == "M":
                continue
            layers[f"conv{i}"] = _conv_spec(c_in, item)
            c_in = item
            i += 1
        feat = c_in
    else:
        blocks_per = _RESNET_PLAN[cfg.arch]
        widths = [64 * (2 ** i) for i in range(len(blocks_per))] \
            if cfg.arch == "resnet18" else [16, 32, 64]
        for si, (n, w) in enumerate(zip(blocks_per, widths)):
            for bi in range(n):
                blk = {"c1": _conv_spec(c_in, w), "c2": _conv_spec(w, w)}
                if c_in != w:
                    blk["proj"] = _conv_spec(c_in, w, k=1)
                layers[f"s{si}b{bi}"] = blk
                c_in = w
        feat = c_in
    spec["layers"] = layers
    spec["head"] = {"w": ParamSpec((feat, cfg.num_classes),
                                   ("channels", None)),
                    "b": ParamSpec((cfg.num_classes,), (None,), init="zeros")}
    return spec


def init_params(key: jax.Array, cfg: VisionConfig):
    return init_tree(key, model_spec(cfg), jnp.float32)


def forward(params: Dict, images: jax.Array, cfg: VisionConfig, *,
            key: Optional[jax.Array] = None, backend: Optional[str] = None,
            train: bool = False) -> Tuple[jax.Array, jax.Array, Dict]:
    """images: (B, H, W, C) in [0, 1]. Returns (logits, hoyer_loss, aux).

    The first layer goes through the SensorFrontend; ``backend`` overrides
    ``cfg.frontend_backend`` per call (e.g. train with "analog", eval with
    "device" or "pallas"). ``key`` feeds whichever backend is stochastic —
    including the Fig. 8 noise injection of the analog path.

    ``train=True`` switches BatchNorm to live batch statistics and returns
    the updated EMA running stats as ``aux["bn_state"]`` (a sub-tree of
    ``params["layers"]`` — apply with ``apply_bn_state`` after the gradient
    step). Eval (the default) consumes the stored running stats, so a
    frame's backbone prediction is independent of its batchmates.
    """
    fe = frontend.SensorFrontend(cfg.frontend)
    # named scopes put each stage's ops under one name in a device trace
    # (``p2m_frontend``, ``backbone/conv{i}``, ``head``); they are trace-time
    # metadata and add no operation
    with jax.named_scope("p2m_frontend"):
        x, fe_aux = fe(params["p2m"], images, key=key, mode=backend)
    # raw hoyer term; cfg.hoyer_coeff is applied exactly once, at the end
    hoyer_total = fe_aux["hoyer_loss"]
    p2m_sparsity = fe_aux["sparsity"]
    bn_state: Dict = {}

    def conv(layer_params, x, stride, binary=True):
        return _conv_apply(layer_params, x, stride, cfg.weight_bits,
                           binary=binary, train=train,
                           bn_momentum=cfg.bn_momentum)

    with jax.named_scope("backbone"):
        if cfg.arch.startswith("vgg"):
            i = 0
            first_pool = True
            for item in _VGG_PLANS[cfg.arch]:
                if item == "M":
                    if first_pool and cfg.remove_first_maxpool:
                        first_pool = False
                        continue
                    first_pool = False
                    if x.shape[1] > 1:
                        # the pool belongs to the conv it follows
                        with jax.named_scope(f"conv{i - 1}"):
                            x = _maxpool(x)
                    continue
                with jax.named_scope(f"conv{i}"):
                    x, hl, st = conv(params["layers"][f"conv{i}"], x, 1)
                if train:
                    bn_state[f"conv{i}"] = st
                hoyer_total += hl
                i += 1
        else:
            names = sorted(params["layers"].keys())
            for name in names:
                blk = params["layers"][name]
                stride = 1
                with jax.named_scope(name):
                    h, hl1, st1 = conv(blk["c1"], x, stride)
                    h, hl2, st2 = conv(blk["c2"], h, 1)
                    sc = x
                    blk_state = {"c1": st1, "c2": st2}
                    if "proj" in blk:
                        sc, _, stp = conv(blk["proj"], x, stride,
                                          binary=False)
                        blk_state["proj"] = stp
                    x = h + sc
                if train:
                    bn_state[name] = blk_state
                hoyer_total += hl1 + hl2

    with jax.named_scope("head"):
        x = jnp.mean(x, axis=(1, 2))
        logits = x @ params["head"]["w"] + params["head"]["b"]
    # surface the full frontend aux (V_CONV stats, global-shutter accounting
    # on hardware backends) minus the loss term consumed above
    aux = {"p2m_sparsity": p2m_sparsity,
           **{k: v for k, v in fe_aux.items()
              if k not in ("hoyer_loss", "sparsity")}}
    if train:
        aux["bn_state"] = bn_state
    return logits, cfg.hoyer_coeff * hoyer_total, aux


def apply_bn_state(params: Dict, bn_state: Optional[Dict]) -> Dict:
    """Merge ``aux["bn_state"]`` (EMA running stats from a ``train=True``
    forward) back into the parameter tree. Pure — returns a new tree."""
    if not bn_state:
        return params

    def merge(p, s):
        if not isinstance(s, dict):
            return s
        return {k: merge(p[k], s[k]) if k in s else p[k] for k in p}

    return {**params, "layers": merge(params["layers"], bn_state)}


def loss_fn(params, batch, cfg: VisionConfig, key=None, train: bool = True):
    # key reaches the frontend: this is what activates the Fig. 8
    # stochastic-switching noise-injection study during training.
    # train=True (the default — this is the TRAINING loss) uses live BN
    # stats and surfaces the EMA update in aux["bn_state"].
    logits, hloss, aux = forward(params, batch["image"], cfg, key=key,
                                 train=train)
    logp = jax.nn.log_softmax(logits)
    nll = -jnp.mean(jnp.take_along_axis(logp, batch["label"][:, None], 1))
    acc = jnp.mean((jnp.argmax(logits, -1) == batch["label"]).astype(jnp.float32))
    return nll + hloss, {"loss": nll, "acc": acc, **aux}
