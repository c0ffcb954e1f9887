"""SensorFrontend — the single API over the P2M in-pixel first layer.

The paper's contribution is ONE physical layer viewed four ways: ideal conv,
Hoyer-trained analog approximation, Monte-Carlo VC-MTJ device simulation, and
a fused Pallas TPU kernel. This module makes those views *backends* behind a
single signature (DESIGN.md §2):

    frontend = SensorFrontend(FrontendConfig(p2m=..., backend="analog"))
    params = frontend.init(key)
    activations, aux = frontend(params, images, key=key, mode="device")

``mode`` (optional) overrides the configured backend per call — this is what
lets a training loop use ``analog`` and its eval loop use ``device`` or
``pallas`` without any string-switching in model code.

Every backend consumes the same ``P2MConfig`` (and through it the same
``PixelCircuitParams`` / ``MTJParams``) and returns ``(activations, aux)``
with the standard aux keys:

    hoyer_loss   raw (un-scaled) Hoyer regularizer term — consumers apply
                 ``hoyer_coeff`` exactly once; 0 for non-training backends
    sparsity     fraction of zeros in the binary activation map
    channel_rates
                 (C,) per-channel activation rate of the emitted map — the
                 live telemetry the lifetime scheduler monitors for
                 drift-triggered recalibration (DESIGN.md §8)
    theta        the global hardware-mapped Hoyer threshold, in conv-output
                 units (for ``pallas`` it is combined from kernel-A partial
                 reductions rather than a shadow conv pass — DESIGN.md §5)
    v_conv_mean / v_conv_min / v_conv_max
                 statistics of the threshold-matched subtractor voltage that
                 would drive the VC-MTJ (paper §2.2.2)

Hardware backends (``device``, ``pallas``) additionally run the explicit
global-shutter stage — ``mtj.burst_read`` of the stored MTJ states plus
reset-pulse accounting (DESIGN.md §4) — and merge its stats into aux.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import p2m
from repro.frontend import shutter
from repro.variation.chip import VariationConfig

# backend signature: (cfg, params, images, key) -> (activations, aux)
BackendFn = Callable[["FrontendConfig", dict, jax.Array,
                      Optional[jax.Array]], Tuple[jax.Array, Dict]]

_BACKENDS: Dict[str, BackendFn] = {}
# backends that leave their result stored in MTJ states and therefore go
# through the global-shutter burst-read stage
_STATEFUL: set = set()
# backends that carry gradients (STE) and are safe under jax.grad
_DIFFERENTIABLE: set = set()


def register_backend(name: str, stateful: bool = False,
                     differentiable: bool = False):
    """Register a frontend backend.

    ``stateful=True`` marks backends whose activations are physically held
    in VC-MTJ states (global-shutter read); ``differentiable=True`` marks
    backends usable under ``jax.grad`` (straight-through estimators).
    """
    def deco(fn: BackendFn) -> BackendFn:
        _BACKENDS[name] = fn
        if stateful:
            _STATEFUL.add(name)
        if differentiable:
            _DIFFERENTIABLE.add(name)
        return fn
    return deco


def get_backend(name: str) -> BackendFn:
    if name not in _BACKENDS:
        raise KeyError(f"unknown frontend backend {name!r}; "
                       f"registered: {list_backends()}")
    return _BACKENDS[name]


def list_backends() -> list:
    return sorted(_BACKENDS)


def differentiable_backends() -> list:
    """Backends safe to train through (STE gradients end to end)."""
    return sorted(_DIFFERENTIABLE)


@dataclasses.dataclass(frozen=True)
class FrontendConfig:
    """Configuration of the sensor frontend (hashable — safe as a jit static).

    ``p2m`` carries all the physics (circuit + device params); the remaining
    fields select and tune the execution backend.
    """
    p2m: p2m.P2MConfig = p2m.P2MConfig()
    backend: str = "analog"
    global_shutter: bool = True   # run burst_read + reset accounting
    # device-variation handle (repro/variation, DESIGN.md §7): when set, the
    # frontend simulates THIS sampled chip — the device/pallas backends
    # thread its mismatch maps through the physics and the analog backend
    # draws its Fig. 8 noise from them. None = the nominal (perfect) chip.
    # At call time a ChipMaps pytree in params["chip"] overrides the
    # config-sampled instance as an array operand (the lifetime subsystem's
    # aged chip, DESIGN.md §8).
    variation: Optional[VariationConfig] = None
    chip_id: int = 0              # which chip of the population this is
    # Pallas tile selection (kernels/autotune.py): None (the default) defers
    # to the per-shape autotuner table — a tuned entry if this process ran
    # the search or loaded a persisted table (``autotune.load_table``;
    # benchmarks/frontend_bench.py writes one next to BENCH_frontend.json,
    # and ``VisionEngine(tile_table=...)`` loads it at construction),
    # deterministic heuristic otherwise. Explicit values pin the tiles
    # (tests, ablations).
    block_n: Optional[int] = None       # kernel-A patch-row block target
                                        # (implicit-im2col MXU tile)
    block_n_elem: Optional[int] = None  # kernel-B row-block cap (elementwise,
                                        # no MXU tile: bigger amortizes
                                        # dispatch)
    # matmul precision of the pallas path (DESIGN.md §14): None defers to the
    # autotuner's per-shape choice; "f32"/"int8" pins it. "int8" quantizes
    # both packed-matmul operands (per-column weight scales + the 1/128
    # activation grid) and folds dequant into the voltage-map epilogue — the
    # device chain after the MAC is the same kernel code either way.
    precision: Optional[str] = None
    # real TPUs only (compiled Pallas): generate the fused path's draw words
    # in-kernel (pltpu.prng_random_bits seeded per (key, block)) instead of
    # streaming ops.draw_bits from HBM. Interpret mode keeps the hash-word
    # oracle so CPU validation stays bit-exact vs kernels/ref.py.
    on_device_rng: bool = False


class SensorFrontend:
    """The one surface every consumer of the P2M first layer talks to."""

    def __init__(self, cfg: FrontendConfig = FrontendConfig()):
        get_backend(cfg.backend)   # fail fast on typos
        self.cfg = cfg

    def init(self, key: jax.Array, dtype=None) -> dict:
        kwargs = {} if dtype is None else {"dtype": dtype}
        return p2m.init_params(key, self.cfg.p2m, **kwargs)

    def __call__(self, params: dict, images: jax.Array, *,
                 key: Optional[jax.Array] = None,
                 mode: Optional[str] = None) -> Tuple[jax.Array, Dict]:
        """images (B, H, W, C) in [0, 1] -> (binary activations, aux).

        ``mode`` overrides ``cfg.backend`` for this call.
        """
        name = mode or self.cfg.backend
        acts, aux = get_backend(name)(self.cfg, params, images, key)
        if self.cfg.global_shutter and name in _STATEFUL:
            # one exposure per batch element: shutter stats are per frame
            acts, shutter_aux = shutter.global_shutter_readout(
                acts, self.cfg.p2m.mtj, frames=acts.shape[0])
            aux = {**aux, **shutter_aux}
        if "channel_rates" not in aux:
            # per-channel activation rates of the map as READ OUT — the
            # lifetime scheduler's monitoring signal. A backend may provide
            # them itself (the fused streaming kernel emits per-block
            # channel partials, sparing this whole-map reduction); the
            # burst read is the identity on clean {0,1} states, so
            # kernel-side (pre-shutter) rates equal the read-out rates.
            aux["channel_rates"] = jnp.mean(
                acts, axis=tuple(range(acts.ndim - 1)))
        # output sparsity = 1 - mean rate (channels are equally populated),
        # derived from the rate vector instead of a second whole-map pass
        aux["sparsity"] = 1.0 - jnp.mean(aux["channel_rates"])
        return acts, aux
