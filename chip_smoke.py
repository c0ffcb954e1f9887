#!/usr/bin/env python3
"""Smoke run of the P2M serving path on a TPU: the quickest proof that the
system still starts on the chip.

One process drives the system through the entry points a user calls, at the
full width of the widest model the repo supports — ``vgg16`` at its
published widths behind the P2M sensor frontend, on CIFAR-10-shaped frames
(32x32x3, 10 classes), with random weights from a seed:

  serve  ``VisionEngine(backend="pallas", microbatch=64)`` streams 4 batches
         of 128 synthetic frames: the exact two-kernel step, the fused
         streaming step and its theta drift guard all run. The compiled
         served step must hold the Pallas kernel (``tpu_custom_call``), its
         probabilities must be finite, and the frontend's activations on
         the chip must match the ``kernels/ref.py`` oracle on the same draw
         words up to the word-boundary rule (``ref.draw_mismatches``).
  fleet  ``FleetEngine`` serves 8 ``(chip_id, 32 frames)`` requests from 8
         chips sampled under a ``VariationConfig``, 4 chips per step.
  train  3 SGD steps of ``vgg16`` through ``train.vision.fit``.

With ``--four-chips`` it runs only the multi-chip comparisons, on a host
with four chips: ``VisionEngine(mesh=make_host_mesh())`` against
``mesh=None`` on the same frames and key, and ``FleetEngine(mesh=...)``
against an unsharded fleet. Labels and the frontend's outputs must be
bit-identical; probabilities agree to 1e-6.

Usage::

    python3 chip_smoke.py                 # one chip
    python3 chip_smoke.py --four-chips    # four chips

Earlier lines report the device, per-phase compile seconds and persistent
compile-cache hits, frames served, the fused/fallback counts and the flip
count. The last line of standard output is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Without a TPU the script exits non-zero before printing anything, and any
failed check ends it non-zero.
"""
import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import platform  # noqa: E402
from repro.core import p2m  # noqa: E402
from repro.data import ImageStream  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.models import vision  # noqa: E402
from repro.serving import FleetEngine, VisionEngine  # noqa: E402
from repro.variation.chip import VariationConfig  # noqa: E402

NUM_CLASSES = 10
HW = 32
# the flip budget of one microbatch's frontend-vs-oracle comparison
MAX_FLIPS = 8


class SmokeError(RuntimeError):
    """A check of the smoke run failed."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeError(msg)


def require_tpu(count: int):
    """The devices, or exit non-zero when JAX finds no TPU (no fallback)."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform "
                 f"{devs[0].platform!r}); nothing was run")
    if len(devs) < count:
        sys.exit(f"chip_smoke: needs {count} TPU chips, found {len(devs)}")
    return devs


class CompileMeter:
    """Backend-compile seconds and persistent-cache hits/misses, read off
    JAX's monitoring events (one listener pair for the process)."""
    _live = None

    def __init__(self):
        self.seconds, self.compiles, self.hits, self.misses = 0.0, 0, 0, 0

    @classmethod
    def current(cls) -> "CompileMeter":
        if cls._live is None:
            def on_duration(event, secs, **_):
                if (cls._live is not None
                        and event == "/jax/core/compile/backend_compile_duration"):
                    cls._live.seconds += secs
                    cls._live.compiles += 1

            def on_event(event, **_):
                if cls._live is None:
                    return
                if event == "/jax/compilation_cache/cache_hits":
                    cls._live.hits += 1
                elif event == "/jax/compilation_cache/cache_misses":
                    cls._live.misses += 1

            jax.monitoring.register_event_duration_secs_listener(on_duration)
            jax.monitoring.register_event_listener(on_event)
        cls._live = cls()
        return cls._live

    def line(self) -> str:
        return (f"compile_s={self.seconds:.1f} compiles={self.compiles} "
                f"cache_hits={self.hits} cache_misses={self.misses}")


def model(arch: str, seed: int = 0):
    cfg = vision.VisionConfig(name=f"{arch}_smoke", arch=arch,
                              num_classes=NUM_CLASSES, in_hw=HW,
                              frontend_backend="pallas")
    return cfg, vision.init_params(jax.random.PRNGKey(seed), cfg)


def frames_of(n: int, seed: int) -> jax.Array:
    return ImageStream(hw=HW, num_classes=NUM_CLASSES, global_batch=n,
                       seed=seed).next_batch()["image"]


def _finite_outputs(out, n: int) -> None:
    probs = np.asarray(out["probs"])
    labels = np.asarray(out["labels"])
    check(probs.shape == (n, NUM_CLASSES), f"probs shape {probs.shape}")
    check(np.isfinite(probs).all(), "non-finite probabilities (logits)")
    check(((labels >= 0) & (labels < NUM_CLASSES)).all(), "bad labels")


def frontend_flips(cfg, params, frames, key) -> int:
    """Run the served frontend (kernel A + kernel B) on ``frames`` and
    compare its activations with the kernels/ref.py oracle on the same
    draw words; returns the flip count (all on word boundaries)."""
    pcfg = cfg.p2m
    c = pcfg.out_channels
    wq = p2m.quantize_weights(params["p2m"]["w"], pcfg.weight_bits)
    acts, aux = ops.p2m_frontend(frames, wq, params["p2m"]["v_th"], key,
                                 kernel=pcfg.kernel_size, stride=pcfg.stride,
                                 pixel_params=pcfg.pixel,
                                 mtj_params=pcfg.mtj)
    acts = acts.reshape(-1, c)
    bits = ops.draw_bits(key, acts.shape[0], c)
    with jax.default_matmul_precision("highest"):
        q = ref.p2m_conv_ref_q(ops.im2col(frames, pcfg.kernel_size,
                                          pcfg.stride),
                               wq.reshape(-1, c), aux["theta"],
                               pixel_params=pcfg.pixel, mtj_params=pcfg.mtj)
    flips, off_boundary = ref.draw_mismatches(acts, q, bits)
    check(off_boundary == 0, f"{off_boundary} frontend activations differ "
          "from the oracle away from a draw-word boundary")
    check(flips <= MAX_FLIPS, f"{flips} frontend-vs-oracle flips "
          f"(> {MAX_FLIPS})")
    return flips


def serve_phase(arch: str = "vgg16", batches: int = 4, batch: int = 128,
                microbatch: int = 64, seed: int = 0) -> dict:
    """Stream frame batches through ``VisionEngine`` on the pallas path."""
    meter = CompileMeter.current()
    cfg, params = model(arch, seed)
    eng = VisionEngine(cfg, params, backend="pallas", seed=seed,
                       microbatch=microbatch)
    stream = [frames_of(batch, seed + 1 + i) for i in range(batches)]
    served = 0
    for frames, out in zip(stream, eng.stream(stream)):
        _finite_outputs(out, frames.shape[0])
        served += frames.shape[0]
    check(eng.fused_step_count >= 1, "the fused streaming step never ran")
    exact = batches * -(-batch // microbatch) - eng.fused_step_count
    key = jax.random.PRNGKey(seed + 7)
    mb = stream[0][:microbatch]
    hlo = eng._step.lower(eng.params, mb, key).compile().as_text()
    # the kernel is compiled exactly where Pallas compiles (on the TPU)
    check(("tpu_custom_call" in hlo) == (not platform.pallas_interpret()),
          "the compiled served step holds no Pallas TPU kernel")
    flips = frontend_flips(cfg, params, mb, key)
    return {"phase": "serve", "arch": arch, "frames": served,
            "fused_steps": eng.fused_step_count,
            "fallbacks": eng.fused_fallback_count,
            "exact_steps": exact + eng.fused_fallback_count,
            "flips": flips, "meter": meter}


def fleet_phase(arch: str = "vgg16", chips: int = 8, frames: int = 32,
                chips_per_step: int = 4, seed: int = 0) -> dict:
    """Serve one request per sampled chip through ``FleetEngine``."""
    meter = CompileMeter.current()
    cfg, params = model(arch, seed)
    cfg = dataclasses.replace(cfg, variation=VariationConfig(
        sigma_logit_offset=0.3, sigma_pixel_gain=0.05,
        sigma_pixel_offset=0.05, chip_seed=seed))
    fleet = FleetEngine(cfg, params, backend="pallas", seed=seed,
                        chips_per_step=chips_per_step)
    reqs = [(cid, frames_of(frames, 100 + cid)) for cid in range(chips)]
    outs = fleet.serve(reqs)
    check(len(outs) == chips, f"{len(outs)} outputs for {chips} requests")
    for out in outs:
        _finite_outputs(out, frames)
    return {"phase": "fleet", "arch": arch, "frames": fleet.frames_served,
            "chips": fleet.state.size, "meter": meter}


def train_phase(arch: str = "vgg16", steps: int = 3, batch: int = 64,
                seed: int = 0) -> dict:
    """SGD steps through ``train.vision.fit`` (the launcher's call)."""
    from repro.train import vision as vision_loop
    meter = CompileMeter.current()
    cfg, params = model(arch, seed)
    cfg = dataclasses.replace(cfg, frontend_backend="analog")
    stream = ImageStream(hw=HW, num_classes=NUM_CLASSES, global_batch=batch,
                         seed=seed)
    losses = []
    params = vision_loop.fit(params, cfg, stream, steps,
                             key=jax.random.PRNGKey(seed + 1), log_every=1,
                             log_fn=losses.append)
    check(len(losses) == steps, f"{len(losses)} of {steps} steps logged")
    leaves = jax.tree.leaves(params)
    check(all(bool(jnp.isfinite(x).all()) for x in leaves),
          "non-finite parameters after training")
    return {"phase": "train", "arch": arch, "steps": steps,
            "last_log": repr(" ".join(losses[-1].split())), "meter": meter}


def _same(a, b) -> bool:
    return np.array_equal(np.asarray(a), np.asarray(b))


# the engine outputs computed by the sensor frontend (batch-global aux)
_FRONTEND_KEYS = ("theta", "p2m_sparsity", "channel_rates",
                  "v_conv_mean", "v_conv_min", "v_conv_max")


def _compare(single: dict, sharded: dict, what: str) -> bool:
    """Labels and frontend outputs bit-identical, probs to 1e-6; returns
    whether every array output was bit-identical."""
    check(_same(single["labels"], sharded["labels"]),
          f"{what}: sharded labels differ")
    for k in _FRONTEND_KEYS:
        check(_same(single[k], sharded[k]), f"{what}: sharded {k} differs")
    diff = float(np.max(np.abs(np.asarray(single["probs"])
                               - np.asarray(sharded["probs"]))))
    check(diff <= 1e-6, f"{what}: sharded probs differ by {diff}")
    return all(_same(single[k], sharded[k]) for k in single
               if hasattr(single[k], "shape") and k != "wall_ms")


def sharded_phase(arch: str = "vgg16", frames: int = 64,
                  fleet_frames: int = 16, seed: int = 0) -> dict:
    """Data-parallel ``VisionEngine`` and fleet-sharded ``FleetEngine`` on
    every local device, each against its unsharded twin."""
    from repro.launch.mesh import make_host_mesh
    meter = CompileMeter.current()
    mesh = make_host_mesh()
    n_dev = len(jax.devices())
    cfg, params = model(arch, seed)
    x = frames_of(frames, seed + 1)
    key = jax.random.PRNGKey(seed + 5)
    one = VisionEngine(cfg, params, backend="pallas", seed=seed)
    many = VisionEngine(cfg, params, backend="pallas", seed=seed, mesh=mesh)
    bit_vision = _compare(one.classify(x, key=key),
                          many.classify(x, key=key), "VisionEngine")
    reqs = [(cid, frames_of(fleet_frames, 200 + cid))
            for cid in range(n_dev)]
    fleets = [FleetEngine(cfg, params, backend="pallas", seed=seed,
                          chips_per_step=n_dev, fused_stream=False,
                          mesh=m) for m in (None, mesh)]
    outs = [f.serve(list(reqs)) for f in fleets]
    bit_fleet = all(_compare(a, b, "FleetEngine") for a, b in zip(*outs))
    return {"phase": "sharded", "arch": arch, "devices": n_dev,
            "vision_bit_identical": bit_vision,
            "fleet_bit_identical": bit_fleet, "meter": meter}


def report(res: dict) -> None:
    meter = res.pop("meter")
    fields = " ".join(f"{k}={v}" for k, v in res.items())
    print(f"{fields} {meter.line()}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip sharded-vs-unsharded checks")
    args = ap.parse_args(argv)
    count = 4 if args.four_chips else 1
    devs = require_tpu(count)
    cache = platform.enable_compile_cache()
    print(f"device platform={devs[0].platform} kind={devs[0].device_kind} "
          f"count={len(devs)} jax={jax.__version__} compile_cache={cache}",
          flush=True)
    t0 = time.perf_counter()
    phases = ([sharded_phase] if args.four_chips
              else [serve_phase, fleet_phase, train_phase])
    for phase in phases:
        t = time.perf_counter()
        res = phase()
        res["wall_s"] = round(time.perf_counter() - t, 1)
        report(res)
    print(f"total_wall_s={time.perf_counter() - t0:.1f}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
