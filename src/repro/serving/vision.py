"""VisionEngine: serve camera frames through the SensorFrontend + backbone.

The serving counterpart of the P2M story: an edge camera produces frames,
the in-pixel frontend (any registered backend — typically ``device`` or
``pallas`` for deployment realism, ``analog``/``ideal`` for upper bounds)
binarizes them at the sensor, and the sparse-BNN backbone classifies. The
whole step is jit-compiled once per (batch shape, backend).

    engine = VisionEngine(cfg, params, backend="pallas")
    out = engine.classify(frames)                       # one batch
    for out in engine.stream(frame_batches):            # a frame stream
        ...

Data parallelism: pass ``mesh=`` (e.g. ``launch.mesh.make_host_mesh()`` or
the 16x16 production mesh) and the engine becomes a data-parallel server —
params are replicated across the mesh once at construction and every frame
batch is sharded over the mesh's batch axes (``("pod", "data")`` per the
``sharding.py`` rule table) before the jitted step, so XLA SPMD-partitions
the whole sensor-to-logits pipeline. The computation is deterministic in the
key regardless of the device layout, so a sharded engine is bit-identical to
a single-device one (asserted in tests/test_serving_sharded.py).

Microbatching: ``microbatch=`` caps the per-step frame count; ``stream()``
splits larger incoming batches and folds a fresh key per microbatch (each
microbatch is one global-shutter exposure draw), then merges the outputs
back into one result per incoming batch.

``out`` is a dict with ``labels``, ``probs``, the frontend aux (sparsity,
per-channel rates, V_CONV stats, per-frame global-shutter energy
accounting) and serving telemetry: measured ``wall_ms`` /
``throughput_fps`` of the step plus the MODELED sensor-side frame latency
(``sensor_latency_us`` / ``sensor_fps`` from ``core/energy.frame_latency_us``
at this engine's frame geometry) — so a deployment can monitor both the
compute link and the physical sensor budget, not just the predictions.

Timing is OFF the hot path (DESIGN.md §12): ``stream()`` dispatches
microbatches without blocking and latches each step's honest end-to-end
latency through a deferred readiness probe (``repro.obs.clock.WallProbe``),
draining once per incoming batch — the merged ``wall_ms`` is the honest
first-dispatch-to-last-ready wall, while the device pipeline stays full
between microbatches. ``sync_timing=True`` restores the old
block-per-microbatch behavior bit-exactly (benches that want per-step
device-synchronized walls). Pass ``obs=`` (a ``repro.obs.Obs``) and the
engine additionally records latency histograms (p50/p95/p99), frame
counters, the host's waits on the device (``serving_host_syncs_total``),
spans (``stream`` > ``key_fold`` / ``microbatch`` > ``theta_sync``, then
``drain`` and ``merge``; ``stream`` carries ``item=``, ``microbatch``
``item=`` and ``part=``) and structured events (recalibration, drift-guard
fallback) — with ``obs=None`` (the default) every instrument call is
behind one ``is None`` check: outputs are bit-identical and jit
caches/census provably unchanged.

Per-chip realism: when ``cfg.variation`` names a sampled chip, pass the
chip's ``calibration=`` artifact (variation/calibrate.py) and the engine
programs its trim into the frontend params at construction — each engine
then simulates one distinct calibrated sensor out of the fleet.

Sensor lifetime (DESIGN.md §8): pass ``drift=`` (a ``lifetime.DriftConfig``)
and the engine's chip is no longer frozen at fabrication: a frame-clock
counts served frames, the chip's maps are re-evolved every step
(``lifetime.evolve_chip`` — time enters as an array operand riding in
``params["chip"]``, so the compiled step NEVER recompiles as the chip
ages), and with ``schedule=`` (a ``lifetime.SchedulePolicy``) +
``calibration_frames=`` a ``RecalibrationScheduler`` watches the streamed
per-channel activation rates and refreshes ``params["cal_trim"]`` in place
when the policy fires — charging each refresh's tester energy. Lifetime
telemetry (age, recalibration count/energy, monitored rate error) rides in
the output dict under ``lifetime_*`` keys. ``drift=None`` (or an all-zero
profile) leaves every code path bit-identical to a non-aging engine —
including with a scheduler armed (nothing drifts, nothing fires).
"""
from __future__ import annotations

import contextlib
import functools
from typing import (ContextManager, Dict, Iterable, Iterator, List, Optional,
                    Tuple)

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import sharding
from repro.core import energy
from repro.models import vision
from repro.obs import clock
from repro.variation import chip as chip_mod

# logical axes of a (B, H, W, C) frame batch: shard batch, replicate pixels
FRAME_AXES = ("batch", None, None, None)
# one count per wait of the engine's host thread on the device in a stream
HOST_SYNCS = "serving_host_syncs_total"
# each increment of HOST_SYNCS also marks the profiler's clock, so a device
# trace counts the syncs inside its window
HOST_SYNC_MARK = "host_sync"


def _named(fn: functools.partial, name: str) -> functools.partial:
    """``fn`` under ``name``: jit names the step's program after it
    (``jit_<name>``), so a device trace can tell the two steps apart."""
    fn.__name__ = name
    return fn


class VisionEngine:
    """Synchronous batched frame-classification engine (optionally sharded)."""

    def __init__(self, cfg: vision.VisionConfig, params,
                 backend: Optional[str] = None, seed: int = 0,
                 mesh: Optional[Mesh] = None,
                 rules: Optional[sharding.ShardingRules] = None,
                 microbatch: Optional[int] = None,
                 calibration=None,
                 drift=None, schedule=None,
                 calibration_frames: Optional[jax.Array] = None,
                 fused_stream: Optional[bool] = None,
                 fused_theta_tol: float = 0.02,
                 fused_theta_ema: float = 0.9,
                 tile_table: Optional[str] = None,
                 obs=None, sync_timing: bool = False):
        self.cfg = cfg
        self.backend = backend or cfg.frontend_backend
        self.mesh = mesh
        self.rules = rules or sharding.ShardingRules.make()
        self.microbatch = microbatch
        self._key = jax.random.PRNGKey(seed)
        self._frame_count = 0
        # telemetry (DESIGN.md §12): obs is a repro.obs.Obs or None; every
        # instrument call sits behind one `is None` check so the disabled
        # path has zero cost. sync_timing=True restores the pre-obs
        # block-per-microbatch honest walls (async probes otherwise).
        self._obs = obs
        self._sync_timing = bool(sync_timing)
        self._pending = clock.ProbeSet()
        self._batch_probes: List[clock.WallProbe] = []
        if fused_stream and self.backend != "pallas":
            raise ValueError("fused_stream=True requires the 'pallas' "
                             f"backend (got {self.backend!r})")
        if tile_table is not None:
            # bring a persisted autotuner search (frontend_bench writes one
            # next to BENCH_frontend.json) into this process: tile/fused
            # resolution then uses the MEASURED per-shape choices instead
            # of the heuristic defaults
            from repro.kernels import autotune
            autotune.load_table(tile_table)
        # fused streaming (DESIGN.md §9): None = auto (pallas streams consult
        # the kernels/autotune table for this shape), True/False pins it
        self._fused_stream = fused_stream
        self._fused_theta_tol = fused_theta_tol
        self._fused_theta_ema = fused_theta_ema
        self._theta_carry: Optional[float] = None
        self.fused_step_count = 0
        self.fused_fallback_count = 0
        if calibration is not None:
            # this engine serves ONE physical chip (cfg.variation/chip_id);
            # program its tester-solved per-channel trim into the frontend
            # params (variation/calibrate.py) — a fleet of distinct
            # calibrated sensors is a set of engines with distinct chip_ids
            # and artifacts sharing the same weights
            from repro.variation.calibrate import apply_calibration
            params = {**params,
                      "p2m": apply_calibration(params["p2m"], calibration)}
        if mesh is not None:
            # model + frontend params are small — replicate once, serve many
            params = jax.device_put(params, NamedSharding(mesh, P()))
        self.params = params
        self._step = jax.jit(_named(functools.partial(
            self._forward, cfg=cfg, backend=self.backend), "_forward"))
        self._fused_step = jax.jit(_named(functools.partial(
            self._forward_fused, cfg=cfg, backend=self.backend),
            "_forward_fused"))
        # modeled sensor-side frame budget at this engine's geometry
        # (core/energy §3.4) — constant telemetry, computed once
        lat = energy.frame_latency_us(self._frame_spec())
        self._sensor_latency_us = float(lat["total_us"])
        self._sensor_fps = float(lat["fps"])
        self.lifetime = None
        self._scheduler = None
        if drift is not None and drift.enabled:
            self._init_lifetime(drift, schedule, calibration_frames)

    def _frame_spec(self) -> energy.FrameSpec:
        cfg, pcfg = self.cfg, self.cfg.p2m
        conv = -(-cfg.in_hw // pcfg.stride)
        return energy.FrameSpec(
            h_in=cfg.in_hw, w_in=cfg.in_hw, c_in=pcfg.in_channels,
            h_out=max(conv // 2, 1), w_out=max(conv // 2, 1),
            c_out=pcfg.out_channels, kernel=pcfg.kernel_size,
            stride=pcfg.stride, n_mtj=pcfg.mtj.n_redundant)

    # --- telemetry plumbing (DESIGN.md §12) ---------------------------------

    def _span(self, name: str, **args) -> ContextManager[None]:
        return (self._obs.span(name, **args) if self._obs is not None
                else contextlib.nullcontext())

    def _event(self, name: str, **args) -> None:
        if self._obs is not None:
            self._obs.event(name, chip_id=self.cfg.chip_id, **args)

    def _record_latency(self, wall_s: float, n_frames: int) -> None:
        if self._obs is not None:
            self._obs.histogram("serving_microbatch_wall_ms").record(
                wall_s * 1e3)
            self._obs.counter("serving_frames_total").inc(n_frames)

    def _record_probe(self, p: clock.WallProbe) -> None:
        self._record_latency(p.latency, p.tags.get("frames", 0))

    def _host_sync(self) -> None:
        if self._obs is not None:
            self._obs.counter(HOST_SYNCS).inc()
            self._obs.mark(HOST_SYNC_MARK)

    def _finish_batch(self, outs: List[Dict], sizes: List[int]) -> Dict:
        """Merge one incoming batch's microbatch outputs; in async mode
        drain the in-flight probes (the ONE blocking point per batch) and
        patch the merged wall to the honest first-dispatch-to-last-ready
        interval. Sync mode with a single microbatch returns the output
        untouched — bit-identical to the pre-obs engine."""
        probes, self._batch_probes = self._batch_probes, []
        if len(self._pending):
            self._host_sync()
        with self._span("drain"):
            for p in self._pending.drain():
                self._record_probe(p)
        with self._span("merge"):
            merged = (_merge_outputs(outs, sizes) if len(outs) > 1
                      else outs[0])
        if probes:
            t0, t1 = clock.span_bounds(probes)
            wall = max(t1 - t0, 1e-9)
            merged = dict(merged)
            merged["wall_ms"] = wall * 1e3
            merged["throughput_fps"] = sum(sizes) / wall
        return merged

    # --- sensor-lifetime state machine (DESIGN.md §8) -----------------------

    def _init_lifetime(self, drift, schedule, calibration_frames) -> None:
        from repro import lifetime as lt
        pcfg = self.cfg.p2m
        c, n = pcfg.out_channels, pcfg.mtj.n_redundant
        vcfg = self.cfg.variation
        chip0 = (chip_mod.sample_chip(vcfg, c, n, self.cfg.chip_id)
                 if vcfg is not None and vcfg.enabled
                 else chip_mod.identity_chip(c, n))
        trim0 = self.params["p2m"].get("cal_trim")
        if trim0 is None:
            # zero trim is a regression-tested bit-exact no-op; keeping the
            # key always present keeps the params pytree structure (and so
            # the jit cache) stable across recalibrations
            trim0 = jnp.zeros((c,), jnp.float32)
        self.lifetime = lt.LifetimeState(
            chip0=chip0,
            maps=lt.sample_drift_maps(drift, c, n, self.cfg.chip_id),
            trim=trim0)
        # ONE compiled evolve for the engine's whole life: drift config is
        # the only static; chip / maps / age are array operands
        self._evolve = jax.jit(functools.partial(lt.evolve_chip, dcfg=drift))
        if schedule is not None:
            self._scheduler = lt.RecalibrationScheduler(
                schedule, pcfg, calibration_frames, self.params["p2m"],
                frame_spec=self._frame_spec(), obs=self._obs)

    def _aged_params(self):
        """The param tree for the current frame-clock age (array operands:
        the jitted step sees the same pytree structure every call)."""
        st = self.lifetime
        chip = self._evolve(st.chip0, st.maps,
                            jnp.asarray(st.age_frames, jnp.float32))
        return {**self.params, "p2m": {**self.params["p2m"],
                                       "chip": chip, "cal_trim": st.trim}}

    def _advance_lifetime(self, out: Dict, n_frames: int) -> Dict:
        """Tick the frame clock, run the scheduler, return telemetry."""
        st = self.lifetime
        st.age_frames += n_frames
        fired = 0.0
        if self._scheduler is not None:
            st.rate_err = self._scheduler.observe(out.get("channel_rates"))
            st.rate_err_history.append(st.rate_err)
            if self._scheduler.should_fire(st.age_frames,
                                           st.last_recal_frame):
                aged = self._evolve(st.chip0, st.maps,
                                    jnp.asarray(st.age_frames, jnp.float32))
                st.trim = self._scheduler.recalibrate(aged)
                st.recal_count += 1
                st.last_recal_frame = st.age_frames
                st.recal_energy_pj += self._scheduler.recal_energy_pj
                fired = 1.0
                self._event("recalibration", age_frames=st.age_frames,
                            recal_count=st.recal_count,
                            rate_err=float(st.rate_err),
                            energy_pj=float(st.recal_energy_pj))
        if self._obs is not None and self._scheduler is not None:
            self._obs.gauge("lifetime_rate_err").set(float(st.rate_err))
        return {"lifetime_age_frames": float(st.age_frames),
                "lifetime_recal_count": float(st.recal_count),
                "lifetime_recal_fired": fired,
                "lifetime_rate_err": float(st.rate_err),
                "lifetime_recal_energy_pj": float(st.recal_energy_pj)}

    # --- the serving step ----------------------------------------------------

    @staticmethod
    def _forward(params, frames, key, *, cfg, backend):
        logits, _, aux = vision.forward(params, frames, cfg, key=key,
                                        backend=backend)
        probs = jax.nn.softmax(logits, axis=-1)
        return {"labels": jnp.argmax(logits, -1), "probs": probs, **aux}

    @staticmethod
    def _forward_fused(params, frames, key, theta_carry, *, cfg, backend):
        """The fused streaming step: identical to ``_forward`` except the
        carried Hoyer threshold rides into the frontend params, which routes
        the pallas backend onto the single-kernel ``p2m_frontend_fused``
        path (DESIGN.md §9). ``theta_carry`` is an ARRAY operand — a new EMA
        value every microbatch against one compilation."""
        params = {**params, "p2m": {**params["p2m"],
                                    "theta_carry": theta_carry}}
        logits, _, aux = vision.forward(params, frames, cfg, key=key,
                                        backend=backend)
        probs = jax.nn.softmax(logits, axis=-1)
        return {"labels": jnp.argmax(logits, -1), "probs": probs, **aux}

    def _stream_fused_enabled(self, n_frames: int, h: int, w: int) -> bool:
        """Whether a stream step of ``n_frames`` (h, w) frames runs the
        fused single-kernel path.

        Explicit ``fused_stream=`` wins; otherwise pallas streams consult
        the autotuner's per-shape choice (``TileChoice.fused`` — measured
        when the deployment ran the search, heuristic default otherwise).
        ``n_frames`` must be the EXECUTED step's frame count — the
        microbatch, not the incoming batch — so the lookup hits the same
        (N, K, C) key the tuner stored for the step that actually runs.
        """
        if self.backend != "pallas":
            return False
        if self._fused_stream is not None:
            return self._fused_stream
        from repro.kernels import autotune, blocking
        pcfg = self.cfg.p2m
        n = (n_frames * blocking.conv_out_hw(h, pcfg.stride)
             * blocking.conv_out_hw(w, pcfg.stride))
        k_eff = pcfg.kernel_size ** 2 * pcfg.in_channels
        return autotune.get(n, k_eff, pcfg.out_channels).fused

    def _on_mesh(self) -> ContextManager:
        """The engine's mesh as JAX's ambient mesh around a step dispatch:
        the Pallas frontend reads it to run its kernels once per device
        (``ops``: the TPU compiler cannot partition a Mosaic kernel)."""
        return (jax.set_mesh(self.mesh) if self.mesh is not None
                else contextlib.nullcontext())

    def _shard_frames(self, frames: jax.Array) -> jax.Array:
        """Lay the frame batch out over the mesh's batch axes (no-op when
        the engine is unsharded or the batch does not divide the axes)."""
        if self.mesh is None:
            return frames
        spec = sharding.logical_to_spec(FRAME_AXES, frames.shape, self.mesh,
                                        self.rules)
        return jax.device_put(frames, NamedSharding(self.mesh, spec))

    def classify(self, frames: jax.Array,
                 key: Optional[jax.Array] = None) -> Dict:
        """frames: (B, H, W, C) in [0, 1]. Returns labels/probs/frontend aux
        plus serving telemetry (wall_ms, throughput_fps, sensor_latency_us).

        Without an explicit ``key`` the engine folds its frame counter into
        the seed key and advances it. An explicit ``key`` (replaying a frame,
        A/B-ing a draw) does NOT advance the counter — nor, on an aging
        engine, the frame-clock: a replay must not age the chip.
        """
        return self._classify(frames, key, advance=key is None)

    def _classify(self, frames: jax.Array, key: Optional[jax.Array],
                  advance: bool, fused: Optional[bool] = None,
                  defer: bool = False, item: Optional[int] = None,
                  part: int = 0) -> Dict:
        """``fused`` is tri-state: None = not a pallas-stream call (classify
        and non-pallas streams — no streaming telemetry keys, bit-identical
        to a plain engine); False = a pallas stream step the tuner/caller
        kept on the exact path; True = attempt the fused carried-theta step.
        Every pallas-stream step (either boolean) emits the SAME aux keys,
        so ``_merge_outputs`` never sees a mixed-key microbatch set even
        when the fused decision differs per microbatch shape (e.g. a
        non-divisible tail).

        ``defer=True`` (stream steps unless ``sync_timing``) dispatches
        WITHOUT blocking: the step's honest end-to-end latency is latched
        by a :class:`repro.obs.clock.WallProbe` at the next non-blocking
        poll or the batch-boundary drain, and ``_finish_batch`` patches
        the merged ``wall_ms``. The per-microbatch ``wall_ms`` on this
        path is the dispatch-side elapsed time only. ``item`` / ``part``
        (a stream's item index and the microbatch's place in it) label the
        ``microbatch`` span."""
        if key is None:
            with self._span("key_fold"):
                key = jax.random.fold_in(self._key, self._frame_count)
            self._frame_count += 1
        params = self.params if self.lifetime is None else self._aged_params()
        n = frames.shape[0]
        # harvest any already-finished in-flight steps before dispatching
        # the next one: their latency latches at the tightest observable
        # timestamp instead of waiting for the batch-boundary drain
        for p in self._pending.poll():
            self._record_probe(p)
        with self._on_mesh():
            probe = None
            t0 = clock.now()
            if fused:
                # the fused drift guard reads the fresh theta on the host, so
                # this path is inherently synchronized — its wall is honest
                with self._span("microbatch", frames=n, path="fused",
                                item=item, part=part):
                    out, drift, ran_fused = self._fused_classify(
                        params, frames, key)
                wall = clock.now() - t0
                if defer and not self._sync_timing:
                    # already measured, but the batch's honest span bounds
                    # must still cover this step
                    self._batch_probes.append(
                        clock.WallProbe.completed(t0, wall, frames=n))
            else:
                drift, ran_fused = 0.0, False
                if defer and not self._sync_timing:
                    with self._span("microbatch", frames=n, path="exact",
                                    item=item, part=part):
                        out = self._step(params, self._shard_frames(frames),
                                         key)
                    probe = self._pending.add(
                        clock.WallProbe(out["labels"], t0=t0, frames=n))
                    self._batch_probes.append(probe)
                    wall = clock.now() - t0
                else:
                    # honest-but-blocking: device-synchronized wall
                    # (classify() single shots and sync_timing=True streams)
                    with self._span("microbatch", frames=n, path="exact",
                                    item=item, part=part):
                        out = jax.block_until_ready(self._step(
                            params, self._shard_frames(frames), key))
                    if defer:
                        self._host_sync()
                    wall = clock.now() - t0
        out = dict(out)
        if fused is not None:
            # streaming telemetry: fraction of fused steps and the audited
            # relative theta drift (0.0 on the exact path / first microbatch)
            out["stream_fused"] = 1.0 if ran_fused else 0.0
            out["stream_theta_drift"] = drift
            if "theta_used" not in out:     # exact step: it used its own
                out["theta_used"] = out["theta"]
        out["wall_ms"] = wall * 1e3
        out["throughput_fps"] = n / wall
        out["sensor_latency_us"] = self._sensor_latency_us
        out["sensor_fps"] = self._sensor_fps
        if probe is None:
            # synchronized paths record immediately; probed steps record
            # when their probe latches (poll or drain)
            self._record_latency(wall, n)
        if self.lifetime is not None and advance:
            if defer and self._scheduler is not None:
                self._host_sync()       # observe reads the channel rates
            out.update(self._advance_lifetime(out, n))
        return out

    def _fused_classify(self, params, frames: jax.Array, key: jax.Array):
        """One streaming microbatch on the fused path, with the theta-EMA
        drift guard (DESIGN.md §9). Returns ``(out, rel_drift, ran_fused)``.

        The first microbatch (no carried threshold yet) runs the exact
        two-kernel step and seeds the carry — bit-identical to a
        non-streaming call. Later microbatches run the single fused kernel
        at the carried EMA threshold; the kernel also emits the FRESH Hoyer
        threshold, and when it has moved more than ``fused_theta_tol``
        (relative) away from the carry, the microbatch is RE-RUN on the
        exact path (same key — the rng sequence is identical either way,
        so guard firings are key-free and deterministic in the frames) and
        the carry is re-seeded. Otherwise the carry advances as
        ``ema * carry + (1 - ema) * fresh``. Each step is dispatched, then
        waited for with its threshold read under ``theta_sync``.
        """
        frames = self._shard_frames(frames)
        if self._theta_carry is None:
            out = self._step(params, frames, key)
            with self._span("theta_sync"):
                out = dict(jax.block_until_ready(out))
                # the exact path thresholds at its own fresh theta;
                # mirroring it under the fused path's aux key keeps every
                # microbatch output of a stream structurally identical for
                # _merge_outputs
                out["theta_used"] = out["theta"]
                self._theta_carry = float(out["theta"])
            self._host_sync()
            return out, 0.0, False
        carry = self._theta_carry
        out = self._fused_step(params, frames, key,
                               jnp.asarray(carry, jnp.float32))
        with self._span("theta_sync"):
            out = jax.block_until_ready(out)
            self.fused_step_count += 1
            if self._obs is not None:
                self._obs.counter("serving_fused_steps_total").inc()
            fresh = float(out["theta"])
        self._host_sync()
        drift = abs(fresh - carry) / max(abs(carry), 1e-9)
        if drift > self._fused_theta_tol:
            # the carried threshold went stale (scene change): serve this
            # microbatch from the exact pipeline and restart the EMA
            self._event("drift_guard_fallback", drift=drift,
                        theta_carry=carry, theta_fresh=fresh)
            if self._obs is not None:
                self._obs.counter("serving_fused_fallback_total").inc()
            out = self._step(params, frames, key)
            with self._span("theta_sync"):
                out = dict(jax.block_until_ready(out))
                out["theta_used"] = out["theta"]
                self._theta_carry = float(out["theta"])
            self._host_sync()
            self.fused_fallback_count += 1
            return out, drift, False
        self._theta_carry = (self._fused_theta_ema * carry
                             + (1.0 - self._fused_theta_ema) * fresh)
        return out, drift, True

    def stream(self, frame_batches: Iterable[jax.Array]) -> Iterator[Dict]:
        """Classify a stream of frame batches; per-batch (and, with
        ``microbatch=``, per-microbatch) rng keys are folded in so the
        stochastic MTJ draws differ exposure to exposure (global shutter:
        every frame is one exposure + burst read). Yields one merged output
        per incoming batch regardless of microbatching. On an aging engine
        the frame-clock advances per microbatch, so the chip the Nth
        microbatch sees is older than the first — and the scheduler may
        refresh the trim mid-stream (a deterministic, key-free event: the
        rng sequence of the draws is identical with or without it).

        Pallas streams run the FUSED single-kernel frontend in steady state
        (``fused_stream=``: None defers to the autotuner's per-shape
        choice): the first microbatch takes the exact two-kernel path
        (bit-identical to ``classify``) and seeds a carried Hoyer-theta
        EMA; later microbatches draw at the carried threshold and fall
        back to the exact path whenever the fresh threshold drifts beyond
        ``fused_theta_tol`` (a key-free, frames-deterministic guard).
        ``stream_fused`` / ``stream_theta_drift`` telemetry rides in every
        output (DESIGN.md §9)."""
        # a new stream is a new scene: drop any carried threshold so the
        # first microbatch of EVERY stream is the exact step that re-seeds
        # it (a stale carry from a previous stream could sit inside the
        # tolerance yet describe a different scene)
        self._theta_carry = None
        for item, frames in enumerate(frame_batches):
            mb = self.microbatch
            b, h, w = frames.shape[0], frames.shape[1], frames.shape[2]

            def fused_arg(n_frames: int) -> Optional[bool]:
                # tri-state: None for non-pallas backends (stream outputs
                # stay exactly as before the fused mode existed)
                if self.backend != "pallas":
                    return None
                return self._stream_fused_enabled(n_frames, h, w)

            with self._span("stream", frames=b, item=item):
                if not mb or b <= mb:
                    outs = [self._classify(frames, None, advance=True,
                                           fused=fused_arg(b), defer=True,
                                           item=item)]
                    sizes = [b]
                else:
                    with self._span("key_fold"):
                        base = jax.random.fold_in(self._key,
                                                  self._frame_count)
                    self._frame_count += 1
                    starts = list(range(0, b, mb))
                    sizes = [min(mb, b - i) for i in starts]
                    outs = []
                    for j, (i, sz) in enumerate(zip(starts, sizes)):
                        chunk = frames[i:i + sz]
                        with self._span("key_fold"):
                            key = jax.random.fold_in(base, j)
                        outs.append(self._classify(
                            chunk, key=key, advance=True, fused=fused_arg(sz),
                            defer=True, item=item, part=j))
                merged = self._finish_batch(outs, sizes)
            yield merged


# aux keys that are per-CHANNEL vectors, not per-example rows: merged by
# frame-weighted mean (concatenating them would grow the channel axis)
_CHANNEL_KEYS = ("channel_rates",)
# cumulative / monotone counters: the batch-level value is the LAST
# microbatch's (averaging would report an age/count/energy the engine never
# had — the non-microbatched path reports the exact running value)
_CUMULATIVE_KEYS = ("lifetime_age_frames", "lifetime_recal_count",
                    "lifetime_recal_energy_pj", "lifetime_rate_err")
# events: fired-anywhere-in-the-batch, not a firing *fraction*
_EVENT_KEYS = ("lifetime_recal_fired",)
# additive costs: the batch's total, not a per-microbatch average
_SUM_KEYS = ("wall_ms",)
# engine constants (modeled sensor budget): identical in every microbatch —
# pass the first through VERBATIM. Frame-weighted averaging them (the old
# fallthrough) silently cast the f64 python float through an f32 stack and
# could drift in the last ulp under non-dyadic weight normalization.
_CONSTANT_KEYS = ("sensor_latency_us", "sensor_fps")


def _weights(sizes: Tuple[int, ...]) -> np.ndarray:
    """The microbatches' frame-count weights in f32, summing to one."""
    w = np.asarray(sizes, np.float32)
    return w / w.sum()


def _reduce(k: str, vals: List, w: np.ndarray, xp):
    """Key ``k``'s batch value computed from its microbatch values:
    ``xp`` is ``jnp`` inside the compiled merge and ``np`` on the host."""
    if k in _CHANNEL_KEYS:
        return xp.sum(xp.stack(vals) * w[:, None], axis=0)
    if xp.ndim(vals[0]) >= 1:
        return xp.concatenate(vals, axis=0)
    if k.endswith("_min"):
        return xp.min(xp.stack(vals))
    if k.endswith("_max"):
        return xp.max(xp.stack(vals))
    return xp.sum(xp.stack(vals) * w)


@functools.partial(jax.jit, static_argnames="sizes")
def _merge_on_device(outs: List[Dict], sizes: Tuple[int, ...]) -> Dict:
    """The device-array keys of a batch merged in one program, with the
    weights as its constants: one compile per (keys, shapes, sizes)."""
    w = _weights(sizes)
    return {k: _reduce(k, [o[k] for o in outs], w, jnp) for k in outs[0]}


def _merge_outputs(outs: List[Dict], sizes: List[int]) -> Dict:
    """Merge per-microbatch outputs into one batch-level dict.

    Per-example arrays (leading dim = microbatch size) are concatenated;
    per-channel vectors (``channel_rates``) and scalar monitoring stats are
    reduced respecting their semantics: cumulative lifetime counters by
    last-value, recalibration events by any-fired, wall clock by total (and
    ``throughput_fps`` recomputed from it), engine constants
    (``sensor_latency_us``/``sensor_fps``) passed through verbatim, min/max
    keys by min/max, everything else — means, rates, and per-frame
    energies — by a frame-count-WEIGHTED mean (the tail microbatch of a
    batch that does not divide evenly must not be over-weighted).

    The keys whose values are device arrays are merged by ONE compiled
    program (``_merge_on_device``); host scalars never go to the device
    (a weighted mean of them is taken in f32 NumPy, a 0-d value), so a
    call moves nothing between host and device.
    """
    sizes = tuple(sizes)
    w = _weights(sizes)
    merged: Dict = {}
    on_device: List[str] = []
    for k in outs[0]:
        vals = [o[k] for o in outs]
        if k in _CUMULATIVE_KEYS:
            merged[k] = vals[-1]
        elif k in _EVENT_KEYS:
            merged[k] = max(float(v) for v in vals)
        elif k in _SUM_KEYS:
            merged[k] = sum(float(v) for v in vals)
        elif k in _CONSTANT_KEYS:
            merged[k] = vals[0]
        elif any(isinstance(v, jax.Array) for v in vals):
            on_device.append(k)
            merged[k] = None            # filled below, in key order
        else:
            merged[k] = _reduce(k, [np.asarray(v, np.float32) for v in vals],
                                w, np)
    if on_device:
        merged.update(_merge_on_device(
            [{k: o[k] for k in on_device} for o in outs], sizes))
    if "wall_ms" in merged:
        merged["throughput_fps"] = sum(sizes) / (merged["wall_ms"] / 1e3)
    return merged
