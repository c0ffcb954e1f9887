"""FleetEngine: multi-tenant serving of a fleet of distinct aging sensors.

``VisionEngine`` serves ONE chip instance; a deployment is a population of
them — each fabricated with its own mismatch (variation/), aging on its own
frame clock (lifetime/), streaming concurrently. This module batches frames
ACROSS chips in one jitted step:

    engine = FleetEngine(cfg, params, backend="pallas", chips_per_step=4)
    outs = engine.serve([(chip_id, frames), ...])   # one output per request
    for outs in engine.stream(request_batches):     # concurrent streams
        ...

Data layout (DESIGN.md §10). A ``FleetState`` registry holds every chip's
identity stacked along a leading chip axis: ``chips0`` (the t = 0 sampled
``ChipMaps``), ``maps`` (frozen ``DriftMaps`` drift directions), ``trim``
(F, C) programmed calibration DACs, plus host-side per-chip telemetry — the
frame-clock age, the rng frame counter, and the recalibration audit trail.
A serving step gathers up to ``chips_per_step`` requests' rows (a plain
outside-jit ``tree.map(lambda a: a[idx])`` — the registry's leading
dimension NEVER enters the trace), evolves the gathered chips to their
current ages (one vmapped ``evolve_chip``), and runs ONE jitted
``vmap``-over-chips forward: kernel B's (4, C) channel operand, the device
maps, and the analog noise maps all ride per-row through the vmap batching
rule, so the compiled step serves ARBITRARY chip mixes with zero recompiles
(jit cache == 1 across chip permutations, sweeps, and fleet sizes at a
fixed executed (G, microbatch) shape — asserted in tests).

Per-chip rng mirrors ``VisionEngine`` exactly: chip ``i``'s stream folds its
OWN frame counter into the engine seed key (microbatch ``j`` of a split
request folds ``j`` into that), so a 1-chip fleet is bit-identical to a
``VisionEngine`` with the same seed — the acceptance criterion this module
is built around. With neither variation nor drift armed the step plants NO
chip operands (``params`` untouched), keeping even the analog backend's
byte-exact parity with a plain engine.

Fused streaming (DESIGN.md §9) runs per chip: each chip carries its own
Hoyer-theta EMA; a step runs fused only when every gathered chip has a
carry, and the drift guard re-runs the whole step on the exact path (same
keys — deterministic in the frames) when any chip's fresh theta moved
beyond tolerance. Steps never pack two microbatches of the same chip, so
per-chip carries always advance in stream order.

Background maintenance: ``sweep=`` arms an amortized staleness-prioritized
recalibration sweep over the fleet — the PR 4 ``RecalibrationScheduler``'s
vmapped tester (``recalibrate_fleet``) refreshes the K most-stale eligible
chips per sweep, budgeted by an energy credit that accrues per served frame
(``maintenance_energy_per_frame_pj``). Sweeps are key-free and
deterministic: they perturb no rng stream.

Warm restarts: ``save()`` persists the FULL fleet — stacked chips, trims,
ages, telemetry, rng frame-clocks and per-chip theta carries — through
``checkpoint/manager.py``; ``load()`` on a freshly constructed engine (same
cfg/params/seed) resumes every stream bit-identically (asserted in tests).
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import (ContextManager, Dict, Iterable, Iterator, List, Optional,
                    Sequence, Tuple)

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import sharding
from repro.core import energy, hoyer, p2m
from repro.models import vision
from repro.obs import clock
from repro.serving.vision import HOST_SYNC_MARK, HOST_SYNCS, _merge_outputs
from repro.variation import chip as chip_mod
from repro.variation.calibrate import solve_trim, target_rates

# logical axes of a (G, B, H, W, C) fleet step: chips over the mesh's
# data-parallel axes, per-chip microbatch replicated (sharding.py rules)
FLEET_FRAME_AXES = ("fleet", "batch", None, None, None)


@dataclasses.dataclass(frozen=True)
class FleetSweepPolicy:
    """The amortized background maintenance loop of a fleet.

    ``policy`` is the per-chip eligibility condition (the PR 4
    ``SchedulePolicy`` — periodic staleness and/or monitored-rate trigger);
    each sweep refreshes at most ``refresh_per_sweep`` eligible chips,
    most-stale first. ``maintenance_energy_per_frame_pj`` caps the sweep
    rate by energy: every served frame accrues that much tester credit and
    each refresh spends ``RecalibrationScheduler.recal_energy_pj`` of it
    (None = no energy cap). ``auto`` runs a sweep after every ``serve()``.
    """
    policy: "object"                      # lifetime.SchedulePolicy
    refresh_per_sweep: int = 4
    maintenance_energy_per_frame_pj: Optional[float] = None
    auto: bool = True


@dataclasses.dataclass
class FleetState:
    """Every chip the engine serves, stacked along a leading (F,) axis."""
    chips0: chip_mod.ChipMaps    # t = 0 sampled instances, leaves (F, ...)
    maps: "object"               # DriftMaps drift directions, leaves (F, ...)
    trim: jax.Array              # (F, C) programmed trim DACs
    chip_ids: List[int]          # registry order (row i serves chip_ids[i])
    age_frames: np.ndarray       # (F,) int64 frame-clock ages
    frame_count: np.ndarray      # (F,) int64 per-chip rng frame counters
    last_recal_frame: np.ndarray  # (F,) int64
    recal_count: np.ndarray      # (F,) int64
    recal_energy_pj: np.ndarray  # (F,) float64 cumulative tester energy
    rate_ema: np.ndarray         # (F, C) float64 monitored channel-rate EMA
    rate_baseline: np.ndarray    # (F, C) float64 post-refresh EMA snapshot
    ema_valid: np.ndarray        # (F,) bool: rate_ema holds observations
    baseline_valid: np.ndarray   # (F,) bool
    rate_err: np.ndarray         # (F,) float64 monitored drift metric

    @property
    def size(self) -> int:
        return len(self.chip_ids)


@dataclasses.dataclass
class _WorkItem:
    """One executed microbatch of one request (planned before stepping)."""
    req: int                     # index into the serve() request list
    slot: int                    # fleet registry row
    chip_id: int
    frames: jax.Array            # (b, H, W, C)
    key: jax.Array               # this microbatch's rng key (pre-folded)
    age: int                     # the chip's frame-clock age THIS item sees
    advance: bool = True         # False: pinned-key replay (ages nothing)


class FleetEngine:
    """Synchronous multi-chip frame-classification engine."""

    def __init__(self, cfg: vision.VisionConfig, params,
                 backend: Optional[str] = None, seed: int = 0,
                 mesh: Optional[Mesh] = None,
                 rules: Optional[sharding.ShardingRules] = None,
                 microbatch: Optional[int] = None,
                 chips_per_step: int = 4,
                 drift=None,
                 sweep: Optional[FleetSweepPolicy] = None,
                 calibration_frames: Optional[jax.Array] = None,
                 birth_calibration: Optional[bool] = None,
                 birth_cal_iters: int = 16, birth_cal_span: float = 2.0,
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
        # telemetry (DESIGN.md §12) — same contract as VisionEngine:
        # obs=None costs one `is None` check per hook; sync_timing=True
        # restores the blocking per-step honest walls
        self._obs = obs
        self._sync_timing = bool(sync_timing)
        self.chips_per_step = int(chips_per_step)
        if self.chips_per_step < 1:
            raise ValueError("chips_per_step must be >= 1")
        self.seed = seed
        self._key = jax.random.PRNGKey(seed)
        if fused_stream and self.backend != "pallas":
            raise ValueError("fused_stream=True requires the 'pallas' "
                             f"backend (got {self.backend!r})")
        if tile_table is not None:
            from repro.kernels import autotune
            autotune.load_table(tile_table)
        self._fused_stream = fused_stream
        self._fused_theta_tol = fused_theta_tol
        self._fused_theta_ema = fused_theta_ema
        # per-chip carried Hoyer-theta EMA, keyed by chip_id (a chip that
        # leaves and rejoins starts a fresh stream)
        self._theta_carry: Dict[int, float] = {}
        self.fused_step_count = 0
        self.fused_fallback_count = 0
        self.frames_served = 0
        self.sweep_count = 0

        self.drift = drift if (drift is not None and drift.enabled) else None
        vcfg = cfg.variation
        self._vcfg = vcfg if (vcfg is not None and vcfg.enabled) else None
        # plant chip/trim operands only when some chip can differ from the
        # nominal device: with neither variation nor drift every backend
        # stays byte-exact with a plain (operand-free) VisionEngine —
        # planting an identity chip would, e.g., arm the analog backend's
        # nominal Fig. 5 flip draws
        self._plant = self._vcfg is not None or self.drift is not None

        if mesh is not None:
            params = jax.device_put(params, NamedSharding(mesh, P()))
        self.params = params
        pcfg = cfg.p2m
        self._c = pcfg.out_channels
        self._n_red = pcfg.mtj.n_redundant

        self._step = jax.jit(jax.vmap(
            functools.partial(self._chip_forward, cfg=cfg,
                              backend=self.backend, plant=self._plant),
            in_axes=(None, 0, 0, 0, 0)))
        self._fused_step = jax.jit(jax.vmap(
            functools.partial(self._chip_forward_fused, cfg=cfg,
                              backend=self.backend, plant=self._plant),
            in_axes=(None, 0, 0, 0, 0, 0)))
        if self.drift is not None:
            from repro import lifetime as lt
            self._evolve = jax.jit(jax.vmap(
                functools.partial(lt.evolve_chip, dcfg=self.drift)))
        else:
            self._evolve = None

        lat = energy.frame_latency_us(self._frame_spec())
        self._sensor_latency_us = float(lat["total_us"])
        self._sensor_fps = float(lat["fps"])

        # the virtual tester: birth calibration + (with sweep=) the
        # scheduler whose vmapped solve the background sweep dispatches
        self._birth_solve = None
        self._scheduler = None
        self.sweep_policy = sweep
        self._energy_credit_pj = 0.0
        if calibration_frames is not None:
            pp = self.params["p2m"]
            u = p2m.hardware_conv(calibration_frames, pp["w"], pcfg)
            theta = hoyer.effective_threshold(u, pp["v_th"]) * pp["v_th"]
            ref = target_rates(u, theta, pcfg)
            # eager on purpose: ``variation.calibrate()`` solves eagerly,
            # and a jitted solve can round one bisection step differently
            # on a borderline channel — birth trims must be bit-identical
            # to the tester artifact a single-chip engine would program
            self._birth_solve = lambda chip: solve_trim(
                u, theta, chip, ref, pcfg,
                iters=birth_cal_iters, span=birth_cal_span)
        if birth_calibration is None:
            birth_calibration = (calibration_frames is not None
                                 and self._vcfg is not None)
        if birth_calibration and self._birth_solve is None:
            raise ValueError("birth_calibration needs calibration_frames")
        self._birth_calibration = birth_calibration
        if sweep is not None:
            from repro import lifetime as lt
            if calibration_frames is None:
                raise ValueError("a sweep policy needs calibration_frames "
                                 "(the tester re-exposes them per refresh)")
            self._scheduler = lt.RecalibrationScheduler(
                sweep.policy, pcfg, calibration_frames, self.params["p2m"],
                frame_spec=self._frame_spec(), obs=self._obs)

        self.state = self._empty_state()

    # --- registry ----------------------------------------------------------

    def _empty_state(self) -> FleetState:
        c, n = self._c, self._n_red
        z = lambda *s: jnp.zeros(s, jnp.float32)
        return FleetState(
            chips0=chip_mod.ChipMaps(z(0, c, n), z(0, c, n), z(0, c, n),
                                     z(0, c, n), z(0, c), z(0, c)),
            maps=self._drift_maps_like(0),
            trim=z(0, c),
            chip_ids=[],
            age_frames=np.zeros((0,), np.int64),
            frame_count=np.zeros((0,), np.int64),
            last_recal_frame=np.zeros((0,), np.int64),
            recal_count=np.zeros((0,), np.int64),
            recal_energy_pj=np.zeros((0,), np.float64),
            rate_ema=np.zeros((0, c), np.float64),
            rate_baseline=np.zeros((0, c), np.float64),
            ema_valid=np.zeros((0,), bool),
            baseline_valid=np.zeros((0,), bool),
            rate_err=np.zeros((0,), np.float64))

    def _drift_maps_like(self, f: int):
        from repro.lifetime.drift import DriftMaps
        c, n = self._c, self._n_red
        z = lambda *s: jnp.zeros(s, jnp.float32)
        return DriftMaps(z(f, c, n), z(f, c, n), z(f, c, n), z(f, c, n),
                         z(f, c), z(f, c))

    def slot_of(self, chip_id: int) -> int:
        try:
            return self.state.chip_ids.index(int(chip_id))
        except ValueError:
            raise KeyError(f"chip {chip_id} is not in the fleet") from None

    def add_chip(self, chip_id: int,
                 calibrate: Optional[bool] = None) -> int:
        """Register one chip; returns its registry row.

        The chip's identity is deterministic in ``(cfg.variation, chip_id)``
        (and its drift directions in ``(drift.drift_seed, chip_id)``) —
        re-adding the same id on a restarted process reproduces the same
        physical chip. ``calibrate`` overrides the engine's
        ``birth_calibration`` default for this chip.
        """
        chip_id = int(chip_id)
        if chip_id in self.state.chip_ids:
            raise ValueError(f"chip {chip_id} is already in the fleet")
        c, n = self._c, self._n_red
        chip = (chip_mod.sample_chip(self._vcfg, c, n, chip_id)
                if self._vcfg is not None else chip_mod.identity_chip(c, n))
        if self.drift is not None:
            from repro import lifetime as lt
            maps = lt.sample_drift_maps(self.drift, c, n, chip_id)
        else:
            maps = self._drift_maps_like(1)
            maps = jax.tree.map(lambda a: a[0], maps)
        do_cal = self._birth_calibration if calibrate is None else calibrate
        if do_cal:
            if self._birth_solve is None:
                raise ValueError("calibrate=True needs calibration_frames")
            trim = self._birth_solve(chip)
        else:
            trim = jnp.zeros((c,), jnp.float32)
        st = self.state
        st.chips0 = jax.tree.map(lambda s, v: jnp.concatenate([s, v[None]]),
                                 st.chips0, chip)
        st.maps = jax.tree.map(lambda s, v: jnp.concatenate([s, v[None]]),
                               st.maps, maps)
        st.trim = jnp.concatenate([st.trim, trim[None].astype(jnp.float32)])
        st.chip_ids.append(chip_id)
        for name in ("age_frames", "frame_count", "last_recal_frame",
                     "recal_count"):
            setattr(st, name, np.concatenate(
                [getattr(st, name), np.zeros((1,), np.int64)]))
        st.recal_energy_pj = np.concatenate(
            [st.recal_energy_pj, np.zeros((1,), np.float64)])
        st.rate_ema = np.concatenate(
            [st.rate_ema, np.zeros((1, c), np.float64)])
        st.rate_baseline = np.concatenate(
            [st.rate_baseline, np.zeros((1, c), np.float64)])
        st.ema_valid = np.concatenate([st.ema_valid, np.zeros((1,), bool)])
        st.baseline_valid = np.concatenate(
            [st.baseline_valid, np.zeros((1,), bool)])
        st.rate_err = np.concatenate(
            [st.rate_err, np.zeros((1,), np.float64)])
        self._event("fleet_join", chip_id=chip_id, fleet_size=st.size,
                    calibrated=bool(do_cal))
        if self._obs is not None:
            self._obs.gauge("fleet_size").set(st.size)
        return st.size - 1

    def remove_chip(self, chip_id: int) -> None:
        """Drop a chip from the registry (a chip leaving mid-stream).

        The remaining chips' rng streams, ages and trims are untouched —
        serving them continues bit-identically (registry rows are gathered
        per step, so the shrunken leading dimension never enters the jit).
        """
        i = self.slot_of(chip_id)
        st = self.state
        cut = lambda a: jnp.concatenate([a[:i], a[i + 1:]])
        st.chips0 = jax.tree.map(cut, st.chips0)
        st.maps = jax.tree.map(cut, st.maps)
        st.trim = cut(st.trim)
        st.chip_ids.pop(i)
        for name in ("age_frames", "frame_count", "last_recal_frame",
                     "recal_count", "recal_energy_pj", "rate_ema",
                     "rate_baseline", "ema_valid", "baseline_valid",
                     "rate_err"):
            a = getattr(st, name)
            setattr(st, name, np.delete(a, i, axis=0))
        self._theta_carry.pop(int(chip_id), None)
        self._event("fleet_leave", chip_id=int(chip_id),
                    fleet_size=st.size)
        if self._obs is not None:
            self._obs.gauge("fleet_size").set(st.size)

    def _ensure_chip(self, chip_id: int) -> int:
        """Row of ``chip_id``, auto-registering unknown ids (a chip joining
        mid-stream gets its deterministic identity + birth calibration)."""
        chip_id = int(chip_id)
        if chip_id in self.state.chip_ids:
            return self.state.chip_ids.index(chip_id)
        return self.add_chip(chip_id)

    # --- geometry / telemetry ---------------------------------------------

    def _frame_spec(self) -> energy.FrameSpec:
        cfg, pcfg = self.cfg, self.cfg.p2m
        conv = -(-cfg.in_hw // pcfg.stride)
        return energy.FrameSpec(
            h_in=cfg.in_hw, w_in=cfg.in_hw, c_in=pcfg.in_channels,
            h_out=max(conv // 2, 1), w_out=max(conv // 2, 1),
            c_out=pcfg.out_channels, kernel=pcfg.kernel_size,
            stride=pcfg.stride, n_mtj=pcfg.mtj.n_redundant)

    def _span(self, name: str, **args) -> ContextManager[None]:
        return (self._obs.span(name, **args) if self._obs is not None
                else contextlib.nullcontext())

    def _event(self, name: str, **args) -> None:
        if self._obs is not None:
            self._obs.event(name, **args)

    def _host_sync(self) -> None:
        if self._obs is not None:
            self._obs.counter(HOST_SYNCS).inc()
            self._obs.mark(HOST_SYNC_MARK)

    def _record_step(self, wall_s: float, n_frames: int) -> None:
        if self._obs is not None:
            self._obs.histogram("fleet_step_wall_ms").record(wall_s * 1e3)
            self._obs.counter("serving_frames_total").inc(n_frames)
            self._obs.counter("fleet_steps_total").inc()
            self._obs.gauge("fleet_size").set(self.state.size)

    # --- the vmapped fleet step -------------------------------------------

    @staticmethod
    def _chip_forward(params, chip, trim, frames, key, *, cfg, backend,
                      plant):
        """One chip row of the fleet step (vmapped over the leading axis).

        ``plant=False`` (no variation, no drift) leaves params untouched —
        chip/trim ride along as dead operands so the step signature (and
        the jit cache) never depends on the fleet's physics profile."""
        if plant:
            params = {**params, "p2m": {**params["p2m"],
                                        "chip": chip, "cal_trim": trim}}
        logits, _, aux = vision.forward(params, frames, cfg, key=key,
                                        backend=backend)
        probs = jax.nn.softmax(logits, axis=-1)
        return {"labels": jnp.argmax(logits, -1), "probs": probs, **aux}

    @staticmethod
    def _chip_forward_fused(params, chip, trim, frames, key, theta_carry, *,
                            cfg, backend, plant):
        """The fused-streaming chip row: each chip draws at ITS OWN carried
        Hoyer threshold (theta_carry is vmapped — one (G,) operand)."""
        p2m_params = {**params["p2m"], "theta_carry": theta_carry}
        if plant:
            p2m_params.update(chip=chip, cal_trim=trim)
        params = {**params, "p2m": p2m_params}
        logits, _, aux = vision.forward(params, frames, cfg, key=key,
                                        backend=backend)
        probs = jax.nn.softmax(logits, axis=-1)
        return {"labels": jnp.argmax(logits, -1), "probs": probs, **aux}

    def _gather_operands(self, slots: np.ndarray, ages: np.ndarray):
        """Chip/trim operands for one step's rows — gathered OUTSIDE the
        jit (the registry's (F, ...) leading dim never enters the trace)
        and evolved to each row's current frame-clock age."""
        idx = jnp.asarray(slots, jnp.int32)
        take = lambda tree: jax.tree.map(lambda a: a[idx], tree)
        chips = take(self.state.chips0)
        trims = self.state.trim[idx]
        if self._evolve is not None:
            chips = self._evolve(chips, take(self.state.maps),
                                 jnp.asarray(ages, jnp.float32))
        return self._put_operands(chips), self._put_operands(trims)

    def _put_operands(self, tree):
        """Shard gathered per-chip operands over the mesh's fleet axis."""
        if self.mesh is None:
            return tree

        def one(a):
            axes = ("fleet",) + (None,) * (a.ndim - 1)
            spec = sharding.logical_to_spec(axes, a.shape, self.mesh,
                                            self.rules)
            return jax.device_put(a, NamedSharding(self.mesh, spec))

        return jax.tree.map(one, tree)

    def _on_mesh(self) -> ContextManager:
        """The engine's mesh as JAX's ambient mesh around a step dispatch:
        the Pallas frontend reads it to run its kernels once per device
        (``ops``: the TPU compiler cannot partition a Mosaic kernel)."""
        return (jax.set_mesh(self.mesh) if self.mesh is not None
                else contextlib.nullcontext())

    def _shard_frames(self, frames: jax.Array) -> jax.Array:
        if self.mesh is None:
            return frames
        spec = sharding.logical_to_spec(FLEET_FRAME_AXES, frames.shape,
                                        self.mesh, self.rules)
        return jax.device_put(frames, NamedSharding(self.mesh, spec))

    def _fused_wanted(self, g: int, n_frames: int, h: int, w: int
                      ) -> Optional[bool]:
        """Tri-state fused decision for a (g, n_frames) step — None for
        non-pallas backends (their outputs carry no streaming keys)."""
        if self.backend != "pallas":
            return None
        if self._fused_stream is not None:
            return self._fused_stream
        from repro.kernels import autotune, blocking
        pcfg = self.cfg.p2m
        n = (n_frames * blocking.conv_out_hw(h, pcfg.stride)
             * blocking.conv_out_hw(w, pcfg.stride))
        k_eff = pcfg.kernel_size ** 2 * pcfg.in_channels
        return autotune.get_fleet(g, n, k_eff, pcfg.out_channels).fused

    # --- planning ----------------------------------------------------------

    def _plan(self, requests: Sequence[Tuple[int, jax.Array]]
              ) -> List[_WorkItem]:
        """Split requests into per-chip microbatch work items, assigning
        each its rng key and frame-clock age EXACTLY as a per-chip
        ``VisionEngine.stream`` would (key order is fixed at plan time, so
        step packing can never perturb the draws)."""
        items: List[_WorkItem] = []
        st = self.state
        age_run: Dict[int, int] = {}
        for r, (cid, frames) in enumerate(requests):
            slot = self._ensure_chip(cid)
            cid = int(cid)
            b = frames.shape[0]
            mb = self.microbatch
            age = age_run.get(slot, int(st.age_frames[slot]))
            if not mb or b <= mb:
                key = jax.random.fold_in(self._key, st.frame_count[slot])
                st.frame_count[slot] += 1
                items.append(_WorkItem(r, slot, cid, frames, key, age))
                age_run[slot] = age + b
                continue
            base = jax.random.fold_in(self._key, st.frame_count[slot])
            st.frame_count[slot] += 1
            starts = list(range(0, b, mb))
            for j, i in enumerate(starts):
                sz = min(mb, b - i)
                items.append(_WorkItem(r, slot, cid, frames[i:i + sz],
                                       jax.random.fold_in(base, j), age))
                age += sz
            age_run[slot] = age
        return items

    def _group(self, items: List[_WorkItem]) -> List[List[_WorkItem]]:
        """Pack items into steps of up to ``chips_per_step`` rows.

        A step's rows must share a frame shape (one stacked operand) and
        hold DISTINCT chips: two microbatches of the same chip run in
        stream order across consecutive steps, so its fused theta carry
        (and its age) advance exactly as a single-chip stream would."""
        groups: List[List[_WorkItem]] = []
        cur: List[_WorkItem] = []
        for it in items:
            fits = (len(cur) < self.chips_per_step
                    and (not cur or (cur[0].frames.shape == it.frames.shape
                                     and all(c.slot != it.slot
                                             for c in cur))))
            if not fits and cur:
                groups.append(cur)
                cur = []
            cur.append(it)
        if cur:
            groups.append(cur)
        return groups

    # --- stepping ----------------------------------------------------------

    def _run_step(self, group: List[_WorkItem], stream: bool = True,
                  defer: bool = False
                  ) -> Tuple[List[Dict], Optional[clock.WallProbe]]:
        """Execute one packed step; returns one output dict per item plus
        the step's readiness probe (None on synchronized paths).

        ``stream=False`` (a bare ``classify``) always runs the exact path,
        emits no streaming telemetry keys and never touches theta carries —
        mirroring the tri-state ``fused=None`` of ``VisionEngine``.

        ``defer=True`` and the plain exact path dispatch WITHOUT blocking:
        the caller drains the probe at the request-batch boundary and
        patches the per-item walls (``_patch_walls``). Fused steps read
        fresh thetas on the host, so they are inherently synchronized and
        always return ``probe=None`` with honest walls."""
        g = len(group)
        slots = np.array([it.slot for it in group])
        ages = np.array([it.age for it in group], np.float64)
        frames = self._shard_frames(jnp.stack([it.frames for it in group]))
        keys = jnp.stack([it.key for it in group])
        chips, trims = self._gather_operands(slots, ages)
        b, h, w = group[0].frames.shape[:3]
        fused = self._fused_wanted(g, b, h, w) if stream else None
        carries = [self._theta_carry.get(it.chip_id) for it in group]
        run_fused = bool(fused) and all(c is not None for c in carries)
        total_frames = g * b

        with self._on_mesh():
            probe = None
            t0 = clock.now()
            if run_fused:
                theta = jnp.asarray(carries, jnp.float32)
                with self._span("step", chips=g, frames=total_frames,
                                path="fused"):
                    out = jax.block_until_ready(self._fused_step(
                        self.params, chips, trims, frames, keys, theta))
                self._host_sync()
                self.fused_step_count += 1
                if self._obs is not None:
                    self._obs.counter("serving_fused_steps_total").inc()
                fresh = np.asarray(out["theta"], np.float64)
                drifts = np.abs(fresh - np.asarray(carries)) / np.maximum(
                    np.abs(np.asarray(carries)), 1e-9)
                if float(np.max(drifts)) > self._fused_theta_tol:
                    # some chip's carried threshold went stale: re-serve the
                    # WHOLE step from the exact pipeline (same keys — the rng
                    # sequence is identical either way) and re-seed every carry
                    self._event("drift_guard_fallback",
                                chip_ids=[it.chip_id for it in group],
                                drift=float(np.max(drifts)))
                    if self._obs is not None:
                        self._obs.counter("serving_fused_fallback_total").inc()
                    out = jax.block_until_ready(self._step(
                        self.params, chips, trims, frames, keys))
                    self._host_sync()
                    self.fused_fallback_count += 1
                    for i, it in enumerate(group):
                        self._theta_carry[it.chip_id] = float(out["theta"][i])
                    ran_fused = False
                else:
                    e = self._fused_theta_ema
                    for i, it in enumerate(group):
                        self._theta_carry[it.chip_id] = (
                            e * carries[i] + (1.0 - e) * float(fresh[i]))
                    ran_fused = True
                drift_vals = [float(d) for d in drifts]
                wall = clock.now() - t0
                self._record_step(wall, total_frames)
            else:
                sync = self._sync_timing or not defer or bool(fused)
                with self._span("step", chips=g, frames=total_frames,
                                path="exact"):
                    out = self._step(self.params, chips, trims, frames, keys)
                    if sync:
                        out = jax.block_until_ready(out)
                if sync and stream:
                    self._host_sync()
                if fused:
                    # the step WANTED fused but some chip had no carry yet
                    # (its stream's first microbatch): the exact run seeds
                    # them all — mirroring VisionEngine's first-microbatch
                    # seeding. The host theta reads synchronize this path
                    # regardless of sync.
                    for i, it in enumerate(group):
                        self._theta_carry[it.chip_id] = float(out["theta"][i])
                ran_fused = False
                drift_vals = [0.0] * g
                wall = clock.now() - t0
                if sync:
                    self._record_step(wall, total_frames)
                else:
                    # async: wall below is dispatch-side; the drain patches it
                    probe = clock.WallProbe(out["labels"], t0=t0,
                                            frames=total_frames, chips=g)

        outs: List[Dict] = []
        for i, it in enumerate(group):
            o = {k: v[i] for k, v in out.items()}
            if fused is not None:
                o["stream_fused"] = 1.0 if ran_fused else 0.0
                o["stream_theta_drift"] = drift_vals[i]
                if "theta_used" not in o:
                    o["theta_used"] = o["theta"]
            # the step's wall clock is shared by its rows; attribute each
            # item its frame share so merged request telemetry stays additive
            o["wall_ms"] = wall * 1e3 * (b / total_frames)
            o["throughput_fps"] = total_frames / wall
            o["sensor_latency_us"] = self._sensor_latency_us
            o["sensor_fps"] = self._sensor_fps
            outs.append(o)
        return outs, probe

    def _commit(self, it: _WorkItem, out: Dict) -> Dict:
        """Advance the chip's host state past one served item and attach
        its lifetime telemetry (mirrors ``VisionEngine._advance_lifetime``
        minus inline recalibration — refreshes happen in sweeps)."""
        st = self.state
        b = it.frames.shape[0]
        if it.advance:
            st.age_frames[it.slot] += b
            self.frames_served += b
            if self.sweep_policy is not None:
                budget = self.sweep_policy.maintenance_energy_per_frame_pj
                if budget is not None:
                    self._energy_credit_pj += b * budget
                self._observe(it.slot, out.get("channel_rates"))
        if self.drift is not None:
            out = dict(out)
            out.update({
                "lifetime_age_frames": float(st.age_frames[it.slot]),
                "lifetime_recal_count": float(st.recal_count[it.slot]),
                "lifetime_recal_fired": 0.0,
                "lifetime_rate_err": float(st.rate_err[it.slot]),
                "lifetime_recal_energy_pj":
                    float(st.recal_energy_pj[it.slot])})
        return out

    def _observe(self, slot: int, rates) -> None:
        """Fold one item's channel rates into the chip's monitoring EMA
        (the per-chip version of ``RecalibrationScheduler.observe``)."""
        if rates is None:
            return
        st = self.state
        r = np.asarray(rates, np.float64)
        e = self.sweep_policy.policy.ema
        if st.ema_valid[slot]:
            st.rate_ema[slot] = e * st.rate_ema[slot] + (1.0 - e) * r
        else:
            st.rate_ema[slot] = r
            st.ema_valid[slot] = True
        if not st.baseline_valid[slot]:
            st.rate_baseline[slot] = st.rate_ema[slot]
            st.baseline_valid[slot] = True
        st.rate_err[slot] = float(np.mean(
            np.abs(st.rate_ema[slot] - st.rate_baseline[slot])))

    # --- public serving API -------------------------------------------------

    def serve(self, requests: Sequence[Tuple[int, jax.Array]]) -> List[Dict]:
        """Serve a batch of ``(chip_id, frames)`` requests.

        Returns one merged output per request (microbatch splitting and
        cross-chip step packing are invisible to the caller). Unknown chip
        ids auto-register. With ``sweep=`` armed (``auto=True``) a
        maintenance sweep runs after the batch.
        """
        requests = list(requests)
        if not requests:
            return []
        items = self._plan(requests)
        defer = not self._sync_timing
        steps: List[Tuple[List[_WorkItem], List[Dict],
                          Optional[clock.WallProbe]]] = []
        with self._span("serve", requests=len(requests)):
            # dispatch every packed step without blocking (async mode) ...
            for group in self._group(items):
                outs, probe = self._run_step(group, defer=defer)
                steps.append((group, outs, probe))
            # ... then drain once: the only blocking point of the batch.
            # Each probed step's honest wall overwrites its dispatch-side
            # per-item shares before commit/merge. With obs enabled the
            # drain itself becomes visible in METRICS too (it used to live
            # only in spans): the per-batch drain wall and the
            # outstanding-probe high-water mark (every probed step is
            # still in flight when the drain starts — dispatch never
            # harvests) land as a gauge/counter pair.
            outstanding = (sum(1 for _, _, p in steps if p is not None)
                           if self._obs is not None else 0)
            drain_t0 = clock.now() if self._obs is not None else 0.0
            if outstanding:
                self._host_sync()
            for group, outs, probe in steps:
                if probe is None:
                    continue
                wall = probe.wait()
                self._record_step(wall, probe.tags["frames"])
                total = probe.tags["frames"]
                for it, o in zip(group, outs):
                    share = it.frames.shape[0] / total
                    o["wall_ms"] = wall * 1e3 * share
                    o["throughput_fps"] = total / wall
            if self._obs is not None:
                drain_ms = (clock.now() - drain_t0) * 1e3
                self._obs.gauge("fleet_drain_wall_ms").set(drain_ms)
                self._obs.gauge("fleet_probe_high_water").set(outstanding)
                self._obs.counter("fleet_probes_drained_total").inc(
                    outstanding)
                self._obs.counter("fleet_drains_total").inc()
        per_req: Dict[int, List[Tuple[_WorkItem, Dict]]] = {}
        for group, outs, _ in steps:
            # commits run in item (plan) order — groups preserve it
            for it, o in zip(group, outs):
                o = self._commit(it, o)
                per_req.setdefault(it.req, []).append((it, o))
        results: List[Dict] = []
        for r in range(len(requests)):
            pairs = per_req[r]
            if len(pairs) == 1:
                o = dict(pairs[0][1])
                n = pairs[0][0].frames.shape[0]
                o["throughput_fps"] = n / (o["wall_ms"] / 1e3)
                results.append(o)
            else:
                results.append(_merge_outputs([o for _, o in pairs],
                                              [it.frames.shape[0]
                                               for it, _ in pairs]))
        if self.sweep_policy is not None and self.sweep_policy.auto:
            self.run_sweep()
        return results

    def classify(self, chip_id: int, frames: jax.Array,
                 key: Optional[jax.Array] = None) -> Dict:
        """One chip, one batch — the ``VisionEngine.classify`` counterpart.

        Always the exact (non-fused) path. An explicit ``key`` is a pinned
        replay: it advances neither the chip's rng frame counter nor its
        frame-clock age (a replay must not age the chip)."""
        slot = self._ensure_chip(chip_id)
        st = self.state
        if key is None:
            key = jax.random.fold_in(self._key, st.frame_count[slot])
            st.frame_count[slot] += 1
            advance = True
        else:
            advance = False
        it = _WorkItem(0, slot, int(chip_id), frames, key,
                       int(st.age_frames[slot]), advance=advance)
        (out,), _ = self._run_step([it], stream=False)
        return self._commit(it, out)

    def stream(self, request_batches: Iterable[Sequence[Tuple[int,
                                                              jax.Array]]]
               ) -> Iterator[List[Dict]]:
        """Serve a stream of request batches (a set of concurrent per-chip
        streams). A new stream is a new scene for EVERY chip: all carried
        thetas drop, so each chip's first microbatch runs the exact step
        and re-seeds its carry — mirroring ``VisionEngine.stream``."""
        self._theta_carry.clear()
        for batch in request_batches:
            yield self.serve(batch)

    # --- the amortized maintenance sweep ------------------------------------

    def run_sweep(self, force: bool = False) -> Dict:
        """One background recalibration sweep over the fleet.

        Eligibility per chip follows the armed ``SchedulePolicy`` (periodic
        staleness and/or monitored-rate trigger; ``force=True`` makes every
        chip eligible). The K most-stale eligible chips — staleness =
        frames since last refresh — are refreshed with ONE vmapped tester
        dispatch (padded to ``refresh_per_sweep`` rows so sweep #100 costs
        no more compilation than sweep #1), spending tester energy from the
        accrued per-frame credit when a budget is set. Key-free and
        deterministic: no rng stream moves.
        """
        report = {"eligible": 0, "refreshed": [], "energy_credit_pj":
                  float(self._energy_credit_pj)}
        if self._scheduler is None:
            return report
        st = self.state
        if st.size == 0:
            return report
        pol = self.sweep_policy.policy
        since = st.age_frames - st.last_recal_frame
        elig = np.zeros((st.size,), bool)
        if force:
            elig[:] = True
        else:
            if pol.period_frames is not None:
                elig |= since >= pol.period_frames
            if pol.rate_err_threshold is not None:
                elig |= ((st.rate_err > pol.rate_err_threshold)
                         & (since >= pol.min_interval_frames))
        cand = np.nonzero(elig)[0]
        report["eligible"] = int(cand.size)
        if cand.size == 0:
            return report
        # most-stale first; the energy budget caps how many we can afford
        cand = cand[np.argsort(-since[cand], kind="stable")]
        k = min(self.sweep_policy.refresh_per_sweep, cand.size)
        cost = self._scheduler.recal_energy_pj
        if self.sweep_policy.maintenance_energy_per_frame_pj is not None:
            k = min(k, int(self._energy_credit_pj // cost))
        if k <= 0:
            return report
        chosen = cand[:k]
        # pad the tester batch to the policy width: ONE compiled vmapped
        # solve serves every sweep regardless of how many chips it refreshes
        width = self.sweep_policy.refresh_per_sweep
        padded = np.concatenate([chosen,
                                 np.full((width - k,), chosen[0])])
        idx = jnp.asarray(padded, jnp.int32)
        chips = jax.tree.map(lambda a: a[idx], st.chips0)
        if self._evolve is not None:
            chips = self._evolve(
                chips, jax.tree.map(lambda a: a[idx], st.maps),
                jnp.asarray(st.age_frames[padded], jnp.float32))
        with self._span("sweep", refreshing=int(k)):
            trims = self._scheduler.recalibrate_fleet(chips)
            st.trim = st.trim.at[jnp.asarray(chosen,
                                             jnp.int32)].set(trims[:k])
        for s in chosen:
            st.recal_count[s] += 1
            st.last_recal_frame[s] = st.age_frames[s]
            st.recal_energy_pj[s] += cost
            # the refreshed chip's post-trim rates are new normal:
            # re-baseline its monitor
            st.ema_valid[s] = False
            st.baseline_valid[s] = False
            st.rate_err[s] = 0.0
        if self.sweep_policy.maintenance_energy_per_frame_pj is not None:
            self._energy_credit_pj -= k * cost
        self.sweep_count += 1
        report["refreshed"] = [int(st.chip_ids[s]) for s in chosen]
        report["energy_credit_pj"] = float(self._energy_credit_pj)
        self._event("fleet_sweep", eligible=report["eligible"],
                    refreshed=report["refreshed"],
                    energy_credit_pj=report["energy_credit_pj"])
        if self._obs is not None:
            self._obs.counter("fleet_sweeps_total").inc()
            self._obs.counter("fleet_chips_refreshed_total").inc(k)
        return report

    # --- warm restarts -------------------------------------------------------

    def _ckpt_tree(self) -> Dict:
        st = self.state
        return {"chips0": st.chips0, "maps": st.maps, "trim": st.trim,
                "age_frames": st.age_frames,
                "frame_count": st.frame_count,
                "last_recal_frame": st.last_recal_frame,
                "recal_count": st.recal_count,
                "recal_energy_pj": st.recal_energy_pj,
                "rate_ema": st.rate_ema,
                "rate_baseline": st.rate_baseline,
                "ema_valid": st.ema_valid,
                "baseline_valid": st.baseline_valid,
                "rate_err": st.rate_err}

    def save(self, directory: str, step: Optional[int] = None,
             keep: int = 3) -> int:
        """Persist the full fleet through ``checkpoint/manager.py``.

        Everything a warm restart needs rides along: stacked chips/maps/
        trims, ages, telemetry, per-chip rng frame-clocks and theta
        carries. Returns the step written."""
        from repro.checkpoint.manager import CheckpointManager
        m = CheckpointManager(directory, keep=keep, async_write=False)
        if step is None:
            latest = m.latest_step()
            step = 0 if latest is None else latest + 1
        extra = {
            "chip_ids": [int(c) for c in self.state.chip_ids],
            "seed": int(self.seed),
            "frames_served": int(self.frames_served),
            "sweep_count": int(self.sweep_count),
            "fused_step_count": int(self.fused_step_count),
            "fused_fallback_count": int(self.fused_fallback_count),
            "energy_credit_pj": float(self._energy_credit_pj),
            # json round-trips python floats exactly (repr-based), so the
            # restored carries reproduce the fused stream bit-for-bit
            "theta_carry": {str(cid): v
                            for cid, v in self._theta_carry.items()},
        }
        m.save(step, {"fleet": self._ckpt_tree()}, extra=extra)
        self._event("checkpoint_save", step=int(step),
                    fleet_size=self.state.size)
        return step

    def load(self, directory: str, step: Optional[int] = None) -> int:
        """Restore a saved fleet into this (freshly constructed) engine.

        The engine must be built with the same ``cfg``/``params``/``seed``
        as the saver; the restored process then resumes every chip's
        stream bit-identically (same rng clocks, ages, trims, carries —
        asserted in tests). Returns the step restored."""
        from repro.checkpoint.manager import CheckpointManager
        m = CheckpointManager(directory)
        if step is None:
            step = m.latest_step()
            if step is None:
                raise FileNotFoundError(f"no checkpoints in {directory}")
        extra = m.manifest(step)["extra"]
        if int(extra["seed"]) != int(self.seed):
            raise ValueError(f"checkpoint seed {extra['seed']} != engine "
                             f"seed {self.seed}: streams would diverge")
        # rebuild the registry rows (deterministic chip identities), then
        # overwrite every leaf with the saved state
        self.state = self._empty_state()
        self._theta_carry.clear()
        for cid in extra["chip_ids"]:
            self.add_chip(int(cid), calibrate=False)
        restored, _ = m.restore(step, {"fleet": self._ckpt_tree()})
        t = restored["fleet"]
        st = self.state
        st.chips0, st.maps, st.trim = t["chips0"], t["maps"], t["trim"]
        for name in ("age_frames", "frame_count", "last_recal_frame",
                     "recal_count", "recal_energy_pj", "rate_ema",
                     "rate_baseline", "ema_valid", "baseline_valid",
                     "rate_err"):
            setattr(st, name, np.asarray(t[name]))
        self.frames_served = int(extra["frames_served"])
        self.sweep_count = int(extra["sweep_count"])
        self.fused_step_count = int(extra.get("fused_step_count", 0))
        self.fused_fallback_count = int(extra.get("fused_fallback_count", 0))
        self._energy_credit_pj = float(extra["energy_credit_pj"])
        self._theta_carry = {int(k): float(v)
                             for k, v in extra["theta_carry"].items()}
        self._event("checkpoint_load", step=int(step),
                    fleet_size=self.state.size)
        return step
