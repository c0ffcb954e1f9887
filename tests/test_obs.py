"""Tests for repro.obs: metrics, tracing, async timing, zero-cost-off.

The load-bearing claims (ISSUE: observability must be OFF the serving
path):

* ``obs=None`` (the default) is bit-identical to the instrumented engine
  and leaves the jit cache and op census untouched.
* The default (async) stream path never calls the module-level
  ``jax.block_until_ready`` between microbatches — latency comes from
  deferred probes; ``sync_timing=True`` restores per-microbatch syncs.
* Histogram quantiles track ``numpy.quantile`` within the bucket ratio.
* Spans nest and order correctly in the exported JSONL.
* ``sensor_latency_us``/``sensor_fps`` survive a mixed-size microbatch
  merge verbatim (the ``_CONSTANT_KEYS`` regression).
"""
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.obs as obs_mod
from repro.obs import clock, export
from repro.obs.metrics import Histogram, MetricsRegistry
from repro.obs.trace import Tracer
from repro.analysis import census, tracecheck
from repro.models import vision
from repro.serving import FleetEngine
from repro.serving.vision import VisionEngine


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

class TestMetrics:
    def test_counter_gauge(self):
        reg = MetricsRegistry()
        reg.counter("frames_total").inc(8)
        reg.counter("frames_total").inc(4)
        assert reg.counter("frames_total").value == 12
        with pytest.raises(ValueError):
            reg.counter("frames_total").inc(-1)
        reg.gauge("fleet_size").set(3)
        assert reg.gauge("fleet_size").value == 3.0
        with pytest.raises(TypeError):
            reg.histogram("fleet_size")     # name already a gauge

    @pytest.mark.parametrize("dist", ["lognormal", "uniform", "bimodal"])
    def test_histogram_quantiles_track_numpy(self, dist):
        rng = np.random.default_rng(0)
        if dist == "lognormal":
            xs = rng.lognormal(mean=2.0, sigma=1.0, size=5000)
        elif dist == "uniform":
            xs = rng.uniform(0.5, 500.0, size=5000)
        else:
            # unequal modes: every tested quantile falls INSIDE a mode
            # (at 50/50 the median sits in the empty gap, where numpy's
            # linear interpolation and any binned sketch legitimately
            # disagree by more than the bucket ratio)
            xs = np.concatenate([rng.normal(5, 0.5, 2300),
                                 rng.normal(800, 40, 2700)])
            xs = np.clip(xs, 0.1, None)
        h = Histogram("t_ms")
        for x in xs:
            h.record(float(x))
        # in-range relative error is bounded by the bucket ratio
        ratio = (h.hi / h.lo) ** (1.0 / h.n_buckets)
        for q in (0.5, 0.95, 0.99):
            got = h.quantile(q)
            want = float(np.quantile(xs, q))
            assert got == pytest.approx(want, rel=2 * (ratio - 1.0))
        assert h.count == len(xs)
        assert h.sum == pytest.approx(float(xs.sum()))
        assert h.quantile(0.0) == float(xs.min())
        assert h.quantile(1.0) == float(xs.max())

    def test_histogram_out_of_range_clamps_to_observed(self):
        h = Histogram("t", lo=1.0, hi=10.0, n_buckets=8)
        for v in (0.01, 0.02, 5000.0):
            h.record(v)
        assert h.quantile(0.25) == 0.01       # underflow -> exact min
        assert h.quantile(0.99) == 5000.0     # overflow -> exact max
        assert math.isnan(Histogram("e").quantile(0.5))

    def test_exposition_shape(self):
        obs = obs_mod.Obs(tracing=False)
        obs.counter("serving_frames_total").inc(7)
        obs.histogram("wall_ms").record(3.0)
        text = obs.exposition()
        assert "# TYPE serving_frames_total counter" in text
        assert "serving_frames_total 7.0" in text
        assert '# TYPE wall_ms histogram' in text
        assert 'wall_ms_bucket{le="' in text
        assert 'wall_ms_bucket{le="+Inf"} 1' in text
        assert 'wall_ms{quantile="0.5"}' in text
        assert "wall_ms_count 1.0" in text

    def test_exposition_bucket_roundtrip(self):
        """The ``_bucket{le=...}`` series must be a faithful cumulative
        view: parsed bucket increments sum to ``_count`` and the +Inf
        bucket equals the total, including under/overflow samples."""
        reg = MetricsRegistry()
        h = reg.histogram("lat_ms", lo=1.0, hi=100.0, n_buckets=16)
        rng = np.random.default_rng(3)
        xs = np.concatenate([rng.lognormal(2.0, 1.0, 500),
                             [0.01, 0.02, 5000.0]])   # under + overflow
        for x in xs:
            h.record(float(x))
        text = export.prometheus_text(reg)
        cums, count = [], None
        for line in text.splitlines():
            if line.startswith('lat_ms_bucket{le="'):
                le = line.split('le="')[1].split('"')[0]
                cum = float(line.rsplit(" ", 1)[1])
                cums.append((math.inf if le == "+Inf" else float(le), cum))
            elif line.startswith("lat_ms_count"):
                count = float(line.rsplit(" ", 1)[1])
        assert count == len(xs)
        # cumulative: non-decreasing edges AND counts, +Inf == _count
        assert cums == sorted(cums)
        assert cums[-1][0] == math.inf and cums[-1][1] == count
        # per-bucket increments (diff of the cumulative series, first
        # bucket included) sum back to _count — the round-trip claim
        increments = [cums[0][1]] + [b - a for (_, a), (_, b)
                                     in zip(cums, cums[1:])]
        assert all(d >= 0 for d in increments)
        assert sum(increments) == count
        # and the cumulative view agrees with the histogram's own API
        assert h.cumulative_buckets() == [(e, int(c)) for e, c in cums]


# ---------------------------------------------------------------------------
# tracing
# ---------------------------------------------------------------------------

class TestTracer:
    def test_span_nesting_and_jsonl_ordering(self, tmp_path):
        tr = Tracer(device_annotations=False)
        with tr.span("stream", frames=8):
            with tr.span("microbatch", frames=4):
                tr.event("recalibration", chip_id=0)
            with tr.span("microbatch", frames=4):
                pass
        path = str(tmp_path / "t.jsonl")
        export.write_jsonl(path, tr.records)
        recs = export.read_jsonl(path)
        assert [json.loads(json.dumps(r))["name"] for r in recs] == \
            ["recalibration", "microbatch", "microbatch", "stream"]
        ev, mb1, mb2, stream = recs
        # the inner spans closed before the outer: depth records nesting
        assert stream["depth"] == 0 and mb1["depth"] == mb2["depth"] == 1
        assert ev["depth"] == 2 and ev["ph"] == "i"
        # child intervals lie inside the parent, and siblings are ordered
        for mb in (mb1, mb2):
            assert mb["ts"] >= stream["ts"]
            assert mb["ts"] + mb["dur"] <= stream["ts"] + stream["dur"] + 1e-3
        assert mb1["ts"] <= mb2["ts"]
        assert stream["args"] == {"frames": 8}


# ---------------------------------------------------------------------------
# clock probes
# ---------------------------------------------------------------------------

class TestWallProbe:
    def test_probe_measures_honest_latency(self):
        x = jnp.ones((256, 256))
        t0 = clock.now()
        y = jnp.dot(x, x)
        p = clock.WallProbe(y, t0=t0, frames=4)
        wall = p.wait()
        assert wall > 0 and p.latency == wall
        assert p.token is None          # refs released once measured
        assert p.poll() is True         # idempotent after latching

    def test_probeset_poll_and_drain(self):
        ps = clock.ProbeSet()
        done = jnp.zeros(())
        done.block_until_ready()
        ps.add(clock.WallProbe(done, frames=1))
        assert len(ps) == 1
        harvested = ps.poll()
        assert len(harvested) == 1 and len(ps) == 0
        ps.add(clock.WallProbe(jnp.ones(()), frames=2))
        drained = ps.drain()
        assert [p.tags["frames"] for p in drained] == [2]

    def test_span_bounds(self):
        a = clock.WallProbe.completed(10.0, 0.25, frames=1)
        b = clock.WallProbe.completed(10.2, 0.30, frames=1)
        assert clock.span_bounds([a, b]) == (10.0, pytest.approx(10.5))


# ---------------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------------

CFG = vision.VisionConfig(name="t", arch="vgg_tiny", num_classes=10)


@pytest.fixture(scope="module")
def params():
    return vision.init_params(jax.random.PRNGKey(0), CFG)


def _batches(sizes, seed=1):
    key = jax.random.PRNGKey(seed)
    return [jax.random.uniform(jax.random.fold_in(key, i), (b, 32, 32, 3))
            for i, b in enumerate(sizes)]


_TIMING_KEYS = ("wall_ms", "throughput_fps")


def _assert_same_outputs(a, b):
    assert set(a) == set(b)
    for k in a:
        if k in _TIMING_KEYS:
            continue
        va, vb = a[k], b[k]
        if hasattr(va, "shape"):
            np.testing.assert_array_equal(np.asarray(va), np.asarray(vb))
        else:
            assert va == vb, k


class TestEngineObs:
    def test_obs_none_bit_identical_and_no_extra_traces(self, params,
                                                        trace_recorder):
        batches = _batches([4, 4])
        plain = VisionEngine(CFG, params, backend="pallas", seed=0)
        ref = [dict(o) for o in plain.stream(batches)]
        obs = obs_mod.Obs()
        eng = VisionEngine(CFG, params, backend="pallas", seed=0, obs=obs)
        got = list(eng.stream(batches))
        for a, b in zip(ref, got):
            _assert_same_outputs(a, b)
        # instrumentation must not add a single compile: both engines hit
        # one _step trace each (same shapes, same cache discipline)
        tracecheck.assert_jit_cache(plain._step, 1, recorder=trace_recorder)
        tracecheck.assert_jit_cache(eng._step, 1, recorder=trace_recorder)

    def test_obs_census_unchanged(self, params):
        frames = _batches([4])[0]
        key = jax.random.PRNGKey(2)
        plain = VisionEngine(CFG, params, backend="pallas", seed=0)
        eng = VisionEngine(CFG, params, backend="pallas", seed=0,
                           obs=obs_mod.Obs())
        a = census.jaxpr_census(plain._step, params, frames, key)
        b = census.jaxpr_census(eng._step, params, frames, key)
        assert a == b

    def test_sync_timing_bit_identical(self, params):
        batches = _batches([4, 4])
        ref = list(VisionEngine(CFG, params, backend="pallas",
                                seed=0).stream(batches))
        got = list(VisionEngine(CFG, params, backend="pallas", seed=0,
                                obs=obs_mod.Obs(),
                                sync_timing=True).stream(batches))
        for a, b in zip(ref, got):
            _assert_same_outputs(a, b)

    def test_async_stream_never_module_syncs(self, params, monkeypatch):
        """The deferred-probe path must keep the dispatch loop free of
        ``jax.block_until_ready``; sync_timing=True restores it."""
        calls = {"n": 0}
        real = jax.block_until_ready

        def counting(x):
            calls["n"] += 1
            return real(x)

        batches = _batches([4, 4, 4])
        eng = VisionEngine(CFG, params, backend="pallas", seed=0,
                           fused_stream=False, obs=obs_mod.Obs())
        list(eng.stream(batches))       # warm the caches un-patched
        monkeypatch.setattr(jax, "block_until_ready", counting)
        outs = list(eng.stream(batches))
        assert calls["n"] == 0
        assert all(o["wall_ms"] > 0 for o in outs)

        sync = VisionEngine(CFG, params, backend="pallas", seed=0,
                            fused_stream=False, obs=obs_mod.Obs(),
                            sync_timing=True)
        calls["n"] = 0
        list(sync.stream(batches))
        assert calls["n"] >= len(batches)

    def test_async_stream_records_honest_latency(self, params):
        obs = obs_mod.Obs()
        eng = VisionEngine(CFG, params, backend="pallas", seed=0, obs=obs,
                           fused_stream=False)     # pin the async exact path
        outs = list(eng.stream(_batches([4, 4])))
        hist = obs.registry.histogram("serving_microbatch_wall_ms")
        assert hist.count == 2          # every probed microbatch landed
        assert hist.min > 0
        assert obs.counter("serving_frames_total").value == 8
        # the batch-level wall is patched from probe span bounds: positive
        # and consistent with the reported throughput
        for o in outs:
            assert o["throughput_fps"] == pytest.approx(
                4 / (o["wall_ms"] / 1e3), rel=1e-6)
        names = [r["name"] for r in obs.tracer.records]
        assert names.count("stream") == 2
        assert names.count("microbatch") == 2
        assert names.count("drain") == 2 and names.count("merge") == 2

    def test_constant_keys_survive_mixed_microbatch_merge(self, params):
        """6 frames at microbatch=4 -> microbatches of 4 and 2; the modeled
        sensor constants must come through verbatim, not frame-averaged."""
        eng = VisionEngine(CFG, params, backend="pallas", seed=0,
                           microbatch=4)
        (out,) = list(eng.stream(_batches([6])))
        assert out["labels"].shape[0] == 6
        assert float(out["sensor_latency_us"]) == eng._sensor_latency_us
        assert float(out["sensor_fps"]) == eng._sensor_fps
        assert type(out["sensor_latency_us"]) is float

    def test_recalibration_event_carries_chip_id(self):
        from repro import lifetime as lt
        from repro.variation import VariationConfig
        cfgv = vision.VisionConfig(
            name="t", arch="vgg_tiny", num_classes=10, chip_id=7,
            variation=VariationConfig(sigma_logit_offset=0.4,
                                      sigma_column=0.15))
        p = vision.init_params(jax.random.PRNGKey(0), cfgv)
        cal = _batches([4])[0]
        obs = obs_mod.Obs()
        eng = VisionEngine(cfgv, p, backend="pallas", seed=0, obs=obs,
                           drift=lt.DriftConfig(sigma_logit_offset=0.2,
                                                tau_frames=100.0),
                           schedule=lt.SchedulePolicy(period_frames=8),
                           calibration_frames=cal)
        list(eng.stream(_batches([4, 4, 4])))
        evs = obs.tracer.events("recalibration")
        assert evs and all(e["args"]["chip_id"] == 7 for e in evs)
        # the refresh itself ran under a tester-solve span
        assert obs.tracer.spans("recal_solve")
        assert obs.registry.gauge("lifetime_rate_err").value is not None


def _within(inner, outer):
    return (inner["ts"] >= outer["ts"] - 1e-3 and inner["ts"] + inner["dur"]
            <= outer["ts"] + outer["dur"] + 1e-3)


class TestStageTracing:
    def test_model_stages_are_scoped(self):
        """Every stage of the served model carries its scope into the
        lowered step's op locations, which a device trace reads."""
        cfg = vision.VisionConfig(name="t16", arch="vgg16", num_classes=10)
        p = vision.init_params(jax.random.PRNGKey(0), cfg)
        eng = VisionEngine(cfg, p, backend="pallas", seed=0)
        frames = _batches([2])[0]
        text = eng._step.lower(p, frames, jax.random.PRNGKey(1)).as_text(
            debug_info=True)
        for scope in (["p2m_frontend", "head"]
                      + [f"backbone/conv{i}/" for i in range(13)]):
            assert scope in text, scope
        assert "backbone/conv13" not in text

    def test_stream_spans_nest_per_item(self, params):
        obs = obs_mod.Obs()
        eng = VisionEngine(CFG, params, backend="pallas", seed=0, obs=obs,
                           microbatch=4)
        list(eng.stream(_batches([8, 8, 8])))
        tr = obs.tracer
        streams = tr.spans("stream")
        assert [s["args"]["item"] for s in streams] == [0, 1, 2]
        for s in streams:
            item = s["args"]["item"]
            mbs = [m for m in tr.spans("microbatch") if _within(m, s)]
            assert [(m["args"]["item"], m["args"]["part"]) for m in mbs] \
                == [(item, 0), (item, 1)]
            # one key for the item, one per microbatch
            assert len([k for k in tr.spans("key_fold")
                        if _within(k, s)]) == 3
            for m in mbs:
                (sync,) = [t for t in tr.spans("theta_sync")
                           if _within(t, m)]
                assert sync["depth"] == m["depth"] + 1
            for name in ("merge", "drain"):
                (child,) = [c for c in tr.spans(name) if _within(c, s)]
                assert child["depth"] == s["depth"] + 1

    @pytest.mark.parametrize("path", ["fused", "deferred", "fleet"])
    def test_host_syncs_match_hand_count(self, params, path):
        obs = obs_mod.Obs()
        batches = _batches([8, 8, 8])
        if path == "fleet":
            fe = FleetEngine(CFG, params, backend="pallas", seed=0, obs=obs,
                             fused_stream=False)
            fe.add_chip(0)
            fe.add_chip(1)
            for b in batches:
                fe.serve([(0, b), (1, b)])
            # the exact steps dispatch without blocking: one drain each
            want = len(batches)
        elif path == "fused":
            eng = VisionEngine(CFG, params, backend="pallas", seed=0,
                               obs=obs, microbatch=4, fused_stream=True)
            list(eng.stream(batches))
            # one guard wait per microbatch, one more per fallback re-run;
            # the drains find no probe pending
            want = 2 * len(batches) + eng.fused_fallback_count
        else:
            eng = VisionEngine(CFG, params, backend="device", seed=0,
                               obs=obs, microbatch=4)
            list(eng.stream(batches))
            # both microbatches dispatch unblocked: one drain per item
            want = len(batches)
        assert obs.counter("serving_host_syncs_total").value == want

    def test_host_syncs_of_blocking_stream_steps(self, params):
        obs = obs_mod.Obs()
        eng = VisionEngine(CFG, params, backend="device", seed=0, obs=obs,
                           microbatch=4, sync_timing=True)
        list(eng.stream(_batches([8, 8])))
        assert obs.counter("serving_host_syncs_total").value == 4
        eng.classify(_batches([4])[0])      # a single shot is no stream
        assert obs.counter("serving_host_syncs_total").value == 4


class TestCompileCounter:
    def test_counts_program_loads_once_per_obs(self):
        seen = []

        def listen(event, duration, **kwargs):
            if event == obs_mod.compiles.EVENT:
                seen.append(event)

        a, b = obs_mod.Obs(), obs_mod.Obs()

        def count(o):
            snap = o.registry.snapshot().get("jax_compiles_total")
            return 0.0 if snap is None else snap["value"]

        jax.monitoring.register_event_duration_secs_listener(listen)
        try:
            f = jax.jit(lambda x: x * 3.0 + 1.0)
            a0, b0 = count(a), count(b)
            f(np.ones((3, 11), np.float32)).block_until_ready()
            fresh = len(seen)
            assert fresh >= 1
            # every live Obs counts each load once: no stacked listener
            assert count(a) - a0 == fresh == count(b) - b0
            f(np.ones((3, 11), np.float32)).block_until_ready()
            assert len(seen) == fresh and count(a) - a0 == fresh
            c = obs_mod.Obs()
            f(np.ones((5, 11), np.float32)).block_until_ready()
            assert count(a) - a0 == len(seen) == count(b) - b0
            assert count(c) == len(seen) - fresh
        finally:
            jax.monitoring.unregister_event_duration_listener(listen)


class TestFleetObs:
    def test_fleet_lifecycle_events_and_parity(self, params):
        obs = obs_mod.Obs()
        fe = FleetEngine(CFG, params, backend="pallas", seed=0, obs=obs)
        ref = FleetEngine(CFG, params, backend="pallas", seed=0)
        for f in (fe, ref):
            f.add_chip(0)
            f.add_chip(1)
        frames = _batches([4])[0]
        got = fe.serve([(0, frames), (1, frames)])
        want = ref.serve([(0, frames), (1, frames)])
        for a, b in zip(want, got):
            _assert_same_outputs(a, b)
        fe.remove_chip(1)
        joins = obs.tracer.events("fleet_join")
        assert [e["args"]["chip_id"] for e in joins] == [0, 1]
        (leave,) = obs.tracer.events("fleet_leave")
        assert leave["args"]["chip_id"] == 1
        assert obs.registry.gauge("fleet_size").value == 1.0
        assert obs.registry.counter("serving_frames_total").value == 8
        assert obs.registry.histogram("fleet_step_wall_ms").count >= 1
        assert obs.tracer.spans("serve") and obs.tracer.spans("step")

    def test_checkpoint_events(self, params, tmp_path):
        obs = obs_mod.Obs()
        fe = FleetEngine(CFG, params, backend="pallas", seed=0, obs=obs)
        fe.add_chip(0)
        fe.save(str(tmp_path), step=3)
        fe2 = FleetEngine(CFG, params, backend="pallas", seed=0, obs=obs)
        fe2.load(str(tmp_path))
        (s,) = obs.tracer.events("checkpoint_save")
        (l,) = obs.tracer.events("checkpoint_load")
        assert s["args"]["step"] == 3 and l["args"]["step"] == 3

    def test_obs_jsonl_export_roundtrip(self, params, tmp_path):
        obs = obs_mod.Obs()
        eng = VisionEngine(CFG, params, backend="pallas", seed=0, obs=obs)
        list(eng.stream(_batches([4])))
        path = str(tmp_path / "obs.jsonl")
        n = obs.export_jsonl(path, meta=obs_mod.bench_meta("test"))
        recs = export.read_jsonl(path)
        assert len(recs) == n and n >= 4
        assert recs[0]["ph"] == "M" and recs[0]["meta"]["bench"] == "test"
        assert any(r["ph"] == "C" and r["name"] == "serving_frames_total"
                   for r in recs)

    def test_fleet_drain_metrics(self, params):
        """The serve() drain wall and outstanding-probe high-water must
        land as a gauge/counter pair when obs is enabled (the async
        off-path telemetry the serving bench reads per window)."""
        obs = obs_mod.Obs()
        # fused steps are inherently synchronized (probe=None): pin the
        # async exact path so the drain actually has probes outstanding
        fe = FleetEngine(CFG, params, backend="pallas", seed=0, obs=obs,
                         fused_stream=False)
        fe.add_chip(0)
        fe.add_chip(1)
        frames = _batches([4])[0]
        fe.serve([(0, frames), (1, frames)])
        fe.serve([(0, frames), (1, frames)])
        reg = obs.registry
        assert reg.gauge("fleet_drain_wall_ms").value >= 0.0
        # two chips' probes outstanding at each drain, latched as the
        # high-water gauge and burned into the drained-total counter
        assert reg.gauge("fleet_probe_high_water").value >= 1.0
        assert reg.counter("fleet_probes_drained_total").value >= 2.0
        assert reg.counter("fleet_drains_total").value == 2.0


# ---------------------------------------------------------------------------
# CLI: the compare subcommand
# ---------------------------------------------------------------------------

class TestCompareCLI:
    def _export(self, tmp_path, name, frames, wall):
        obs = obs_mod.Obs(tracing=False)
        obs.counter("serving_frames_total").inc(frames)
        obs.gauge("fleet_size").set(2)
        for w in wall:
            obs.histogram("wall_ms").record(w)
        path = str(tmp_path / name)
        obs.export_jsonl(path)
        return path

    def test_compare_diffs_two_runs(self, tmp_path, capsys):
        from repro.obs.__main__ import main as obs_main
        a = self._export(tmp_path, "a.jsonl", frames=8, wall=[1.0, 2.0])
        b = self._export(tmp_path, "b.jsonl", frames=12, wall=[1.0, 2.0,
                                                               40.0])
        assert obs_main(["compare", a, b]) == 0
        out = capsys.readouterr().out
        assert "3 metric(s) in A, 3 in B" in out
        # counter delta with relative change, histogram count + p99 drift
        assert "serving_frames_total" in out and "+4" in out
        assert "hist  wall_ms" in out and "count +1" in out
        assert "fleet_size" in out

    def test_compare_reports_one_sided_metrics(self, tmp_path, capsys):
        from repro.obs.__main__ import main as obs_main
        a = self._export(tmp_path, "a.jsonl", frames=8, wall=[1.0])
        obs = obs_mod.Obs(tracing=False)
        obs.counter("recal_total").inc(1)
        b = str(tmp_path / "b.jsonl")
        obs.export_jsonl(b)
        assert obs_main(["compare", a, b]) == 0
        out = capsys.readouterr().out
        assert "recal_total" in out and "only in B" in out
        assert "only in A" in out

    def test_compare_fails_without_metrics(self, tmp_path, capsys):
        from repro.obs.__main__ import main as obs_main
        empty = str(tmp_path / "e.jsonl")
        export.write_jsonl(empty, [{"ph": "i", "name": "x", "ts": 0.0}])
        assert obs_main(["compare", empty, empty]) == 1
        assert "FAIL" in capsys.readouterr().err
