"""VisionEngine serving tests: data-parallel sharding equivalence on the
host mesh, microbatched streaming, and the pinned-key replay fix."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs as obs_mod
from repro.launch.mesh import make_host_mesh
from repro.models import vision
from repro.serving import VisionEngine
from repro.serving import vision as serving_vision


def _engine_fixture(backend="pallas", **kw):
    cfg = vision.VisionConfig(name="t", arch="vgg_tiny", num_classes=10)
    params = vision.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params, VisionEngine(cfg, params, backend=backend, **kw)


def _frames(b=4, seed=1):
    return jax.random.uniform(jax.random.PRNGKey(seed), (b, 32, 32, 3))


class TestShardedEquivalence:
    @pytest.mark.parametrize("backend", ["pallas", "device"])
    def test_sharded_matches_single_device(self, backend):
        """Acceptance: a data-parallel engine on the host mesh produces the
        SAME labels/probs as an unsharded one for the same key — sharding
        is a layout decision, not a numerics decision."""
        mesh = make_host_mesh()
        cfg, params, single = _engine_fixture(backend=backend)
        _, _, sharded = _engine_fixture(backend=backend, mesh=mesh)
        frames = _frames(b=2 * len(jax.devices()))
        key = jax.random.PRNGKey(5)
        out_s = single.classify(frames, key=key)
        out_m = sharded.classify(frames, key=key)
        np.testing.assert_array_equal(np.asarray(out_s["labels"]),
                                      np.asarray(out_m["labels"]))
        np.testing.assert_allclose(np.asarray(out_s["probs"]),
                                   np.asarray(out_m["probs"]), atol=1e-6)

    def test_frames_actually_sharded(self):
        """conftest splits the host CPU into >= 2 XLA devices so this suite
        tests real sharding; skip (don't fail) if the caller's XLA_FLAGS
        forces a single device."""
        if len(jax.devices()) < 2:
            pytest.skip("single-device host: caller forced XLA_FLAGS")
        mesh = make_host_mesh()
        _, _, eng = _engine_fixture(mesh=mesh)
        frames = _frames(b=2 * len(jax.devices()))
        sharded = eng._shard_frames(frames)
        # the batch axis is laid out over the mesh's data axis
        assert len(sharded.sharding.device_set) == len(jax.devices())


class TestKeyFolding:
    def test_pinned_key_does_not_advance_frame_counter(self):
        """Regression: replaying a frame with an explicit key used to bump
        _frame_count, perturbing every subsequent auto-keyed draw."""
        frames = _frames()
        _, _, a = _engine_fixture()
        _, _, b = _engine_fixture()
        r1 = a.classify(frames)                                # auto key 0
        a.classify(frames, key=jax.random.PRNGKey(99))         # pinned replay
        r2 = a.classify(frames)                                # auto key 1
        b.classify(frames)                                     # auto key 0
        r2_ref = b.classify(frames)                            # auto key 1
        np.testing.assert_array_equal(np.asarray(r2["probs"]),
                                      np.asarray(r2_ref["probs"]))
        assert a._frame_count == 2 and b._frame_count == 2
        del r1

    def test_auto_keys_differ_per_frame(self):
        frames = _frames()
        _, _, eng = _engine_fixture()
        p1 = eng.classify(frames)["probs"]
        p2 = eng.classify(frames)["probs"]
        assert not np.array_equal(np.asarray(p1), np.asarray(p2))


class TestMicrobatchedStream:
    def test_stream_merges_microbatches(self):
        _, _, eng = _engine_fixture(microbatch=2)
        frames = _frames(b=6)
        (out,) = list(eng.stream([frames]))
        assert out["labels"].shape == (6,)
        assert out["probs"].shape == (6, 10)
        # scalar monitoring stats stay scalars after the merge
        assert jnp.ndim(out["p2m_sparsity"]) == 0
        assert float(out["v_conv_min"]) <= float(out["v_conv_max"])

    def test_stream_microbatch_key_folding_is_deterministic(self):
        """Two engines with the same seed stream identically; the draws are
        folded per microbatch so shards see distinct randomness."""
        _, _, a = _engine_fixture(microbatch=2)
        _, _, b = _engine_fixture(microbatch=2)
        frames = _frames(b=4)
        (oa,) = list(a.stream([frames]))
        (ob,) = list(b.stream([frames]))
        np.testing.assert_array_equal(np.asarray(oa["probs"]),
                                      np.asarray(ob["probs"]))

    def test_stream_without_microbatch_unchanged(self):
        _, _, eng = _engine_fixture()
        outs = list(eng.stream([_frames(b=2), _frames(b=2, seed=9)]))
        assert len(outs) == 2
        assert all(o["labels"].shape == (2,) for o in outs)


class TestStreamEdgeCases:
    """The stream() corners the lifetime state machine leans on."""

    def test_non_divisible_microbatch_remainder(self):
        """b=5 over mb=2 -> chunks (2, 2, 1): per-example arrays concatenate
        back to 5 and the tail chunk is weighted 1/5 (not 1/3) in the
        scalar merge."""
        _, _, eng = _engine_fixture(backend="device", microbatch=2)
        frames = _frames(b=5)
        (out,) = list(eng.stream([frames]))
        assert out["labels"].shape == (5,)
        assert out["probs"].shape == (5, 10)
        assert jnp.ndim(out["p2m_sparsity"]) == 0
        # remainder weighting: sparsity is the frame-weighted mean of the
        # chunks, which equals the mean over per-chunk recomputation only
        # when the weights are frame counts
        assert 0.0 <= float(out["p2m_sparsity"]) <= 1.0

    def test_empty_batch_iterable_yields_nothing(self):
        _, _, eng = _engine_fixture(backend="device", microbatch=2)
        assert list(eng.stream([])) == []
        assert list(eng.stream(iter([]))) == []
        assert eng._frame_count == 0          # nothing consumed a key

    def test_channel_rates_merge_is_weighted_mean_not_concat(self):
        """channel_rates is a per-CHANNEL vector: merging microbatches must
        reduce it (frame-weighted), never concatenate it."""
        _, _, eng = _engine_fixture(backend="device", microbatch=2)
        frames = _frames(b=6)
        (out,) = list(eng.stream([frames]))
        assert out["channel_rates"].shape == (32,)   # C, not 3 chunks x C
        assert 0.0 <= float(jnp.min(out["channel_rates"]))
        assert float(jnp.max(out["channel_rates"])) <= 1.0


def eager_merge(outs, sizes):
    """The per-key eager merge that the compiled one replaced, kept
    verbatim as the reference for its rules."""
    sv = serving_vision
    w = jnp.asarray(sizes, jnp.float32)
    w = w / jnp.sum(w)
    merged = {}
    for k in outs[0]:
        vals = [o[k] for o in outs]
        if k in sv._CHANNEL_KEYS:
            merged[k] = jnp.sum(jnp.stack(vals) * w[:, None], axis=0)
        elif k in sv._CUMULATIVE_KEYS:
            merged[k] = vals[-1]
        elif k in sv._EVENT_KEYS:
            merged[k] = max(float(v) for v in vals)
        elif k in sv._SUM_KEYS:
            merged[k] = sum(float(v) for v in vals)
        elif k in sv._CONSTANT_KEYS:
            merged[k] = vals[0]
        elif getattr(vals[0], "ndim", 0) >= 1:
            merged[k] = jnp.concatenate(vals, axis=0)
        elif k.endswith("_min"):
            merged[k] = jnp.min(jnp.stack(vals))
        elif k.endswith("_max"):
            merged[k] = jnp.max(jnp.stack(vals))
        else:
            merged[k] = jnp.sum(jnp.stack(vals) * w)
    if "wall_ms" in merged:
        merged["throughput_fps"] = sum(sizes) / (merged["wall_ms"] / 1e3)
    return merged


def assert_merge_matches(got, ref, sizes):
    """``got`` holds every key of ``ref`` at its shape and dtype. Keys
    whose rule picks, joins or adds values are bit-identical; so is every
    key of a two-microbatch merge. A frame-weighted mean over three or
    more microbatches may differ in the last f32 bits (``rtol=1e-6``):
    one fused multiply-reduce rounds in another order than the eager
    multiply, then sum."""
    sv = serving_vision
    exact = (sv._CUMULATIVE_KEYS + sv._EVENT_KEYS + sv._SUM_KEYS
             + sv._CONSTANT_KEYS + ("throughput_fps",))
    assert set(got) == set(ref)
    for k in ref:
        g, r = np.asarray(got[k]), np.asarray(ref[k])
        assert (g.shape, g.dtype) == (r.shape, r.dtype), k
        mean = k in sv._CHANNEL_KEYS or (
            r.ndim == 0 and k not in exact
            and not k.endswith(("_min", "_max")))
        if len(sizes) == 2 or not mean:
            np.testing.assert_array_equal(g, r, err_msg=k)
        else:
            np.testing.assert_allclose(g, r, rtol=1e-6, err_msg=k)


def _microbatch_outputs(sizes, extras, seed=0):
    """Outputs shaped as a stream's microbatches give them: device arrays
    for the step's own keys, Python floats for the host telemetry and the
    lifetime keys."""
    rng = np.random.default_rng(seed)

    def dev(x):
        return jnp.asarray(x, jnp.float32)

    outs = []
    for j, n in enumerate(sizes):
        o = {"labels": jnp.asarray(rng.integers(0, 10, n), jnp.int32),
             "probs": dev(rng.random((n, 10))),
             "channel_rates": dev(rng.random(32)),
             "p2m_sparsity": dev(rng.random()),
             "read_energy_pj": dev(1e3 * rng.random()),
             "theta": dev(rng.random()),
             "v_conv_min": dev(-rng.random()),
             "v_conv_max": dev(rng.random()),
             "wall_ms": 1.0 + 5.0 * rng.random(),
             "throughput_fps": 1e3 * rng.random(),
             "sensor_latency_us": 61.3,
             "sensor_fps": 1e6 / 61.3}
        if "stream" in extras:
            o["theta_used"] = dev(rng.random())
            o["stream_fused"] = 0.0 if j == 0 else 1.0
            o["stream_theta_drift"] = 0.0 if j == 0 else 0.01 * rng.random()
        if "lifetime" in extras:
            o["lifetime_age_frames"] = float(sum(sizes[:j + 1]))
            o["lifetime_recal_count"] = float(j // 2)
            o["lifetime_recal_fired"] = 1.0 if j == 1 else 0.0
            o["lifetime_rate_err"] = float(rng.random())
            o["lifetime_recal_energy_pj"] = 12.5 * (j // 2)
        outs.append(o)
    return outs


class TestMergeProgram:
    """A stream item's microbatch outputs are merged by one compiled
    program, under the same rules as the eager per-key merge."""

    @pytest.mark.parametrize("extras", [("stream",), ("lifetime",),
                                        ("stream", "lifetime")])
    @pytest.mark.parametrize("sizes", [(2, 2), (2, 2, 1), (2, 2, 2, 2, 1)])
    def test_matches_eager_merge(self, sizes, extras):
        outs = _microbatch_outputs(sizes, extras, seed=len(sizes))
        assert_merge_matches(serving_vision._merge_outputs(outs, list(sizes)),
                             eager_merge(outs, list(sizes)), sizes)

    def test_one_program_for_same_shape_items(self):
        obs = obs_mod.Obs()
        _, _, eng = _engine_fixture(microbatch=2, obs=obs)

        def compiles():
            snap = obs.registry.snapshot().get("jax_compiles_total")
            return 0.0 if snap is None else snap["value"]

        serving_vision._merge_on_device.clear_cache()
        items = eng.stream([_frames(b=4, seed=s) for s in (1, 2, 3)])
        next(items)
        before = compiles()
        assert len(list(items)) == 2
        assert serving_vision._merge_on_device._cache_size() == 1
        assert compiles() == before


class TestServingTelemetry:
    """Satellite: wall-clock/throughput counters + modeled sensor latency
    in every output, independent of the drift feature."""

    def test_classify_reports_throughput_and_sensor_budget(self):
        _, _, eng = _engine_fixture(backend="device")
        out = eng.classify(_frames(b=4))
        assert out["wall_ms"] > 0
        assert out["throughput_fps"] > 0
        # modeled sensor-side budget (core/energy.frame_latency_us) is a
        # constant of the engine's frame geometry
        assert out["sensor_latency_us"] > 0
        assert out["sensor_fps"] == pytest.approx(
            1e6 / out["sensor_latency_us"], rel=1e-6)

    def test_stream_merges_telemetry_to_scalars(self):
        _, _, eng = _engine_fixture(backend="device", microbatch=2)
        (out,) = list(eng.stream([_frames(b=6)]))
        for k in ("wall_ms", "throughput_fps", "sensor_latency_us",
                  "sensor_fps"):
            assert jnp.ndim(out[k]) == 0, k
            assert float(out[k]) > 0, k
