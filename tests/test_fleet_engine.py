"""FleetEngine tests (serving/fleet.py): 1-chip parity with VisionEngine,
jit-cache discipline across chip mixes, ragged fleets (tails, join/leave,
pinned replay), the amortized maintenance sweep, and warm restarts."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.analysis import tracecheck
from repro.launch.mesh import make_host_mesh
from repro.lifetime import DriftConfig, SchedulePolicy
from repro.models import vision
from repro.serving import FleetEngine, FleetSweepPolicy, VisionEngine
from repro.serving import fleet as fleet_mod
from repro.variation.calibrate import calibrate
from repro.variation.chip import VariationConfig
from test_serving_sharded import assert_merge_matches, eager_merge

CFG = vision.VisionConfig(arch="vgg_tiny")
VPROFILE = VariationConfig(sigma_logit_offset=0.4, sigma_pixel_offset=0.25,
                           sigma_pixel_gain=0.05)
DPROFILE = DriftConfig(sigma_pixel_offset=0.2, sigma_logit_offset=0.1,
                       tau_frames=50.0)


@pytest.fixture(scope="module")
def params():
    return vision.init_params(jax.random.PRNGKey(0), CFG)


@pytest.fixture(scope="module")
def cal_frames():
    return jax.random.uniform(jax.random.PRNGKey(42), (8, 32, 32, 3))


def _frames(seed: int, b: int = 4) -> jax.Array:
    return jax.random.uniform(jax.random.PRNGKey(seed), (b, 32, 32, 3))


def _same(a, b) -> bool:
    return np.array_equal(np.asarray(a), np.asarray(b))


class TestSingleChipParity:
    """A 1-chip fleet IS a VisionEngine: bit-identical outputs, same keys."""

    @pytest.mark.parametrize("backend", ["ideal", "device", "analog",
                                         "pallas"])
    def test_classify_matches_vision_engine(self, params, backend):
        ve = VisionEngine(CFG, params, backend=backend, seed=0)
        fe = FleetEngine(CFG, params, backend=backend, seed=0)
        f = _frames(1)
        a, b = ve.classify(f), fe.classify(7, f)
        assert _same(a["labels"], b["labels"])
        assert _same(a["probs"], b["probs"])
        assert set(a) == set(b)

    def test_microbatched_fused_stream_matches(self, params):
        batches = [_frames(i + 10, 5) for i in range(3)]
        ve = VisionEngine(CFG, params, backend="pallas", seed=0,
                          microbatch=2)
        fe = FleetEngine(CFG, params, backend="pallas", seed=0,
                         microbatch=2)
        for ov, (of,) in zip(ve.stream(batches),
                             fe.stream([[(3, b)] for b in batches])):
            assert _same(ov["labels"], of["labels"])
            assert _same(ov["probs"], of["probs"])
            assert _same(ov["theta_used"], of["theta_used"])
            assert float(ov["stream_fused"]) == float(of["stream_fused"])
            assert set(ov) == set(of)
        # both engines carried the SAME theta EMA through the stream
        assert ve._theta_carry == fe._theta_carry[3]

    def test_variation_drift_stream_matches(self, params, cal_frames):
        """The full physics stack: a sampled chip, birth calibration, and
        per-microbatch aging — the fleet row must reproduce the single-chip
        engine draw for draw (same planted operands, same rng, same ages).
        """
        cfgv = vision.VisionConfig(arch="vgg_tiny", variation=VPROFILE,
                                   chip_id=5)
        art = calibrate(params["p2m"], cfgv.p2m, VPROFILE, cal_frames,
                        chip_id=5)
        ve = VisionEngine(cfgv, params, backend="pallas", seed=0,
                          microbatch=2, calibration=art, drift=DPROFILE)
        fe = FleetEngine(cfgv, params, backend="pallas", seed=0,
                         microbatch=2, drift=DPROFILE,
                         calibration_frames=cal_frames)
        # birth calibration solves the SAME trim the tester artifact holds
        fe.add_chip(5)
        assert _same(art.trim, fe.state.trim[0])
        batches = [_frames(i + 10, 5) for i in range(3)]
        for ov, (of,) in zip(ve.stream(batches),
                             fe.stream([[(5, b)] for b in batches])):
            assert _same(ov["labels"], of["labels"])
            assert _same(ov["probs"], of["probs"])
            assert (float(ov["lifetime_age_frames"])
                    == float(of["lifetime_age_frames"]))
            assert set(ov) == set(of)

    def test_no_variation_no_drift_plants_nothing(self, params):
        """With neither axis armed the step must not plant chip operands:
        even the analog backend (whose nominal error rates are nonzero —
        an identity chip is NOT a bit-exact no-op there) stays byte-exact
        with a plain engine."""
        fe = FleetEngine(CFG, params, backend="analog", seed=0)
        assert not fe._plant

    def test_classify_does_not_touch_stream_carry(self, params):
        fe = FleetEngine(CFG, params, backend="pallas", seed=0)
        fe.classify(0, _frames(1))
        assert fe._theta_carry == {}


class TestJitCacheDiscipline:
    """One compiled step serves every chip mix at a fixed (G, mb) shape."""

    def test_chip_permutations_and_joins_share_one_trace(self, params,
                                                         trace_recorder):
        fe = FleetEngine(CFG, params, backend="pallas", seed=0,
                         chips_per_step=3)
        mixes = [(0, 1, 2), (2, 0, 1), (5, 3, 0), (7, 8, 9)]
        for s, mix in enumerate(mixes):
            fe.serve([(c, _frames(10 * s + i)) for i, c in enumerate(mix)])
        # first serve compiles the exact step (seeding carries); steady
        # state runs the fused step — ONE entry each, regardless of which
        # chips (or how many registry rows) the steps gathered
        tracecheck.assert_jit_cache(fe._step, 1, recorder=trace_recorder,
                                    what="fe._step")
        tracecheck.assert_jit_cache(fe._fused_step, 1, le=True,
                                    recorder=trace_recorder,
                                    what="fe._fused_step")
        assert fe.state.size == 8

    def test_sweeps_do_not_recompile_the_serving_step(self, params,
                                                      cal_frames,
                                                      trace_recorder):
        cfgv = vision.VisionConfig(arch="vgg_tiny", variation=VPROFILE)
        sweep = FleetSweepPolicy(policy=SchedulePolicy(period_frames=8),
                                 refresh_per_sweep=2)
        fe = FleetEngine(cfgv, params, backend="pallas", seed=0,
                         chips_per_step=2, drift=DPROFILE, sweep=sweep,
                         calibration_frames=cal_frames)
        for s in range(4):
            fe.serve([(0, _frames(20 + s)), (1, _frames(30 + s))])
        assert fe.state.recal_count.sum() > 0          # sweeps actually ran
        tracecheck.assert_jit_cache(fe._step, 1, recorder=trace_recorder,
                                    what="fe._step")
        tracecheck.assert_jit_cache(fe._fused_step, 1, le=True,
                                    recorder=trace_recorder,
                                    what="fe._fused_step")

    def test_fleet_growth_never_enters_the_trace(self, params,
                                                 trace_recorder):
        """Serving the same (G, mb) shape out of a 2-chip and a 40-chip
        registry hits the same executable (gathers happen outside jit)."""
        fe = FleetEngine(CFG, params, backend="pallas", seed=0,
                        chips_per_step=2, fused_stream=False)
        fe.serve([(0, _frames(1)), (1, _frames(2))])
        for c in range(2, 40):
            fe.add_chip(c)
        fe.serve([(30, _frames(3)), (17, _frames(4))])
        tracecheck.assert_jit_cache(fe._step, 1, recorder=trace_recorder,
                                    what="fe._step")


class TestRaggedFleets:
    def test_mixed_chip_tail_microbatches(self, params):
        """Unequal request lengths: the shared full-size steps pack chips
        together, each tail runs at its own shape — outputs must equal the
        chips' solo streams (packing is invisible to the rng)."""
        fe = FleetEngine(CFG, params, backend="pallas", seed=0,
                         microbatch=4, chips_per_step=2, fused_stream=False)
        reqs = [(0, _frames(1, 10)), (1, _frames(2, 7))]
        out_a, out_b = fe.serve(reqs)
        assert out_a["labels"].shape == (10,)
        assert out_b["labels"].shape == (7,)
        solo0 = FleetEngine(CFG, params, backend="pallas", seed=0,
                            microbatch=4, fused_stream=False)
        ref0 = solo0.serve([(0, _frames(1, 10))])[0]
        assert _same(out_a["labels"], ref0["labels"])
        assert _same(out_a["probs"], ref0["probs"])

    def test_chip_joins_mid_stream(self, params):
        """An unknown chip id in a request auto-registers (deterministic
        identity) — and does not perturb the incumbents' streams."""
        fe = FleetEngine(CFG, params, backend="pallas", seed=0,
                         fused_stream=False)
        ref = FleetEngine(CFG, params, backend="pallas", seed=0,
                          fused_stream=False)
        fe.serve([(0, _frames(1))])
        ref.serve([(0, _frames(1))])
        outs = fe.serve([(0, _frames(2)), (9, _frames(3))])   # 9 joins here
        (r0,) = ref.serve([(0, _frames(2))])
        assert fe.state.chip_ids == [0, 9]
        assert _same(outs[0]["labels"], r0["labels"])
        assert _same(outs[0]["probs"], r0["probs"])

    def test_chip_leaves_mid_stream(self, params):
        """Removing a chip must leave the survivors' streams untouched."""
        fe = FleetEngine(CFG, params, backend="pallas", seed=0,
                         fused_stream=False)
        ref = FleetEngine(CFG, params, backend="pallas", seed=0,
                          fused_stream=False)
        fe.serve([(0, _frames(1)), (1, _frames(2))])
        ref.serve([(0, _frames(1)), (1, _frames(2))])
        fe.remove_chip(1)
        (a,) = fe.serve([(0, _frames(3))])
        (b,) = ref.serve([(0, _frames(3))])
        assert fe.state.chip_ids == [0]
        assert _same(a["labels"], b["labels"])
        assert _same(a["probs"], b["probs"])
        with pytest.raises(KeyError):
            fe.slot_of(1)

    def test_remove_unknown_chip_raises(self, params):
        fe = FleetEngine(CFG, params, backend="pallas", seed=0)
        with pytest.raises(KeyError):
            fe.remove_chip(3)


class TestRequestMerge:
    def test_split_request_merges_as_eager_reference(self, params,
                                                     monkeypatch):
        """A request split over several work items is merged by the
        compiled merge under the eager merge's rules: streaming, lifetime
        and constant keys alike."""
        calls = []
        merge = fleet_mod._merge_outputs

        def spy(outs, sizes):
            merged = merge(outs, sizes)
            calls.append((outs, sizes, merged))
            return merged

        monkeypatch.setattr(fleet_mod, "_merge_outputs", spy)
        fe = FleetEngine(CFG, params, backend="pallas", seed=0,
                         microbatch=2, chips_per_step=2, drift=DPROFILE)
        for s in range(2):
            fe.serve([(0, _frames(s + 1, 5)), (1, _frames(s + 5, 4))])
        assert [c[1] for c in calls] == [[2, 2, 1], [2, 2]] * 2
        for outs, sizes, merged in calls:
            assert {"stream_fused", "lifetime_age_frames",
                    "sensor_fps"} <= set(merged)
            assert_merge_matches(merged, eager_merge(outs, sizes), sizes)


class TestMaintenanceSweep:
    @pytest.fixture()
    def aging_fleet(self, params, cal_frames):
        cfgv = vision.VisionConfig(arch="vgg_tiny", variation=VPROFILE)

        def make(sweep, **kw):
            return FleetEngine(cfgv, params, backend="pallas", seed=0,
                               chips_per_step=4, drift=DPROFILE,
                               sweep=sweep, calibration_frames=cal_frames,
                               **kw)

        return make

    def test_staleness_priority(self, aging_fleet):
        """With more eligible chips than the per-sweep budget, the stalest
        chips (most frames since refresh) are refreshed first."""
        sweep = FleetSweepPolicy(policy=SchedulePolicy(period_frames=4),
                                 refresh_per_sweep=1, auto=False)
        fe = aging_fleet(sweep)
        fe.serve([(0, _frames(1, 8))])                 # chip 0 ages 8
        fe.serve([(1, _frames(2, 4))])                 # chip 1 ages 4
        report = fe.run_sweep()
        assert report["eligible"] == 2
        assert report["refreshed"] == [0]              # stalest first
        assert fe.state.recal_count[fe.slot_of(0)] == 1
        assert fe.state.recal_count[fe.slot_of(1)] == 0
        # chip 0 is now fresh: the next sweep refreshes chip 1
        assert fe.run_sweep()["refreshed"] == [1]

    def test_refresh_updates_trim_and_audit_trail(self, aging_fleet):
        sweep = FleetSweepPolicy(policy=SchedulePolicy(period_frames=4),
                                 refresh_per_sweep=4, auto=False)
        fe = aging_fleet(sweep)
        fe.serve([(0, _frames(1, 8)), (1, _frames(2, 8))])
        trim_before = np.asarray(fe.state.trim)
        report = fe.run_sweep()
        assert sorted(report["refreshed"]) == [0, 1]
        assert not np.array_equal(np.asarray(fe.state.trim), trim_before)
        assert (fe.state.recal_count == 1).all()
        assert (fe.state.last_recal_frame == fe.state.age_frames).all()
        assert (fe.state.recal_energy_pj > 0).all()

    def test_energy_budget_gates_refreshes(self, aging_fleet):
        """With a maintenance energy budget, refreshes wait until served
        frames have accrued one refresh's worth of tester credit."""
        # size the per-frame credit off the tester cost (~1e9 pJ at the
        # paper geometry) so 16 served frames afford exactly one refresh
        cost = aging_fleet(
            FleetSweepPolicy(policy=SchedulePolicy(period_frames=4),
                             auto=False))._scheduler.recal_energy_pj
        sweep = FleetSweepPolicy(policy=SchedulePolicy(period_frames=4),
                                 refresh_per_sweep=4, auto=False,
                                 maintenance_energy_per_frame_pj=cost / 16)
        fe = aging_fleet(sweep)
        fe.serve([(0, _frames(1, 8))])
        assert fe._energy_credit_pj == pytest.approx(cost / 2)
        report = fe.run_sweep()
        assert report["eligible"] == 1 and report["refreshed"] == []
        # serve enough frames to afford one refresh, then it fires
        fe.serve([(0, _frames(2, 8))])
        report = fe.run_sweep()
        assert report["refreshed"] == [0]
        assert fe._energy_credit_pj >= 0.0

    def test_sweep_is_rng_free(self, aging_fleet):
        """A sweep must not move any chip's rng stream: the draws after a
        forced refresh equal those of a fleet that never swept (trims
        changed, keys did not — only the *physics* of later frames moves).
        """
        sweep = FleetSweepPolicy(policy=SchedulePolicy(period_frames=10 ** 9),
                                 refresh_per_sweep=4, auto=False)
        fe = aging_fleet(sweep)
        ref = aging_fleet(sweep)
        fe.serve([(0, _frames(1))])
        ref.serve([(0, _frames(1))])
        fe.run_sweep(force=True)
        assert fe.state.frame_count[0] == ref.state.frame_count[0]
        # same rng clock -> the next keys fold identically
        assert fe.state.age_frames[0] == ref.state.age_frames[0]


class TestWarmRestart:
    def test_save_restore_resumes_bit_identically(self, params, cal_frames,
                                                  tmp_path):
        cfgv = vision.VisionConfig(arch="vgg_tiny", variation=VPROFILE)
        sweep = FleetSweepPolicy(policy=SchedulePolicy(period_frames=8),
                                 refresh_per_sweep=2)

        def make():
            return FleetEngine(cfgv, params, backend="pallas", seed=0,
                               microbatch=4, chips_per_step=3,
                               drift=DPROFILE, sweep=sweep,
                               calibration_frames=cal_frames)

        fe = make()
        fe.serve([(0, _frames(1)), (1, _frames(2)), (2, _frames(3))])
        fe.serve([(2, _frames(4)), (0, _frames(5))])
        step = fe.save(str(tmp_path))
        cont = [[(0, _frames(20)), (2, _frames(21)), (1, _frames(22))],
                [(1, _frames(23)), (0, _frames(24))]]
        ref = [fe.serve(b) for b in cont]

        fe2 = make()
        assert fe2.load(str(tmp_path)) == step
        assert fe2.state.chip_ids == [0, 1, 2]
        got = [fe2.serve(b) for b in cont]
        for rb, gb in zip(ref, got):
            for r, g in zip(rb, gb):
                assert _same(r["labels"], g["labels"])
                assert _same(r["probs"], g["probs"])
                assert (float(r["lifetime_age_frames"])
                        == float(g["lifetime_age_frames"]))
                assert (float(r["lifetime_recal_count"])
                        == float(g["lifetime_recal_count"]))

    def test_restore_checks_seed(self, params, tmp_path):
        fe = FleetEngine(CFG, params, backend="pallas", seed=0)
        fe.serve([(0, _frames(1))])
        fe.save(str(tmp_path))
        other = FleetEngine(CFG, params, backend="pallas", seed=1)
        with pytest.raises(ValueError, match="seed"):
            other.load(str(tmp_path))

    def test_pinned_key_replay_on_restored_fleet_ages_nothing(
            self, params, cal_frames, tmp_path):
        cfgv = vision.VisionConfig(arch="vgg_tiny", variation=VPROFILE)
        fe = FleetEngine(cfgv, params, backend="pallas", seed=0,
                         drift=DPROFILE, calibration_frames=cal_frames)
        fe.serve([(0, _frames(1)), (1, _frames(2))])
        fe.save(str(tmp_path))
        fe2 = FleetEngine(cfgv, params, backend="pallas", seed=0,
                          drift=DPROFILE, calibration_frames=cal_frames)
        fe2.load(str(tmp_path))
        age0 = fe2.state.age_frames.copy()
        fc0 = fe2.state.frame_count.copy()
        key = jax.random.PRNGKey(99)
        a = fe2.classify(0, _frames(30), key=key)
        b = fe2.classify(0, _frames(30), key=key)
        assert _same(a["labels"], b["labels"])
        assert _same(a["probs"], b["probs"])
        assert np.array_equal(fe2.state.age_frames, age0)
        assert np.array_equal(fe2.state.frame_count, fc0)


class TestShardedFleet:
    def test_sharded_equals_unsharded(self, params):
        mesh = make_host_mesh()
        fe = FleetEngine(CFG, params, backend="pallas", seed=0,
                         chips_per_step=2, fused_stream=False)
        fs = FleetEngine(CFG, params, backend="pallas", seed=0,
                         chips_per_step=2, fused_stream=False, mesh=mesh)
        reqs = [(0, _frames(1)), (1, _frames(2))]
        for a, b in zip(fe.serve(list(reqs)), fs.serve(list(reqs))):
            assert _same(a["labels"], b["labels"])
            np.testing.assert_allclose(np.asarray(a["probs"]),
                                       np.asarray(b["probs"]), atol=1e-6)
