"""Tests for repro.serving.loadgen: the deterministic serving load
generator (ISSUE: the harness must be reproducible byte-for-byte).

The load-bearing claims:

* Schedules are pure functions of ``(seed, offered_fps, n_requests)`` —
  identical across calls AND across processes (a subprocess loading the
  module from its file path, with jax provably unimported, produces the
  same bytes), and different seeds genuinely differ.
* Nothing in the module reads ``repro.obs.clock.now`` — the generator
  runs with the clock monkeypatched to raise.
* The admission plan partitions the schedule in order, never overfills a
  window, and closes tails at ``open + deadline``.
* The queueing simulation decomposes latency exactly as queue-wait +
  service, reports slowdown 1.0 when the server keeps up and > 1 when
  it cannot, and ``find_knee`` fires on either saturation signal.
* ``deterministic_trace()`` (the --quick byte-identity surface of
  BENCH_serving.json) serializes identically on repeated calls.
"""
import json
import math
import subprocess
import sys

import pytest

import repro.obs as obs_mod
from repro.serving import loadgen


def _model(batch) -> float:
    return 1e-3 + 2.5e-4 * batch.n_frames


# ---------------------------------------------------------------------------
# schedule determinism
# ---------------------------------------------------------------------------

class TestSchedule:
    def test_hash_u01_deterministic_uniform(self):
        xs = [loadgen.hash_u01(5, i) for i in range(2000)]
        assert xs == [loadgen.hash_u01(5, i) for i in range(2000)]
        assert all(0.0 <= x < 1.0 for x in xs)
        # the finalizer avalanches: the mean of a seeded stream is ~1/2
        assert sum(xs) / len(xs) == pytest.approx(0.5, abs=0.02)
        assert xs[:64] != [loadgen.hash_u01(6, i) for i in range(64)]

    def test_same_seed_identical_different_seed_not(self):
        cfg = loadgen.LoadgenConfig(seed=3, offered_fps=1500.0,
                                    n_requests=64)
        a = loadgen.make_schedule(cfg)
        b = loadgen.make_schedule(cfg)
        assert a == b                      # frozen dataclasses: deep equal
        c = loadgen.make_schedule(
            loadgen.LoadgenConfig(seed=4, offered_fps=1500.0,
                                  n_requests=64))
        assert [r.t_arrival for r in c] != [r.t_arrival for r in a]

    def test_poisson_rate_and_uniform_isochrony(self):
        cfg = loadgen.LoadgenConfig(seed=0, offered_fps=1000.0,
                                    n_requests=512)
        sched = loadgen.make_schedule(cfg)
        mean_gap = sched[-1].t_arrival / len(sched)
        assert mean_gap == pytest.approx(1e-3, rel=0.1)
        iso = loadgen.make_schedule(
            loadgen.LoadgenConfig(seed=0, offered_fps=1000.0,
                                  n_requests=16, arrival="uniform"))
        gaps = [b.t_arrival - a.t_arrival for a, b in zip(iso, iso[1:])]
        assert all(g == pytest.approx(1e-3) for g in gaps)

    def test_chip_round_robin_and_frames(self):
        sched = loadgen.make_schedule(
            loadgen.LoadgenConfig(seed=1, offered_fps=800.0, n_requests=6,
                                  frames_per_request=2, chips=3))
        assert [r.chip_id for r in sched] == [0, 1, 2, 0, 1, 2]
        assert all(r.n_frames == 2 for r in sched)

    def test_config_validation(self):
        with pytest.raises(ValueError):
            loadgen.LoadgenConfig(offered_fps=0.0)
        with pytest.raises(ValueError):
            loadgen.LoadgenConfig(arrival="bursty")

    def test_cross_process_byte_identity_without_jax(self):
        """Two fresh interpreters loading loadgen.py straight from its
        file path (no repro package, provably no jax import) must print
        byte-identical schedules, plans, and simulation digests."""
        prog = (
            "import importlib.util, json, sys\n"
            "spec = importlib.util.spec_from_file_location('lg', "
            "sys.argv[1])\n"
            "m = importlib.util.module_from_spec(spec)\n"
            "sys.modules['lg'] = m   # dataclasses resolves via sys.modules\n"
            "spec.loader.exec_module(m)\n"
            "assert 'jax' not in sys.modules, 'loadgen pulled in jax'\n"
            "assert 'numpy' not in sys.modules, 'loadgen pulled in numpy'\n"
            "cfg = m.LoadgenConfig(seed=3, offered_fps=1500.0, "
            "n_requests=64)\n"
            "sched = m.make_schedule(cfg)\n"
            "plan = m.plan_microbatches(sched, 8, 0.004)\n"
            "sim = m.simulate(plan, lambda b: 1e-3 + 2.5e-4 * b.n_frames, "
            "slo_ms=8.0)\n"
            "print(json.dumps({'sched': [r.to_json() for r in sched], "
            "'plan': [b.to_json() for b in plan], "
            "'sim': sim}, sort_keys=True))\n"
        )
        path = loadgen.__file__
        runs = [subprocess.run([sys.executable, "-c", prog, path],
                               capture_output=True, check=True)
                for _ in range(2)]
        assert runs[0].stdout == runs[1].stdout
        assert json.loads(runs[0].stdout)["sched"]

    def test_no_clock_reads(self, monkeypatch):
        """The whole virtual-time pipeline must run with the host clock
        banned — loadgen supplies its own time axis."""
        from repro.obs import clock

        def boom():          # pragma: no cover - must never fire
            raise AssertionError("loadgen read the wall clock")

        monkeypatch.setattr(clock, "now", boom)
        cfg = loadgen.LoadgenConfig(seed=2, offered_fps=2000.0,
                                    n_requests=32)
        plan = loadgen.plan_microbatches(loadgen.make_schedule(cfg), 8,
                                         0.004)
        sim = loadgen.simulate(plan, _model, slo_ms=8.0)
        assert loadgen.find_knee([{"offered_fps": 1.0,
                                   "latency_p99_ms": 1.0,
                                   "slowdown": sim["slowdown"]}]) or True


# ---------------------------------------------------------------------------
# admission planning
# ---------------------------------------------------------------------------

class TestPlan:
    def test_partition_order_and_cap(self):
        cfg = loadgen.LoadgenConfig(seed=7, offered_fps=3000.0,
                                    n_requests=100)
        sched = loadgen.make_schedule(cfg)
        plan = loadgen.plan_microbatches(sched, 8, 0.002)
        ids = [r.req_id for b in plan for r in b.requests]
        assert ids == list(range(100))     # every request exactly once,
        assert all(b.n_frames <= 8 for b in plan)          # in order
        assert [b.index for b in plan] == list(range(len(plan)))
        # windows never close before their last admit arrives
        for b in plan:
            assert b.t_close >= b.requests[-1].t_arrival

    def test_full_window_closes_at_last_admit(self):
        sched = [loadgen.Request(i, i * 1e-4) for i in range(8)]
        (b,) = loadgen.plan_microbatches(sched, 8, 1.0)
        assert b.t_close == pytest.approx(7e-4)

    def test_deadline_closes_sparse_windows(self):
        # arrivals 10ms apart, 4ms deadline: every request rides alone
        # and its window closes exactly deadline after it arrived
        sched = [loadgen.Request(i, i * 1e-2) for i in range(4)]
        plan = loadgen.plan_microbatches(sched, 8, 4e-3)
        assert [len(b.requests) for b in plan] == [1, 1, 1, 1]
        for b in plan:
            assert b.t_close == pytest.approx(
                b.requests[0].t_arrival + 4e-3)

    def test_overflow_closes_at_next_arrival(self):
        # 3-frame requests into a 4-frame window: each window holds one
        # request and closes when the next (overflowing) request arrives
        sched = [loadgen.Request(i, i * 1e-4, n_frames=3) for i in range(3)]
        plan = loadgen.plan_microbatches(sched, 4, 1.0)
        assert [b.n_frames for b in plan] == [3, 3, 3]
        assert plan[0].t_close == pytest.approx(sched[1].t_arrival)

    def test_bad_cap_raises(self):
        with pytest.raises(ValueError):
            loadgen.plan_microbatches([], 0, 1.0)


# ---------------------------------------------------------------------------
# queueing simulation
# ---------------------------------------------------------------------------

class TestSimulate:
    def _plan(self, fps, n=48, seed=5):
        cfg = loadgen.LoadgenConfig(seed=seed, offered_fps=fps,
                                    n_requests=n)
        return loadgen.plan_microbatches(loadgen.make_schedule(cfg), 8,
                                         8 / 2000.0)

    def test_latency_decomposition_exact(self):
        sim = loadgen.simulate(self._plan(1800.0), _model, slo_ms=10.0)
        for r in sim["requests"]:
            assert r["latency_ms"] == pytest.approx(
                r["queue_wait_ms"] + r["service_ms"])
            assert r["queue_wait_ms"] >= 0
        for b in sim["batches"]:
            assert b["t_dispatch_ms"] >= b["t_close_ms"]
            assert b["ttfa_ms"] == pytest.approx(
                b["t_ready_ms"] - b["t_close_ms"])

    def test_unloaded_server_never_queues(self):
        # service far below the inter-window gap: dispatch == close for
        # every window, and the loaded makespan equals the unloaded one
        sim = loadgen.simulate(self._plan(500.0), lambda b: 1e-5)
        for b in sim["batches"]:
            assert b["t_dispatch_ms"] == pytest.approx(b["t_close_ms"])
        assert sim["slowdown"] == pytest.approx(1.0)

    def test_overload_queues_and_slows_down(self):
        plan = self._plan(4000.0, n=96)
        slow = loadgen.simulate(plan, lambda b: 8e-3)   # >> window gap
        fast = loadgen.simulate(plan, lambda b: 1e-5)
        assert slow["slowdown"] > 1.2 > fast["slowdown"]
        assert slow["makespan_ms"] > slow["unloaded_makespan_ms"]
        # queue wait compounds: the last request waits longer than the
        # first (every window behind an ever-later server-free time)
        qw = [r["queue_wait_ms"] for r in slow["requests"]]
        assert qw[-1] > qw[0]
        assert slow["queue_depth_high_water"] > \
            fast["queue_depth_high_water"]

    def test_measured_walls_sequence_and_mismatch(self):
        plan = self._plan(1800.0)
        walls = [2e-3] * len(plan)
        sim = loadgen.simulate(plan, walls)
        assert all(b["service_ms"] == pytest.approx(2.0)
                   for b in sim["batches"])
        with pytest.raises(ValueError):
            loadgen.simulate(plan, walls[:-1])

    def test_slo_flagging(self):
        sim = loadgen.simulate(self._plan(1800.0), _model, slo_ms=1e-6)
        assert all(r["slo_violation"] for r in sim["requests"])
        sim = loadgen.simulate(self._plan(1800.0), _model, slo_ms=1e9)
        assert not any(r["slo_violation"] for r in sim["requests"])


# ---------------------------------------------------------------------------
# SLO accounting + knee
# ---------------------------------------------------------------------------

class TestRecordSloAndKnee:
    def test_record_slo_instruments(self):
        cfg = loadgen.LoadgenConfig(seed=2, offered_fps=2500.0,
                                    n_requests=40)
        plan = loadgen.plan_microbatches(loadgen.make_schedule(cfg), 8,
                                         4e-3)
        sim = loadgen.simulate(plan, _model, slo_ms=3.0)
        obs = obs_mod.Obs()
        summ = loadgen.record_slo(obs, sim, 3.0)
        reg = obs.registry
        assert reg.histogram("serving_request_latency_ms").count == 40
        assert reg.histogram("serving_queue_wait_ms").count == 40
        assert reg.histogram("serving_ttfa_ms").count == len(plan)
        n_viol = sum(r["latency_ms"] > 3.0 for r in sim["requests"])
        assert reg.counter("slo_violations_total").value == n_viol
        assert summ["slo_violations"] == n_viol
        assert reg.counter("serving_requests_total").value == 40
        assert reg.gauge("serving_queue_depth").value == \
            sim["queue_depth_high_water"]
        assert summ["latency_p50_ms"] <= summ["latency_p99_ms"]
        # the simulation's virtual times land in histograms only: no span
        # claims an interval on the host's clock
        assert obs.tracer.spans() == []

    def test_find_knee_latency_and_slowdown_criteria(self):
        def row(fps, p99, slowdown=1.0):
            return {"offered_fps": fps, "latency_p99_ms": p99,
                    "achieved_fps": fps, "slowdown": slowdown}

        assert loadgen.find_knee([]) is None
        flat = [row(100.0, 5.0), row(200.0, 5.5), row(400.0, 6.0)]
        assert loadgen.find_knee(flat) is None
        lat = flat + [row(800.0, 20.0)]
        knee = loadgen.find_knee(lat)
        assert knee["offered_fps"] == 800.0
        assert knee["p99_over_baseline"] == pytest.approx(4.0)
        slow = flat + [row(800.0, 6.5, slowdown=1.4)]
        knee = loadgen.find_knee(slow)
        assert knee["offered_fps"] == 800.0 and knee["slowdown"] == 1.4
        # the threshold is strict: 1.05 exactly does not fire
        assert loadgen.find_knee(flat + [row(800.0, 6.5, 1.05)]) is None


# ---------------------------------------------------------------------------
# the bench's byte-identity surface
# ---------------------------------------------------------------------------

class TestDeterministicTrace:
    def test_trace_serializes_identically(self):
        from benchmarks import serving_bench
        a = json.dumps(serving_bench.deterministic_trace(), sort_keys=True)
        b = json.dumps(serving_bench.deterministic_trace(), sort_keys=True)
        assert a == b
        trace = json.loads(a)
        assert len(trace["schedule"]) == serving_bench.TRACE_REQUESTS
        assert trace["simulated"]["requests"]
        assert math.isfinite(trace["simulated"]["slowdown"])
