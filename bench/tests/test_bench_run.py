"""A whole run of each cell through ``run.main`` at a tiny size on the CPU.

The chip check is the one thing replaced (``require=``); the configuration
files are tiny copies in a scratch checkout, everything else is the
harness as the chip runs it: set-up, the window, the seeded sample and its
comparison with the reference, the metric readers and the result line.
A kind the harness has never seen (``data/toy_kind.py``) runs through it
as new files only.
"""
import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

from bench import loops, run
from conftest import ROOT, tiny

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """A scratch root: BENCHMARK.json, bench/'s data files and kinds, with
    tiny configurations, a slow open-loop rate with long windows and a peak
    row for the CPU."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    for d in ("traffic", "cells", "metrics", "kinds"):
        shutil.copytree(os.path.join(ROOT, "bench", d), root / "bench" / d)
    (root / "bench" / "configs").mkdir()
    for name, hw in (("vgg16_cifar10", 8), ("vgg16_imagenet", 16)):
        (root / "bench" / "configs" / (name + ".json")).write_text(
            json.dumps(tiny(name, hw)))
    open_mix = root / "bench" / "traffic" / "open.json"
    mix = json.loads(open_mix.read_text())
    mix["window_frames"] = tiny("vgg16_cifar10")["microbatch"]
    # windows close full of distinct frames however fast the CPU serves:
    # a window of one frame padded with copies of itself would hide a
    # threshold taken over half of the microbatch
    mix["window_ms"] = 100.0
    open_mix.write_text(json.dumps(mix))
    for cell in (root / "bench" / "cells").glob("*.json"):
        spec = json.loads(cell.read_text())
        if "rate_per_s" in spec:
            spec["rate_per_s"] = 150.0
        cell.write_text(json.dumps(spec))
    with open(os.path.join(ROOT, "bench", "peaks.json")) as f:
        peaks = json.load(f)
    peaks["devices"]["cpu"] = peaks["devices"]["TPU v5 lite"]
    (root / "bench" / "peaks.json").write_text(json.dumps(peaks))
    return str(root)


@pytest.fixture(scope="module")
def toy_checkout(checkout, tmp_path_factory):
    """A copy of the scratch checkout with a new kind added as new files
    and entries only: ``bench/kinds/toy.py``, its configuration, a traffic
    mix that asks for the kind's own loop, and two cells; plus two
    configurations whose kind is unknown or not named."""
    root = tmp_path_factory.mktemp("toy") / "checkout"
    shutil.copytree(checkout, root)
    shutil.copy(os.path.join(DATA, "toy_kind.py"),
                root / "bench" / "kinds" / "toy.py")
    configs = {"toy_mlp": {"kind": "toy", "name": "toy_mlp", "inputs": 24,
                           "hidden": 32, "classes": 10, "microbatch": 8,
                           "limits": {"logit_gap": 1e-4}},
               "ghost": {"kind": "no_such_kind", "name": "ghost"},
               "nameless": {"name": "nameless"}}
    for name, cfg in configs.items():
        (root / "bench" / "configs" / (name + ".json")).write_text(
            json.dumps(cfg))
    (root / "bench" / "traffic" / "toy_stream.json").write_text(json.dumps(
        {"loop": "toy_closed", "batch_microbatches": 2}))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for cfg, traffic in (("toy_mlp", "stream"), ("toy_mlp", "toy_stream"),
                         ("ghost", "stream"), ("nameless", "stream")):
        bench["workloads"].append({"name": f"{cfg}.{traffic}",
                                   "config": cfg, "traffic": traffic,
                                   "chips": 1, "why": "harness test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in ("frames_per_s", "step_mfu"):
            m["workloads"] += ["toy_mlp.stream", "toy_mlp.toy_stream"]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)


def _cpu(n):
    return jax.devices()[:n]


_CACHE_OPTIONS = ("jax_compilation_cache_dir",
                  "jax_persistent_cache_min_compile_time_secs",
                  "jax_persistent_cache_min_entry_size_bytes")


def drive(root, workload, trace=0, seed=2 ** 32 + 17, capsys=None):
    """One run.main; the process-wide compile-cache options it sets are
    put back afterwards, so other tests in this process see none."""
    saved = {k: getattr(jax.config, k) for k in _CACHE_OPTIONS}
    try:
        rc = run.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", "0.5", "--trace", str(trace)],
                      root=root, require=_cpu)
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
    out, err = capsys.readouterr()
    return rc, json.loads(out.strip().splitlines()[-1]), err


def _cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("workload", [w["name"]
                                      for w in _cells()["workloads"]])
def test_each_cell_runs_and_is_correct(checkout, workload, capsys):
    rc, res, err = drive(checkout, workload, capsys=capsys)
    assert rc == 0 and res["correct"] is True
    assert set(res) >= {"correct", "attempted", "failed", "metrics",
                        "device"}
    assert list(res)[-1] == "checks"
    want = {m["name"] for m in _cells()["end_to_end"]
            if workload in m.get("workloads", [workload])}
    assert set(res["metrics"]) == want
    assert res["attempted"] > 0 and res["failed"] == 0
    assert all(m["value"] > 0 for m in res["metrics"].values())
    # the compared numbers close standard error, each beside its limit
    last = err.strip().splitlines()[-len(res["checks"]):]
    assert all(line.startswith("check ") and "limit" in line
               for line in last)


def test_traced_run_reports_per_layer_metrics(checkout, capsys):
    rc, res, _ = drive(checkout, "vgg16_cifar10.stream", trace=1,
                       capsys=capsys)
    assert rc == 0 and res["correct"] is True
    # the CPU has no device plane: the trace's device metrics stay silent
    assert set(res["metrics"]) == {"step_mfu", "fused_step_share"}
    assert 0 < res["metrics"]["fused_step_share"]["value"] <= 100
    assert res["device"]["window_s"] > 0 and "breakdown" in res


def test_open_loop_traced_run(checkout, capsys):
    rc, res, err = drive(checkout, "vgg16_cifar10.open", trace=1,
                         capsys=capsys)
    assert rc == 0
    assert set(res["metrics"]) == {"open.latency_p95_ms",
                                   "open.queue_wait_p95_ms",
                                   "open.service_p95_ms"}
    assert "generator late" in err


def test_no_tpu_exits_nonzero_and_prints_no_result(capsys):
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "vgg16_cifar10.stream", "--seed", "1",
                  "--seconds", "1", "--trace", "0"])
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""


def test_command_in_a_bare_checkout_exits_nonzero(tmp_path):
    """Only BENCHMARK.json and bench/: the program is missing, so the
    command fails before printing a result."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", ".jax_cache",
                                                  "traces", "__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "vgg16_cifar10.stream", "--seed", "1", "--seconds",
                        "1", "--trace", "0"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout == ""


@pytest.mark.parametrize("traffic", ["stream", "toy_stream"])
def test_new_kind_runs_as_new_files(toy_checkout, traffic, capsys):
    """A two-layer classifier with its own reference, through the shared
    closed loop and through a loop of its own."""
    rc, res, err = drive(toy_checkout, f"toy_mlp.{traffic}", capsys=capsys)
    assert rc == 0 and res["correct"] is True, res["checks"]
    assert [c["name"] for c in res["checks"]] == ["logit_gap"]
    assert set(res["metrics"]) == {"frames_per_s", "setup_s"}
    assert res["attempted"] > 0 and res["failed"] == 0
    assert ("toy loop: closed" in err) == (traffic == "toy_stream")


def test_new_kind_reads_its_own_work(toy_checkout, capsys):
    rc, res, _ = drive(toy_checkout, "toy_mlp.stream", trace=1,
                       capsys=capsys)
    assert rc == 0 and res["correct"] is True
    assert set(res["metrics"]) == {"step_mfu"}
    assert res["metrics"]["step_mfu"]["value"] > 0


def test_new_kind_with_a_planted_fault_is_incorrect(toy_checkout, capsys,
                                                    monkeypatch):
    """One served logit altered where the program produces it."""
    toy = run.load_kind(toy_checkout, "toy")
    forward = toy.forward
    monkeypatch.setattr(toy, "forward",
                        lambda p, x: forward(p, x).at[0, 0].add(0.5))
    rc, res, _ = drive(toy_checkout, "toy_mlp.stream", capsys=capsys)
    assert rc == 0 and res["correct"] is False
    assert res["checks"][0]["value"] > res["checks"][0]["limit"]


@pytest.mark.parametrize("workload,error", [
    ("ghost.stream", "unknown kind 'no_such_kind'"),
    ("nameless.stream", "names no kind")])
def test_unknown_kind_exits_nonzero_and_prints_no_result(
        toy_checkout, workload, error, capsys):
    with pytest.raises(SystemExit) as e:
        drive(toy_checkout, workload, capsys=capsys)
    assert e.value.code not in (0, None) and error in str(e.value.code)
    assert capsys.readouterr().out == ""


class _Clock:
    """A clock that moves ``step`` seconds at each reading, so that a
    window serves the same items however fast the CPU is."""

    def __init__(self, step: float):
        self.t, self.step = 0.0, step

    def __call__(self) -> float:
        self.t += self.step
        return self.t


# each cell's requests and compared numbers on one seed at the tiny
# sizes, with the loops on ``_Clock(0.05)``: what the harness read before
# the kinds were taken out of it, to the last digit
PINNED = {
    "vgg16_cifar10.stream": (80, {
        "rate_flips": 0.0, "theta_gap": 6.452976057246378e-07,
        "frames_off": 0.0, "theta_guard": 0.013351517539288772}),
    "vgg16_imagenet.stream": (80, {
        "rate_flips": 0.0, "theta_gap": 0.0, "frames_off": 0.0,
        "theta_guard": 0.0}),
    "vgg16_cifar10.open": (75, {
        "rate_flips": 0.0, "theta_gap": 5.784842613233232e-07,
        "frames_off": 0.0, "theta_guard": 0.007896078830206961}),
}


@pytest.mark.parametrize("workload", sorted(PINNED))
def test_checks_are_pinned(checkout, workload, capsys, monkeypatch):
    monkeypatch.setattr(loops, "now", _Clock(0.05))
    rc, res, _ = drive(checkout, workload, seed=2 ** 32 + 17,
                       capsys=capsys)
    assert rc == 0
    assert (res["attempted"], {c["name"]: c["value"]
                               for c in res["checks"]}) == PINNED[workload]
