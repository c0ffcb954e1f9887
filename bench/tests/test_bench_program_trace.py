"""The reduction from profiler events to the program's stages, and the
readers of the metrics built on it."""
import glob
import json
import os
import types

import jax
import numpy as np
import pytest

from bench import program_trace as pt
from bench import run, trace_reduce
from conftest import ROOT

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


HLO = """HloModule jit__forward, is_scheduled=true, entry_computation_layout={()}

%fused_computation (p: f32[2]) -> f32[2] {
  ROOT %multiply.9 = f32[2]{0} multiply(%p, %p), metadata={op_name="jit(_forward)/p2m_frontend/mul"}
}

ENTRY %main.1 (a: f32[2]) -> f32[2] {
  %fusion.1 = f32[2]{0} fusion(%a), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(_forward)/p2m_frontend/mul" source_file="/x/vision.py" source_line=9}
  %custom-call.2 = f32[2]{0} custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(_forward)/p2m_frontend/p2m_kernel_fused/pallas_call"}
  %convolution.3 = f32[2]{0} convolution(%custom-call.2), metadata={op_name="jit(_forward)/backbone/conv0/conv_general_dilated"}
  %fusion.4 = f32[2]{0} fusion(%convolution.3), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(_forward)/backbone/conv1/reduce_window_max"}
  ROOT %dot.5 = f32[2]{0} dot(%fusion.4), metadata={op_name="jit(_forward)/head/dot_general"}
}
"""


def _events():
    # device ops (ns): five of the step's program, one op of an eager
    # program whose instruction name the step also uses, one op outside
    # any program run; the engine's spans on the host: a gap under
    # "theta_sync", two under "merge", one under "stream" alone and one
    # after the item under no engine span
    device = {0: [("fusion.1", 0, 100, ""),
                  ("custom-call.2", 100, 50, "p2m_kernel"),
                  ("convolution.3", 200, 300, ""),
                  ("fusion.4", 600, 100, ""),
                  ("dot.5", 900, 20, ""),
                  ("fusion.1", 1200, 10, ""),
                  ("copy.6", 1500, 10, "")]}
    modules = {0: [("jit__forward(77)", 0, 1000, ""),
                   ("jit_concatenate(5)", 1150, 150, "")]}
    host = [("stream", 0, 1100, ""),
            ("microbatch", 0, 180, ""),
            ("theta_sync", 140, 40, ""),            # covers 150..200
            ("PjitFunction(_forward)", 160, 10, ""),
            ("merge", 500, 500, ""),                # covers 500..600
            ("PjitFunction(concatenate)", 700, 150, ""),   # 700..900
            ("np.asarray", 1100, 300, "")]          # 1210..1500
    return {"device": device, "modules": modules, "host": host}


def _reduce(kind, events=None):
    return pt.reduce(events or _events(), 2e-6, pt.scopes([HLO], kind.SCOPES),
                     kind.SPANS)


def test_scope_is_the_innermost_model_scope(p2m_vgg):
    def scope_of(op_name):
        return pt.scope_of(op_name, p2m_vgg.SCOPES)
    assert scope_of("jit(f)/backbone/conv12/add") == "backbone/conv12"
    assert scope_of("jit(f)/backbone/s1b0/c1") == "backbone/s1b0"
    assert scope_of("jit(f)/backbone/add") == "backbone"
    assert scope_of("jit(f)/p2m_frontend/jit(g)/p2m_kernel_a/while/"
                    "body/add") == "p2m_frontend"
    assert scope_of("jit(f)/headless/mul") == ""


def test_hlo_text_gives_each_instruction_its_scope(p2m_vgg):
    table = pt.scopes([HLO], p2m_vgg.SCOPES)
    assert table["jit__forward"] == {
        "multiply.9": "p2m_frontend", "fusion.1": "p2m_frontend",
        "custom-call.2": "p2m_frontend", "convolution.3": "backbone/conv0",
        "fusion.4": "backbone/conv1", "dot.5": "head"}


def test_device_seconds_by_scope(p2m_vgg):
    s = _reduce(p2m_vgg)["scope_s"]
    assert s["p2m_frontend"] == pytest.approx(150e-9)
    assert s["backbone/conv0"] == pytest.approx(300e-9)
    assert s["backbone/conv1"] == pytest.approx(100e-9)
    assert s["backbone"] == pytest.approx(400e-9)
    assert s["head"] == pytest.approx(20e-9)
    # the eager program's fusion.1 and the op outside any run
    assert s[""] == pytest.approx(20e-9)


def test_idle_gaps_go_to_the_innermost_engine_span(p2m_vgg):
    out = _reduce(p2m_vgg)
    assert out["idle_s"] == {"theta_sync": pytest.approx(50e-9),
                             "merge": pytest.approx(300e-9),
                             "stream": pytest.approx(280e-9),
                             "harness": pytest.approx(290e-9)}
    assert set(out["span_s"]) == {"stream", "microbatch", "theta_sync",
                                  "merge"}
    assert out["span_n"] == {"stream": 1, "microbatch": 1, "theta_sync": 1,
                             "merge": 1}
    assert [g[1:] for g in out["top_gaps"]] == [
        ["harness", "np.asarray"], ["stream", "stream"],
        # an eager op dispatched inside the merge
        ["merge", "PjitFunction(concatenate)"], ["merge", "merge"],
        ["theta_sync", "theta_sync"]]
    assert out["top_gaps"][0][0] == pytest.approx(290e-9)


def test_a_program_without_scopes_or_spans_gives_empty_tables(p2m_vgg):
    ev = _events()
    ev["host"] = [("PjitFunction(f)", 0, 2000, "")]
    out = pt.reduce(ev, 2e-6, pt.scopes([HLO.replace("backbone", "x")
                                         .replace("p2m_frontend", "y")
                                         .replace("head", "z")],
                                        p2m_vgg.SCOPES), p2m_vgg.SPANS)
    assert set(out["scope_s"]) == {""} and out["span_s"] == {}
    assert set(out["idle_s"]) == {"harness"}
    assert pt.reduce({"device": {}, "host": []}, 1.0, {},
                     p2m_vgg.SPANS)["idle_s"] == {}


_NEW = ("backbone_flops_share", "frontend_scope_roofline_share",
        "engine.host_syncs_per_microbatch", "open.host_syncs_per_microbatch",
        "engine.merge_idle_share", "window_compiles", "open.window_compiles")


def _ctx(kind, tiny_cifar, program, **rec):
    """The readers' shared context with the window's reduction by program
    stage in place."""
    rec = types.SimpleNamespace(**{"frames": 1000, "window_s": 10.0,
                                   "stream_steps": 40, "counters": {},
                                   "traced": {"busy_s": 1.0,
                                              "window_s": 10.0,
                                              "program": program},
                                   **rec})
    return {"rec": rec, "config": tiny_cifar, "kind": kind,
            "peak": run.peak_of(ROOT, "TPU v5 lite"), "chips": 1}


@pytest.mark.parametrize("name", _NEW)
def test_reader_is_silent_without_its_input(name, p2m_vgg, tiny_cifar):
    """Run against a program that lacks the scopes, spans and counters
    (an older one), or a window with no device, each reader returns None
    and raises nothing."""
    older = pt.reduce({"device": {}, "host": []}, 1.0, {}, p2m_vgg.SPANS)
    older.update(marks={"compiles": None, "syncs": None},
                 span_n={"microbatch": 3})
    ctx = _ctx(p2m_vgg, tiny_cifar, older)
    assert run.read_metric(ROOT, name)(ctx) is None
    assert run.read_metric(ROOT, name)(
        _ctx(p2m_vgg, tiny_cifar, None)) is None


def test_readers_compute_from_their_inputs(p2m_vgg, tiny_cifar):
    from bench.stats import least_seconds
    program = {"scope_s": {"backbone": 0.5, "p2m_frontend": 0.25},
               "span_s": {"merge": 3.0}, "idle_s": {"merge": 2.0},
               "window_s": 10.0, "marks": {"compiles": 0, "syncs": 42},
               "span_n": {"microbatch": 40}}
    ctx = _ctx(p2m_vgg, tiny_cifar, program)
    peak = ctx["peak"]

    def read(name):
        return run.read_metric(ROOT, name)(ctx)

    flops = 2 * p2m_vgg.backbone_macs(tiny_cifar) * 1000
    assert read("backbone_flops_share") == pytest.approx(
        100 * flops / peak["bf16_flops_per_s"] / 0.5)
    least, _ = least_seconds(p2m_vgg.work(tiny_cifar)["p2m_frontend"], 1000,
                             peak)
    assert read("frontend_scope_roofline_share") == pytest.approx(
        100 * least / 0.25)
    assert read("engine.host_syncs_per_microbatch") == pytest.approx(1.05)
    assert read("open.host_syncs_per_microbatch") == pytest.approx(1.05)
    assert read("engine.merge_idle_share") == pytest.approx(20.0)
    assert read("window_compiles") == 0
    assert read("open.window_compiles") == 0


def test_of_run_reads_once_and_needs_a_device(p2m_vgg, tiny_cifar):
    """No device time in the window (a CPU run) or no traced window:
    nothing was reduced by stage. Otherwise every reader gets the one
    reduction the window's stop made."""
    for traced in (None, {"busy_s": 0.0, "window_s": 1.0, "program": None}):
        ctx = _ctx(p2m_vgg, tiny_cifar, None, traced=traced)
        assert pt.of_run(ctx) is None
    kept = {"kept": True}
    ctx = _ctx(p2m_vgg, tiny_cifar, kept)
    assert pt.of_run(ctx) is kept and pt.of_run(ctx) is kept


def test_step_programs_name_every_scope(p2m_vgg, tiny_cifar, monkeypatch):
    """The served engine lowers its own step programs (the exact step
    and, on pallas, the fused one), whose HLO names each instruction's
    model scope; no engine is built for it."""
    server = p2m_vgg.Server(tiny_cifar, 0)
    # building an engine from here on fails
    monkeypatch.setattr(p2m_vgg.VisionEngine, "__init__", None)
    texts = server.step_programs()
    assert len(texts) == 2
    tables = pt.scopes(texts, p2m_vgg.SCOPES)
    assert set(tables) == {"jit__forward", "jit__forward_fused"}
    for table in tables.values():
        found = set(table.values())
        assert {"p2m_frontend", "head", "backbone/conv0",
                "backbone/conv2"} <= found


def _host_events(tmp_path):
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    return [e[0] for e in trace_reduce.load_events(path, [])["host"]]


def test_compile_marks_land_in_the_trace(p2m_vgg, tmp_path):
    """Each program load while an ``Obs`` lives leaves one ``jax_compile``
    mark on the profiler's clock, read back by ``load_events``."""
    from repro.obs import Obs
    obs = Obs()
    f = jax.jit(lambda x: x * 5.0 - 2.0)
    jax.profiler.start_trace(str(tmp_path))
    try:
        before = obs.registry.snapshot().get("jax_compiles_total")
        f(np.ones((7, 13), np.float32)).block_until_ready()
        f(np.ones((7, 13), np.float32)).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    loads = (obs.registry.snapshot()["jax_compiles_total"]["value"]
             - (before or {}).get("value", 0.0))
    assert loads >= 1
    assert _host_events(tmp_path).count(p2m_vgg.MARKS["compiles"]) == loads


@pytest.mark.parametrize("cfg_name", ["tiny_cifar", "tiny_imagenet"])
def test_host_sync_marks_land_in_the_trace(cfg_name, p2m_vgg, request,
                                           tmp_path):
    """A stream of two-microbatch items under the profiler, on the fused
    (pallas) and the deferred (device) path: one ``host_sync`` mark per
    increment of ``serving_host_syncs_total`` and one ``microbatch`` span
    per microbatch, as ``of_window`` counts them."""
    cfg = request.getfixturevalue(cfg_name)
    server = p2m_vgg.Server(cfg, 3, trace=True, pool_blocks=2)
    obs = server.obs
    outs = server.engine.stream(iter([server.pool] * 3))
    next(outs)                  # compiles outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        syncs0 = obs.counter("serving_host_syncs_total").value
        next(outs)
        next(outs)
        syncs = obs.counter("serving_host_syncs_total").value - syncs0
    finally:
        jax.profiler.stop_trace()
    names = _host_events(tmp_path)
    assert syncs >= 2
    assert names.count(p2m_vgg.MARKS["syncs"]) == syncs
    assert names.count("microbatch") == 4
    assert p2m_vgg.MARKS == {"compiles": "jax_compile", "syncs": "host_sync"}


def test_recorded_chip_trace(p2m_vgg):
    """One stream item of a TPU v5e trace of the vgg16_imagenet.stream
    window, with the scope of each instruction read from the programs'
    HLO and the numbers both reductions gave when it was recorded."""
    with open(os.path.join(DATA, "tpu_v5e_imagenet_stream_item.json")) as f:
        rec = json.load(f)
    events = {"device": {int(k): [tuple(e) for e in v]
                         for k, v in rec["device"].items()},
              "modules": {int(k): [tuple(e) for e in v]
                          for k, v in rec["modules"].items()},
              "host": [tuple(e) for e in rec["host"]]}
    old = trace_reduce.reduce(events, rec["window_s"])
    assert old["busy_s"] == pytest.approx(rec["expect"]["busy_s"])
    out = pt.reduce(events, rec["window_s"], rec["programs"], p2m_vgg.SPANS)
    assert out["scope_s"] == pytest.approx(rec["expect"]["scope_s"])
    assert out["idle_s"] == pytest.approx(rec["expect"]["idle_s"])
    s = out["scope_s"]
    assert {f"backbone/conv{i}" for i in range(13)} <= set(s)
    assert s["p2m_frontend"] > 0 and s["head"] > 0
    layers = sum(v for k, v in s.items() if k.startswith("backbone/"))
    # every backbone op sits in one of its convs
    assert layers > 0 and s["backbone"] == pytest.approx(layers)
    # every op of the item is counted once: under a scope or under none
    # ("backbone" sums its layers and the ops directly under it)
    assert sum(v for k, v in s.items() if not k.startswith("backbone/")) \
        == pytest.approx(sum(e[2] for e in events["device"][0]) * 1e-9)
    assert "merge" in out["idle_s"]
