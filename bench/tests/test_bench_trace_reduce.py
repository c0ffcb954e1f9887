"""The reduction from profiler events to busy time, kernel time and the
idle-gap breakdown."""
import json
import os

import pytest

from bench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _events():
    # device ops (ns): two overlapping ops, a kernel, a gap under a host
    # dispatch, a gap under a host span, and an op after the last gap
    device = {0: [("fusion.1", 0, 100, ""),
                  ("fusion.2", 50, 100, ""),          # overlaps: busy 0..150
                  ("custom-call.3", 200, 50, "p2m_kernel"),
                  ("convolution.4", 400, 100, ""),
                  ("fusion.5", 1000, 100, "")]}
    host = [("stream", 0, 1100, ""),
            ("PjitFunction(_fused_step)", 150, 60, ""),   # covers 175
            ("microbatch", 500, 400, "")]                  # covers 750
    return {"device": device, "host": host}


def test_busy_is_the_union_of_device_intervals():
    out = tr.reduce(_events(), window_s=2e-6)
    assert out["busy_s"] == pytest.approx((150 + 50 + 100 + 100) * 1e-9)
    assert out["window_s"] == 2e-6


def test_kernel_time_sums_scoped_events():
    out = tr.reduce(_events(), window_s=2e-6)
    assert out["kernel_s"] == {"p2m_kernel": pytest.approx(50e-9)}
    ops = dict(out["breakdown"]["device_ops"])
    assert ops["p2m_kernel:custom-call.3"] == pytest.approx(50e-9)
    assert ops["fusion.1"] == pytest.approx(100e-9)


def test_idle_gaps_are_named_by_the_innermost_host_event():
    gaps = dict(tr.reduce(_events(), window_s=2e-6)["breakdown"]["idle_gaps"])
    # 150..200 under the dispatch, 250..400 under "stream" only,
    # 500..1000 under "microbatch"
    assert gaps["PjitFunction(_fused_step)"] == pytest.approx(50e-9)
    assert gaps["stream"] == pytest.approx(150e-9)
    assert gaps["microbatch"] == pytest.approx(500e-9)


def test_no_device_plane_gives_no_busy_time():
    out = tr.reduce({"device": {}, "host": []}, window_s=1.0)
    assert out["busy_s"] == 0.0 and out["kernel_s"] == {}


def test_scope_is_found_in_event_metadata(p2m_vgg):
    stats = [("tf_op", "jit(_forward)/p2m_kernel_fused/pallas_call")]
    kernels = p2m_vgg.KERNELS
    assert tr._scope_of("custom-call.7", stats, kernels) == "p2m_kernel"
    assert tr._scope_of("fusion.7", [("tf_op", "jit(_forward)/conv")],
                        kernels) == ""


def test_recorded_chip_trace():
    """A slice of a TPU v5e trace of the vgg16_cifar10.stream window,
    with the numbers the reduction gave for it when it was recorded."""
    with open(os.path.join(DATA, "tpu_v5e_cifar10_stream_slice.json")) as f:
        rec = json.load(f)
    events = {"device": {int(k): [tuple(e) for e in v]
                         for k, v in rec["device"].items()},
              "host": [tuple(e) for e in rec["host"]]}
    out = tr.reduce(events, rec["window_s"])
    assert out["busy_s"] == pytest.approx(rec["expect"]["busy_s"])
    assert out["kernel_s"] == pytest.approx(rec["expect"]["kernel_s"])
    assert 0 < out["busy_s"] < out["window_s"]
    assert out["kernel_s"]["p2m_kernel"] > 0
    assert dict(out["breakdown"]["idle_gaps"]), "gaps name host work"
