"""The comparison fails what it must: the control, and a run whose timed
path is broken underneath.

The control is the reference in the program's place one precision step
below the configuration's. The faults are planted in the program while
``run.main`` drives a whole tiny run on the CPU (chip check replaced):
an answer altered where it is produced, the frontend's threshold taken
over half of the microbatch, and an engine step that leaves its state
(the frame counter that keys the draws) unchanged. One chip: no exchange
between chips to leave out.
"""
import contextlib
import json

import jax
import pytest

from bench import correct, loops
from test_bench_run import checkout, drive  # noqa: F401  (fixture)


def test_control_is_incorrect(p2m_vgg, tiny_cifar):
    seed = 21
    server = p2m_vgg.Server(tiny_cifar, seed)
    rec = loops.closed(server, {"batch_microbatches": 2}, 0.5, seed, 6)
    lim = p2m_vgg.limits(tiny_cifar)
    ok, _ = correct.judge(p2m_vgg.readings(tiny_cifar, server, seed,
                                           rec.samples), lim)
    assert ok
    ok, rows = correct.judge(p2m_vgg.readings(tiny_cifar, server, seed,
                                              rec.samples, control=True),
                             lim)
    assert not ok, rows


@contextlib.contextmanager
def _patched(obj, name, make):
    orig = getattr(obj, name)
    setattr(obj, name, make(orig))
    jax.clear_caches()
    try:
        yield
    finally:
        setattr(obj, name, orig)
        jax.clear_caches()


def _answer_altered():
    from repro.models import vision

    def make(orig):
        def forward(*a, **k):
            logits, h, aux = orig(*a, **k)
            return logits.at[0, 0].add(3.0), h, aux
        return forward
    return _patched(vision, "forward", make)


def _half_batch_threshold():
    """The frontend's Hoyer threshold over the first half of the
    microbatch only (both the Pallas and the XLA backend)."""
    from repro.frontend import backends
    from repro.kernels import ops
    stack = contextlib.ExitStack()
    stack.enter_context(_patched(
        ops, "combine_hoyer_partials",
        lambda orig: lambda p, v: orig(p[:p.shape[0] // 2], v)))
    stack.enter_context(_patched(
        backends, "_theta",
        lambda orig: lambda u, v: orig(u[:u.shape[0] // 2], v)))
    return stack


def _state_unchanged():
    """Every stream item leaves the engine's frame counter, which keys the
    draws, where the stream found it."""
    from repro.serving import VisionEngine

    def make(orig):
        def stream(self, batches):
            count = self._frame_count
            for out in orig(self, batches):
                self._frame_count = count
                yield out
        return stream
    return _patched(VisionEngine, "stream", make)


FAULTS = {"answer_altered": _answer_altered,
          "half_batch_threshold": _half_batch_threshold,
          "state_unchanged": _state_unchanged}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("workload", ["vgg16_cifar10.stream",
                                      "vgg16_imagenet.stream",
                                      "vgg16_cifar10.open"])
def test_fault_is_incorrect(checkout, workload, fault, capsys):  # noqa: F811
    with FAULTS[fault]():
        rc, res, err = drive(checkout, workload, capsys=capsys)
    assert rc == 0 and res["correct"] is False, json.dumps(res["checks"])
