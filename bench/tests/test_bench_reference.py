"""The benchmark's plain reference agrees with the program on the CPU.

``VisionEngine`` runs its Pallas kernels in interpret mode here and its
convs in f32, so at a tiny size the reference must reproduce each served
microbatch exactly: the frontend's per-channel rates and fresh threshold,
and the log-probabilities -- on the exact first step, on a fused step at
the carried threshold it reports (``theta_used``), on each microbatch of a
stream item the engine splits and merges, and on the ``device`` backend's
per-MTJ draws.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import correct, inputs, loops, system


def _served(cfg, seed, steps, per_item=1):
    """``steps`` stream items of ``per_item`` microbatches each, and their
    samples. Every microbatch holds the same frames, so the fused steps'
    carried threshold stays within the drift guard."""
    params = inputs.weights(cfg, seed)
    pool = inputs.frame_pool(cfg, 2 * cfg["microbatch"], seed)
    eng = system.engine(cfg, params, inputs.engine_seed(seed))
    server = loops.Server(eng, pool, cfg["microbatch"])
    idx = np.tile(np.arange(cfg["microbatch"]), per_item)
    frames = jnp.take(pool, jnp.asarray(idx), axis=0)
    samples = []
    for _ in range(steps):
        _, out, parts, i = server.serve(frames)
        samples += loops.item_samples(i, idx, len(idx), out, parts)
    return params, pool, samples


def _compare(cfg, params, pool, seed, sample):
    frames = jnp.take(pool, jnp.asarray(sample.frames), axis=0)
    prog = correct._program_view(sample)
    with jax.default_matmul_precision("highest"):
        ref = correct.reference_step(
            cfg, params, frames,
            correct.step_key(seed, sample.index, sample.part, sample.parts),
            jnp.asarray(prog["theta_used"], jnp.float32))
    np.testing.assert_array_equal(prog["channel_rates"],
                                  ref["channel_rates"])
    assert abs(prog["theta"] - ref["theta"]) <= 1e-5 * abs(ref["theta"])
    np.testing.assert_allclose(prog["logp"], ref["logp"], atol=1e-5)
    return prog


def test_pallas_exact_and_fused_steps(tiny_cifar):
    seed = 2 ** 33 + 7
    params, pool, samples = _served(tiny_cifar, seed, 2)
    first = _compare(tiny_cifar, params, pool, seed, samples[0])
    second = _compare(tiny_cifar, params, pool, seed, samples[1])
    assert not first["fused"] and second["fused"]
    # the fused step drew at the carried threshold, not its own
    assert second["theta_used"] == pytest.approx(first["theta"], rel=1e-6)


def test_each_microbatch_of_a_merged_item(tiny_cifar):
    """Two items of two microbatches: the engine keys each microbatch by
    its place in the item and merges their answers; every microbatch
    matches the reference at its own threshold, on its rows of the served
    result."""
    seed = 2 ** 32 + 3
    params, pool, samples = _served(tiny_cifar, seed, 2, per_item=2)
    assert [(s.index, s.part, s.parts) for s in samples] == [
        (0, 0, 2), (0, 1, 2), (1, 0, 2), (1, 1, 2)]
    views = [_compare(tiny_cifar, params, pool, seed, s) for s in samples]
    assert [v["fused"] for v in views] == [False, True, True, True]
    # the second microbatch drew at the carry the first one left
    assert views[1]["theta_used"] == pytest.approx(views[0]["theta"],
                                                   rel=1e-6)


def test_device_backend_per_mtj_draws(tiny_imagenet):
    seed = 11
    params, pool, samples = _served(tiny_imagenet, seed, 2)
    for s in samples:
        _compare(tiny_imagenet, params, pool, seed, s)


def test_reference_readings_are_zero_on_the_cpu(tiny_cifar):
    seed = 5
    params, pool, samples = _served(tiny_cifar, seed, 3)
    read = correct.readings(tiny_cifar, params, pool, seed, samples)
    assert read["rate_flips"] == 0.0 and read["frames_off"] == 0.0
    assert read["theta_gap"] < 1e-5
