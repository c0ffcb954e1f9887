"""The ``p2m_vlm`` kind through ``run.main`` at a tiny size on the CPU.

A scratch checkout holds the benchmark's files with a tiny copy of the
``kimi_vl_a3b`` configuration (the same keys, small widths, f32): a run of
the unchanged program reads ``correct``; each of two planted routing faults
(softmax in place of sigmoid routing; the selection bias in the weights)
and a planted token fault (argmin in place of the greedy pick) reads
``correct: false``; a traced run reports the kind's work; the
kind's configuration is the program's registered architecture but for its
depth; and the work of a request is counted from the configuration's
shapes.
"""
import copy
import dataclasses
import json
import os
import shutil

import jax
import jax.numpy as jnp
import pytest

from bench import run
from conftest import ROOT, load_config
from test_bench_run import drive

CELL = "kimi_vl_a3b.stream"

# the smallest model of the same keys, and requests short enough for the
# CPU; the CPU computes f32 matmuls on f32 operands
TINY = {"vocab_size": 256, "hidden_size": 64, "intermediate_size": 96,
        "moe_intermediate_size": 32, "num_hidden_layers": 3,
        "num_attention_heads": 4, "num_key_value_heads": 4,
        "n_routed_experts": 8, "num_experts_per_tok": 2,
        "n_shared_experts": 1, "kv_lora_rank": 32, "qk_rope_head_dim": 8,
        "qk_nope_head_dim": 16, "v_head_dim": 16,
        "projector": {"patch": 14, "hidden": 64},
        "requests": {"batch": 4, "text_tokens": 8, "new_tokens": 8},
        "precision": {"weights": "float32", "activations": "float32",
                      "router": "float32", "accumulation": "float32"},
        # f32 on the CPU: the program's logits sit ~4e-7 from the
        # reference's, its routes and draws agree; the threshold's sum
        # order moves it ~1e-5
        "limits": {"logit_err": 1e-4, "logit_err_p50": 1e-4,
                   "route_disagree": 0.0, "rate_flips": 5,
                   "theta_gap": 1e-4, "token_faults": 0}}


def tiny_vlm() -> dict:
    cfg = copy.deepcopy(load_config("kimi_vl_a3b"))
    cfg.update(copy.deepcopy(TINY))
    fe = cfg["frontend"]
    fe.update({"in_hw": 56, "microbatch": 2,
               "precision": {"frontend": "float32"}})
    return cfg


def write_tiny_vlm(root) -> None:
    """The tiny configuration into a scratch checkout's ``bench/configs``."""
    path = os.path.join(str(root), "bench", "configs", "kimi_vl_a3b.json")
    with open(path, "w") as f:
        json.dump(tiny_vlm(), f)


@pytest.fixture(scope="module")
def vlm_checkout(tmp_path_factory):
    root = tmp_path_factory.mktemp("vlm") / "checkout"
    os.makedirs(root / "bench")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    for d in ("traffic", "cells", "metrics", "kinds"):
        shutil.copytree(os.path.join(ROOT, "bench", d), root / "bench" / d)
    (root / "bench" / "configs").mkdir()
    write_tiny_vlm(root)
    with open(os.path.join(ROOT, "bench", "peaks.json")) as f:
        peaks = json.load(f)
    peaks["devices"]["cpu"] = peaks["devices"]["TPU v5 lite"]
    (root / "bench" / "peaks.json").write_text(json.dumps(peaks))
    return str(root)


@pytest.fixture
def vlm(vlm_checkout):
    kind = run.load_kind(vlm_checkout, "p2m_vlm")
    steps = kind.reference.KEEP_STEPS
    # the tiny requests decode 8 tokens: compare early, middle and last
    kind.reference.KEEP_STEPS = (0, 1, 4, 7)
    yield kind
    kind.reference.KEEP_STEPS = steps


def test_cell_runs_and_is_correct(vlm_checkout, vlm, capsys):
    rc, res, err = drive(vlm_checkout, CELL, capsys=capsys)
    assert rc == 0 and res["correct"] is True, res["checks"]
    assert [c["name"] for c in res["checks"]] == list(TINY["limits"])
    assert set(res["metrics"]) == {"frames_per_s", "setup_s"}
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["attempted"] % TINY["requests"]["batch"] == 0


def _softmax_routing(logits, bias, top_k, scale):
    gates, idx = jax.lax.top_k(logits, top_k)
    return jax.nn.softmax(gates, axis=-1) * scale, idx


def _bias_in_weights(logits, bias, top_k, scale):
    scores = jax.nn.sigmoid(logits) + bias
    _, idx = jax.lax.top_k(scores, top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    return w / jnp.sum(w, axis=-1, keepdims=True) * scale, idx


@pytest.mark.parametrize("fault,failing", [
    (_softmax_routing, {"logit_err", "logit_err_p50", "route_disagree"}),
    (_bias_in_weights, {"logit_err", "logit_err_p50"})])
def test_planted_routing_fault_is_incorrect(vlm_checkout, vlm, capsys,
                                            monkeypatch, fault, failing):
    """Each fault moves the logits; softmax routing also picks other
    experts (the weights' fault does in later layers, through the
    changed hidden states); the sensor's numbers hold."""
    from repro.models import blocks
    monkeypatch.setattr(blocks, "route_sigmoid", fault)
    rc, res, _ = drive(vlm_checkout, CELL, capsys=capsys)
    assert rc == 0 and res["correct"] is False
    over = {c["name"] for c in res["checks"] if c["value"] > c["limit"]}
    assert failing <= over and not over & {"rate_flips", "theta_gap"}


def _argmin_pick(logits, temperature, rng):
    return jnp.argmin(logits, axis=-1).astype(jnp.int32)


def test_planted_token_fault_is_incorrect(vlm_checkout, vlm, capsys,
                                          monkeypatch):
    """Tokens that are not the greedy picks (argmin in place of argmax):
    the reference is teacher-forced on the returned tokens, so logits and
    routes still agree, and the token check alone calls the run
    incorrect."""
    from repro.serving import engine
    monkeypatch.setattr(engine, "_pick", _argmin_pick)
    rc, res, _ = drive(vlm_checkout, CELL, capsys=capsys)
    assert rc == 0 and res["correct"] is False
    over = {c["name"] for c in res["checks"] if c["value"] > c["limit"]}
    assert over == {"token_faults"}


def test_traced_run_reports_the_kinds_work(vlm_checkout, vlm, capsys):
    rc, res, _ = drive(vlm_checkout, CELL, trace=1, capsys=capsys)
    assert rc == 0 and res["correct"] is True
    # the CPU has no device plane: the trace's device metrics stay silent
    assert set(res["metrics"]) == {"step_mfu"}
    assert res["metrics"]["step_mfu"]["value"] > 0


def test_configuration_is_the_registered_architecture():
    """The benchmark's file is the program's ``kimi-vl-a3b`` but for its
    depth (and the dtypes it states)."""
    from repro import configs
    kind = run.load_kind(ROOT, "p2m_vlm")
    cfg = load_config("kimi_vl_a3b")
    got = kind.arch_config(cfg)
    want = dataclasses.replace(configs.get_arch("kimi-vl-a3b"),
                               name=got.name, num_layers=9, remat="none")
    assert got == want


def test_work_of_a_request():
    """Counted by hand at the cell's shapes: 64 experts of 3 x 2048 x 1408
    bf16 weights in 8 layers; 6 assignments per position per layer."""
    kind = run.load_kind(ROOT, "p2m_vlm")
    cfg = load_config("kimi_vl_a3b")
    experts = 8 * 64 * 3 * 2048 * 1408 * 2
    assert kind.expert_bytes(cfg) == experts == 8_858_370_048
    w = kind.work(cfg)
    assign = 2 * 3 * 2048 * 1408 * 6 * 8
    assert w["experts_prefill"] == {"flops": 128 * assign,
                                    "bytes": experts // 64}
    assert w["experts_decode"] == {"flops": 127 * assign,
                                   "bytes": 127 * experts // 64}
    # a decode step reads more than the experts and less than every weight
    step = kind.step_weight_bytes(cfg)
    assert experts < step < experts + 2 * 1_300_000_000
    assert w["decode"]["bytes"] > 127 * step // 64
