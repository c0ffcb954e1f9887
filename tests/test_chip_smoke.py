"""CPU checks of ``chip_smoke.py``, the on-chip smoke run, and of the
compile-cache rule it applies (``repro.platform.enable_compile_cache``).

Without a TPU the script must refuse to run; with the platform check
stubbed, its phases run in-process at a tiny size (``vgg_tiny``, a few
frames) so their control flow and checks are exercised here."""
import functools
import json
import os
import subprocess
import sys

import jax
import pytest

from repro import platform

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def smoke(monkeypatch):
    sys.path.insert(0, ROOT)
    try:
        import chip_smoke
    finally:
        sys.path.remove(ROOT)
    monkeypatch.setattr(chip_smoke, "require_tpu",
                        lambda count: jax.devices())
    # this process keeps running other tests: leave JAX's cache config alone
    monkeypatch.setattr(chip_smoke.platform, "enable_compile_cache",
                        lambda: "unchanged")
    tiny = dict(arch="vgg_tiny")
    for name, kw in (("serve_phase", dict(batches=2, batch=8, microbatch=4)),
                     ("fleet_phase", dict(chips=2, frames=4,
                                          chips_per_step=2)),
                     ("train_phase", dict(steps=1, batch=4)),
                     ("sharded_phase", dict(frames=4, fleet_frames=2))):
        monkeypatch.setattr(chip_smoke, name, functools.partial(
            getattr(chip_smoke, name), **tiny, **kw))
    return chip_smoke


def _last_json(text: str) -> dict:
    return json.loads(text.strip().splitlines()[-1])


def test_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert proc.stdout == ""          # no result, no CPU fallback
    assert "no TPU" in proc.stderr


def test_phases_run_in_process(smoke, capsys):
    assert smoke.main([]) == 0
    out = capsys.readouterr().out
    assert _last_json(out) == {"ok": True, "device": {
        "platform": jax.devices()[0].platform,
        "kind": jax.devices()[0].device_kind, "count": len(jax.devices())}}
    for phase in ("phase=serve", "phase=fleet", "phase=train"):
        assert phase in out
    assert "flips=" in out and "fused_steps=" in out


def test_sharded_phase_in_process(smoke, capsys):
    assert smoke.main(["--four-chips"]) == 0
    out = capsys.readouterr().out
    assert "phase=sharded" in out and "phase=serve" not in out
    assert _last_json(out)["device"]["count"] == len(jax.devices())


def test_failed_check_raises(smoke):
    with pytest.raises(smoke.SmokeError, match="boom"):
        smoke.check(False, "boom")


class TestCompileCache:
    def test_env_dir_is_used_and_nothing_set(self, monkeypatch, tmp_path):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        before = jax.config.jax_compilation_cache_dir
        assert platform.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before

    def test_fixed_in_checkout_dir_otherwise(self, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        before = jax.config.jax_compilation_cache_dir
        try:
            path = platform.enable_compile_cache()
            assert path == os.path.join(ROOT, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == path
        finally:
            jax.config.update("jax_compilation_cache_dir", before)


class TestPallasMode:
    def test_interpreted_off_a_tpu_compiled_on_one(self, monkeypatch):
        assert platform.pallas_interpret() is (not platform.on_tpu())
        monkeypatch.setattr(platform, "on_tpu", lambda: True)
        assert platform.pallas_interpret() is False
        assert platform.pallas_interpret(True) is True   # explicit wins
