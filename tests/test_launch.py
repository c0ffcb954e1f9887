"""Launch-layer tests: sharding rules, input specs, HLO collective parsing,
and a miniature dry-run (lower+compile) on the host device mesh."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import configs, sharding
from repro.configs.base import ShapeSpec
from repro.configs.reduced import reduced
from repro.launch import hlo_analysis
from repro.launch.mesh import make_host_mesh
from repro.launch.specs import (abstract_params_sharded, batch_spec,
                                input_specs)


class TestShardingRules:
    def test_default_rules_map(self):
        mesh = make_host_mesh()
        rules = sharding.ShardingRules.make()
        spec = sharding.logical_to_spec(("vocab", "embed"), (64, 32), mesh,
                                        rules)
        assert spec == P("model", None)

    def test_non_divisible_replicates(self):
        # emulate the production 16-way model axis with an abstract mesh
        mesh = jax.sharding.AbstractMesh((2, 4), ("data", "model"))
        rules = sharding.ShardingRules.make()
        # 7 not divisible by the 4-way model axis -> replicated
        spec = sharding.logical_to_spec(("heads",), (7,), mesh, rules)
        assert spec == P(None)
        spec8 = sharding.logical_to_spec(("heads",), (8,), mesh, rules)
        assert spec8 == P("model")

    def test_overrides(self):
        rules = sharding.ShardingRules.make({"heads": None})
        assert rules.lookup("heads") is None
        assert rules.lookup("ffn") == "model"

    def test_axis_used_once(self):
        """The same mesh axis must not shard two dims of one tensor."""
        mesh = make_host_mesh()
        rules = sharding.ShardingRules.make(
            {"vocab": "data", "embed": "data"})
        spec = sharding.logical_to_spec(("vocab", "embed"),
                                        (len(jax.devices()) * 2,
                                         len(jax.devices()) * 2), mesh, rules)
        flat = [s for s in spec if s is not None]
        assert len(flat) <= 1


class TestInputSpecs:
    def test_batch_spec_falls_back_to_replicated(self):
        mesh = make_host_mesh()
        # batch=1 cannot shard over data axis unless data==1
        sp = batch_spec(mesh, 1)
        if len(jax.devices()) > 1:
            assert sp == P(None) or sp == P(())

    def test_train_specs_shapes(self):
        mesh = make_host_mesh()
        cfg = reduced(configs.get_arch("granite-8b"))
        shape = ShapeSpec("t", 64, len(jax.devices()) * 2, "train")
        ins = input_specs(cfg, shape, mesh)
        assert ins["tokens"].shape == (shape.global_batch, 64)
        assert ins["labels"].dtype == jnp.int32

    def test_encdec_gets_encoder_stub(self):
        mesh = make_host_mesh()
        cfg = reduced(configs.get_arch("whisper-base"))
        ins = input_specs(cfg, ShapeSpec("t", 32, 2, "train"), mesh)
        assert "encoder_embeddings" in ins
        assert ins["encoder_embeddings"].shape == (2, cfg.encoder_seq,
                                                   cfg.d_model)


HLO_SAMPLE = """
  %x = bf16[8,128]{1,0} parameter(0)
  %ag = bf16[8,2048]{1,0} all-gather(bf16[8,128]{1,0} %x), dimensions={1}
  %ar = f32[256]{0} all-reduce(f32[256]{0} %y), to_apply=%sum
  %rs.1 = f32[16,8]{1,0} reduce-scatter(f32[128,8]{1,0} %z), dimensions={0}
  %cp = bf16[4,4]{1,0} collective-permute(bf16[4,4]{1,0} %w)
  %ags = (bf16[8,64]{1,0}, bf16[8,64]{1,0}) all-gather-start(bf16[8,64]{1,0} %v)
  %agd = bf16[8,64]{1,0} all-gather-done((bf16[8,64]{1,0}) %ags)
  %dot = f32[8,8]{1,0} dot(f32[8,16]{1,0} %a, f32[16,8]{1,0} %b)
"""


class TestHLOAnalysis:
    def test_collective_stats_parses_kinds(self):
        st = hlo_analysis.collective_stats(HLO_SAMPLE)
        assert st["all-gather"] == 8 * 2048 * 2 + 8 * 64 * 2  # + async start
        assert st["all-reduce"] == 2 * 256 * 4               # 2x volume model
        assert st["reduce-scatter"] == 128 * 8 * 4   # volume ~ larger buffer
        assert st["collective-permute"] == 4 * 4 * 2
        assert st["count"] == 5                              # done not counted

    def test_roofline_terms(self):
        rf = hlo_analysis.roofline(
            {"flops": 197e12, "bytes accessed": 819e9},
            {"total_bytes": 50e9, "count": 3}, n_chips=256)
        np.testing.assert_allclose(rf["t_compute_s"], 1.0)
        np.testing.assert_allclose(rf["t_memory_s"], 1.0)
        np.testing.assert_allclose(rf["t_collective_s"], 1.0)

    def test_model_flops_positive_all_archs(self):
        from repro.configs.base import TRAIN_4K, DECODE_32K
        for name, cfg in configs.ARCHS.items():
            f_train = hlo_analysis.model_flops_estimate(cfg, TRAIN_4K)
            f_dec = hlo_analysis.model_flops_estimate(cfg, DECODE_32K)
            assert f_train > 0 and f_dec > 0
            assert f_train > f_dec   # train processes far more tokens


class TestMiniDryRun:
    """lower+compile a reduced cell on the actual host mesh — exercises the
    same build path as the 512-device production dry-run."""

    @pytest.mark.parametrize("arch", ["granite-8b", "deepseek-v2-236b",
                                      "recurrentgemma-2b"])
    def test_train_cell_compiles(self, arch):
        from repro.launch.dryrun import build_cell
        cfg = reduced(configs.get_arch(arch))
        mesh = make_host_mesh()
        shape = ShapeSpec("t", 32, max(2, len(jax.devices())), "train")
        with mesh:
            fn, args = build_cell(cfg, shape, mesh)
            compiled = fn.lower(*args).compile()
            assert compiled.cost_analysis().get("flops", 0) > 0

    def test_prefill_cell_compiles(self):
        """The dry-run lowers the engine's own prefill (cache grown by the
        token it picks)."""
        from repro.launch.dryrun import build_cell
        cfg = reduced(configs.get_arch("glm4-9b"))
        mesh = make_host_mesh()
        shape = ShapeSpec("p", 64, max(2, len(jax.devices())), "prefill")
        with mesh:
            fn, args = build_cell(cfg, shape, mesh)
            compiled = fn.lower(*args).compile()
            assert compiled.cost_analysis().get("flops", 0) > 0

    def test_decode_cell_compiles(self):
        from repro.launch.dryrun import build_cell
        cfg = reduced(configs.get_arch("glm4-9b"))
        mesh = make_host_mesh()
        shape = ShapeSpec("d", 64, max(2, len(jax.devices())), "decode")
        with mesh:
            fn, args = build_cell(cfg, shape, mesh)
            mem = fn.lower(*args).compile().memory_analysis()
            assert getattr(mem, "argument_size_in_bytes", 1) > 0
