"""Tile-autotuner tests (kernels/autotune.py): deterministic resolution,
cache-hit stability, JSON persistence, the measured search, and the
no-jit-cache-growth property of autotuned frontend calls."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import tracecheck
from repro.core import p2m
from repro.kernels import autotune, ops

CFG = p2m.P2MConfig()


@pytest.fixture(autouse=True)
def _fresh_table():
    """Each test starts from an empty in-process table and leaves none of
    its entries behind (the table is process-global by design)."""
    saved = dict(autotune._TABLE)
    autotune.clear()
    yield
    autotune.clear()
    autotune._TABLE.update(saved)


class TestDeterministicResolution:
    def test_get_records_default_and_is_stable(self):
        a = autotune.get(4096, 27, 32)
        b = autotune.get(4096, 27, 32)
        assert a == b == autotune.default_choice(4096, 27, 32)
        assert autotune.lookup(4096, 27, 32) == a

    def test_resolve_explicit_wins(self):
        autotune.put(512, 27, 32, autotune.TileChoice(64, 128))
        assert autotune.resolve(512, 27, 32, 256, 1024) == (256, 1024)
        assert autotune.resolve(512, 27, 32, None, 1024) == (64, 1024)
        assert autotune.resolve(512, 27, 32) == (64, 128)

    def test_resolve_fused_whole_n_default(self):
        assert autotune.resolve_fused(512, 27, 32) == 512
        autotune.put(512, 27, 32,
                     autotune.TileChoice(64, 128, block_n_fused=256))
        assert autotune.resolve_fused(512, 27, 32) == 256
        assert autotune.resolve_fused(512, 27, 32, 128) == 128

    def test_tuned_entry_survives_repeated_resolution(self):
        tuned = autotune.TileChoice(block_n=128, block_n_elem=512,
                                    block_n_fused=512, fused=False)
        autotune.put(512, 27, 32, tuned)
        for _ in range(3):
            assert autotune.get(512, 27, 32) == tuned

    def test_default_choice_keeps_exact_path_at_two_plus_steps(self):
        """The heuristic must never hand the exact path a whole-N block —
        that would double the per-step matmul census past the 1.2x-of-ideal
        budget (frontend_bench --quick gates it)."""
        for n in (128, 512, 4096, 65536):
            c = autotune.default_choice(n, 27, 32)
            assert c.block_n <= max(n // 2, 1)
            assert c.block_n_fused == n


class TestPersistence:
    def test_json_roundtrip(self, tmp_path):
        autotune.put(4096, 27, 32, autotune.TileChoice(2048, 4096, 4096,
                                                       True))
        autotune.put(512, 27, 32, autotune.TileChoice(128, 512, 512, False))
        path = str(tmp_path / "tiles.json")
        autotune.save_table(path)
        autotune.clear()
        assert autotune.lookup(4096, 27, 32) is None
        # the "_meta" provenance stamp is present but NOT a table entry
        import json
        with open(path) as f:
            raw = json.load(f)
        assert raw["_meta"]["bench"] == "autotune"
        assert raw["_meta"]["entries"] == 2
        assert autotune.load_table(path) == 2
        assert autotune.lookup(4096, 27, 32) == autotune.TileChoice(
            2048, 4096, 4096, True)
        assert autotune.lookup(512, 27, 32) == autotune.TileChoice(
            128, 512, 512, False)


    def test_refuses_a_table_tuned_on_another_device(self, tmp_path):
        """Tiles tuned on one device say nothing about another: a table
        stamped for another backend/device kind must not load."""
        import json
        autotune.put(4096, 27, 32, autotune.TileChoice(2048, 4096, 4096,
                                                       True))
        path = str(tmp_path / "tiles.json")
        autotune.save_table(path)
        with open(path) as f:
            raw = json.load(f)
        raw["_meta"].update(backend="tpu", device_kind="TPU v5 lite")
        with open(path, "w") as f:
            json.dump(raw, f)
        autotune.clear()
        with pytest.raises(ValueError, match="tuned on"):
            autotune.load_table(path)
        assert autotune.lookup(4096, 27, 32) is None

    def test_refuses_the_unstamped_cpu_bench_table(self):
        """The committed BENCH_frontend_tiles.json was tuned on the CPU
        interpreter before tables carried a device kind."""
        import os
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        with pytest.raises(ValueError, match="unstamped"):
            autotune.load_table(os.path.join(root,
                                             "BENCH_frontend_tiles.json"))


class TestSearch:
    def test_autotune_frontend_stores_a_candidate(self):
        params = p2m.init_params(jax.random.PRNGKey(0), CFG)
        frames = jax.random.uniform(jax.random.PRNGKey(1), (2, 16, 16, 3))
        wq = p2m.quantize_weights(params["w"], CFG.weight_bits)
        choice, report = autotune.autotune_frontend(
            frames, wq, params["v_th"], jax.random.PRNGKey(2), repeats=1)
        n = 2 * 8 * 8
        assert (choice.block_n, choice.block_n_elem) in {
            (c.block_n, c.block_n_elem) for c in autotune.candidate_choices(n)}
        assert choice.block_n_fused in set(autotune.fused_candidates(n))
        assert autotune.lookup(n, 27, CFG.out_channels) == choice
        assert report["two_kernel"] and report["fused"]
        assert all(ms > 0 for ms in report["two_kernel"].values())

    def test_search_result_changes_resolution_not_results(self):
        """Tuning moves tiles, never numerics: at the precision the search
        picks (f32 or int8 — a timing-dependent choice), the frontend
        output for a fixed key is identical before and after the search."""
        params = p2m.init_params(jax.random.PRNGKey(0), CFG)
        frames = jax.random.uniform(jax.random.PRNGKey(1), (2, 16, 16, 3))
        wq = p2m.quantize_weights(params["w"], CFG.weight_bits)
        key = jax.random.PRNGKey(5)
        before = {prec: ops.p2m_frontend(frames, wq, params["v_th"], key,
                                         precision=prec)
                  for prec in ("f32", "int8")}
        choice, _ = autotune.autotune_frontend(
            frames, wq, params["v_th"], jax.random.PRNGKey(2), repeats=1)
        after, aux_a = ops.p2m_frontend(frames, wq, params["v_th"], key)
        acts_b, aux_b = before[choice.precision]
        np.testing.assert_array_equal(np.asarray(acts_b), np.asarray(after))
        np.testing.assert_allclose(float(aux_b["theta"]),
                                   float(aux_a["theta"]), rtol=1e-6)


class TestJitCacheStability:
    def test_no_jit_cache_growth_on_repeated_autotuned_calls(self):
        """Auto-resolved tiles are a pure function of the shape, so after
        the first call at a shape, further calls (fresh keys, fresh frames,
        repeated table resolution) never compile the inner frontend again
        — and a second shape adds at most one new entry."""
        params = p2m.init_params(jax.random.PRNGKey(0), CFG)
        wq = p2m.quantize_weights(params["w"], CFG.weight_bits)
        frames = jax.random.uniform(jax.random.PRNGKey(1), (2, 24, 24, 3))
        ops.p2m_frontend(frames, wq, params["v_th"], jax.random.PRNGKey(0))
        size1 = ops._p2m_frontend._cache_size()
        with tracecheck.capture() as rec:
            for i in range(1, 4):
                ops.p2m_frontend(
                    jax.random.uniform(jax.random.PRNGKey(i),
                                       (2, 24, 24, 3)),
                    wq, params["v_th"], jax.random.PRNGKey(i))
            tracecheck.assert_jit_cache(ops._p2m_frontend, size1,
                                        recorder=rec,
                                        what="ops._p2m_frontend")
            frames2 = jax.random.uniform(jax.random.PRNGKey(9),
                                         (4, 24, 24, 3))
            ops.p2m_frontend(frames2, wq, params["v_th"],
                             jax.random.PRNGKey(0))
            size2 = ops._p2m_frontend._cache_size()
            assert size2 <= size1 + 1
            for i in range(1, 3):
                ops.p2m_frontend(frames2, wq, params["v_th"],
                                 jax.random.PRNGKey(i))
            tracecheck.assert_jit_cache(ops._p2m_frontend, size2,
                                        recorder=rec,
                                        what="ops._p2m_frontend")

    def test_fused_wrapper_cache_stable_across_theta_values(self):
        params = p2m.init_params(jax.random.PRNGKey(0), CFG)
        wq = p2m.quantize_weights(params["w"], CFG.weight_bits)
        frames = jax.random.uniform(jax.random.PRNGKey(1), (2, 24, 24, 3))
        ops.p2m_frontend_fused(frames, wq, params["v_th"], jnp.asarray(0.7),
                               jax.random.PRNGKey(0))
        size1 = ops._p2m_frontend_fused._cache_size()
        with tracecheck.capture() as rec:
            for i, th in enumerate((0.3, 0.5, 0.9)):
                ops.p2m_frontend_fused(frames, wq, params["v_th"],
                                       jnp.asarray(th),
                                       jax.random.PRNGKey(i))
            tracecheck.assert_jit_cache(ops._p2m_frontend_fused, size1,
                                        recorder=rec,
                                        what="ops._p2m_frontend_fused")


class TestFleetLookups:
    """Fleet-shape-aware lookups (PR 6): a (G, N, K, C) fleet step resolves
    through the per-chip (N, K, C) table row — the chip axis never keys the
    table, so the cache cannot grow with the fleet."""

    def test_fleet_key_drops_the_chip_axis(self):
        for g in (1, 2, 5, 9):
            assert autotune.fleet_key(g, 4096, 27, 32) == \
                autotune.shape_key(4096, 27, 32)

    def test_get_fleet_matches_single_chip_choice(self):
        single = autotune.get(4096, 27, 32)
        for g in (1, 3, 7):
            assert autotune.get_fleet(g, 4096, 27, 32) == single

    def test_fleet_resolution_sees_tuned_entries(self):
        tuned = autotune.TileChoice(block_n=128, block_n_elem=512,
                                    block_n_fused=256, fused=True)
        autotune.put(512, 27, 32, tuned)
        assert autotune.resolve_fleet(4, 512, 27, 32) == (128, 512)
        assert autotune.resolve_fleet_fused(4, 512, 27, 32) == 256
        assert autotune.get_fleet(4, 512, 27, 32).fused

    def test_table_does_not_grow_with_chip_count(self):
        for g in range(1, 12):
            autotune.get_fleet(g, 2048, 27, 32)
            autotune.resolve_fleet(g, 2048, 27, 32)
            autotune.resolve_fleet_fused(g, 2048, 27, 32)
        assert len(autotune._TABLE) == 1

    def test_fleet_wrapper_jit_cache_stable_across_fleet_sizes(self):
        """ops.p2m_frontend_fleet vmaps one per-chip kernel: growing the
        chip axis adds (at most) one cache entry per G, and repeated calls
        at a G re-use it — the table itself stays at one row."""
        params = p2m.init_params(jax.random.PRNGKey(0), CFG)
        wq = p2m.quantize_weights(params["w"], CFG.weight_bits)

        def call(g, seed=0):
            frames = jax.random.uniform(jax.random.PRNGKey(seed),
                                        (g, 2, 24, 24, 3))
            keys = jax.random.split(jax.random.PRNGKey(seed + 1), g)
            return ops.p2m_frontend_fleet(frames, wq, params["v_th"], keys)

        call(2)
        size1 = ops._p2m_frontend._cache_size()
        with tracecheck.capture() as rec:
            for i in range(1, 4):
                call(2, seed=i)
            tracecheck.assert_jit_cache(ops._p2m_frontend, size1,
                                        recorder=rec,
                                        what="ops._p2m_frontend")
        assert len(autotune._TABLE) == 1
