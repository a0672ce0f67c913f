"""Unit + property tests for the core tile framework.

Property tests use hypothesis when installed (requirements-dev.txt) and fall
back to a fixed deterministic case table otherwise (_hypothesis_compat).
"""
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import tiles
from repro.core.grid_swizzle import (SwizzleConfig, ROW_MAJOR, dma_bytes,
                                     is_permutation, schedule_order,
                                     best_window, chiplet_transform_chunked)
from repro.core.cache_model import CacheHW, simulate_gemm_schedule
from repro.core.schedule import PINGPONG, INTERLEAVE, WAVE_SPECIALIZED, get_schedule
from repro.core import perf_model as pm


class TestTiles:
    def test_native_tiling(self):
        assert tiles.native_tiling("float32") == (8, 128)
        assert tiles.native_tiling("bfloat16") == (16, 128)
        assert tiles.native_tiling("int8") == (32, 128)

    def test_tile_legality(self):
        tiles.TileSpec(256, 256, "bfloat16")
        with pytest.raises(ValueError):
            tiles.TileSpec(100, 256, "bfloat16")   # rows not sublane-aligned
        with pytest.raises(ValueError):
            tiles.TileSpec(256, 100, "bfloat16")   # cols not lane-aligned

    def test_vmem_budget(self):
        used = tiles.check_vmem_budget(
            [((512, 512), "bfloat16"), ((512, 512), "bfloat16")],
            n_buffers=2, scratch_bytes=512 * 512 * 4)
        assert used == 2 * 2 * 512 * 512 * 2 + 512 * 512 * 4
        with pytest.raises(ValueError):
            tiles.check_vmem_budget([((8192, 8192), "float32")], n_buffers=4)

    @given(st.integers(1, 64), st.integers(1, 64))
    def test_padded_bytes_at_least_exact(self, r, c):
        exact = r * c * 2
        assert tiles.padded_tile_bytes((r, c), "bfloat16") >= exact


class TestSwizzle:
    @given(rows=st.integers(1, 40), cols=st.integers(1, 40),
           window=st.integers(1, 16), chunk=st.integers(1, 64),
           n_xcd=st.sampled_from([2, 4, 8]))
    @settings(max_examples=200, deadline=None)
    def test_algorithm1_is_permutation(self, rows, cols, window, chunk, n_xcd):
        cfg = SwizzleConfig(window=window, chunk=chunk, n_xcd=n_xcd)
        assert is_permutation(cfg, rows, cols)

    @given(blocks=st.integers(1, 512), chunk=st.integers(1, 32),
           n_xcd=st.sampled_from([2, 4, 8]))
    @settings(max_examples=100, deadline=None)
    def test_chiplet_transform_bijective(self, blocks, chunk, n_xcd):
        xy = np.arange(blocks)
        out = chiplet_transform_chunked(xy, blocks, n_xcd, chunk)
        assert sorted(out.tolist()) == list(range(blocks))

    def test_traced_remap_matches_numpy(self):
        import jax
        import jax.numpy as jnp
        cfg = SwizzleConfig(window=8, chunk=64)
        order = schedule_order(cfg, 36, 36)
        f = jax.jit(lambda t: cfg.remap(t, 36, 36))
        for i in (0, 17, 500, 36 * 36 - 1):
            r, c = f(jnp.int32(i))
            assert (int(r), int(c)) == tuple(order[i])

    def test_dma_model_row_major_reuses_a(self):
        # row-major keeps the A row-block for num_cols consecutive steps
        b = dma_bytes(ROW_MAJOR, 16, 16, 1000, 1000)
        assert b == (16 + 256) * 1000

    def test_best_window_picks_larger_operand(self):
        # much bigger B blocks => column-runs (large W) should win
        cfg = best_window(16, 16, 10, 100000, candidates=(1, 16))
        assert cfg.window == 16
        cfg = best_window(16, 16, 100000, 10, candidates=(1, 16))
        assert cfg.window == 1


class TestCacheModel:
    def test_l2_llc_tradeoff(self):
        """Paper Tab. 4: maximizing L2 alone (huge chunk) degrades LLC."""
        base = simulate_gemm_schedule(ROW_MAJOR, m=9216, n=9216, k=9216,
                                      block_m=192, block_n=256, block_k=64)
        l2_greedy = simulate_gemm_schedule(
            SwizzleConfig(window=7, chunk=216), m=9216, n=9216, k=9216,
            block_m=192, block_n=256, block_k=64)
        assert l2_greedy.l2_hit > base.l2_hit
        assert l2_greedy.llc_hit < base.llc_hit

    def test_hit_rates_are_rates(self):
        r = simulate_gemm_schedule(SwizzleConfig(window=5, chunk=25),
                                   m=2304, n=2304, k=2304,
                                   block_m=192, block_n=256, block_k=64)
        assert 0 <= r.l2_hit <= 1 and 0 <= r.llc_hit <= 1
        assert r.l2_hit + r.llc_hit <= 1 + 1e-9
        assert r.modeled_tflops > 0


class TestPerfModel:
    def test_output_tile_dominates(self):
        """Paper Tab. 2's conclusion, on the TPU model: bigger output tile →
        higher arithmetic intensity → more modeled TFLOPs."""
        small = pm.gemm_step_model(INTERLEAVE, k_total=8192)
        big = pm.gemm_step_model(PINGPONG, k_total=8192)
        assert big["modeled_tflops"] > small["modeled_tflops"]
        assert big["arithmetic_intensity"] > small["arithmetic_intensity"]

    def test_producer_tax_shrinks_best_tile(self):
        """Wave specialization's VMEM tax shrinks the feasible output tile
        (the paper's Tab. 2 negative result)."""
        full = pm.best_output_tile(tiles.VMEM_BYTES, 2, 512)
        taxed = pm.best_output_tile(WAVE_SPECIALIZED.vmem_budget(), 2, 512)
        assert taxed[0] * taxed[1] <= full[0] * full[1]

    def test_ridge_point(self):
        # 512x512 tiles are compute bound on v5e; 256x256 are not
        assert pm.gemm_step_model(PINGPONG, k_total=4096)["bound"] == "compute"
        s = get_schedule("interleave")
        assert pm.gemm_step_model(s, k_total=4096)["bound"] == "memory"

    def test_roofline_terms(self):
        r = pm.roofline(1e15, 1e12, 1e11, n_chips=256)
        assert r.compute_s > 0 and r.memory_s > 0 and r.collective_s > 0
        assert r.bound in ("compute", "memory", "collective")


class TestCompileCache:
    """The persistent compile cache: the caller's JAX_COMPILATION_CACHE_DIR
    when set (and no other directory set in code), else the fixed
    <checkout>/.jax_cache."""

    @pytest.fixture
    def jax_config(self):
        import jax
        names = ("jax_compilation_cache_dir",
                 "jax_persistent_cache_min_compile_time_secs")
        saved = {n: getattr(jax.config, n) for n in names}
        yield jax.config
        for n, v in saved.items():
            jax.config.update(n, v)

    def test_env_dir_wins(self, jax_config, monkeypatch, tmp_path):
        from repro.util import enable_compile_cache
        jax_config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax_config.jax_compilation_cache_dir is None

    def test_fixed_checkout_dir(self, jax_config, monkeypatch):
        import os
        from repro.util import CHECKOUT, enable_compile_cache
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        path = os.path.join(CHECKOUT, ".jax_cache")
        assert enable_compile_cache() == path
        assert enable_compile_cache() == path      # stable across calls
        assert jax_config.jax_compilation_cache_dir == path
        assert os.path.isfile(os.path.join(CHECKOUT, "chip_smoke.py"))
