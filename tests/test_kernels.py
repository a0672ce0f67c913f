"""Per-kernel correctness: shape/dtype sweeps, Pallas (interpret) vs ref.py."""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import autotune
from repro.core.grid_swizzle import SwizzleConfig
from repro.core.policy import make_policy
from repro.kernels.gemm import (Epilogue, Prologue, gemm, gemm_fused,
                                gemm_fused_ref, gemm_ref)
from repro.kernels.attention import (attention, attention_ref,
                                     flash_attention_fwd)
from repro.kernels.attention.ref import attention_ref_chunked
from repro.kernels.fused_norm import (dropout_residual_layernorm,
                                      fused_dropout_residual_layernorm_ref)
from repro.kernels.fused_norm.ref import dropout_keep_mask_ref
from repro.kernels.rope import rope, rope_ref, rope_tables

KEY = jax.random.PRNGKey(0)


class TestGemm:
    @pytest.mark.parametrize("m,n,k", [(128, 128, 128), (256, 512, 384),
                                       (512, 256, 1280), (384, 384, 256)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, m, n, k, dtype):
        a = jax.random.normal(KEY, (m, k), dtype)
        b = jax.random.normal(jax.random.PRNGKey(1), (k, n), dtype)
        pol = make_policy("gemm", block_m=256, block_n=256, block_k=256)
        out = gemm(a, b, policy=pol, out_dtype=jnp.float32)
        ref = gemm_ref(a, b, jnp.float32)
        # k-blocked accumulation reassociates adds; tolerance covers that
        tol = 1e-3 if dtype == jnp.float32 else 3e-2
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=tol, atol=tol)

    def test_autotuned_matches_ref(self):
        """The no-keyword surface (autotuner resolution) stays exact too."""
        a = jax.random.normal(KEY, (256, 384), jnp.float32)
        b = jax.random.normal(jax.random.PRNGKey(1), (384, 256), jnp.float32)
        out = gemm(a, b, out_dtype=jnp.float32)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(gemm_ref(a, b, jnp.float32)),
                                   rtol=1e-3, atol=1e-3)

    @pytest.mark.parametrize("swizzle", [
        SwizzleConfig(window=2, chunk=4),
        SwizzleConfig(window=4, chunk=2, enable_chiplet=False)])
    def test_swizzle_invariance(self, swizzle):
        """Grid order must never change the numbers — Algorithm 1 is a pure
        scheduling transform, so every swizzle is BITWISE identical to the
        row-major traversal (same blocks, explicit policies)."""
        a = jax.random.normal(KEY, (512, 256), jnp.float32)
        b = jax.random.normal(jax.random.PRNGKey(1), (256, 512), jnp.float32)
        base_pol = make_policy("gemm", block_m=128, block_n=128, block_k=128)
        swz_pol = make_policy("gemm", block_m=128, block_n=128, block_k=128,
                              swizzle=swizzle)
        base = gemm(a, b, policy=base_pol, out_dtype=jnp.float32)
        out = gemm(a, b, policy=swz_pol, out_dtype=jnp.float32)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(base))

    def test_legacy_swizzle_shim_routes_through_autotuner(self):
        """The swizzle-only legacy surface no longer pins the hard-coded
        pingpong-512 schedule: it ranks the autotuner's candidates under
        the requested traversal order (and still warns). The resolved
        policy's blocks tile the problem exactly — no silent _fit_policy
        clamp for small shapes."""
        m, n, k = 192, 320, 160   # divisor-unfriendly for 512-blocks
        a = jax.random.normal(KEY, (m, k), jnp.float32)
        b = jax.random.normal(jax.random.PRNGKey(1), (k, n), jnp.float32)
        sw = SwizzleConfig(window=2, enable_chiplet=False)
        with pytest.warns(DeprecationWarning, match="policy=KernelPolicy"):
            out = gemm(a, b, swizzle=sw, out_dtype=jnp.float32)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(gemm_ref(a, b, jnp.float32)),
                                   rtol=1e-3, atol=1e-3)
        pol = autotune.select_policy("gemm", (m, n, k), "float32", swizzle=sw)
        assert pol.swizzle == sw
        assert pol.fits(m, n, k), pol.describe()


def _rand(key, shape, dtype):
    x = jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32) * 0.5
    return x.astype(dtype)


# every epilogue chain shape the model layers use, plus compositions
EPILOGUE_CHAINS = [
    Epilogue(),
    Epilogue(bias=True),
    Epilogue(activation="relu"),
    Epilogue(bias=True, activation="gelu"),
    Epilogue(bias=True, activation="silu", residual=True),
    Epilogue(residual=True, scale=True),           # fused down-proj store
    Epilogue(activation="silu", gate=True),        # dual-output SwiGLU
    Epilogue(activation="gelu", gate=True, residual=True, scale=True),
    Epilogue(rope=True, head_dim=64),              # QKV→RoPE prologue
    Epilogue(bias=True, rope=True, head_dim=64, scale=True),
    Epilogue(scale=True, scale_kind="row"),        # fp8 per-row dequant
    Epilogue(scale=True, scale_kind="col", activation="gelu"),  # per-channel
    Epilogue(scale=True, scale_kind="col", gate=True, activation="silu"),
]

# {fp32, bf16, fp8-scaled} × oracle tolerance. fp8 operands feed the MXU as
# bf16 (exact), but the oracle contracts in fp32 — tolerance covers the
# product rounding; the scale chain is exercised on top for every dtype.
EPILOGUE_DTYPES = [(jnp.float32, 1e-3), (jnp.bfloat16, 3e-2),
                   (jnp.float8_e4m3fn, 6e-2)]


class TestEpilogue:
    """Fused GEMM epilogue/prologue chains vs the unfused jnp oracle."""

    def _operands(self, epilogue, m, n, k, dtype):
        ops = {}
        if epilogue.gate:
            ops["b2"] = _rand(2, (k, n), dtype)
        if epilogue.bias:
            ops["bias"] = _rand(3, (n,), jnp.float32)
        if epilogue.residual:
            ops["residual"] = _rand(4, (m, n), jnp.float32)
        if epilogue.scale:
            if epilogue.scale_kind == "row":
                ops["scale"] = _rand(5, (m, 1), jnp.float32) * 0.1 + 1.0
            elif epilogue.scale_kind == "col":
                ops["scale"] = _rand(5, (n,), jnp.float32) * 0.1 + 1.0
            else:
                ops["scale"] = 0.625
        if epilogue.rope:
            sin, cos = rope_tables(jnp.arange(m), epilogue.head_dim)
            ops["sin"], ops["cos"] = sin, cos
        return ops

    @pytest.mark.parametrize("dtype,tol", EPILOGUE_DTYPES,
                             ids=["fp32", "bf16", "fp8"])
    @pytest.mark.parametrize("ep", EPILOGUE_CHAINS,
                             ids=[e.describe() for e in EPILOGUE_CHAINS])
    def test_chain_matches_oracle(self, ep, dtype, tol):
        m, k, n = 128, 256, 256
        a = _rand(0, (m, k), dtype)
        b = _rand(1, (k, n), dtype)
        ops = self._operands(ep, m, n, k, dtype)
        out = gemm_fused(a, b, epilogue=ep, out_dtype=jnp.float32, **ops)
        ref = gemm_fused_ref(a, b, epilogue=ep, out_dtype=jnp.float32, **ops)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=tol, atol=tol)

    @pytest.mark.parametrize("dtype,tol", EPILOGUE_DTYPES,
                             ids=["fp32", "bf16", "fp8"])
    def test_fp8_style_scaled_dequant(self, dtype, tol):
        """scale epilogue = the fp8 dequant path: out = s·(A@B), with the
        scale applied to BOTH accumulators of the dual-output GEMM."""
        m, k, n = 128, 128, 256
        a = _rand(0, (m, k), dtype)
        b = _rand(1, (k, n), dtype)
        b2 = _rand(2, (k, n), dtype)
        s = 0.125
        ep = Epilogue(activation="silu", gate=True, scale=True)
        out = gemm_fused(a, b, b2=b2, scale=s, epilogue=ep,
                         out_dtype=jnp.float32)
        af, bf, b2f = (x.astype(jnp.float32) for x in (a, b, b2))
        ref = jax.nn.silu(s * (af @ bf)) * (s * (af @ b2f))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=tol, atol=tol)

    def test_swiglu_dual_output_matches_mlp_oracle(self):
        """The dual-output GEMM is exactly the two-up-projection SwiGLU."""
        t, d, f = 128, 256, 384
        x = _rand(0, (t, d), jnp.float32)
        wg = _rand(1, (d, f), jnp.float32)
        wi = _rand(2, (d, f), jnp.float32)
        out = gemm_fused(x, wg, b2=wi,
                         epilogue=Epilogue(activation="silu", gate=True),
                         out_dtype=jnp.float32)
        ref = jax.nn.silu(x @ wg) * (x @ wi)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-3, atol=1e-3)

    @pytest.mark.parametrize("head_dim", [64, 128])
    def test_qkv_rope_prologue_matches_oracle(self, head_dim):
        """rope epilogue == project-then-rotate with the rope kernel oracle."""
        s, d, heads = 256, 128, 4
        n = heads * head_dim
        x = _rand(0, (s, d), jnp.float32)
        w = _rand(1, (d, n), jnp.float32)
        sin, cos = rope_tables(jnp.arange(s), head_dim)
        out = gemm_fused(x, w, sin=sin, cos=cos,
                         epilogue=Epilogue(rope=True, head_dim=head_dim),
                         out_dtype=jnp.float32)
        proj = (x @ w).reshape(s, heads, head_dim).transpose(1, 0, 2)[None]
        ref = rope_ref(proj, sin, cos)[0].transpose(1, 0, 2).reshape(s, n)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-3, atol=1e-3)

    def test_swizzle_invariance_with_epilogue(self):
        """Grid order must never change fused-store numbers either."""
        m = k = n = 256
        a = _rand(0, (m, k), jnp.float32)
        b = _rand(1, (k, n), jnp.float32)
        res = _rand(2, (m, n), jnp.float32)
        ep = Epilogue(activation="gelu", residual=True)
        outs = []
        for window in (1, 2):
            pol = make_policy("gemm", block_m=128, block_n=128, block_k=128,
                              swizzle=SwizzleConfig(window=window,
                                                    enable_chiplet=False),
                              epilogue=ep)
            outs.append(gemm_fused(a, b, residual=res, epilogue=ep,
                                   policy=pol, out_dtype=jnp.float32))
        np.testing.assert_array_equal(np.asarray(outs[0]),
                                      np.asarray(outs[1]))

    def test_operand_validation(self):
        a = _rand(0, (128, 128), jnp.float32)
        with pytest.raises(ValueError, match="missing"):
            gemm_fused(a, a, epilogue=Epilogue(bias=True))
        with pytest.raises(ValueError, match="not accepted"):
            gemm_fused(a, a, epilogue=Epilogue(), bias=jnp.zeros(128))
        with pytest.raises(ValueError, match="activation"):
            Epilogue(gate=True)
        with pytest.raises(ValueError, match="head_dim"):
            Epilogue(rope=True, head_dim=0)
        with pytest.raises(ValueError, match="scale_kind"):
            Epilogue(scale_kind="row")          # vector kind needs scale=True
        with pytest.raises(ValueError, match="scale_kind"):
            Epilogue(scale=True, scale_kind="diag")

    def test_vector_scale_vmem_and_traffic_accounting(self):
        """Per-channel scales enter the VMEM legality rule and the traffic
        model as real streamed blocks, not scalars."""
        scalar = Epilogue(scale=True)
        col = Epilogue(scale=True, scale_kind="col")
        row = Epilogue(scale=True, scale_kind="row")
        assert col.scale_block(128, 256) == (1, 256)
        assert row.scale_block(128, 256) == (128, 1)
        m, n = 512, 1024
        assert col.extra_read_bytes(m, n, 2) == n * 4
        assert row.extra_read_bytes(m, n, 2) == m * 4
        assert scalar.extra_read_bytes(m, n, 2) == 4
        base = make_policy("gemm", block_m=256, block_n=256, block_k=256,
                           epilogue=scalar)
        vec = make_policy("gemm", block_m=256, block_n=256, block_k=256,
                          epilogue=col)
        assert vec.vmem_bytes() > base.vmem_bytes()

    def test_epilogue_aware_vmem_legality(self):
        """The gate chain's extra B2 buffers + second accumulator count
        against the VMEM budget: a policy legal without the epilogue can be
        illegal with it."""
        base = make_policy("gemm", block_m=512, block_n=512, block_k=512,
                           n_buffers=3)
        gated = make_policy("gemm", block_m=512, block_n=512, block_k=512,
                            n_buffers=3,
                            epilogue=Epilogue(activation="silu", gate=True))
        assert gated.vmem_bytes() > base.vmem_bytes()
        assert gated.scratch_bytes() == 2 * base.scratch_bytes()

    def test_autotuned_epilogue_policy_carries_chain(self):
        ep = Epilogue(activation="silu", gate=True)
        pol = autotune.select_policy("gemm", (512, 512, 512), "bfloat16",
                                     epilogue=ep)
        assert pol.epilogue == ep
        assert pol.describe()["epilogue"] == "silu*gate"

    def test_plain_gemm_ignores_policy_epilogue(self):
        """The plain op cannot supply epilogue operands: a chain-carrying
        policy contributes its blocks only (no silent relu(A@B))."""
        a = _rand(0, (128, 128), jnp.float32)
        b = _rand(1, (128, 128), jnp.float32)
        pol = autotune.select_policy("gemm", (128, 128, 128), "float32",
                                     epilogue=Epilogue(activation="relu"))
        out = gemm(a, b, policy=pol, out_dtype=jnp.float32)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(gemm_ref(a, b, jnp.float32)),
                                   rtol=1e-3, atol=1e-3)

    def test_gemm_fused_rejects_diverging_policy_epilogue(self):
        a = _rand(0, (128, 128), jnp.float32)
        pol = autotune.select_policy("gemm", (128, 128, 128), "float32",
                                     epilogue=Epilogue(activation="relu"))
        with pytest.raises(ValueError, match="carries epilogue"):
            gemm_fused(a, a, epilogue=Epilogue(activation="silu"),
                       policy=pol, out_dtype=jnp.float32)


# every prologue the model layers use: rmsnorm/layernorm × beta, both
# stats paths (recompute pins block_k == K; @rstd streams row stats)
PROLOGUE_CHAINS = [
    Prologue(norm="rmsnorm"),
    Prologue(norm="layernorm"),
    Prologue(norm="layernorm", beta=True),
    Prologue(norm="rmsnorm", precomputed_stats=True),
    Prologue(norm="layernorm", beta=True, precomputed_stats=True),
]

PROLOGUE_DTYPES = [(jnp.float32, 1e-3), (jnp.bfloat16, 3e-2)]


class TestPrologue:
    """Fused norm→GEMM A-tile prologues vs the unfused jnp oracle
    (DESIGN.md §10)."""

    def _operands(self, prologue, a, k):
        ops = {}
        if prologue.norm != "none":
            ops["gamma"] = _rand(30, (k,), jnp.float32) * 0.2 + 1.0
            if prologue.beta:
                ops["beta"] = _rand(31, (k,), jnp.float32) * 0.2
            if prologue.precomputed_stats:
                ops.update(prologue.compute_stats(a))
        return ops

    @pytest.mark.parametrize("dtype,tol", PROLOGUE_DTYPES,
                             ids=["fp32", "bf16"])
    @pytest.mark.parametrize("pro", PROLOGUE_CHAINS,
                             ids=[p.describe() for p in PROLOGUE_CHAINS])
    def test_norm_matches_oracle(self, pro, dtype, tol):
        m, k, n = 128, 256, 256
        a = _rand(0, (m, k), dtype)
        b = _rand(1, (k, n), dtype)
        ops = self._operands(pro, a, k)
        out = gemm_fused(a, b, prologue=pro, out_dtype=jnp.float32, **ops)
        ref = gemm_fused_ref(a, b, prologue=pro, out_dtype=jnp.float32, **ops)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=tol, atol=tol)

    @pytest.mark.parametrize("norm", ["rmsnorm", "layernorm"])
    def test_oracle_matches_standalone_norm(self, norm):
        """The prologue oracle IS norm-then-GEMM: gemm_fused_ref must equal
        models.common.{rmsnorm,layernorm} followed by the plain GEMM (the
        HBM-round-trip chain the prologue eliminates)."""
        from repro.models.common import layernorm, rmsnorm
        m, k, n = 64, 128, 128
        a = _rand(0, (m, k), jnp.float32)
        b = _rand(1, (k, n), jnp.float32)
        gamma = _rand(2, (k,), jnp.float32) * 0.2 + 1.0
        beta = _rand(3, (k,), jnp.float32) * 0.2
        if norm == "rmsnorm":
            pro, ops = Prologue(norm="rmsnorm"), {"gamma": gamma}
            normed = rmsnorm(a, gamma)
        else:
            pro = Prologue(norm="layernorm", beta=True)
            ops = {"gamma": gamma, "beta": beta}
            normed = layernorm(a, gamma, beta)
        out = gemm_fused(a, b, prologue=pro, out_dtype=jnp.float32, **ops)
        ref = normed.astype(jnp.float32) @ b
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-3, atol=1e-3)

    def test_fast_path_matches_recompute(self):
        """precomputed-rstd keeps K-blocking: a policy with block_k < K is
        legal on the fast path and matches the full-K recompute (up to
        k-blocked accumulation reassociation)."""
        m, k, n = 128, 512, 256
        a = _rand(0, (m, k), jnp.float32)
        b = _rand(1, (k, n), jnp.float32)
        gamma = _rand(2, (k,), jnp.float32) + 1.0
        full = gemm_fused(a, b, prologue=Prologue(norm="rmsnorm"),
                          gamma=gamma, out_dtype=jnp.float32)
        fast_pro = Prologue(norm="rmsnorm", precomputed_stats=True)
        pol = make_policy("gemm", block_m=128, block_n=128, block_k=128,
                          prologue=fast_pro)
        fast = gemm_fused(a, b, prologue=fast_pro, gamma=gamma,
                          policy=pol, **fast_pro.compute_stats(a),
                          out_dtype=jnp.float32)
        np.testing.assert_allclose(np.asarray(fast), np.asarray(full),
                                   rtol=1e-4, atol=1e-4)

    def test_prologue_epilogue_composed_one_launch(self):
        """Norm prologue + dual-output SwiGLU gate + residual/scale epilogue
        in ONE launch == the full eager pre-norm MLP-up chain."""
        t, d, f = 128, 256, 256
        x = _rand(0, (t, d), jnp.float32)
        wg = _rand(1, (d, f), jnp.float32) * 0.2
        wi = _rand(2, (d, f), jnp.float32) * 0.2
        gamma = _rand(3, (d,), jnp.float32) * 0.2 + 1.0
        from repro.models.common import rmsnorm
        out = gemm_fused(x, wg, b2=wi, prologue=Prologue(norm="rmsnorm"),
                         gamma=gamma,
                         epilogue=Epilogue(activation="silu", gate=True),
                         out_dtype=jnp.float32)
        xn = rmsnorm(x, gamma).astype(jnp.float32)
        ref = jax.nn.silu(xn @ wg) * (xn @ wi)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-3, atol=1e-3)

    def test_swizzle_invariance_with_prologue(self):
        """Grid order must never change prologue-fused numbers either."""
        m = k = n = 256
        a = _rand(0, (m, k), jnp.float32)
        b = _rand(1, (k, n), jnp.float32)
        gamma = _rand(2, (k,), jnp.float32) + 1.0
        pro = Prologue(norm="rmsnorm")
        outs = []
        for window in (1, 2):
            pol = make_policy("gemm", block_m=128, block_n=128, block_k=k,
                              swizzle=SwizzleConfig(window=window,
                                                    enable_chiplet=False),
                              prologue=pro)
            outs.append(gemm_fused(a, b, prologue=pro, gamma=gamma,
                                   policy=pol, out_dtype=jnp.float32))
        np.testing.assert_array_equal(np.asarray(outs[0]),
                                      np.asarray(outs[1]))

    def test_spec_validation(self):
        a = _rand(0, (128, 128), jnp.float32)
        with pytest.raises(ValueError, match="beta"):
            Prologue(norm="rmsnorm", beta=True)
        with pytest.raises(ValueError, match="unknown norm"):
            Prologue(norm="batchnorm")
        with pytest.raises(ValueError, match="only meaningful"):
            Prologue(beta=True)
        with pytest.raises(ValueError, match="missing"):
            gemm_fused(a, a, prologue=Prologue(norm="rmsnorm"))
        with pytest.raises(ValueError, match="not accepted"):
            gemm_fused(a, a, gamma=jnp.ones(128))
        # the recompute path refuses block_k < K at the spec level...
        with pytest.raises(ValueError, match="full feature dim"):
            Prologue(norm="rmsnorm").check_blocks(64, 128)
        # ...and _fit_policy clamps a small-block policy up to the full K
        # (the clamp-not-raise convention), so the launch still matches
        pol = make_policy("gemm", block_m=128, block_n=128, block_k=64,
                          prologue=Prologue(norm="rmsnorm"))
        gamma = jnp.ones(128)
        out = gemm_fused(a, a, prologue=Prologue(norm="rmsnorm"),
                         gamma=gamma, policy=pol, out_dtype=jnp.float32)
        ref = gemm_fused_ref(a, a, prologue=Prologue(norm="rmsnorm"),
                             gamma=gamma, out_dtype=jnp.float32)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-3, atol=1e-3)

    def test_prologue_aware_vmem_legality(self):
        """The prologue's gamma/beta rows and stats columns count against
        the VMEM budget, and the autotuned recompute-path policy always
        carries block_k == K."""
        base = make_policy("gemm", block_m=256, block_n=256, block_k=512)
        pro = Prologue(norm="layernorm", beta=True, precomputed_stats=True)
        with_pro = make_policy("gemm", block_m=256, block_n=256, block_k=512,
                               prologue=pro)
        assert with_pro.vmem_bytes() > base.vmem_bytes()
        pol = autotune.select_policy("gemm", (512, 512, 384), "bfloat16",
                                     prologue=Prologue(norm="rmsnorm"))
        assert pol.block_k == 384
        assert pol.prologue == Prologue(norm="rmsnorm")
        assert pol.describe()["prologue"] == "rmsnorm"

    def test_gemm_fused_rejects_diverging_policy_prologue(self):
        a = _rand(0, (128, 128), jnp.float32)
        pol = autotune.select_policy("gemm", (128, 128, 128), "float32",
                                     prologue=Prologue(norm="rmsnorm"))
        with pytest.raises(ValueError, match="carries prologue"):
            gemm_fused(a, a, prologue=Prologue(norm="layernorm"),
                       gamma=jnp.ones(128), policy=pol,
                       out_dtype=jnp.float32)


class TestNormFusionPlan:
    def test_norm_mlp_plan_selected_from_dma_bytes(self):
        """The norm-prologue MLP plan wins on modeled bytes alone, by
        >= 1.3x vs the unfused fused_norm→gemm pair at production shape
        (the ISSUE acceptance bar)."""
        plan = autotune.select_fusion("mlp", (4096, 2048, 8192, True),
                                      prenorm="rmsnorm")
        assert plan["plan"] == "fused"
        assert plan["fused_bytes"] < plan["unfused_bytes"]
        assert plan["traffic_reduction"] >= 1.3

    def test_norm_plan_beats_plain_plan(self):
        """Folding the norm must strictly increase the modeled saving: the
        prologue removes the norm round trip on top of the epilogue wins."""
        shape = (4096, 2048, 8192, True)
        plain = autotune.select_fusion("mlp", shape)
        normed = autotune.select_fusion("mlp", shape, prenorm="rmsnorm")
        assert normed["traffic_reduction"] > plain["traffic_reduction"]
        # layernorm streams a beta row too: never cheaper than rmsnorm
        ln = autotune.select_fusion("mlp", shape, prenorm="layernorm")
        assert ln["fused_bytes"] >= normed["fused_bytes"]

    def test_norm_qkv_plan(self):
        plan = autotune.select_fusion("qkv_rope", (4096, 2048, 16, 4, 128),
                                      prenorm="rmsnorm")
        assert plan["plan"] == "fused"
        assert plan["fused_bytes"] < plan["unfused_bytes"]


class TestPrologueModelPaths:
    """Model-layer parity: the norm-fused pre-norm block vs the reference
    chain, incl. grad-parity against the f32 ground truth (f32 params make
    the reference path exact, so it IS the ground truth here)."""

    def _setup(self):
        cfg = types.SimpleNamespace(mlp_act="swiglu", norm="rmsnorm")
        d, f = 256, 512
        x = _rand(0, (2, 64, d), jnp.float32)
        res = _rand(1, (2, 64, d), jnp.float32)
        p = {"w_gate": _rand(2, (d, f), jnp.float32) * 0.1,
             "w_in": _rand(3, (d, f), jnp.float32) * 0.1,
             "w_out": _rand(4, (f, d), jnp.float32) * 0.1,
             "ln_scale": _rand(5, (d,), jnp.float32) * 0.2 + 1.0}
        return cfg, p, x, res

    def test_norm_fused_mlp_block_matches_reference(self):
        from repro.models.common import mlp_forward, norm_params
        cfg, p, x, res = self._setup()
        pn = norm_params(p, "ln")
        ref = mlp_forward(cfg, p, x, mode="reference", residual=res,
                          residual_scale=0.7, prenorm=pn)
        out = mlp_forward(cfg, p, x, mode="pallas_interpret", residual=res,
                          residual_scale=0.7, prenorm=pn)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=3e-4, atol=3e-4)

    def test_norm_fused_mlp_grad_parity_f32_truth(self):
        """jax.grad through the norm-prologue megakernel == the f32
        reference gradient (incl. the norm scale's own gradient), via the
        custom VJP that differentiates the unfused oracle."""
        from repro.models.common import mlp_forward, norm_params
        cfg, p, x, res = self._setup()

        def loss(p_, mode):
            return jnp.sum(mlp_forward(cfg, p_, x, mode=mode, residual=res,
                                       residual_scale=0.9,
                                       prenorm=norm_params(p_, "ln")) ** 2)

        g_truth = jax.grad(lambda p_: loss(p_, "reference"))(p)
        g_fused = jax.grad(lambda p_: loss(p_, "pallas_interpret"))(p)
        for key in p:
            np.testing.assert_allclose(np.asarray(g_fused[key]),
                                       np.asarray(g_truth[key]),
                                       rtol=2e-3, atol=2e-3, err_msg=key)

    def test_norm_fused_attention_layer_matches_reference(self):
        from repro.models.attention import (attention_layer,
                                            fused_project_qkv_rope)
        h, hkv, hd, d = 4, 2, 64, 256
        cfg = types.SimpleNamespace(num_heads=h, num_kv_heads=hkv,
                                    head_dim=hd, d_model=d, qkv_bias=False,
                                    rope_style="half", rope_theta=10000.0,
                                    norm="rmsnorm")
        b, s = 2, 128
        x = _rand(0, (b, s, d), jnp.float32)
        p = {"wqk": _rand(1, (d, (h + hkv) * hd), jnp.float32) * 0.1,
             "wv": _rand(2, (d, hkv * hd), jnp.float32) * 0.1,
             "wo": _rand(3, (h * hd, d), jnp.float32) * 0.1}
        pn = (_rand(4, (d,), jnp.float32) * 0.2 + 1.0, None)
        # the norm-fused prologue actually engages for this config
        assert fused_project_qkv_rope(cfg, p, x, jnp.arange(s),
                                      "pallas_interpret",
                                      prenorm=pn) is not None
        ref = attention_layer(cfg, p, x, causal=True, mode="reference",
                              prenorm=pn)
        out = attention_layer(cfg, p, x, causal=True,
                              mode="pallas_interpret", prenorm=pn)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=5e-4, atol=5e-4)


class TestFitPolicyClamp:
    """_fit_policy clamps to the largest divisor block instead of raising."""

    @pytest.mark.parametrize("m,n,k", [(192, 320, 160), (300, 200, 100),
                                       (128, 384, 1280)])
    def test_non_divisible_problems_clamp(self, m, n, k):
        a = _rand(0, (m, k), jnp.float32)
        b = _rand(1, (k, n), jnp.float32)
        pol = make_policy("gemm", block_m=512, block_n=512, block_k=512)
        out = gemm(a, b, policy=pol, out_dtype=jnp.float32)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(gemm_ref(a, b, jnp.float32)),
                                   rtol=1e-3, atol=1e-3)

    @pytest.mark.parametrize("shape", [(192, 320, 160), (384, 640, 256),
                                       (1536, 1024, 768)])
    def test_autotuner_candidates_fit(self, shape):
        """The autotuner never emits a candidate whose blocks would have
        needed the clamp (divisibility is part of candidate legality)."""
        sig = autotune.OpSignature("gemm", shape)
        cands = autotune.candidate_policies(sig)
        assert cands
        for pol in cands:
            assert pol.fits(*shape), (pol.describe(), shape)


class TestFusionPlan:
    def test_mlp_plan_selected_from_dma_bytes(self):
        """The fused MLP plan wins on modeled bytes alone, by >= 1.5x at
        production shape (the ISSUE acceptance bar)."""
        plan = autotune.select_fusion("mlp", (4096, 2048, 8192, True))
        assert plan["plan"] == "fused"
        assert plan["fused_bytes"] < plan["unfused_bytes"]
        assert plan["traffic_reduction"] >= 1.5

    def test_qkv_plan_selected_from_dma_bytes(self):
        plan = autotune.select_fusion("qkv_rope", (4096, 2048, 16, 4, 128))
        assert plan["plan"] == "fused"
        assert plan["fused_bytes"] < plan["unfused_bytes"]

    def test_no_hardcoded_preference(self):
        """The decision really comes from the byte model: when the chain
        saves ~nothing (tiny token count vs huge weights), the margin
        collapses — the plan field is always derived from the same
        numbers, never from a flag."""
        plan = autotune.select_fusion("mlp", (8, 4096, 16384, True))
        assert plan["traffic_reduction"] < 1.05
        # and the plan field is derived from the same numbers
        expect = ("fused" if plan["fused_bytes"] < plan["unfused_bytes"]
                  else "unfused")
        assert plan["plan"] == expect

    def test_qkv_packed_weights_win_at_small_tokens(self):
        """[wq|wk] is pre-packed at param-build time, so the fused qkv plan
        no longer pays a token-independent in-graph concat: it strictly
        removes passes and wins even at tiny token counts (the case the
        concat used to lose) — still decided from the byte model, whose
        margin collapses toward 1 as the weights dominate."""
        plan = autotune.select_fusion("qkv_rope", (64, 4096, 32, 8, 128))
        assert plan["plan"] == "fused"
        assert plan["fused_bytes"] < plan["unfused_bytes"]
        assert plan["traffic_reduction"] < 1.1  # weight-dominated margin

    def test_moe_expert_plan_has_no_residual_term(self):
        """The expert FFN chain carries no residual add: its plan must be
        scored without the phantom residual traffic."""
        with_res = autotune.select_fusion("mlp", (256, 512, 1024, True),
                                          residual=True)
        without = autotune.select_fusion("mlp", (256, 512, 1024, True),
                                         residual=False)
        assert without["unfused_bytes"] < with_res["unfused_bytes"]
        assert without["traffic_reduction"] < with_res["traffic_reduction"]


class TestFusedModelPaths:
    """Model-layer parity: fused megakernel paths vs the reference chains."""

    def test_mlp_forward_fused_matches_reference(self):
        cfg = types.SimpleNamespace(mlp_act="swiglu")
        d, f = 256, 512
        x = _rand(0, (2, 64, d), jnp.float32)
        res = _rand(1, (2, 64, d), jnp.float32)
        p = {"w_gate": _rand(2, (d, f), jnp.float32) * 0.1,
             "w_in": _rand(3, (d, f), jnp.float32) * 0.1,
             "w_out": _rand(4, (f, d), jnp.float32) * 0.1}
        from repro.models.common import mlp_forward
        ref = mlp_forward(cfg, p, x, mode="reference", residual=res,
                          residual_scale=0.7)
        out = mlp_forward(cfg, p, x, mode="pallas_interpret", residual=res,
                          residual_scale=0.7)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("qkv_bias", [False, True])
    def test_attention_layer_fused_qkv_rope_matches_reference(self, qkv_bias):
        from repro.models.attention import (attention_layer,
                                            fused_project_qkv_rope)
        h, hkv, hd, d = 4, 2, 64, 256
        cfg = types.SimpleNamespace(num_heads=h, num_kv_heads=hkv,
                                    head_dim=hd, d_model=d, qkv_bias=qkv_bias,
                                    rope_style="half", rope_theta=10000.0)
        b, s = 2, 128
        x = _rand(0, (b, s, d), jnp.float32)
        p = {"wqk": _rand(1, (d, (h + hkv) * hd), jnp.float32) * 0.1,
             "wv": _rand(3, (d, hkv * hd), jnp.float32) * 0.1,
             "wo": _rand(4, (h * hd, d), jnp.float32) * 0.1}
        if qkv_bias:
            p.update(bqk=_rand(5, ((h + hkv) * hd,), jnp.float32) * 0.1,
                     bv=_rand(7, (hkv * hd,), jnp.float32) * 0.1)
        # the fused prologue actually engages for this config
        assert fused_project_qkv_rope(cfg, p, x, jnp.arange(s),
                                      "pallas_interpret") is not None
        ref = attention_layer(cfg, p, x, causal=True, mode="reference")
        out = attention_layer(cfg, p, x, causal=True, mode="pallas_interpret")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=5e-4, atol=5e-4)

    def test_fused_mlp_grad_matches_reference(self):
        """gemm_fused's custom VJP (autodiff of the unfused oracle) keeps
        the fused MLP trainable with reference-exact gradients."""
        from repro.models.common import mlp_forward
        cfg = types.SimpleNamespace(mlp_act="swiglu")
        d, f = 128, 256
        x = _rand(0, (1, 64, d), jnp.float32)
        res = _rand(1, (1, 64, d), jnp.float32)
        p = {"w_gate": _rand(2, (d, f), jnp.float32) * 0.2,
             "w_in": _rand(3, (d, f), jnp.float32) * 0.2,
             "w_out": _rand(4, (f, d), jnp.float32) * 0.2}

        def loss(p, mode):
            return jnp.sum(mlp_forward(cfg, p, x, mode=mode, residual=res,
                                       residual_scale=0.9) ** 2)

        g_ref = jax.grad(lambda p_: loss(p_, "reference"))(p)
        g_fus = jax.grad(lambda p_: loss(p_, "pallas_interpret"))(p)
        for key in p:
            np.testing.assert_allclose(np.asarray(g_fus[key]),
                                       np.asarray(g_ref[key]),
                                       rtol=2e-3, atol=2e-3)

    def test_moe_dense_fused_matches_reference(self):
        from repro.models.moe import moe_dense
        cfg = types.SimpleNamespace(
            mlp_act="swiglu",
            moe=types.SimpleNamespace(num_experts=4, top_k=2,
                                      capacity_factor=1.25, impl="dense",
                                      shard="expert"))
        d, f = 128, 256
        x = _rand(0, (1, 32, d), jnp.float32)
        p = {"router": _rand(1, (d, 4), jnp.float32) * 0.1,
             "w_in": _rand(2, (4, d, f), jnp.float32) * 0.1,
             "w_gate": _rand(3, (4, d, f), jnp.float32) * 0.1,
             "w_out": _rand(4, (4, f, d), jnp.float32) * 0.1}
        o_ref, aux_ref = moe_dense(cfg, p, x, mode="reference")
        o_fus, aux_fus = moe_dense(cfg, p, x, mode="pallas_interpret")
        np.testing.assert_allclose(np.asarray(o_fus), np.asarray(o_ref),
                                   rtol=3e-4, atol=3e-4)
        np.testing.assert_allclose(np.asarray(aux_fus), np.asarray(aux_ref),
                                   rtol=1e-6, atol=1e-6)


class TestAttention:
    @pytest.mark.parametrize("h,hkv", [(2, 2), (4, 1), (8, 2)])
    @pytest.mark.parametrize("d", [64, 128])
    @pytest.mark.parametrize("causal", [False, True])
    def test_fwd_matches_ref(self, h, hkv, d, causal):
        b, s = 2, 256
        ks = jax.random.split(KEY, 3)
        q = jax.random.normal(ks[0], (b, h, s, d))
        k = jax.random.normal(ks[1], (b, hkv, s, d))
        v = jax.random.normal(ks[2], (b, hkv, s, d))
        out, _ = flash_attention_fwd(q, k, v, causal=causal)
        ref = attention_ref(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("window", [64, 128, 1000])
    def test_sliding_window(self, window):
        b, h, s, d = 1, 2, 384, 64
        ks = jax.random.split(KEY, 3)
        q = jax.random.normal(ks[0], (b, h, s, d))
        k = jax.random.normal(ks[1], (b, h, s, d))
        v = jax.random.normal(ks[2], (b, h, s, d))
        out, _ = flash_attention_fwd(q, k, v, causal=True, window=window)
        ref = attention_ref(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("h,hkv,causal,window", [
        (2, 2, False, None), (4, 2, True, None), (4, 1, True, 128)])
    def test_bwd_matches_autodiff(self, h, hkv, causal, window):
        b, s, d = 1, 256, 64
        ks = jax.random.split(KEY, 4)
        q = jax.random.normal(ks[0], (b, h, s, d))
        k = jax.random.normal(ks[1], (b, hkv, s, d))
        v = jax.random.normal(ks[2], (b, hkv, s, d))
        do = jax.random.normal(ks[3], (b, h, s, d))

        def f_kernel(q, k, v):
            return (attention(q, k, v, causal=causal, window=window) * do).sum()

        def f_ref(q, k, v):
            return (attention(q, k, v, causal=causal, window=window,
                              mode="reference") * do).sum()

        gk = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(gk, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=1e-3, atol=1e-3)

    def test_bf16_inputs(self):
        b, h, s, d = 1, 2, 256, 64
        ks = jax.random.split(KEY, 3)
        q = jax.random.normal(ks[0], (b, h, s, d), jnp.bfloat16)
        k = jax.random.normal(ks[1], (b, h, s, d), jnp.bfloat16)
        v = jax.random.normal(ks[2], (b, h, s, d), jnp.bfloat16)
        out, _ = flash_attention_fwd(q, k, v, causal=True)
        ref = attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=3e-2, atol=3e-2)

    def test_chunked_ref_matches_direct(self):
        b, h, s, d = 1, 4, 512, 64
        ks = jax.random.split(KEY, 3)
        q = jax.random.normal(ks[0], (b, h, s, d))
        k = jax.random.normal(ks[1], (b, h, s, d))
        v = jax.random.normal(ks[2], (b, h, s, d))
        o1 = attention_ref(q, k, v, causal=True)
        o2 = attention_ref_chunked(q, k, v, causal=True, chunk=128)
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                                   rtol=1e-5, atol=1e-5)

    @given(sq=st.sampled_from([128, 256]), skv=st.sampled_from([128, 256, 384]))
    @settings(max_examples=10, deadline=None)
    def test_cross_lengths(self, sq, skv):
        """Property: works for Sq != Skv (cross-attention shapes)."""
        b, h, d = 1, 2, 64
        ks = jax.random.split(KEY, 3)
        q = jax.random.normal(ks[0], (b, h, sq, d))
        k = jax.random.normal(ks[1], (b, h, skv, d))
        v = jax.random.normal(ks[2], (b, h, skv, d))
        out, _ = flash_attention_fwd(q, k, v, causal=False)
        ref = attention_ref(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


class TestFusedNorm:
    @pytest.mark.parametrize("rows,d", [(256, 128), (512, 1024), (128, 768)])
    @pytest.mark.parametrize("p", [0.0, 0.1, 0.5])
    def test_matches_ref(self, rows, d, p):
        ks = jax.random.split(KEY, 4)
        x = jax.random.normal(ks[0], (rows, d))
        r = jax.random.normal(ks[1], (rows, d))
        w = jax.random.normal(ks[2], (d,))
        b = jax.random.normal(ks[3], (d,))
        o1, r1 = dropout_residual_layernorm(x, r, w, b, 7, dropout_p=p)
        o2, r2 = fused_dropout_residual_layernorm_ref(x, r, w, b, 7,
                                                      dropout_p=p)
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-5)
        np.testing.assert_allclose(np.asarray(r1), np.asarray(r2), atol=1e-5)

    def test_normalization_property(self):
        """Output rows (pre-affine) have mean≈0, var≈1."""
        x = jax.random.normal(KEY, (64, 512))
        r = jnp.zeros((64, 512))
        o, _ = dropout_residual_layernorm(x, r, jnp.ones(512), jnp.zeros(512))
        of = np.asarray(o, np.float64)
        np.testing.assert_allclose(of.mean(1), 0, atol=1e-4)
        np.testing.assert_allclose(of.var(1), 1, atol=1e-2)

    @given(p=st.floats(0.05, 0.9), seed=st.integers(0, 2**31 - 1))
    @settings(max_examples=20, deadline=None)
    def test_dropout_rate_property(self, p, seed):
        """Keep rate ≈ 1-p, and the mask is deterministic in the seed."""
        mask1 = dropout_keep_mask_ref(seed, (256, 512), p)
        mask2 = dropout_keep_mask_ref(seed, (256, 512), p)
        assert (np.asarray(mask1) == np.asarray(mask2)).all()
        rate = float(np.asarray(mask1).mean())
        assert abs(rate - (1 - p)) < 0.02

    def test_dropout_scaling_preserves_mean(self):
        x = jnp.ones((512, 512))
        r = jnp.zeros((512, 512))
        _, resid = dropout_residual_layernorm(x, r, jnp.ones(512),
                                              jnp.zeros(512), 3, dropout_p=0.3)
        assert abs(float(jnp.mean(resid)) - 1.0) < 0.05


class TestRope:
    @pytest.mark.parametrize("b,h,s,d", [(2, 4, 256, 128), (1, 2, 512, 64)])
    def test_matches_ref(self, b, h, s, d):
        x = jax.random.normal(KEY, (b, h, s, d))
        sin, cos = rope_tables(jnp.arange(s), d)
        np.testing.assert_allclose(np.asarray(rope(x, sin, cos)),
                                   np.asarray(rope_ref(x, sin, cos)),
                                   atol=1e-5)

    def test_norm_preservation_property(self):
        """Rotation preserves the norm of each (x_i, x_{i+d/2}) pair."""
        x = jax.random.normal(KEY, (1, 1, 256, 64))
        sin, cos = rope_tables(jnp.arange(256), 64)
        y = np.asarray(rope(x, sin, cos), np.float64)
        xn = np.asarray(x, np.float64)
        n_in = xn[..., :32] ** 2 + xn[..., 32:] ** 2
        n_out = y[..., :32] ** 2 + y[..., 32:] ** 2
        np.testing.assert_allclose(n_in, n_out, atol=1e-5)

    def test_relative_property(self):
        """<rope(q,m), rope(k,n)> depends only on m-n (the RoPE guarantee)."""
        d = 64
        q = jax.random.normal(KEY, (1, 1, 1, d))
        k = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 1, d))
        def dot_at(m, n):
            sin_m, cos_m = rope_tables(jnp.asarray([m]), d)
            sin_n, cos_n = rope_tables(jnp.asarray([n]), d)
            qm = rope_ref(q, sin_m, cos_m)
            kn = rope_ref(k, sin_n, cos_n)
            return float(jnp.sum(qm * kn))
        assert abs(dot_at(5, 3) - dot_at(102, 100)) < 1e-4
        assert abs(dot_at(7, 7) - dot_at(0, 0)) < 1e-4


class TestModes:
    """A mode string other than reference / pallas_interpret / pallas_tpu
    raises in every op; it never selects the compiled kernels silently."""

    @pytest.mark.parametrize("op", ["gemm", "gemm_fused", "attention",
                                    "attention_decode", "decode_paged",
                                    "rope", "fused_norm"])
    def test_unknown_mode_raises(self, op):
        from repro.kernels.attention import (attention_decode,
                                             attention_decode_paged)
        a = jnp.ones((8, 128), jnp.float32)
        q = jnp.ones((1, 2, 8, 128), jnp.float32)
        sin, cos = rope_tables(jnp.arange(8), 128, 10000.0)
        calls = {
            "gemm": lambda m: gemm(a, a.T, mode=m),
            "gemm_fused": lambda m: gemm_fused(a, a.T, mode=m),
            "attention": lambda m: attention(q, q, q, mode=m),
            "attention_decode": lambda m: attention_decode(
                q[:, :, :1], q, q, 8, mode=m),
            "decode_paged": lambda m: attention_decode_paged(
                q[:, :, :1], q.reshape(2, 1, 8, 128), q.reshape(2, 1, 8, 128),
                jnp.ones((1, 2), jnp.int32), 8, mode=m),
            "rope": lambda m: rope(q, sin, cos, mode=m),
            "fused_norm": lambda m: dropout_residual_layernorm(
                a, a, a[0], a[0], mode=m),
        }
        with pytest.raises(ValueError, match="unknown kernel mode"):
            calls[op]("tpu")

    def test_build_model_checks_mode(self):
        from repro.configs import get_config
        from repro.models import build_model
        cfg = get_config("granite-8b", smoke=True)
        with pytest.raises(ValueError, match="unknown kernel mode"):
            build_model(cfg, mode="pallas")
        # GSPMD cannot partition Mosaic kernels
        with pytest.raises(ValueError, match="single-device mesh"):
            build_model(cfg, mode="pallas_tpu",
                        mesh=types.SimpleNamespace(size=4))

    def test_interpret_launches_counted(self):
        from repro import obs
        a = jnp.ones((128, 128), jnp.float32)
        with obs.capture() as cap:
            gemm(a, a, mode="reference")
            gemm(a, a, mode="pallas_interpret", out_dtype=jnp.float32)
        assert cap.counter("kernels.interpret_launch") == 1

    def test_active_chip_from_device_kind(self, monkeypatch):
        """On a TPU backend the ChipSpec comes from device_kind; a kind the
        model does not describe raises instead of defaulting to v5e."""
        from repro.core import perf_model as pm

        def fake_tpu(kind):
            monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
            monkeypatch.setattr(jax, "devices", lambda *a: [
                types.SimpleNamespace(device_kind=kind)])
            autotune._backend_chip.cache_clear()

        try:
            fake_tpu("TPU v5 lite")
            assert autotune.active_chip() is pm.V5E
            fake_tpu("TPU v9 imaginary")
            with pytest.raises(ValueError, match="no ChipSpec"):
                autotune.active_chip()
        finally:
            monkeypatch.undo()
            autotune._backend_chip.cache_clear()
