"""Compile-only checks of the main-path kernels for a TPU v5e.

Each test lowers a kernel at the widths the chip runs (``granite-8b``:
d=4096, 32/8 heads, head_dim 128, d_ff=14336; ``llama-100m`` training:
head_dim 64) and compiles it with the TPU compiler for one chip of a
described ``v5e:2x2`` topology: nothing runs, but the compiler refuses what
the chip would refuse (misaligned blocks, scoped-VMEM overflow, shapes
Mosaic cannot lay out). The topology is described inside a fixture, never
while a module is imported, so every test worker collects the same tests.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.attention import (attention, attention_decode,
                                     attention_decode_paged,
                                     resolve_decode_policy)
from repro.kernels.fused_norm import dropout_residual_layernorm
from repro.kernels.gemm import Epilogue, gemm_fused, norm_prologue
from repro.kernels.rope import rope, rope_tables

M = "pallas_tpu"
T, D, F = 2048, 4096, 14336          # granite-8b prefill tokens and widths


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _bf(*shape):
    return shape, jnp.bfloat16


def _i32(*shape):
    return shape, jnp.int32


def _sum(fn):
    return lambda *a: fn(*a).astype(jnp.float32).sum()


SWIGLU = Epilogue(activation="silu", gate=True)


@pytest.mark.parametrize("prologue", [True, False])
def test_gemm_fused_swiglu(one_chip, prologue):
    """The dual-GEMM SwiGLU up-projection, with and without the rmsnorm
    prologue (the prologue's full-K tile needs the raised VMEM limit)."""
    if prologue:
        fn = lambda x, wg, wi, g: gemm_fused(
            x, wg, b2=wi, epilogue=SWIGLU, prologue=norm_prologue("rmsnorm"),
            gamma=g, mode=M)
        _compile(fn, one_chip, _bf(T, D), _bf(D, F), _bf(D, F), _bf(D))
    else:
        fn = lambda x, wg, wi: gemm_fused(x, wg, b2=wi, epilogue=SWIGLU,
                                          mode=M)
        _compile(fn, one_chip, _bf(T, D), _bf(D, F), _bf(D, F))


def test_gemm_fused_down_residual(one_chip):
    fn = lambda h, w, r: gemm_fused(
        h, w, epilogue=Epilogue(residual=True, scale=True), residual=r,
        scale=1.0, mode=M)
    _compile(fn, one_chip, _bf(T, F), _bf(F, D), _bf(T, D))


def test_gemm_fused_swiglu_prologue_backward(one_chip):
    """llama-100m training shape: the kernel backward's dgamma partials."""
    fn = jax.grad(_sum(lambda x, wg, wi, g: gemm_fused(
        x, wg, b2=wi, epilogue=SWIGLU, prologue=norm_prologue("rmsnorm"),
        gamma=g, mode=M)), argnums=(0, 1, 2, 3))
    text = _compile(fn, one_chip, _bf(8192, 768), _bf(768, 2048),
                    _bf(768, 2048), _bf(768))
    assert "_gemm_bwd_da" in text and "_gemm_bwd_db" in text


@pytest.mark.parametrize("head_dim,heads", [(64, 12), (128, 32)])
def test_gemm_fused_qkv_rope_backward(one_chip, head_dim, heads):
    """The fused QKV->RoPE store at llama-100m (head_dim 64) and
    granite-8b (head_dim 128) widths, forward and kernel backward."""
    rows, d = 2048, heads * head_dim
    ep = Epilogue(rope=True, head_dim=head_dim)

    def fn(x, w):
        sin, cos = rope_tables(jnp.arange(rows), head_dim, 10000.0)
        return gemm_fused(x, w, epilogue=ep, sin=sin, cos=cos, mode=M)

    _compile(jax.grad(_sum(fn), argnums=(0, 1)), one_chip, _bf(rows, d),
             _bf(d, 2 * d))


def test_dropout_residual_layernorm(one_chip):
    fn = lambda x, r, w, b: dropout_residual_layernorm(x, r, w, b, mode=M)
    _compile(fn, one_chip, _bf(4096, 4096), _bf(4096, 4096), _bf(4096),
             _bf(4096))


def test_rope(one_chip):
    def fn(x):
        sin, cos = rope_tables(jnp.arange(4096), 128, 10000.0)
        return rope(x, sin, cos, mode=M)

    _compile(fn, one_chip, _bf(1, 32, 4096, 128))


@pytest.mark.parametrize("backward", [False, True])
@pytest.mark.parametrize("head_dim", [128, 64])
def test_flash_attention(one_chip, head_dim, backward):
    """Causal GQA flash attention, B1 H32/8 S4096 (the lse block layout)."""
    fn = lambda q, k, v: attention(q, k, v, causal=True, mode=M)
    if backward:
        fn = jax.grad(_sum(fn), argnums=(0, 1, 2))
    text = _compile(fn, one_chip, _bf(1, 32, 4096, head_dim),
                    _bf(1, 8, 4096, head_dim), _bf(1, 8, 4096, head_dim))
    assert ("_flash_bwd" in text) == backward


@pytest.mark.parametrize("paged", [False, True])
def test_split_kv_decode(one_chip, paged):
    """Split-KV decode, B16 H32/8 head_dim 128: contiguous (ring) cache at
    S4096 and a 64-token-page pool (the stat block layout)."""
    if paged:
        fn = lambda q, kp, vp, pt, n: attention_decode_paged(
            q, kp, vp, pt, n, mode=M)
        _compile(fn, one_chip, _bf(16, 32, 1, 128), _bf(1025, 8, 64, 128),
                 _bf(1025, 8, 64, 128), _i32(16, 64), _i32(16))
    else:
        fn = lambda q, k, v, n: attention_decode(q, k, v, n, mode=M)
        _compile(fn, one_chip, _bf(16, 32, 1, 128), _bf(16, 8, 4096, 128),
                 _bf(16, 8, 4096, 128), _i32(16))


@pytest.mark.parametrize("batch,q_tokens", [(32, 1), (1, 512)])
def test_paged_decode_at_chat_shapes(one_chip, batch, q_tokens):
    """The paged kernel at the serving cell's shapes: the decode step (32
    slots) and a 512-token prefill chunk over a 769 x 8 x 64 x 128 pool
    and 64-page tables. The call keeps its name, and its partials hold
    one split per block of pages, not one per page."""
    fn = lambda q, kp, vp, pt, n: attention_decode_paged(
        q, kp, vp, pt, n, mode=M)
    text = _compile(fn, one_chip, _bf(batch, 32, q_tokens, 128),
                    _bf(769, 8, 64, 128), _bf(769, 8, 64, 128),
                    _i32(batch, 64), _i32(batch))
    pol = resolve_decode_policy(batch, 8, 4, 64 * 64, 128, jnp.bfloat16,
                                page_size=64, q_tokens=q_tokens)
    splits = -(-64 // (pol.block_kv // 64))
    assert splits < 64
    call = [ln for ln in text.splitlines()
            if ln.lstrip().startswith("%flash_decode_paged")]
    assert call, "no custom call named flash_decode_paged"
    assert f"f32[{batch},8,{splits},{4 * q_tokens},128]" in call[0]


@pytest.mark.parametrize("plan", ["ring", "gather"])
@pytest.mark.parametrize("variant", ["all_gather", "reduce_scatter"])
def test_collective_gemm_four_chips(topo, variant, plan):
    """The ring collective GEMM at 4096^3 on the 2x2 host: the kernels run
    per shard under shard_map, and the ring's hops are collective-permutes."""
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.kernels.gemm import gemm_collective_sharded
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((4,), ("model",), devices=topo.devices)
    rep = NamedSharding(mesh, PartitionSpec())
    fn = lambda x, w: gemm_collective_sharded(
        x, w, mesh=mesh, variant=variant, mode=M, out_dtype=jnp.float32,
        plan=plan)
    text = _compile(fn, rep, _bf(4096, 4096), _bf(4096, 4096))
    assert ("collective-permute" in text) == (plan == "ring")
