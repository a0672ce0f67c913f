"""Sharding rules + multi-device (subprocess) distribution tests."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import spec_for


class FakeMesh:
    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)


class TestSpecRules:
    def test_divisible_shards(self):
        mesh = FakeMesh({"data": 16, "model": 16})
        assert spec_for((152064, 8192), ("vocab", "embed"), mesh) == \
            P("model", None)
        assert spec_for((8192, 29568), ("embed", "ffn"), mesh) == \
            P(None, "model")

    def test_indivisible_replicates(self):
        mesh = FakeMesh({"data": 16, "model": 16})
        report = []
        spec = spec_for((51865, 512), ("vocab", "embed"), mesh, report=report)
        assert spec == P(None, None)
        assert report  # the fallback is reported, not silent

    def test_batch_axes_compose(self):
        mesh = FakeMesh({"pod": 2, "data": 16, "model": 16})
        assert spec_for((256, 4096), ("batch", None), mesh) == \
            P(("pod", "data"), None)


class TestZero1Fsdp:
    """ZeRO-1/FSDP shard the largest free divisible dim (not just dim0) —
    required for stacked MoE tensors like (24, 128, 5120, 8192)."""

    def test_shard_free_dim_picks_largest(self):
        from repro.distributed.sharding import _shard_free_dim
        from jax.sharding import NamedSharding
        mesh = jax.make_mesh((1, 1), ("data", "model"))
        sh = NamedSharding(mesh, P(None, "model", None, None))
        out = _shard_free_dim(sh, (24, 128, 5120, 8192), mesh, "data")
        assert out is not None
        assert out.spec[3] == "data"          # largest free dim
        assert out.spec[1] == "model"         # existing sharding kept

    def test_vocab_padding_config(self):
        import dataclasses
        from repro.configs import get_config
        cfg = dataclasses.replace(get_config("minicpm-2b"),
                                  vocab_pad_multiple=128)
        assert cfg.padded_vocab() % 128 == 0
        assert cfg.padded_vocab() >= cfg.vocab_size
        assert cfg.padded_vocab() - cfg.vocab_size < 128


class TestMultiDevice:
    def test_dp_tp_train_step(self, subproc):
        """2x4 mesh: sharded init + sharded train step run and give finite
        loss; params stay sharded."""
        out = subproc("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.models import build_model
from repro.train import make_train_step, sharded_init
from repro.optim import AdamWConfig, constant_schedule
from repro.data.pipeline import DataConfig, DataIterator
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ('data', 'model'))
cfg = get_config('qwen2-72b', smoke=True)
model = build_model(cfg, mode='reference', mesh=mesh)
state = sharded_init(model, jax.random.PRNGKey(0), mesh, zero1=True)
dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=64, global_batch=4)
it = DataIterator(dcfg, mesh=mesh)
step = make_train_step(model, AdamWConfig(schedule=constant_schedule(1e-3)), mesh=mesh, zero1=True)
s2, m = step(state, next(it))
print('loss', float(m['loss']))
assert np.isfinite(float(m['loss']))
# a TP-sharded leaf really is distributed
leaf = s2['params']['blocks']['attn']['wqk']
assert len(leaf.sharding.device_set) > 1
print('OK')
""")
        assert "OK" in out

    def test_moe_ep_multidevice(self, subproc):
        out = subproc("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import ModelConfig, MoEConfig
from repro.models.moe import moe_forward, moe_defs, moe_dense
from repro.models.common import init_params
cfg = ModelConfig(name='t', family='lm', num_layers=1, d_model=64,
                  num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=64,
                  block_pattern=('moe',),
                  moe=MoEConfig(num_experts=8, top_k=2, capacity_factor=4.0))
params = init_params(moe_defs(cfg, 'moe'), jax.random.PRNGKey(0))['moe']
x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 64))
mesh = jax.make_mesh((2, 4), ('data', 'model'))
o_ep, _ = moe_forward(cfg, params, x, mesh=mesh)
o_d, _ = moe_dense(cfg, params, x)
assert float(jnp.abs(o_ep - o_d).max()) < 1e-4
print('OK')
""")
        assert "OK" in out

    def test_elastic_checkpoint_reshard(self, subproc):
        """Save on a 4-device data mesh, restore onto a 2x2 mesh (different
        sharding) — values must round-trip exactly."""
        out = subproc("""
import jax, jax.numpy as jnp, numpy as np, tempfile
from repro.configs import get_config
from repro.models import build_model
from repro.train import init_state, state_shardings, checkpoint as ckpt
cfg = get_config('granite-8b', smoke=True)
with tempfile.TemporaryDirectory() as d:
    mesh1 = jax.make_mesh((4, 2), ('data', 'model'))
    model1 = build_model(cfg, mode='reference', mesh=mesh1)
    state = init_state(model1, jax.random.PRNGKey(0))
    ckpt.save(state, d, 7)
    mesh2 = jax.make_mesh((2, 4), ('data', 'model'))
    model2 = build_model(cfg, mode='reference', mesh=mesh2)
    tpl = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), state)
    sh = state_shardings(model2, mesh2, zero1=True)
    restored, step = ckpt.restore(d, tpl, shardings=sh)
    assert step == 7
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
print('OK')
""")
        assert "OK" in out

    @pytest.mark.slow
    def test_mini_dryrun_512(self, subproc):
        """The real thing: 512 fake devices, production meshes, one arch ×
        shape on both meshes, roofline terms extracted."""
        out = subproc("""
from repro.launch.dryrun import run_cell
for mesh in ('single', 'multi'):
    rec = run_cell('mamba2-130m', 'train_4k', mesh, verbose=False)
    assert rec['status'] == 'ok', rec
    assert rec['roofline']['flops_per_chip'] > 0
    assert rec['roofline']['collective_bytes_per_chip'] > 0
print('OK')
""", devices=512, timeout=900)
        assert "OK" in out


# ---------------------------------------------------------------------------
# ShardSpec: sharding as a first-class plan dimension (DESIGN.md §16)
# ---------------------------------------------------------------------------
class TestShardSpec:
    def test_construction_and_describe(self):
        from repro.distributed.sharding import ShardSpec
        sp = ShardSpec(mesh=(("model", 4),),
                       partition=(("expert", "model"),),
                       collective="all_to_all")
        assert sp.n_shards == 4
        assert sp.axis_size("model") == 4
        assert sp.describe() == "model=4|expert@model|all_to_all"
        assert hash(sp) == hash(ShardSpec(
            mesh=(("model", 4),), partition=(("expert", "model"),),
            collective="all_to_all"))

    def test_validation(self):
        from repro.distributed.sharding import ShardSpec
        with pytest.raises(ValueError):
            ShardSpec(collective="broadcast")
        with pytest.raises(ValueError):
            ShardSpec(mesh=(("model", 4),),
                      partition=(("ffn", "tensor"),))  # axis not in mesh
        with pytest.raises(ValueError):
            ShardSpec(mesh=(("model", 0),))

    def test_for_axis_from_live_mesh(self):
        from repro.distributed.sharding import ShardSpec
        mesh = jax.make_mesh((1, 1), ("data", "model"))
        sp = ShardSpec.for_axis(mesh, "model", dim="ffn",
                                collective="all_reduce")
        assert sp.mesh == (("model", 1),) and sp.n_shards == 1

    def test_train_shard_spec_dispatch(self):
        from repro.distributed.sharding import train_shard_spec
        from repro.configs.base import ModelConfig, MoEConfig
        mesh = FakeMesh({"data": 2, "model": 4})
        ep_cfg = ModelConfig(name="t", family="lm", num_layers=1, d_model=64,
                             num_heads=4, num_kv_heads=2, d_ff=128,
                             vocab_size=64, block_pattern=("moe",),
                             moe=MoEConfig(num_experts=8, top_k=2))
        sp = train_shard_spec(ep_cfg, mesh)
        assert sp.collective == "all_to_all" and sp.n_shards == 4
        tp_cfg = ModelConfig(name="t", family="lm", num_layers=1, d_model=64,
                             num_heads=4, num_kv_heads=2, d_ff=128,
                             vocab_size=64, block_pattern=("moe",),
                             moe=MoEConfig(num_experts=3, top_k=2))
        assert train_shard_spec(tp_cfg, mesh).collective == "all_reduce"
        dense = ModelConfig(name="t", family="lm", num_layers=1, d_model=64,
                            num_heads=4, num_kv_heads=2, d_ff=128,
                            vocab_size=64)
        assert train_shard_spec(dense, mesh).collective == "all_reduce"
        assert train_shard_spec(dense, FakeMesh({"data": 8})) is None
        assert train_shard_spec(dense, None) is None


# ---------------------------------------------------------------------------
# Shared sharding helpers (S1/S3): divisibility, sizing, free-dim edge cases
# ---------------------------------------------------------------------------
class TestShardingHelpers:
    def test_divisible_axes(self):
        from repro.distributed.sharding import divisible_axes
        mesh = FakeMesh({"pod": 2, "data": 4, "model": 2})
        assert divisible_axes(16, mesh, ("pod", "data")) == ("pod", "data")
        assert divisible_axes(12, mesh, ("pod", "data")) is None  # 12 % 8
        assert divisible_axes(12, mesh, ("data",)) == ("data",)
        # axes missing from the mesh are filtered, not fatal
        assert divisible_axes(16, FakeMesh({"model": 2}),
                              ("pod", "data")) is None

    def test_leaf_nbytes(self):
        from repro.distributed.sharding import leaf_nbytes
        assert leaf_nbytes(jnp.zeros((4, 8), jnp.float32)) == 128
        assert leaf_nbytes(jax.ShapeDtypeStruct((4, 8), jnp.bfloat16)) == 64

    def test_shard_free_dim_axis_already_used(self):
        from repro.distributed.sharding import _shard_free_dim
        from jax.sharding import NamedSharding
        mesh = jax.make_mesh((1, 1), ("data", "model"))
        sh = NamedSharding(mesh, P("data", None))
        assert _shard_free_dim(sh, (8, 8), mesh, "data") is None
        # axis inside a tuple entry also counts as used
        sh2 = NamedSharding(mesh, P(("data", "model"), None))
        assert _shard_free_dim(sh2, (8, 8), mesh, "data") is None

    def test_shard_free_dim_no_divisible_dim(self):
        from repro.distributed.sharding import _shard_free_dim
        from jax.sharding import NamedSharding

        class _Sh:   # minimal stand-in with a .spec (no device checks hit)
            spec = P(None, None)
        mesh = FakeMesh({"data": 3})
        assert _shard_free_dim(_Sh(), (4, 5), mesh, "data") is None
        # dims smaller than the axis extent don't shard either
        assert _shard_free_dim(_Sh(), (2, 1), mesh, "data") is None

    def test_fsdp_min_bytes_cutoff(self):
        from repro.distributed.sharding import fsdp_shardings
        from jax.sharding import NamedSharding
        mesh = jax.make_mesh((1, 1), ("data", "model"))
        sh = NamedSharding(mesh, P(None, None))
        small = jnp.zeros((4, 4), jnp.float32)          # 64 B < min_bytes
        big = jax.ShapeDtypeStruct((1024, 1024), jnp.float32)   # 4 MiB
        out = fsdp_shardings({"a": sh, "b": sh}, {"a": small, "b": big},
                             mesh, min_bytes=2**20)
        assert out["a"] is sh                            # untouched
        assert out["b"].spec != sh.spec                  # resharded
        assert "data" in jax.tree.leaves(tuple(out["b"].spec))

    def test_fsdp_without_data_axis_is_identity(self):
        from repro.distributed.sharding import fsdp_shardings
        mesh = FakeMesh({"model": 4})
        tree = {"a": object()}
        assert fsdp_shardings(tree, {"a": jnp.zeros((8, 8))}, mesh) is tree

    def test_batch_specs_fallback_replicates(self):
        from repro.distributed.sharding import batch_specs
        mesh = jax.make_mesh((1, 1), ("data", "model"))
        out = batch_specs({"x": jnp.zeros((4, 8))}, mesh)
        assert out["x"].spec in (P("data", None), P(("data",), None))

    def test_spec_for_reports_fallback(self):
        from repro.distributed.sharding import shardings_for_tree
        mesh = jax.make_mesh((1, 1), ("data", "model"))
        report = []
        # vocab 51865 is indivisible by any >1 axis; on the 1x1 mesh it
        # shards, so force the fallback with a fake 16-way mesh via spec_for
        spec = spec_for((51865, 512), ("vocab", "embed"),
                        FakeMesh({"model": 16}), report=report)
        assert spec == P(None, None)
        assert report[0][1] == "vocab" and report[0][3] == 16


# ---------------------------------------------------------------------------
# Collective chain models (perf_model §16)
# ---------------------------------------------------------------------------
class TestCollectiveModels:
    def test_wire_bytes(self):
        from repro.core import perf_model as pm
        nb = 1 << 20
        assert pm.collective_wire_bytes("all_gather", nb, 4) == nb * 3 // 4
        assert pm.collective_wire_bytes("reduce_scatter", nb, 4) == nb * 3 // 4
        assert pm.collective_wire_bytes("all_reduce", nb, 4) == 2 * nb * 3 // 4
        assert pm.collective_wire_bytes("all_gather", nb, 1) == 0
        assert pm.collective_wire_bytes("none", nb, 8) == 0

    def test_chain_model_overlap_fields(self):
        from repro.core import perf_model as pm
        chain = pm.mlp_chain_model(tokens=4096, d_model=2048, d_ff=8192,
                                   gated=True, dtype_bytes=2, fused=True)
        out = pm.collective_chain_model(chain, collective="all_to_all",
                                        nbytes=4096 * 2048 * 2, n_shards=4)
        assert out["collective"] == "all_to_all"
        assert out["collective_bytes"] > 0
        assert 0.0 <= out["overlap_fraction"] <= 1.0
        assert out["dma_bytes"] > out["hbm_dma_bytes"]   # wire folded in
        assert out["time_s"] >= chain["time_s"]

    def test_collective_gemm_ring_beats_gather(self):
        from repro.core import perf_model as pm
        ring = pm.collective_gemm_model(m=4096, n=4096, k=4096, n_shards=4,
                                        fused=True)
        gath = pm.collective_gemm_model(m=4096, n=4096, k=4096, n_shards=4,
                                        fused=False)
        assert ring["dma_bytes"] < gath["dma_bytes"]
        assert ring["overlap_fraction"] > 0.0
        assert gath["overlap_fraction"] == 0.0
        assert ring["ring_steps"] == 4
        assert ring["time_s"] <= gath["time_s"]

    def test_partial_softmax_allreduce(self):
        from repro.core import perf_model as pm
        out = pm.partial_softmax_allreduce_model(rows=4096, head_dim=128,
                                                 n_shards=4)
        assert out["kind"] == "all_reduce"
        # rows * (head_dim + 2) fp32 values, 2(n-1)/n wire factor
        want = 2 * 4096 * 130 * 4 * 3 // 4
        assert out["wire_bytes"] == want


# ---------------------------------------------------------------------------
# Sharded plan selection: memo keys, journaling, pretuned keys
# ---------------------------------------------------------------------------
class TestShardedPlans:
    def _spec(self, collective="all_to_all", dim="expert"):
        from repro.distributed.sharding import ShardSpec
        return ShardSpec(mesh=(("model", 4),), partition=((dim, "model"),),
                         collective=collective)

    def test_select_fusion_sharded_plan_journaled(self):
        from repro import obs
        from repro.core import autotune
        sp = self._spec()
        with obs.capture() as rec:
            plan = autotune.select_fusion("mlp", (4096, 2048, 2048, 1),
                                          "bfloat16", residual=False,
                                          shard=sp)
        assert plan["plan"] == "fused"
        assert plan["shard"] == sp.describe()
        assert plan["overlap_fraction"] > 0.0
        evs = [e for e in rec.plans if e.kind == "fusion"
               and e.chosen.get("shard") == sp.describe()]
        assert evs, "sharded fusion verdict must be plan-audit journaled"

    def test_shard_joins_memo_key(self):
        from repro.core import autotune
        shape = (2048, 1024, 4096, 1)
        plain = autotune.select_fusion("mlp", shape, "bfloat16",
                                       residual=False)
        sharded = autotune.select_fusion("mlp", shape, "bfloat16",
                                         residual=False, shard=self._spec())
        assert "shard" not in plain
        assert sharded["shard"] and sharded is not plain

    def test_pretuned_fusion_key_shard_token(self):
        from repro.core import autotune
        base = autotune.pretuned_fusion_key(
            "mlp", (4096, 2048, 8192, 1), "bfloat16", residual=False,
            prenorm="none", backward=False, causal=False, softcap=False,
            sink=False)
        sharded = autotune.pretuned_fusion_key(
            "mlp", (4096, 2048, 8192, 1), "bfloat16", residual=False,
            prenorm="none", backward=False, causal=False, softcap=False,
            sink=False, shard=self._spec())
        assert "shard=" not in base          # shipped tables stay valid
        assert sharded == base + "|shard=model=4|expert@model|all_to_all"

    def test_signature_bucket_carries_shard(self):
        from repro.core.autotune import OpSignature
        sig = OpSignature(op="gemm", shape=(128, 128, 128),
                          dtype="bfloat16", shard=self._spec())
        assert sig.bucket()[-1] == self._spec()
        assert OpSignature(op="gemm", shape=(128, 128, 128),
                           dtype="bfloat16").bucket()[-1] is None

    def test_gemm_collective_kind_requires_shard(self):
        from repro.core import autotune
        with pytest.raises(ValueError):
            autotune.select_fusion("gemm_collective", (4096, 4096, 4096),
                                   "bfloat16")
        plan = autotune.select_fusion(
            "gemm_collective", (4096, 4096, 4096), "bfloat16",
            shard=self._spec(collective="all_gather", dim="rows"))
        assert plan["plan"] == "fused" and plan["overlap_fraction"] > 0

    def test_policies_for_model_sharded(self):
        from repro.core import autotune
        from repro.configs import get_config
        cfg = get_config("mixtral-8x7b", smoke=True)
        pols = autotune.policies_for_model(cfg, batch=2, seq_len=128,
                                           shard=self._spec())
        assert pols  # resolves without error, sharded cells included


class TestMultiDeviceFused:
    """Fused shard_map experts + ring collective GEMM: bitwise contracts on
    the 8-forced-host-device harness (DESIGN.md §16)."""

    def test_moe_fused_bitwise_ep_and_tp(self, subproc):
        out = subproc("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import ModelConfig, MoEConfig
from repro.models.moe import moe_forward, moe_defs
from repro.models.common import init_params
mesh = jax.make_mesh((2, 4), ('data', 'model'))
for impl, n_exp in (('ep', 8), ('tp', 8)):
    cfg = ModelConfig(name='t', family='lm', num_layers=1, d_model=128,
                      num_heads=4, num_kv_heads=2, d_ff=256, vocab_size=64,
                      block_pattern=('moe',),
                      moe=MoEConfig(num_experts=n_exp, top_k=2,
                                    capacity_factor=4.0, impl=impl,
                                    shard='expert' if impl == 'ep' else 'ffn'))
    params = init_params(moe_defs(cfg, 'moe'), jax.random.PRNGKey(0))['moe']
    x = (jax.random.normal(jax.random.PRNGKey(1), (2, 64, 128)) * 0.1
         ).astype(jnp.float32)
    prenorm = (jnp.ones((128,)) * 1.5, None)
    o_ref, _ = moe_forward(cfg, params, x, mesh=mesh, mode='reference',
                           prenorm=prenorm)
    o_fus, _ = moe_forward(cfg, params, x, mesh=mesh,
                           mode='pallas_interpret', prenorm=prenorm)
    diff = float(jnp.abs(o_ref - o_fus).max())
    print(impl, 'bitwise', diff)
    assert diff == 0.0, (impl, diff)
print('OK')
""")
        assert "OK" in out

    def test_moe_collective_mode_fallback_observable(self, subproc):
        """pallas_tpu inside shard_map is gated to reference — the fallback
        must hit the counter AND the plan-audit journal (satellite S2)."""
        out = subproc("""
import jax, jax.numpy as jnp
from repro import obs
from repro.configs.base import ModelConfig, MoEConfig
from repro.models.moe import moe_forward, moe_defs
from repro.models.common import init_params
cfg = ModelConfig(name='t', family='lm', num_layers=1, d_model=64,
                  num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=64,
                  block_pattern=('moe',),
                  moe=MoEConfig(num_experts=8, top_k=2, capacity_factor=4.0))
params = init_params(moe_defs(cfg, 'moe'), jax.random.PRNGKey(0))['moe']
x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 64))
mesh = jax.make_mesh((2, 4), ('data', 'model'))
with obs.capture() as rec:
    o, _ = moe_forward(cfg, params, x, mesh=mesh, mode='pallas_tpu')
assert rec.counters.get('moe.collective_mode_fallback', 0) >= 1
evs = [e for e in rec.plans if e.kind == 'collective_mode']
assert evs and evs[0].chosen['requested'] == 'pallas_tpu'
assert evs[0].chosen['mode'] == 'reference'
print('OK')
""")
        assert "OK" in out

    def test_gemm_collective_ring_bitwise(self, subproc):
        """Ring == gather-then-gemm == jnp oracle, bitwise, both variants,
        reference and pallas_interpret (acceptance gate); and within
        float32 rounding of one plain dot."""
        out = subproc("""
import jax, jax.numpy as jnp, numpy as np
from repro.kernels.gemm import (gemm_collective_sharded,
                                gemm_collective_oracle)
mesh = jax.make_mesh((4,), ('model',))
M, K, N = 64, 128, 96
x = (jax.random.normal(jax.random.PRNGKey(0), (M, K)) * 0.1
     ).astype(jnp.float32)
w = (jax.random.normal(jax.random.PRNGKey(1), (K, N)) * 0.1
     ).astype(jnp.float32)
plain = np.asarray(jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST))
for variant in ('all_gather', 'reduce_scatter'):
    oracle = gemm_collective_oracle(x, w, variant=variant, axis_size=4)
    if variant == 'reduce_scatter':
        oracle = oracle.reshape(-1, N)
    for mode in ('reference', 'pallas_interpret'):
        ring = gemm_collective_sharded(x, w, mesh=mesh, variant=variant,
                                       mode=mode, plan='ring')
        gather = gemm_collective_sharded(x, w, mesh=mesh, variant=variant,
                                         mode=mode, plan='gather')
        assert jnp.array_equal(ring, gather), (variant, mode, 'ring!=gather')
        assert jnp.array_equal(ring, oracle), (variant, mode, 'ring!=oracle')
        # independent of the ring's panel decomposition: one plain dot
        np.testing.assert_allclose(np.asarray(ring).reshape(-1, N), plain,
                                   rtol=1e-5, atol=1e-6)
        print(variant, mode, 'bitwise OK')
print('OK')
""", devices=4)
        assert "OK" in out

    def test_gemm_collective_autotuned_plan(self, subproc):
        """plan=None consults select_fusion with the interconnect term; on
        square train shapes the ring must win and be journaled."""
        out = subproc("""
import jax, jax.numpy as jnp
from repro import obs
from repro.kernels.gemm import gemm_collective_sharded
mesh = jax.make_mesh((4,), ('model',))
x = (jax.random.normal(jax.random.PRNGKey(0), (64, 128)) * 0.1
     ).astype(jnp.float32)
w = (jax.random.normal(jax.random.PRNGKey(1), (128, 96)) * 0.1
     ).astype(jnp.float32)
with obs.capture() as rec:
    gemm_collective_sharded(x, w, mesh=mesh, variant='all_gather',
                            mode='pallas_interpret', plan=None)
assert rec.counters.get('gemm_collective.all_gather.ring', 0) >= 1
print('OK')
""", devices=4)
        assert "OK" in out

    def test_train_loop_sharded_plan_pins(self, subproc):
        """train_loop on a dp×tp mesh pins bucket policies through the
        sharded plan path (train_shard_spec) without breaking the step."""
        out = subproc("""
import jax, numpy as np
from repro import obs
from repro.configs import get_config
from repro.models import build_model
from repro.train import train_loop
from repro.optim import AdamWConfig, constant_schedule
from repro.data.pipeline import DataConfig, DataIterator
from repro.launch.mesh import make_mesh
cfg = get_config('mixtral-8x7b', smoke=True)
mesh = make_mesh((2, 4), ('data', 'model'))
model = build_model(cfg, mode='reference', mesh=mesh)
dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=32, global_batch=2)
it = DataIterator(dcfg, mesh=mesh)
res = train_loop(model, it, 2, AdamWConfig(schedule=constant_schedule(1e-3)),
                 mesh=mesh, log=lambda *a, **k: None)
assert len(res.losses) == 2 and all(np.isfinite(l) for l in res.losses)
assert res.policies, 'bucket policies must be pinned'
print('OK')
""")
        assert "OK" in out


class TestShardedPagedEngine:
    """Per-host page-pool topology (serve/topology.py)."""

    def _setup(self):
        from repro.configs import get_config
        from repro.models import build_model
        cfg = get_config("granite-8b", smoke=True)
        model = build_model(cfg, mode="reference")
        params = model.init(jax.random.PRNGKey(0))
        return cfg, model, params

    def _reqs(self, cfg, n=4, max_new=4):
        from repro.serve import Request
        out = []
        for i in range(n):
            prompt = jax.random.randint(jax.random.PRNGKey(100 + i),
                                        (6 + i,), 0, cfg.vocab_size)
            out.append(Request(uid=i, prompt=prompt,
                               max_new_tokens=max_new))
        return out

    def test_parity_with_single_engine(self):
        from repro.serve import Engine, ShardedPagedEngine
        cfg, model, params = self._setup()
        reqs = self._reqs(cfg)
        eng = ShardedPagedEngine(model, params, n_hosts=2, batch_slots=2,
                                 page_size=8, max_pages_per_seq=4)
        for r in reqs:
            eng.submit(r)
        results = eng.run()
        golden = Engine(model, params, max_len=64)
        for r in reqs:
            want = golden.generate(r.prompt[None, :],
                                   r.max_new_tokens).tokens[0]
            assert jnp.array_equal(jnp.asarray(results[r.uid]),
                                   jnp.asarray(want)), r.uid

    def test_placement_and_report(self):
        from repro.serve import ShardedPagedEngine
        cfg, model, params = self._setup()
        reqs = self._reqs(cfg, n=4)
        eng = ShardedPagedEngine(model, params, n_hosts=2, batch_slots=2,
                                 page_size=8, max_pages_per_seq=4)
        for r in reqs:
            eng.submit(r)
        eng.run()
        rep = eng.report()
        assert rep["n_hosts"] == 2
        assert sum(rep["admissions_by_host"]) == 4
        # deterministic least-loaded admission spreads the 4 requests 2/2
        assert rep["admissions_by_host"] == [2, 2]
        assert rep["completed"] == 4
        assert set(rep["placements"]) == {0, 1, 2, 3}
        assert len(rep["per_host"]) == 2
        assert rep["page_pool_size"] == 2 * rep["per_host"][0]["page_pool_size"]

    def test_duplicate_uid_rejected(self):
        from repro.serve import ShardedPagedEngine
        cfg, model, params = self._setup()
        (req,) = self._reqs(cfg, n=1)
        eng = ShardedPagedEngine(model, params, n_hosts=2, batch_slots=2,
                                 page_size=8, max_pages_per_seq=4)
        eng.submit(req)
        with pytest.raises(ValueError):
            eng.submit(req)

    def test_bad_host_count_rejected(self):
        from repro.serve import ShardedPagedEngine
        cfg, model, params = self._setup()
        with pytest.raises(ValueError):
            ShardedPagedEngine(model, params, n_hosts=0)
