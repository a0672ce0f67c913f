"""Declarative epilogue/prologue chains for the blocked GEMM megakernel.

HipKittens' biggest wins are in memory-bound settings where fused kernels
avoid HBM round trips (paper Fig. 9); ThunderKittens makes the same case for
"AI kernels = GEMM + a short elementwise chain" on NVIDIA. An
:class:`Epilogue` is that chain, declared as a frozen (hashable, jit-static)
spec and applied inside the GEMM kernel's final ``@pl.when(k == nk-1)``
store — the output tile is transformed while still resident in VMEM, so the
consumer ops (bias, activation, SwiGLU gating, residual add, fp8 dequant,
RoPE rotation) never re-read the activation from HBM.

Canonical chain order (each stage optional):

    acc --[scale]--> --[+bias]--> --[rope]--> --[act | act*acc2]--> --[+residual]--> store

  * ``scale``    — multiply by a runtime scalar. Doubles as the fp8 dequant
                   scale and as the model's residual_scale (out = s·C + res).
  * ``bias``     — add a broadcast (1, N) row vector.
  * ``rope``     — rotary rotation applied per ``head_dim`` column chunk
                   (the fused QKV→RoPE *prologue* of attention: q/k tiles are
                   rotated before they ever hit HBM). sin/cos are streamed as
                   (M, head_dim) row blocks.
  * ``gate``     — dual-output GEMM: the kernel accumulates a second
                   product A@B2 and stores ``act(acc) * acc2`` (SwiGLU/GeGLU
                   fusing the two MLP up-projections into one pass over A).
  * ``activation`` — plain silu/gelu/relu when not gated.
  * ``residual`` — add a streamed (M, N) tile.

The same :meth:`Epilogue.apply` implements the chain for both the Pallas
kernel (on VMEM tiles) and the jnp oracle (on full arrays) — every stage is
elementwise or row-broadcast, so tile-wise application is exact. This
chain-spec protocol (``operand_names`` / ``extra_operand_blocks`` /
``check_blocks`` / ``apply`` / ``extra_read_bytes`` / ``describe``) is
shared with the load-side :class:`~repro.kernels.gemm.prologue.Prologue`
(DESIGN.md §10), which transforms the A tiles on the way *in* the same way
this spec transforms the output tiles on the way out.

Extra-operand convention (the order kernels and ops agree on; prologue
operands precede these in the kernel ref list):
``b2?, bias?, residual?, scale?, sin?, cos?`` — see :meth:`operand_names`.

Legality (DESIGN.md §9): the extra streamed blocks and the second
accumulator count against the VMEM budget via
:meth:`extra_operand_blocks` / :meth:`extra_scratch_accumulators`, which
``KernelPolicy`` consults when ``policy.epilogue`` is set; ``rope`` further
requires ``block_n % head_dim == 0`` (the rotation reshapes the tile to
whole heads), enforced by :meth:`check_blocks`.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

ACTIVATIONS = ("none", "silu", "gelu", "relu")
SCALE_KINDS = ("scalar", "row", "col")

# f32-in/f32-out activation bodies; gelu matches models/common.act_fn
# (approximate=True).
_ACT_FNS = {
    "silu": jax.nn.silu,
    "gelu": lambda x: jax.nn.gelu(x, approximate=True),
    "relu": jax.nn.relu,
}


def _act_grad(name: str, x, g):
    """cotangent of _ACT_FNS[name] at x — derived with jax.vjp so the
    transpose rule can never drift from the forward body."""
    _, vjp = jax.vjp(_ACT_FNS[name], x)
    return vjp(g)[0]


def rope_rotate(x, sin, cos, head_dim: int):
    """Rotate-half RoPE on a (rows, cols) tile whose columns are whole heads.

    sin/cos: (rows, head_dim) duplicated-halves tables (one row per token
    row of the tile). Identical math to kernels.rope.ref.rope_ref, applied
    per head_dim-sized column chunk.

    Written with lane rolls and a lane mask, not a (rows, heads, head_dim)
    reshape: Mosaic cannot split the lane dim into 64-wide heads.
    """
    rows, cols = x.shape
    half = head_dim // 2
    reps = cols // head_dim
    if reps > 1:
        sin = jnp.concatenate([sin] * reps, axis=1)
        cos = jnp.concatenate([cos] * reps, axis=1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1) % head_dim
    # rotate-half within each head: lane j takes -x[j + half] in the first
    # half of its head and x[j - half] in the second; the rolls' wrapped
    # lanes are never selected
    rotated = jnp.where(lane < half, -jnp.roll(x, -half, axis=1),
                        jnp.roll(x, half, axis=1))
    return x * cos + rotated * sin


@dataclasses.dataclass(frozen=True)
class Epilogue:
    """A frozen, hashable epilogue chain spec (jit-static by construction)."""

    bias: bool = False
    activation: str = "none"     # 'none' | 'silu' | 'gelu' | 'relu'
    gate: bool = False           # dual-output GEMM: store act(acc) * acc2
    residual: bool = False
    scale: bool = False          # runtime scale: fp8 dequant / residual_scale
    scale_kind: str = "scalar"   # 'scalar' | 'row' (M,1) | 'col' (1,N) —
                                 # per-channel fp8 dequant vectors
    rope: bool = False           # per-head rotary rotation (QKV prologue)
    head_dim: int = 0            # required (and >0, even) when rope=True

    def __post_init__(self):
        if self.activation not in ACTIVATIONS:
            raise ValueError(f"unknown activation {self.activation!r}; "
                             f"have {ACTIVATIONS}")
        if self.scale_kind not in SCALE_KINDS:
            raise ValueError(f"unknown scale_kind {self.scale_kind!r}; "
                             f"have {SCALE_KINDS}")
        if self.scale_kind != "scalar" and not self.scale:
            raise ValueError("scale_kind is only meaningful with scale=True")
        if self.gate and self.activation == "none":
            raise ValueError("gate=True needs an activation (SwiGLU/GeGLU "
                             "stores act(acc) * acc2)")
        if self.gate and self.bias:
            raise ValueError("gate=True excludes bias (the dual-output "
                             "up-projection GEMM is bias-free)")
        if self.rope:
            if self.gate or self.residual or self.activation != "none":
                raise ValueError("rope composes only with bias/scale (it is "
                                 "the QKV-projection prologue, not an MLP "
                                 "epilogue)")
            if self.head_dim <= 0 or self.head_dim % 2:
                raise ValueError(f"rope=True needs an even head_dim > 0, "
                                 f"got {self.head_dim}")
        elif self.head_dim:
            raise ValueError("head_dim is only meaningful with rope=True")

    # -- identity / shape of the chain -------------------------------------
    @property
    def is_identity(self) -> bool:
        return not (self.bias or self.gate or self.residual or self.scale
                    or self.rope or self.activation != "none")

    @property
    def n_accumulators(self) -> int:
        return 2 if self.gate else 1

    def operand_names(self) -> tuple:
        """Runtime extra operands, in the canonical kernel order."""
        names = []
        if self.gate:
            names.append("b2")
        if self.bias:
            names.append("bias")
        if self.residual:
            names.append("residual")
        if self.scale:
            names.append("scale")
        if self.rope:
            names += ["sin", "cos"]
        return tuple(names)

    # -- VMEM legality accounting (consumed by KernelPolicy) ----------------
    def extra_operand_blocks(self, block_m: int, block_n: int, block_k: int,
                             in_dtype: str) -> list:
        """(shape, dtype) of each extra pipelined block, for vmem budgeting."""
        blocks = []
        if self.gate:
            blocks.append(((block_k, block_n), in_dtype))
        if self.bias:
            blocks.append(((1, block_n), in_dtype))
        if self.residual:
            blocks.append(((block_m, block_n), in_dtype))
        if self.scale:
            blocks.append((self.scale_block(block_m, block_n), "float32"))
        if self.rope:
            blocks += [((block_m, self.head_dim), "float32")] * 2
        return blocks

    def scale_block(self, block_m: int, block_n: int) -> tuple:
        """The streamed f32 scale block per scale_kind: one scalar, an (M, 1)
        per-row column, or a (1, N) per-channel dequant row."""
        if self.scale_kind == "row":
            return (block_m, 1)
        if self.scale_kind == "col":
            return (1, block_n)
        return (1, 1)

    def extra_scratch_accumulators(self) -> int:
        """Accumulators beyond the first (the gate path pins a second)."""
        return self.n_accumulators - 1

    def check_blocks(self, block_n: int) -> None:
        """Raise on block shapes the chain cannot legally tile."""
        if self.rope and block_n % self.head_dim:
            raise ValueError(
                f"rope epilogue needs block_n % head_dim == 0 "
                f"(got block_n={block_n}, head_dim={self.head_dim})")

    # -- modeled HBM traffic of the extra streamed operands -----------------
    def extra_read_bytes(self, m: int, n: int, dtype_bytes: int) -> int:
        """Bytes the fused kernel reads beyond A/B panels and the C store.

        The gate operand (B2) is *not* counted here — it streams like B and
        is accounted at the panel level (doubled B traffic) by the scorer.
        """
        extra = 0
        if self.bias:
            extra += n * dtype_bytes
        if self.residual:
            extra += m * n * dtype_bytes
        if self.scale:
            extra += 4 * {"scalar": 1, "row": m, "col": n}[self.scale_kind]
        if self.rope:
            extra += 2 * m * self.head_dim * 4
        return extra

    # -- the chain itself ---------------------------------------------------
    def apply(self, acc, acc2=None, *, bias=None, residual=None, scale=None,
              sin=None, cos=None):
        """Run the chain on an fp32 accumulator (tile or full array).

        All operands must already be fp32; broadcasting rules make the same
        code exact for a (block_m, block_n) tile and the full (M, N) array.
        """
        out = acc
        if self.scale:
            out = out * scale
        if self.bias:
            out = out + bias
        if self.rope:
            out = rope_rotate(out, sin, cos, self.head_dim)
        if self.gate:
            g2 = acc2 * scale if self.scale else acc2
            out = _ACT_FNS[self.activation](out) * g2
        elif self.activation != "none":
            out = _ACT_FNS[self.activation](out)
        if self.residual:
            out = out + residual
        return out

    # -- the chain transpose (DESIGN.md §11) --------------------------------
    @property
    def needs_saved_preact(self) -> bool:
        """True when the bwd transpose needs the raw fp32 accumulator(s) the
        fwd store consumed: the activation transpose is act'(preact)·g (and
        the gate also needs preact2), and dscale is a <g, preact> reduction.
        rope alone does not qualify — the rotation is invertible, so the
        table cotangents re-derive the pre-rope value from the saved output.
        """
        return self.gate or self.activation != "none" or self.scale

    @property
    def saved_accumulators(self) -> int:
        """How many accumulators the fwd launch stores for the kernel bwd."""
        return self.n_accumulators if self.needs_saved_preact else 0

    @property
    def preact_keeps_f32(self) -> bool:
        """scale chains save fp32 preactivations: dscale is a <g, preact>
        *reduction*, so the summed cotangent inherits the operand's
        precision (act' only modulates g elementwise and tolerates the MXU
        input rounding). One predicate shared by the fwd launch's save, the
        policy VMEM rule, and the bwd traffic model."""
        return self.scale

    def _transpose_core(self, g, preact=None, preact2=None, *, bias=None,
                        scale=None, sin=None, cos=None) -> dict:
        """The shared transpose chain: walks the fwd stage order backwards,
        recomputing the activation/rope input from the saved accumulator.
        Returns every intermediate cotangent the rules below pick from:
        'g_acc'/'g_acc2' (raw-accumulator cotangents, the bwd GEMM streams),
        'g_bias' (pre-bias-point cotangent, column-reduced into dbias),
        'g_scaled'/'g_scaled2' (post-scale-point cotangents, the dscale
        reduction operands). All elementwise/broadcast, so the same code is
        exact on a VMEM tile and on the full array.
        """
        out = {}
        gy = g  # the residual add transposes to identity on the main path
        if self.gate:
            u = preact * scale if self.scale else preact
            v2 = preact2 * scale if self.scale else preact2
            du = _act_grad(self.activation, u, gy * v2)
            dv2 = _ACT_FNS[self.activation](u) * gy
            out["g_scaled"], out["g_scaled2"] = du, dv2
            out["g_acc"] = du * scale if self.scale else du
            out["g_acc2"] = dv2 * scale if self.scale else dv2
            return out
        if self.activation != "none":
            # u = the activation input: scale then bias applied to preact
            u = preact
            if self.scale:
                u = u * scale
            if self.bias:
                u = u + bias
            du = _act_grad(self.activation, u, gy)
        elif self.rope:
            # rotation adjoint = rotation by -theta
            du = rope_rotate(gy, -sin, cos, self.head_dim)
        else:
            du = gy
        out["g_bias"] = du
        out["g_scaled"] = du
        out["g_acc"] = du * scale if self.scale else du
        return out

    def transpose_tile(self, g, preact=None, preact2=None, *, bias=None,
                       scale=None, sin=None, cos=None) -> dict:
        """Tile-local half of the declarative transpose rule (DESIGN.md §11):
        grad_out tile -> the cotangent streams the bwd GEMM launches consume.
        'g_acc' (and 'g_acc2' for the dual-output gate) feed dA = g_acc@Bᵀ
        and dB = Aᵀ@g_acc; 'g_bias' (present iff bias) is the pre-bias-point
        cotangent the dB launch column-reduces into dbias inside its store.
        This is the fwd epilogue run as a *prologue on g*: applied to each g
        tile as it streams into the bwd launches.
        """
        core = self._transpose_core(g, preact, preact2, bias=bias,
                                    scale=scale, sin=sin, cos=cos)
        keep = {"g_acc"}
        if self.gate:
            keep.add("g_acc2")
        if self.bias:
            keep.add("g_bias")
        return {k: v for k, v in core.items() if k in keep}

    def operand_grads(self, g, preact=None, preact2=None, out=None, *,
                      bias=None, residual=None, scale=None, sin=None,
                      cos=None) -> dict:
        """Reduction half of the transpose rule, on full arrays (jnp): the
        cotangents of the chain's extra operands. The kernel path folds the
        dbias column-sum into the dB launch store, so it only consults this
        for dresidual (identity), dscale (a <g, preact> reduction shaped per
        scale_kind) and the rope-table cotangents (which re-derive the
        pre-rope value — from the saved preact when one exists, else by
        inverting the rotation on the saved output). The jnp bwd oracle uses
        every entry, dbias included. Unused entries are DCE'd under jit.
        """
        core = self._transpose_core(g, preact, preact2, bias=bias,
                                    scale=scale, sin=sin, cos=cos)
        grads = {}
        if self.residual:
            grads["residual"] = g
        if self.bias:
            grads["bias"] = jnp.sum(core["g_bias"], axis=0, keepdims=True)
        if self.scale:
            ds = core["g_scaled"] * preact
            if self.gate:
                ds = ds + core["g_scaled2"] * preact2
            axis = {"scalar": (0, 1), "row": (1,), "col": (0,)}[self.scale_kind]
            grads["scale"] = jnp.sum(ds, axis=axis, keepdims=True)
        if self.rope:
            if preact is not None:
                u = preact * scale if self.scale else preact
                if self.bias:
                    u = u + bias
            else:
                u = rope_rotate(out, -sin, cos, self.head_dim)
            rows, cols = u.shape
            hd, half = self.head_dim, self.head_dim // 2
            uh = u.reshape(rows, cols // hd, hd)
            gh = g.reshape(rows, cols // hd, hd)
            rot = jnp.concatenate([-uh[..., half:], uh[..., :half]], axis=-1)
            grads["sin"] = jnp.sum(gh * rot, axis=1)
            grads["cos"] = jnp.sum(gh * uh, axis=1)
        return grads

    def describe(self) -> str:
        """Short tag for reports/benchmark rows, e.g. 'bias+silu*gate+res'."""
        if self.is_identity:
            return "none"
        parts = []
        if self.scale:
            parts.append("scale" if self.scale_kind == "scalar"
                         else f"scale:{self.scale_kind}")
        if self.bias:
            parts.append("bias")
        if self.rope:
            parts.append(f"rope{self.head_dim}")
        if self.gate:
            parts.append(f"{self.activation}*gate")
        elif self.activation != "none":
            parts.append(self.activation)
        if self.residual:
            parts.append("res")
        return "+".join(parts)


EPILOGUE_NONE = Epilogue()
