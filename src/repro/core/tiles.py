"""Tile abstractions with TPU-native alignment rules (HipKittens C1, TPU-adapted).

HipKittens restricts tile rows/columns to multiples of the AMD matrix-core
shape and derives per-instruction swizzles so that every co-occurring access
pattern is bank-conflict free *by construction at tile-creation time*.

On TPU the analogous hazards are:
  * relayout / padding waste when the last two dims of a VMEM block are not
    multiples of the dtype's native tiling (sublane, lane);
  * MXU underutilization when matmul dims are not multiples of 128;
  * VMEM overflow when the pipeline's working set exceeds the ~128 MiB budget.

``TileSpec`` encodes the legality rules; every Pallas BlockSpec in this repo is
built through :func:`block_spec` so misaligned tiles are rejected at trace time.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Sequence

import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# ---------------------------------------------------------------------------
# TPU v5e hardware constants (single core).
# ---------------------------------------------------------------------------
LANE = 128            # minor-dim vector lane count
MXU = 128             # systolic array dimension (128x128)
VMEM_BYTES = 128 * 1024 * 1024   # per-core VMEM budget we target (v5e: 128MiB)
SMEM_BYTES = 1 * 1024 * 1024

# Native (sublane, lane) tiling per element width. A VMEM block whose last two
# dims are multiples of this incurs no relayout/padding.
_SUBLANE_BY_ITEMSIZE = {4: 8, 2: 16, 1: 32}


def native_tiling(dtype) -> tuple[int, int]:
    """Return the native (sublane, lane) tile for ``dtype``."""
    itemsize = jnp.dtype(dtype).itemsize
    if itemsize not in _SUBLANE_BY_ITEMSIZE:
        raise ValueError(f"unsupported dtype for tiles: {dtype}")
    return (_SUBLANE_BY_ITEMSIZE[itemsize], LANE)


def is_aligned(shape: Sequence[int], dtype) -> bool:
    """True if the trailing dims of ``shape`` are native-tile multiples."""
    if len(shape) == 0:
        return False
    sub, lane = native_tiling(dtype)
    if len(shape) == 1:
        return shape[-1] % lane == 0
    return shape[-1] % lane == 0 and shape[-2] % sub == 0


@dataclasses.dataclass(frozen=True)
class TileSpec:
    """A 2-D tile of ``dtype`` living in VMEM.

    Mirrors HK's register/shared tiles: shape is validated against the
    hardware-native tiling, exactly as HK validates against MFMA shapes.
    ``pinned`` requests explicit scratch allocation (the TPU analogue of HK's
    pinned register ranges — see DESIGN.md §2).
    """

    rows: int
    cols: int
    dtype: str = "bfloat16"
    pinned: bool = False

    def __post_init__(self):
        sub, lane = native_tiling(self.dtype)
        if self.rows <= 0 or self.cols <= 0:
            raise ValueError(f"tile dims must be positive, got {self.rows}x{self.cols}")
        if self.rows % sub != 0:
            raise ValueError(
                f"tile rows {self.rows} not a multiple of sublane {sub} for {self.dtype}"
            )
        if self.cols % lane != 0:
            raise ValueError(
                f"tile cols {self.cols} not a multiple of lane {lane} for {self.dtype}"
            )

    @property
    def shape(self) -> tuple[int, int]:
        return (self.rows, self.cols)

    @property
    def nbytes(self) -> int:
        return self.rows * self.cols * jnp.dtype(self.dtype).itemsize

    def mxu_aligned(self) -> bool:
        """True if both dims are MXU-dimension multiples (full systolic use)."""
        return self.rows % MXU == 0 and self.cols % MXU == 0


def assert_tile(shape: Sequence[int], dtype, *, what: str = "block") -> None:
    """Raise if the trailing 2 dims of ``shape`` are not a legal tile."""
    if len(shape) < 2:
        if len(shape) == 1 and shape[0] % LANE == 0:
            return
        raise ValueError(f"{what}: shape {tuple(shape)} too small / misaligned")
    TileSpec(shape[-2], shape[-1], str(jnp.dtype(dtype)))


def block_spec(shape: Sequence[int], index_map: Callable, dtype="bfloat16",
               *, allow_ragged_minor: bool = False) -> pl.BlockSpec:
    """Build a Pallas BlockSpec, enforcing native-tiling legality.

    ``allow_ragged_minor`` permits a final dim that is not a LANE multiple
    (e.g. head_dim=64 tiles), which Pallas pads — we account for the padding
    in vmem_bytes but allow it since head_dim 64 attention is a paper
    workload (Fig. 7). With the flag off the contract is strict: *every*
    non-multiple minor dim is rejected, including the lane/2 case (callers on
    the head-dim-64 path must opt in explicitly).
    """
    shape = tuple(shape)
    if not allow_ragged_minor:
        # Trailing-2 dims must be native-tile multiples; leading dims are free.
        trailing = [d for d in shape if d is not None]
        if len(trailing) >= 2:
            sub, lane = native_tiling(dtype)
            r, c = trailing[-2], trailing[-1]
            if c % lane != 0:
                raise ValueError(f"block minor dim {c} not {lane}-aligned "
                                 f"(pass allow_ragged_minor=True to accept "
                                 f"padded tiles, e.g. head_dim 64)")
            if r % sub != 0:
                raise ValueError(f"block sublane dim {r} not {sub}-aligned")
    return pl.BlockSpec(shape, index_map)


def shape_ragged(rows_dim: int, minor_dim: int, dtype) -> bool:
    """True when the *problem* dims themselves are not native-tile aligned.

    Any tiling of an unaligned problem dim pads, so kernels waive
    :func:`block_spec`'s strict gate for exactly these shapes (reproducing
    the padding the pre-policy raw BlockSpecs accepted) while keeping the
    gate active for aligned problems, where a misaligned *block* is a bug.
    """
    sub, lane = native_tiling(dtype)
    return rows_dim % sub != 0 or minor_dim % lane != 0


def compiler_params(*, dimension_semantics: tuple | None = None):
    """Mosaic compiler params for every pallas_call in the repo. The scoped
    VMEM limit is the same ``VMEM_BYTES`` the legality checks budget
    against, so a policy the autotuner calls legal is one the compiler
    accepts (its default scoped limit is far lower)."""
    return pltpu.CompilerParams(dimension_semantics=dimension_semantics,
                                vmem_limit_bytes=VMEM_BYTES)


def padded_tile_bytes(shape: Sequence[int], dtype) -> int:
    """Bytes a block occupies in VMEM after padding to native tiling."""
    sub, lane = native_tiling(dtype)
    dims = [d for d in shape if d is not None]
    if not dims:
        return 0
    padded = list(dims)
    padded[-1] = math.ceil(padded[-1] / lane) * lane
    if len(padded) >= 2:
        padded[-2] = math.ceil(padded[-2] / sub) * sub
    return math.prod(padded) * jnp.dtype(dtype).itemsize


def pipeline_vmem_bytes(operand_blocks: Sequence[tuple[Sequence[int], object]],
                        *, n_buffers: int = 2,
                        scratch_bytes: int = 0) -> int:
    """Working-set estimate for a pipelined pallas_call.

    Each operand block is multi-buffered ``n_buffers`` deep (the PINGPONG
    schedule uses 2). This is the TPU analogue of HK's register-budget
    accounting in Tab. 2: schedules that blow the budget are rejected.
    """
    total = scratch_bytes
    for shape, dtype in operand_blocks:
        total += n_buffers * padded_tile_bytes(shape, dtype)
    return total


def check_vmem_budget(operand_blocks, *, n_buffers=2, scratch_bytes=0,
                      budget=VMEM_BYTES, what="kernel") -> int:
    used = pipeline_vmem_bytes(operand_blocks, n_buffers=n_buffers,
                               scratch_bytes=scratch_bytes)
    if used > budget:
        raise ValueError(
            f"{what}: VMEM working set {used/2**20:.1f} MiB exceeds budget "
            f"{budget/2**20:.1f} MiB — shrink tiles or pipeline depth"
        )
    return used
