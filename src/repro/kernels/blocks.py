"""Per-(op, backend) block configurations for the kernel substrate.

One :class:`BlockConfig` describes every tiling/launch knob a kernel
implementation understands.  Each op uses a subset of the fields:

  ==================  =======================================  ==========
  op                  fields                                   gpu extras
  ==================  =======================================  ==========
  lmme                block_n, block_m, block_d                num_warps,
  diagonal_scan       block_t, block_c, algo                   num_stages
  matrix_scan         block_t, algo
  cumulative_lmme     block_t, algo
  xla_reference ops   block_t (matrix/cumulative ref chunking)
  ==================  =======================================  ==========

``algo`` names the GPU scan ops' time-axis algorithm (``"seq"`` /
``"tree"`` / ``"two_pass"``; ``None`` = auto by sequence length) — it is
an autotunable launch knob like any tile size, swept and cached per
``(op, backend, device_kind, shape-bucket)``.

Defaults live in :data:`DEFAULTS`, keyed ``(op, backend)``.  Sizes are
*hints*: the kernel wrappers clamp them to the (padded) problem, so small
shapes never over-pad.  Resolution precedence (the engine implements it):

  1. explicit ``engine.use_blocks()`` overrides,
  2. the persisted autotune cache (``kernels/autotune.py``), keyed
     ``(op, backend, device_kind, shape-bucket, algo)``,
  3. :data:`DEFAULTS`.

Nothing outside ``kernels/`` names a block size — callers hand the engine
shapes and get a resolved :class:`BlockConfig` flowing into ``get_impl``.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

__all__ = ["BlockConfig", "DEFAULTS", "default_blocks", "merge",
           "shape_bucket", "OPS"]

OPS = ("lmme", "diagonal_scan", "matrix_scan", "cumulative_lmme")


@dataclasses.dataclass(frozen=True)
class BlockConfig:
    """Tiling/launch knobs for one (op, backend) pair.  ``None`` = unused
    by that implementation (or "inherit the default" when merging)."""

    block_t: Optional[int] = None   # scans: time tile
    block_c: Optional[int] = None   # diagonal scan: channel tile
    block_n: Optional[int] = None   # lmme: output-row tile
    block_m: Optional[int] = None   # lmme: output-col tile
    block_d: Optional[int] = None   # lmme: contraction tile
    num_warps: Optional[int] = None   # gpu (Triton) launch knobs
    num_stages: Optional[int] = None
    algo: Optional[str] = None      # gpu scans: seq | tree | two_pass
    #                                 (None = auto by sequence length)

    def to_dict(self) -> Dict[str, object]:
        """The non-None fields, for JSON persistence / repr."""
        return {f.name: getattr(self, f.name)
                for f in dataclasses.fields(self)
                if getattr(self, f.name) is not None}


def merge(base: BlockConfig, override: BlockConfig) -> BlockConfig:
    """``override``'s non-None fields win over ``base``."""
    return dataclasses.replace(base, **override.to_dict())


# ---------------------------------------------------------------------------
# defaults per (op, backend)
# ---------------------------------------------------------------------------
_TPU_LMME = BlockConfig(block_n=128, block_m=128, block_d=128)
_TPU_DIAG = BlockConfig(block_t=256, block_c=512)
_TPU_MAT = BlockConfig(block_t=128)
# The B-carrying matrix scan holds twice the live tiles of the zero-B
# (cumulative) one: at block_t=128 with 16x16 heads it overflows v5e's
# default scoped VMEM; 64 compiles.
_TPU_MAT_B = BlockConfig(block_t=64)
# GPU tiles are warp-shaped: power-of-2, >=16 on dot dims so tl.dot maps to
# tensor cores; the time tile is small because the in-kernel loop is
# sequential (GPU grids are parallel CTAs — no cross-step grid carry).
_GPU_LMME = BlockConfig(block_n=64, block_m=64, block_d=32,
                        num_warps=4, num_stages=2)
_GPU_DIAG = BlockConfig(block_t=64, block_c=128, num_warps=4, num_stages=1)
_GPU_MAT = BlockConfig(block_t=32, num_warps=4, num_stages=1)
# xla_reference matrix ops chunk their associative scan over time for
# bounded memory — block_t is that chunk length (autotunable like any tile).
_REF_MAT = BlockConfig(block_t=128)

DEFAULTS: Dict[Tuple[str, str], BlockConfig] = {}
for _backend, _lmme, _diag, _mat_b, _mat in (
    ("pallas_tpu", _TPU_LMME, _TPU_DIAG, _TPU_MAT_B, _TPU_MAT),
    ("pallas_interpret", _TPU_LMME, _TPU_DIAG, _TPU_MAT_B, _TPU_MAT),
    ("pallas_gpu", _GPU_LMME, _GPU_DIAG, _GPU_MAT, _GPU_MAT),
    ("pallas_gpu_interpret", _GPU_LMME, _GPU_DIAG, _GPU_MAT, _GPU_MAT),
    ("xla_reference", BlockConfig(), BlockConfig(), _REF_MAT, _REF_MAT),
):
    DEFAULTS[("lmme", _backend)] = _lmme
    DEFAULTS[("diagonal_scan", _backend)] = _diag
    DEFAULTS[("matrix_scan", _backend)] = _mat_b
    DEFAULTS[("cumulative_lmme", _backend)] = _mat


def default_blocks(op: str, backend: str) -> BlockConfig:
    try:
        return DEFAULTS[(op, backend)]
    except KeyError:
        raise KeyError(f"no default BlockConfig for op {op!r} on backend "
                       f"{backend!r}") from None


# ---------------------------------------------------------------------------
# shape buckets (autotune cache granularity)
# ---------------------------------------------------------------------------
def _pow2_ceil(n: int) -> int:
    return 1 if n <= 1 else 1 << (int(n) - 1).bit_length()


def shape_bucket(dims: Tuple[int, ...]) -> Tuple[int, ...]:
    """Round each problem dim up to a power of two.

    Nearby shapes share one autotuned winner: tile choice is driven by
    orders of magnitude (does the tile fit? how many CTAs launch?), not by
    exact sizes — and the kernel wrappers clamp tiles to the padded problem
    anyway.  The bucket is part of the autotune cache key."""
    return tuple(_pow2_ceil(d) for d in dims)
