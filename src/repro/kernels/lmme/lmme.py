"""Pallas-TPU kernel: tiled LMME with online per-tile max rescaling.

The paper's LMME (eq. 10) scales by one *global* per-row / per-column max
before a single real matmul.  On TPU we instead stream K-tiles through VMEM
and carry a *running* row/column max per output tile — the same online
rescaling flash-attention uses for softmax, applied to the signed
log-sum-exp contraction.  Each K-tile is exponentiated near unit scale and
fed to the MXU, so the contraction never sees a scale worse than the spread
*within one tile*, rather than the spread across the whole contraction.

Grid: ``(batch, n_tiles, m_tiles, k_tiles)`` — the contraction axis is the
minor (sequential) grid dimension, so the f32 accumulator and running maxima
live in VMEM scratch across K-steps.

Layout notes (TPU):
  * block shapes default to 128×128/256 — MXU-aligned (multiples of 8×128);
  * sign planes are f32 ±1 and ride the VPU exp/multiply before the MXU dot;
  * accumulation is f32 via ``preferred_element_type``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# A very negative but finite stand-in for -inf maxima (all-zero tiles).
# exp(x - _NEG) with x = -inf still gives 0; with x finite it overflows only
# if x > _NEG + 88 in f32 log-space, which cannot happen for a tile max.
_NEG = -1e30


def _lmme_kernel(
    a_log_ref,
    a_sign_ref,
    b_log_ref,
    b_sign_ref,
    out_log_ref,
    out_sign_ref,
    acc_ref,
    m_row_ref,
    m_col_ref,
    *,
    k_tiles: int,
):
    j = pl.program_id(3)

    al = a_log_ref[0]  # (bn, bd)
    asn = a_sign_ref[0]
    bl = b_log_ref[0]  # (bd, bm)
    bsn = b_sign_ref[0]

    # Per-tile maxima (guard all-zero rows/cols: max == -inf).
    mr = jnp.max(al, axis=1, keepdims=True)  # (bn, 1)
    mc = jnp.max(bl, axis=0, keepdims=True)  # (1, bm)
    mr = jnp.where(mr > -jnp.inf, mr, _NEG)
    mc = jnp.where(mc > -jnp.inf, mc, _NEG)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_row_ref[...] = jnp.full_like(m_row_ref, _NEG)
        m_col_ref[...] = jnp.full_like(m_col_ref, _NEG)

    mr_old = m_row_ref[...]
    mc_old = m_col_ref[...]
    mr_new = jnp.maximum(mr_old, mr)
    mc_new = jnp.maximum(mc_old, mc)

    # Rescale the existing accumulator to the new reference scales.
    acc = acc_ref[...] * jnp.exp(mr_old - mr_new) * jnp.exp(mc_old - mc_new)

    # Exponentiate this K-tile near unit scale and contract on the MXU.
    ea = asn * jnp.exp(al - mr_new)  # (bn, bd)
    eb = bsn * jnp.exp(bl - mc_new)  # (bd, bm)
    # HIGHEST: an f32 contraction on the MXU, never a one-pass bf16 product
    acc = acc + jnp.dot(ea, eb, precision=jax.lax.Precision.HIGHEST,
                        preferred_element_type=jnp.float32)

    acc_ref[...] = acc
    m_row_ref[...] = mr_new
    m_col_ref[...] = mc_new

    @pl.when(j == k_tiles - 1)
    def _finalize():
        a = acc_ref[...]
        out_log_ref[0] = jnp.log(jnp.abs(a)) + m_row_ref[...] + m_col_ref[...]
        out_sign_ref[0] = jnp.where(a >= 0, 1.0, -1.0).astype(out_sign_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_n", "block_m", "block_d", "interpret")
)
def lmme_kernel_call(
    a_log: jax.Array,
    a_sign: jax.Array,
    b_log: jax.Array,
    b_sign: jax.Array,
    *,
    block_n: int = 128,
    block_m: int = 128,
    block_d: int = 128,
    interpret: bool = False,
):
    """Raw kernel entry: shapes (B, n, d) x (B, d, m), all f32, all dims
    divisible by their block sizes.  Returns (out_log, out_sign): (B, n, m).
    """
    bsz, n, d = a_log.shape
    m = b_log.shape[-1]
    grid = (bsz, n // block_n, m // block_m, d // block_d)

    a_spec = pl.BlockSpec((1, block_n, block_d), lambda b, i, k, j: (b, i, j))
    b_spec = pl.BlockSpec((1, block_d, block_m), lambda b, i, k, j: (b, j, k))
    o_spec = pl.BlockSpec((1, block_n, block_m), lambda b, i, k, j: (b, i, k))

    out_shape = [
        jax.ShapeDtypeStruct((bsz, n, m), jnp.float32),
        jax.ShapeDtypeStruct((bsz, n, m), jnp.float32),
    ]
    return pl.pallas_call(
        functools.partial(_lmme_kernel, k_tiles=grid[-1]),
        grid=grid,
        in_specs=[a_spec, a_spec, b_spec, b_spec],
        out_specs=[o_spec, o_spec],
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_n, block_m), jnp.float32),  # acc
            pltpu.VMEM((block_n, 1), jnp.float32),  # running row max
            pltpu.VMEM((1, block_m), jnp.float32),  # running col max
        ],
        interpret=interpret,
    )(a_log, a_sign, b_log, b_sign)
