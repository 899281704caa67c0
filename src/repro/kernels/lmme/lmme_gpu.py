"""Pallas-GPU kernel: tiled LMME with online per-tile max rescaling.

Same math as the TPU kernel (``lmme.py``) reshaped for a GPU launch:

  * the grid is ``(batch, n_tiles, m_tiles)`` — one CTA per output tile.
    GPU grid steps are *parallel* CTAs (unlike TPU's sequential grid), so
    the contraction axis cannot be a grid dimension with a scratch carry;
    instead each CTA walks the K tiles with an in-kernel ``fori_loop``,
    carrying the f32 accumulator and the running row/column maxima in
    registers (the loop carry — the GPU analog of the TPU kernel's VMEM
    scratch);
  * K tiles are loaded with ``pl.ds`` dynamic slices from the full-K
    operand blocks and contracted with ``pl.dot`` (f32 accumulation on
    tensor cores under the Triton lowering);
  * tile shapes are warp-friendly: powers of two, >= 16 on every ``pl.dot``
    dimension; ``num_warps`` / ``num_stages`` ride in via
    ``plgpu.CompilerParams``.

Lowering: Pallas's Triton path on CUDA devices; ``interpret=True`` runs
the identical body on CPU for CI parity (the ``pallas_gpu_interpret``
backend).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import triton as plgpu

from .lmme import _NEG


def _lmme_gpu_kernel(
    a_log_ref,
    a_sign_ref,
    b_log_ref,
    b_sign_ref,
    out_log_ref,
    out_sign_ref,
    *,
    k_tiles: int,
    block_d: int,
):
    bn, bm = out_log_ref.shape[-2], out_log_ref.shape[-1]

    def body(j, carry):
        acc, mr_old, mc_old = carry
        ks = pl.ds(j * block_d, block_d)
        al = a_log_ref[0, :, ks]   # (bn, bd)
        asn = a_sign_ref[0, :, ks]
        bl = b_log_ref[0, ks, :]   # (bd, bm)
        bsn = b_sign_ref[0, ks, :]

        # Per-tile maxima (guard all-zero rows/cols: max == -inf).
        mr = jnp.max(al, axis=1, keepdims=True)
        mc = jnp.max(bl, axis=0, keepdims=True)
        mr = jnp.where(mr > -jnp.inf, mr, _NEG)
        mc = jnp.where(mc > -jnp.inf, mc, _NEG)
        mr_new = jnp.maximum(mr_old, mr)
        mc_new = jnp.maximum(mc_old, mc)

        # Rescale the accumulator to the new reference scales, then
        # exponentiate this K-tile near unit scale and contract.
        acc = acc * jnp.exp(mr_old - mr_new) * jnp.exp(mc_old - mc_new)
        ea = asn * jnp.exp(al - mr_new)
        eb = bsn * jnp.exp(bl - mc_new)
        return acc + pl.dot(ea, eb), mr_new, mc_new

    acc, mr, mc = jax.lax.fori_loop(
        0, k_tiles, body,
        (jnp.zeros((bn, bm), jnp.float32),
         jnp.full((bn, 1), _NEG, jnp.float32),
         jnp.full((1, bm), _NEG, jnp.float32)),
    )
    out_log_ref[0] = jnp.log(jnp.abs(acc)) + mr + mc
    out_sign_ref[0] = jnp.where(acc >= 0, 1.0, -1.0).astype(out_sign_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_n", "block_m", "block_d", "num_warps",
                     "num_stages", "interpret"),
)
def lmme_gpu_kernel_call(
    a_log: jax.Array,
    a_sign: jax.Array,
    b_log: jax.Array,
    b_sign: jax.Array,
    *,
    block_n: int = 64,
    block_m: int = 64,
    block_d: int = 32,
    num_warps: int = 4,
    num_stages: int = 2,
    interpret: bool = False,
):
    """Raw kernel entry: shapes (B, n, d) x (B, d, m), all f32, all dims
    divisible by their block sizes.  Returns (out_log, out_sign): (B, n, m).
    """
    bsz, n, d = a_log.shape
    m = b_log.shape[-1]
    grid = (bsz, n // block_n, m // block_m)

    a_spec = pl.BlockSpec((1, block_n, d), lambda b, i, k: (b, i, 0))
    b_spec = pl.BlockSpec((1, d, block_m), lambda b, i, k: (b, 0, k))
    o_spec = pl.BlockSpec((1, block_n, block_m), lambda b, i, k: (b, i, k))

    out_shape = [
        jax.ShapeDtypeStruct((bsz, n, m), jnp.float32),
        jax.ShapeDtypeStruct((bsz, n, m), jnp.float32),
    ]
    return pl.pallas_call(
        functools.partial(_lmme_gpu_kernel, k_tiles=d // block_d,
                          block_d=block_d),
        grid=grid,
        in_specs=[a_spec, a_spec, b_spec, b_spec],
        out_specs=[o_spec, o_spec],
        out_shape=out_shape,
        compiler_params=plgpu.CompilerParams(
            num_warps=num_warps, num_stages=num_stages),
        interpret=interpret,
    )(a_log, a_sign, b_log, b_sign)
