"""Public jit'd wrapper for the Pallas LMME kernel.

Handles batching (arbitrary leading dims), padding to block multiples
(padded contraction entries are exact zeros: log = -inf, so they contribute
``exp(-inf) == 0`` to every sum — no masking needed), and a custom VJP
(backward pass reuses the reference implementation's autodiff on the saved
inputs, which computes the same mathematical function).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.goom import Goom
from repro.core.ops import lmme_reference

from .lmme import lmme_kernel_call
from .lmme_gpu import lmme_gpu_kernel_call

__all__ = ["lmme_pallas"]


def _pad_to(x: jax.Array, axis: int, mult: int, fill: float) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=fill)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10))
def _lmme_planes(a_log, a_sign, b_log, b_sign, block_n, block_m, block_d,
                 num_warps, num_stages, interpret, variant):
    return _lmme_fwd_impl(
        a_log, a_sign, b_log, b_sign, block_n, block_m, block_d,
        num_warps, num_stages, interpret, variant
    )


def _lmme_fwd_impl(a_log, a_sign, b_log, b_sign, block_n, block_m, block_d,
                   num_warps, num_stages, interpret, variant):
    n, d = a_log.shape[-2:]
    m = b_log.shape[-1]
    batch = a_log.shape[:-2]

    def flat(x):
        return x.reshape((-1,) + x.shape[-2:])

    # Pad with exact zeros (log=-inf, sign=+1): padded K entries add 0 to
    # every contraction; padded N/M rows are sliced away below.
    al = _pad_to(_pad_to(flat(a_log), 1, block_n, -jnp.inf), 2, block_d, -jnp.inf)
    asn = _pad_to(_pad_to(flat(a_sign), 1, block_n, 1.0), 2, block_d, 1.0)
    bl = _pad_to(_pad_to(flat(b_log), 1, block_d, -jnp.inf), 2, block_m, -jnp.inf)
    bsn = _pad_to(_pad_to(flat(b_sign), 1, block_d, 1.0), 2, block_m, 1.0)

    if variant == "gpu":
        out_log, out_sign = lmme_gpu_kernel_call(
            al, asn, bl, bsn,
            block_n=block_n, block_m=block_m, block_d=block_d,
            num_warps=num_warps, num_stages=num_stages, interpret=interpret,
        )
    else:
        out_log, out_sign = lmme_kernel_call(
            al, asn, bl, bsn,
            block_n=block_n, block_m=block_m, block_d=block_d,
            interpret=interpret,
        )
    out_log = out_log[:, :n, :m].reshape(batch + (n, m))
    out_sign = out_sign[:, :n, :m].reshape(batch + (n, m))
    return out_log, out_sign


def _lmme_fwd(a_log, a_sign, b_log, b_sign, block_n, block_m, block_d,
              num_warps, num_stages, interpret, variant):
    out = _lmme_fwd_impl(
        a_log, a_sign, b_log, b_sign, block_n, block_m, block_d,
        num_warps, num_stages, interpret, variant
    )
    return out, (a_log, a_sign, b_log, b_sign)


def _lmme_bwd(block_n, block_m, block_d, num_warps, num_stages, interpret,
              variant, res, cts):
    a_log, a_sign, b_log, b_sign = res
    g_log, _g_sign = cts  # sign planes are piecewise-constant: no cotangent

    def f(al, bl):
        return lmme_reference(Goom(al, a_sign), Goom(bl, b_sign)).log_abs

    _, vjp = jax.vjp(f, a_log, b_log)
    d_al, d_bl = vjp(g_log)
    return d_al, jnp.zeros_like(a_sign), d_bl, jnp.zeros_like(b_sign)


_lmme_planes.defvjp(_lmme_fwd, _lmme_bwd)


def lmme_pallas(
    a: Goom,
    b: Goom,
    *,
    block_n: int = 128,
    block_m: int = 128,
    block_d: int = 128,
    num_warps: int = 4,
    num_stages: int = 2,
    interpret: bool = False,
    variant: str = "tpu",
) -> Goom:
    """LMME over GOOMs via the tiled online-rescaled Pallas kernels.

    ``a``: (..., n, d), ``b``: (..., d, m) — leading dims broadcast like
    ``jnp.matmul``.  f32 planes only (kernel dtype).  ``variant`` selects
    the TPU-shaped kernel (sequential K grid + VMEM scratch) or the
    GPU-shaped one (in-kernel K loop + register carries, Triton lowering).
    """
    # Broadcast leading batch dims.
    batch = jnp.broadcast_shapes(a.shape[:-2], b.shape[:-2])
    if 0 in batch:
        # nothing to launch (associative scans combine empty slices), and a
        # kernel grid cannot be empty
        shape = batch + (a.shape[-2], b.shape[-1])
        return Goom(jnp.full(shape, -jnp.inf, jnp.float32),
                    jnp.ones(shape, jnp.float32))
    al = jnp.broadcast_to(a.log_abs, batch + a.shape[-2:]).astype(jnp.float32)
    asn = jnp.broadcast_to(a.sign, batch + a.shape[-2:]).astype(jnp.float32)
    bl = jnp.broadcast_to(b.log_abs, batch + b.shape[-2:]).astype(jnp.float32)
    bsn = jnp.broadcast_to(b.sign, batch + b.shape[-2:]).astype(jnp.float32)

    # Clamp block sizes to (padded) dims to avoid huge pads for small inputs.
    # GPU tiles keep every pl.dot dim >= 16 so tl.dot maps to tensor cores.
    n, d = al.shape[-2:]
    m = bl.shape[-1]
    if variant == "gpu":
        bn = min(block_n, max(16, 1 << (n - 1).bit_length()))
        bm = min(block_m, max(16, 1 << (m - 1).bit_length()))
        bd = min(block_d, max(16, 1 << (d - 1).bit_length()))
    else:
        bn = min(block_n, max(8, 1 << (n - 1).bit_length()))
        bm = min(block_m, max(128, 1 << (m - 1).bit_length()))
        bd = min(block_d, max(128, 1 << (d - 1).bit_length()))

    out_log, out_sign = _lmme_planes(al, asn, bl, bsn, bn, bm, bd,
                                     num_warps, num_stages, interpret, variant)
    return Goom(out_log, out_sign)
