"""Pallas-TPU kernel: chunked prefix scan of a diagonal GOOM recurrence.

Computes all states of  ``x_t = a_t ⊙ x_{t-1} ⊕ b_t``  over GOOM
(log-magnitude, sign) planes, where ⊙ is log-space multiply and ⊕ is signed
LSE.  This is the hot path of RWKV6 / Mamba layers at long sequence length.

TPU mapping: the grid is ``(channel_tiles, time_tiles)`` with *time minor* —
TPU grids iterate sequentially, so the inter-chunk state carry lives in VMEM
scratch and never round-trips HBM.  Within a chunk the inclusive scan is a
log2(BT)-depth associative scan (pure VPU element-wise work); chunk results
are folded into the carry with one extra combine.

Work: O(T·C·log BT) elementwise flops and exactly one HBM read of (a, b)
and one HBM write of x — the kernel is memory-bound by design, matching
the roofline of any scan.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# Finite stand-in for -inf maxima, mirroring kernels/lmme/lmme.py: anything
# at or below _NEG is an exact zero for combining purposes.
_NEG = -1e30


def _lse2(l1, s1, l2, s2):
    """Signed LSE of two (log, sign) pairs; -inf == exact zero.

    The zero-zero path (both logs -inf, or compounded floors below ``_NEG``)
    is explicit: the result is forced to (-inf, +1) through a double-where so
    neither the primal nor a jit'd gradient ever evaluates ``log(0)`` on a
    live branch — previously the -inf result fell out of ``jnp.log(0)`` only
    by accident and NaN'd under differentiation."""
    m = jnp.maximum(l1, l2)
    m_safe = jnp.where(m <= _NEG, 0.0, m)
    t = s1 * jnp.exp(l1 - m_safe) + s2 * jnp.exp(l2 - m_safe)
    mag = jnp.abs(t)
    is_zero = (m <= _NEG) | (mag == 0.0)  # all-zero inputs or exact cancellation
    log = jnp.where(is_zero, -jnp.inf, jnp.log(jnp.where(is_zero, 1.0, mag)) + m_safe)
    return log, jnp.where(t >= 0, 1.0, -1.0)


def _combine(e, l):
    """Diagonal recurrence combine in log space (earlier, later)."""
    ea_l, ea_s, eb_l, eb_s = e
    la_l, la_s, lb_l, lb_s = l
    a_l = la_l + ea_l
    a_s = la_s * ea_s
    t_l = la_l + eb_l  # a_later ⊙ b_earlier
    t_s = la_s * eb_s
    b_l, b_s = _lse2(t_l, t_s, lb_l, lb_s)
    return (a_l, a_s, b_l, b_s)


def _tree_scan(combine, elems):
    """Inclusive scan along the leading axis, paired exactly as
    ``lax.associative_scan`` pairs it (odd/even recursion).

    ``associative_scan`` itself does not lower in Mosaic: its strided slices
    along time are refused.  Here the odd/even split is a reshape of the
    time axis plus static slices, and the interleave a stack plus reshape,
    which Mosaic lowers.  Keeping the reference's pairing keeps the kernel's
    rounding that of the XLA reference: with signed operands at e±200,
    compounds cancel inside the scan, and another pairing (a doubling scan)
    moves results by more than the parity tests allow.
    """
    n = elems[0].shape[0]
    if n < 2:
        return elems
    half = n // 2

    def pairs(x):  # (x[0:2h:2], x[1:2h:2])
        x2 = x[:2 * half].reshape((half, 2) + x.shape[1:])
        return (jax.lax.index_in_dim(x2, 0, 1, keepdims=False),
                jax.lax.index_in_dim(x2, 1, 1, keepdims=False))

    evens, odds = zip(*(pairs(x) for x in elems))
    odd_out = _tree_scan(combine, combine(evens, odds))  # positions 1, 3, ..
    if n % 2:  # positions 2, 4, .., n - 1
        later = tuple(jnp.concatenate([e[1:], x[n - 1:]]) for e, x in zip(evens, elems))
        even_out = combine(odd_out, later)
    elif half > 1:  # positions 2, 4, .., n - 2
        even_out = combine(tuple(o[:-1] for o in odd_out),
                           tuple(e[1:] for e in evens))
    else:
        even_out = None
    out = []
    for i, x in enumerate(elems):
        ev = x[:1] if even_out is None else jnp.concatenate([x[:1], even_out[i]])
        both = jnp.stack([ev[:half], odd_out[i]], axis=1)
        both = both.reshape((2 * half,) + x.shape[1:])
        out.append(jnp.concatenate([both, ev[half:]]) if n % 2 else both)
    return tuple(out)


def _scan_kernel(
    a_log_ref,
    a_sign_ref,
    b_log_ref,
    b_sign_ref,
    x0_log_ref,
    x0_sign_ref,
    x_log_ref,
    x_sign_ref,
    carry_log_ref,
    carry_sign_ref,
):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        carry_log_ref[...] = x0_log_ref[...]
        carry_sign_ref[...] = x0_sign_ref[...]

    al = a_log_ref[...]  # (BT, BC)
    asn = a_sign_ref[...]
    bl = b_log_ref[...]
    bsn = b_sign_ref[...]

    # In-chunk inclusive scan of the (A, B) compound pairs.
    a_star_l, a_star_s, b_star_l, b_star_s = _tree_scan(
        _combine, (al, asn, bl, bsn))

    # Fold the carried state:  x = A* ⊙ x_carry ⊕ B*.
    cl = carry_log_ref[...]  # (1, BC)
    cs = carry_sign_ref[...]
    x_l, x_s = _lse2(a_star_l + cl, a_star_s * cs, b_star_l, b_star_s)

    x_log_ref[...] = x_l
    x_sign_ref[...] = x_s
    carry_log_ref[...] = x_l[-1:]
    carry_sign_ref[...] = x_s[-1:]


@functools.partial(jax.jit, static_argnames=("block_t", "block_c", "interpret"))
def goom_scan_kernel_call(
    a_log: jax.Array,
    a_sign: jax.Array,
    b_log: jax.Array,
    b_sign: jax.Array,
    x0_log: jax.Array,
    x0_sign: jax.Array,
    *,
    block_t: int = 256,
    block_c: int = 512,
    interpret: bool = False,
):
    """Raw kernel entry: (T, C) planes + (1, C) initial state, all f32,
    T % block_t == 0 and C % block_c == 0.  Returns (x_log, x_sign): (T, C).
    """
    t, c = a_log.shape
    grid = (c // block_c, t // block_t)  # time minor => sequential carry

    ab_spec = pl.BlockSpec((block_t, block_c), lambda ci, ti: (ti, ci))
    x0_spec = pl.BlockSpec((1, block_c), lambda ci, ti: (0, ci))

    out_shape = [
        jax.ShapeDtypeStruct((t, c), jnp.float32),
        jax.ShapeDtypeStruct((t, c), jnp.float32),
    ]
    return pl.pallas_call(
        _scan_kernel,
        grid=grid,
        in_specs=[ab_spec, ab_spec, ab_spec, ab_spec, x0_spec, x0_spec],
        out_specs=[ab_spec, ab_spec],
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((1, block_c), jnp.float32),
            pltpu.VMEM((1, block_c), jnp.float32),
        ],
        interpret=interpret,
    )(a_log, a_sign, b_log, b_sign, x0_log, x0_sign)
