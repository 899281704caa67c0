"""Pallas-TPU kernel: fused chunked prefix scan of the matrix GOOM recurrence.

Computes all states of  ``X_t = A_t X_{t-1} ⊕ B_t``  (paper §4.3, eq. 26) —
the headline non-diagonal recurrence — as PSCAN∘LMME in one kernel:

  * the grid is ``(batch, time_tiles)`` with *time minor*: TPU grids iterate
    sequentially, so the inter-chunk state carry lives in VMEM scratch and
    never round-trips HBM;
  * within a chunk the inclusive scan of ``(A, B)`` compound pairs is a
    log2(BT)-depth associative scan whose combine is a *batched LMME*: each
    K-contraction is rescaled by detached per-row / per-column maxima
    (the same per-tile running-max machinery as ``kernels/lmme``, at the
    d ≤ one-MXU-tile granularity where a single rescale is the whole
    online pass) and fed to the MXU via ``dot_general``;
  * the carried state is folded as ``X = A* ∘ X_carry ⊕ B*`` with one more
    batched LMME, and the chunk's last state becomes the next carry.

Work: O(T·d²·(d+m)·log BT) MXU flops, one HBM read of (A, B) and one HBM
write of X.  The combine math matches ``core.scan.matrix_scan`` with
``lmme_reference`` exactly (same detached-max rescaling identity), so the
XLA reference is both the numerical oracle and the backward-pass function
for the wrapper's custom VJP (see ``kernels/goom_scan/ops.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .goom_scan import _NEG, _lse2, _tree_scan


def _blmme(al, asn, bl, bsn):
    """Batched LMME: (L, n, k) ∘ (L, k, m) -> (L, n, m) in (log, sign) planes.

    Per-position detached row/col max rescaling keeps every exp near unit
    scale; ``_NEG`` guards all-zero rows/columns (max == -inf) exactly as in
    ``kernels/lmme/lmme.py``.  The contraction itself runs on the MXU via a
    batched ``dot_general`` with f32 accumulation.
    """
    mr = jnp.max(al, axis=-1, keepdims=True)  # (L, n, 1)
    mc = jnp.max(bl, axis=-2, keepdims=True)  # (L, 1, m)
    mr = jnp.where(mr > _NEG, mr, _NEG)
    mc = jnp.where(mc > _NEG, mc, _NEG)

    ea = asn * jnp.exp(al - mr)
    eb = bsn * jnp.exp(bl - mc)
    prod = jax.lax.dot_general(
        ea, eb,
        dimension_numbers=(((2,), (1,)), ((0,), (0,))),
        precision=jax.lax.Precision.HIGHEST,  # f32, as in kernels/lmme
        preferred_element_type=jnp.float32,
    )
    mag = jnp.abs(prod)
    scale = mr + mc  # broadcasts to (L, n, m)
    is_zero = (mag == 0.0) | (scale <= _NEG)
    log = jnp.where(is_zero, -jnp.inf,
                    jnp.log(jnp.where(is_zero, 1.0, mag)) + scale)
    return log, jnp.where(prod >= 0, 1.0, -1.0)


def _mat_combine(e, l):
    """Matrix recurrence combine (earlier, later) over (log, sign) planes."""
    ea_l, ea_s, eb_l, eb_s = e
    la_l, la_s, lb_l, lb_s = l
    a_l, a_s = _blmme(la_l, la_s, ea_l, ea_s)  # A = A_l ∘ A_e
    t_l, t_s = _blmme(la_l, la_s, eb_l, eb_s)  # A_l ∘ B_e
    b_l, b_s = _lse2(t_l, t_s, lb_l, lb_s)     # B = A_l ∘ B_e ⊕ B_l
    return (a_l, a_s, b_l, b_s)


def _matrix_scan_kernel(
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
        carry_log_ref[...] = x0_log_ref[0, 0]
        carry_sign_ref[...] = x0_sign_ref[0, 0]

    al = a_log_ref[0]  # (BT, d, d)
    asn = a_sign_ref[0]
    bl = b_log_ref[0]  # (BT, d, m)
    bsn = b_sign_ref[0]

    # In-chunk inclusive scan of the (A, B) compound pairs (MXU combines).
    a_star_l, a_star_s, b_star_l, b_star_s = _tree_scan(
        _mat_combine, (al, asn, bl, bsn))

    # Fold the carried state:  X_t = A*_t ∘ X_carry ⊕ B*_t.
    bt = al.shape[0]
    cl = jnp.broadcast_to(carry_log_ref[...], (bt,) + carry_log_ref.shape)
    cs = jnp.broadcast_to(carry_sign_ref[...], (bt,) + carry_sign_ref.shape)
    ax_l, ax_s = _blmme(a_star_l, a_star_s, cl, cs)
    x_l, x_s = _lse2(ax_l, ax_s, b_star_l, b_star_s)

    x_log_ref[0] = x_l
    x_sign_ref[0] = x_s
    # static last-row slice: integer indexing lowers to a dynamic_slice,
    # which Mosaic does not implement
    carry_log_ref[...] = jax.lax.index_in_dim(x_l, bt - 1, keepdims=False)
    carry_sign_ref[...] = jax.lax.index_in_dim(x_s, bt - 1, keepdims=False)


def _prod_combine(e, l):
    """Prefix-product combine (earlier, later): A = A_later ∘ A_earlier."""
    ea_l, ea_s = e
    la_l, la_s = l
    return _blmme(la_l, la_s, ea_l, ea_s)


def _matrix_scan_kernel_zero_b(
    a_log_ref,
    a_sign_ref,
    x0_log_ref,
    x0_sign_ref,
    x_log_ref,
    x_sign_ref,
    carry_log_ref,
    carry_sign_ref,
):
    """Zero-B variant: with B ≡ 0 the recurrence collapses to prefix
    products ``X_t = (A_t ∘ ⋯ ∘ A_1) ∘ X_0`` — only the transition half of
    the compound is scanned, and no B operand exists in the launch.  This
    is how ``cumulative_lmme`` rides the fused kernel."""
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        carry_log_ref[...] = x0_log_ref[0, 0]
        carry_sign_ref[...] = x0_sign_ref[0, 0]

    al = a_log_ref[0]  # (BT, d, d)
    asn = a_sign_ref[0]

    a_star_l, a_star_s = _tree_scan(_prod_combine, (al, asn))

    bt = al.shape[0]
    cl = jnp.broadcast_to(carry_log_ref[...], (bt,) + carry_log_ref.shape)
    cs = jnp.broadcast_to(carry_sign_ref[...], (bt,) + carry_sign_ref.shape)
    x_l, x_s = _blmme(a_star_l, a_star_s, cl, cs)

    x_log_ref[0] = x_l
    x_sign_ref[0] = x_s
    # static last-row slice: integer indexing lowers to a dynamic_slice,
    # which Mosaic does not implement
    carry_log_ref[...] = jax.lax.index_in_dim(x_l, bt - 1, keepdims=False)
    carry_sign_ref[...] = jax.lax.index_in_dim(x_s, bt - 1, keepdims=False)


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def matrix_scan_kernel_call_zero_b(
    a_log: jax.Array,
    a_sign: jax.Array,
    x0_log: jax.Array,
    x0_sign: jax.Array,
    *,
    block_t: int = 128,
    interpret: bool = False,
):
    """Zero-B kernel entry: a (G, T, d, d), x0 (G, 1, d, m), all f32,
    T % block_t == 0.  Returns (x_log, x_sign): (G, T, d, m)."""
    g, t, d, _ = a_log.shape
    m = x0_log.shape[-1]
    grid = (g, t // block_t)  # time minor => sequential carry

    a_spec = pl.BlockSpec((1, block_t, d, d), lambda gi, ti: (gi, ti, 0, 0))
    o_spec = pl.BlockSpec((1, block_t, d, m), lambda gi, ti: (gi, ti, 0, 0))
    x0_spec = pl.BlockSpec((1, 1, d, m), lambda gi, ti: (gi, 0, 0, 0))

    out_shape = [
        jax.ShapeDtypeStruct((g, t, d, m), jnp.float32),
        jax.ShapeDtypeStruct((g, t, d, m), jnp.float32),
    ]
    return pl.pallas_call(
        _matrix_scan_kernel_zero_b,
        grid=grid,
        in_specs=[a_spec, a_spec, x0_spec, x0_spec],
        out_specs=[o_spec, o_spec],
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((d, m), jnp.float32),
            pltpu.VMEM((d, m), jnp.float32),
        ],
        interpret=interpret,
    )(a_log, a_sign, x0_log, x0_sign)


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def matrix_scan_kernel_call(
    a_log: jax.Array,
    a_sign: jax.Array,
    b_log: jax.Array,
    b_sign: jax.Array,
    x0_log: jax.Array,
    x0_sign: jax.Array,
    *,
    block_t: int = 128,
    interpret: bool = False,
):
    """Raw kernel entry: a (G, T, d, d), b (G, T, d, m), x0 (G, 1, d, m),
    all f32, T % block_t == 0.  Returns (x_log, x_sign): (G, T, d, m).

    Shape/padding/batching conveniences live in ``ops.matrix_scan_pallas``;
    the engine (``repro.core.engine``) is the intended entry point.
    """
    g, t, d, _ = a_log.shape
    m = b_log.shape[-1]
    grid = (g, t // block_t)  # time minor => sequential carry

    a_spec = pl.BlockSpec((1, block_t, d, d), lambda gi, ti: (gi, ti, 0, 0))
    b_spec = pl.BlockSpec((1, block_t, d, m), lambda gi, ti: (gi, ti, 0, 0))
    x0_spec = pl.BlockSpec((1, 1, d, m), lambda gi, ti: (gi, 0, 0, 0))

    out_shape = [
        jax.ShapeDtypeStruct((g, t, d, m), jnp.float32),
        jax.ShapeDtypeStruct((g, t, d, m), jnp.float32),
    ]
    return pl.pallas_call(
        _matrix_scan_kernel,
        grid=grid,
        in_specs=[a_spec, a_spec, b_spec, b_spec, x0_spec, x0_spec],
        out_specs=[b_spec, b_spec],
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((d, m), jnp.float32),
            pltpu.VMEM((d, m), jnp.float32),
        ],
        interpret=interpret,
    )(a_log, a_sign, b_log, b_sign, x0_log, x0_sign)
