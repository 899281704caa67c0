"""Pallas-GPU kernel: fused chunked prefix scan of the matrix GOOM recurrence.

Same PSCAN∘LMME math as the TPU kernel (``matrix_scan.py``) reshaped for a
GPU launch:

  * the grid is ``(batch,)`` — one CTA per independent recurrence.  GPU
    grid steps are *parallel* CTAs, so the sequential time axis cannot be
    a grid dimension with a scratch carry; each CTA walks its time tiles
    with an in-kernel ``fori_loop``, threading the ``(d, m)`` state carry
    through the loop in registers;
  * within a tile the inclusive scan of ``(A, B)`` compound pairs is the
    log2(BT)-depth associative scan whose combine is the batched LMME with
    per-position detached row/column max rescaling (``_blmme``, shared with
    the TPU kernel — the contraction lowers to ``dot_general`` on tensor
    cores under Triton);
  * the ``zero_b`` variant drops the B half of the compound entirely:
    with B ≡ 0 the recurrence collapses to prefix products
    ``X_t = (A_t ∘ ⋯ ∘ A_1) ∘ X_0`` — this is how ``cumulative_lmme``
    rides the fused kernel without materializing a dense zero B tensor.

Like the diagonal kernels (``goom_scan_gpu.py``), three time algorithms
share this math:

  * ``seq`` — one CTA per batch element walking its time tiles with an
    in-kernel ``fori_loop`` (O(T) depth; fallback + parity oracle);
  * ``tree`` — one CTA per batch element, the whole power-of-two-padded
    time extent scanned by the Blelloch up/down-sweep (``tree.tree_scan``,
    2(T-1) combines at depth 2·log2 T);
  * ``two_pass`` — grid ``(batch, time_tiles)``, every CTA independent:
    pass 1 tree-scans each tile and emits its ``(A*, B*)`` compound, the
    per-tile carries are stitched at XLA level with the same monoid
    combine ``kernels/sharded.py`` uses across devices
    (``sharded._carry_combine``), and pass 2 folds each tile's incoming
    state in.  O(log T) total depth.

Lowering: Pallas's Triton path on CUDA devices; ``interpret=True`` runs
the identical body on CPU for CI parity (``pallas_gpu_interpret``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import triton as plgpu

from .goom_scan import _lse2
from .matrix_scan import _blmme, _mat_combine, _prod_combine
from .tree import mat_identity, prod_identity, tree_scan


def _matrix_scan_gpu_kernel(
    a_log_ref,
    a_sign_ref,
    b_log_ref,
    b_sign_ref,
    x0_log_ref,
    x0_sign_ref,
    x_log_ref,
    x_sign_ref,
    *,
    t_tiles: int,
    block_t: int,
):
    def body(ti, carry):
        cl, cs = carry  # (d, m) state entering this time tile
        ts = pl.ds(ti * block_t, block_t)
        al = a_log_ref[0, ts]  # (BT, d, d)
        asn = a_sign_ref[0, ts]
        bl = b_log_ref[0, ts]  # (BT, d, m)
        bsn = b_sign_ref[0, ts]

        a_star_l, a_star_s, b_star_l, b_star_s = jax.lax.associative_scan(
            _mat_combine, (al, asn, bl, bsn), axis=0
        )

        # Fold the carried state:  X_t = A*_t ∘ X_carry ⊕ B*_t.
        bt = al.shape[0]
        clb = jnp.broadcast_to(cl, (bt,) + cl.shape)
        csb = jnp.broadcast_to(cs, (bt,) + cs.shape)
        ax_l, ax_s = _blmme(a_star_l, a_star_s, clb, csb)
        x_l, x_s = _lse2(ax_l, ax_s, b_star_l, b_star_s)
        x_log_ref[0, ts] = x_l
        x_sign_ref[0, ts] = x_s
        return x_l[-1], x_s[-1]

    jax.lax.fori_loop(
        0, t_tiles, body, (x0_log_ref[0, 0], x0_sign_ref[0, 0]))


def _matrix_scan_gpu_kernel_zero_b(
    a_log_ref,
    a_sign_ref,
    x0_log_ref,
    x0_sign_ref,
    x_log_ref,
    x_sign_ref,
    *,
    t_tiles: int,
    block_t: int,
):
    def body(ti, carry):
        cl, cs = carry  # (d, m) state entering this time tile
        ts = pl.ds(ti * block_t, block_t)
        al = a_log_ref[0, ts]  # (BT, d, d)
        asn = a_sign_ref[0, ts]

        # With B ≡ 0 only the transition half of the compound survives:
        # the in-tile scan is the prefix products A*_t = A_t ∘ ⋯ ∘ A_1.
        a_star_l, a_star_s = jax.lax.associative_scan(
            _prod_combine, (al, asn), axis=0
        )

        bt = al.shape[0]
        clb = jnp.broadcast_to(cl, (bt,) + cl.shape)
        csb = jnp.broadcast_to(cs, (bt,) + cs.shape)
        x_l, x_s = _blmme(a_star_l, a_star_s, clb, csb)
        x_log_ref[0, ts] = x_l
        x_sign_ref[0, ts] = x_s
        return x_l[-1], x_s[-1]

    jax.lax.fori_loop(
        0, t_tiles, body, (x0_log_ref[0, 0], x0_sign_ref[0, 0]))


@functools.partial(
    jax.jit,
    static_argnames=("block_t", "num_warps", "num_stages", "interpret"),
)
def matrix_scan_gpu_kernel_call(
    a_log: jax.Array,
    a_sign: jax.Array,
    b_log: jax.Array,
    b_sign: jax.Array,
    x0_log: jax.Array,
    x0_sign: jax.Array,
    *,
    block_t: int = 32,
    num_warps: int = 4,
    num_stages: int = 1,
    interpret: bool = False,
):
    """Raw kernel entry: a (G, T, d, d), b (G, T, d, m), x0 (G, 1, d, m),
    all f32, T % block_t == 0.  Returns (x_log, x_sign): (G, T, d, m).
    """
    g, t, d, _ = a_log.shape
    m = b_log.shape[-1]
    grid = (g,)

    a_spec = pl.BlockSpec((1, t, d, d), lambda gi: (gi, 0, 0, 0))
    b_spec = pl.BlockSpec((1, t, d, m), lambda gi: (gi, 0, 0, 0))
    x0_spec = pl.BlockSpec((1, 1, d, m), lambda gi: (gi, 0, 0, 0))

    out_shape = [
        jax.ShapeDtypeStruct((g, t, d, m), jnp.float32),
        jax.ShapeDtypeStruct((g, t, d, m), jnp.float32),
    ]
    return pl.pallas_call(
        functools.partial(_matrix_scan_gpu_kernel, t_tiles=t // block_t,
                          block_t=block_t),
        grid=grid,
        in_specs=[a_spec, a_spec, b_spec, b_spec, x0_spec, x0_spec],
        out_specs=[b_spec, b_spec],
        out_shape=out_shape,
        compiler_params=plgpu.CompilerParams(
            num_warps=num_warps, num_stages=num_stages),
        interpret=interpret,
    )(a_log, a_sign, b_log, b_sign, x0_log, x0_sign)


@functools.partial(
    jax.jit,
    static_argnames=("block_t", "num_warps", "num_stages", "interpret"),
)
def matrix_scan_gpu_kernel_call_zero_b(
    a_log: jax.Array,
    a_sign: jax.Array,
    x0_log: jax.Array,
    x0_sign: jax.Array,
    *,
    block_t: int = 32,
    num_warps: int = 4,
    num_stages: int = 1,
    interpret: bool = False,
):
    """Zero-B kernel entry: a (G, T, d, d), x0 (G, 1, d, m), all f32,
    T % block_t == 0.  Returns (x_log, x_sign): (G, T, d, m) — the prefix
    products applied to x0.  No B operand exists anywhere in the launch.
    """
    g, t, d, _ = a_log.shape
    m = x0_log.shape[-1]
    grid = (g,)

    a_spec = pl.BlockSpec((1, t, d, d), lambda gi: (gi, 0, 0, 0))
    o_spec = pl.BlockSpec((1, t, d, m), lambda gi: (gi, 0, 0, 0))
    x0_spec = pl.BlockSpec((1, 1, d, m), lambda gi: (gi, 0, 0, 0))

    out_shape = [
        jax.ShapeDtypeStruct((g, t, d, m), jnp.float32),
        jax.ShapeDtypeStruct((g, t, d, m), jnp.float32),
    ]
    return pl.pallas_call(
        functools.partial(_matrix_scan_gpu_kernel_zero_b,
                          t_tiles=t // block_t, block_t=block_t),
        grid=grid,
        in_specs=[a_spec, a_spec, x0_spec, x0_spec],
        out_specs=[o_spec, o_spec],
        out_shape=out_shape,
        compiler_params=plgpu.CompilerParams(
            num_warps=num_warps, num_stages=num_stages),
        interpret=interpret,
    )(a_log, a_sign, x0_log, x0_sign)


# ---------------------------------------------------------------------------
# tree: whole-T Blelloch scan, one CTA per batch element
# ---------------------------------------------------------------------------
def _fold_state(a_star_l, a_star_s, cl, cs):
    """Apply the prefix transitions to a (d, m) state: A*_t ∘ x, every t."""
    bt = a_star_l.shape[0]
    clb = jnp.broadcast_to(cl, (bt,) + cl.shape)
    csb = jnp.broadcast_to(cs, (bt,) + cs.shape)
    return _blmme(a_star_l, a_star_s, clb, csb)


def _matrix_scan_gpu_tree_kernel(
    a_log_ref,
    a_sign_ref,
    b_log_ref,
    b_sign_ref,
    x0_log_ref,
    x0_sign_ref,
    x_log_ref,
    x_sign_ref,
):
    al = a_log_ref[0]  # (T, d, d): the whole (pow2-padded) sequence
    asn = a_sign_ref[0]
    bl = b_log_ref[0]  # (T, d, m)
    bsn = b_sign_ref[0]
    d, m = al.shape[-1], bl.shape[-1]

    a_star_l, a_star_s, b_star_l, b_star_s = tree_scan(
        _mat_combine, (al, asn, bl, bsn), mat_identity(d, m))

    ax_l, ax_s = _fold_state(a_star_l, a_star_s,
                             x0_log_ref[0, 0], x0_sign_ref[0, 0])
    x_l, x_s = _lse2(ax_l, ax_s, b_star_l, b_star_s)
    x_log_ref[0] = x_l
    x_sign_ref[0] = x_s


def _matrix_scan_gpu_tree_kernel_zero_b(
    a_log_ref,
    a_sign_ref,
    x0_log_ref,
    x0_sign_ref,
    x_log_ref,
    x_sign_ref,
):
    al = a_log_ref[0]  # (T, d, d)
    asn = a_sign_ref[0]
    a_star_l, a_star_s = tree_scan(
        _prod_combine, (al, asn), prod_identity(al.shape[-1]))
    x_l, x_s = _fold_state(a_star_l, a_star_s,
                           x0_log_ref[0, 0], x0_sign_ref[0, 0])
    x_log_ref[0] = x_l
    x_sign_ref[0] = x_s


@functools.partial(
    jax.jit,
    static_argnames=("num_warps", "num_stages", "interpret"),
)
def matrix_scan_gpu_tree_call(
    a_log: jax.Array,
    a_sign: jax.Array,
    b_log: jax.Array,
    b_sign: jax.Array,
    x0_log: jax.Array,
    x0_sign: jax.Array,
    *,
    num_warps: int = 4,
    num_stages: int = 1,
    interpret: bool = False,
):
    """Tree-scan entry: a (G, T, d, d), b (G, T, d, m), x0 (G, 1, d, m),
    all f32, T a power of two.  Returns (x_log, x_sign): (G, T, d, m).
    """
    g, t, d, _ = a_log.shape
    m = b_log.shape[-1]
    grid = (g,)

    a_spec = pl.BlockSpec((1, t, d, d), lambda gi: (gi, 0, 0, 0))
    b_spec = pl.BlockSpec((1, t, d, m), lambda gi: (gi, 0, 0, 0))
    x0_spec = pl.BlockSpec((1, 1, d, m), lambda gi: (gi, 0, 0, 0))

    out_shape = [
        jax.ShapeDtypeStruct((g, t, d, m), jnp.float32),
        jax.ShapeDtypeStruct((g, t, d, m), jnp.float32),
    ]
    return pl.pallas_call(
        _matrix_scan_gpu_tree_kernel,
        grid=grid,
        in_specs=[a_spec, a_spec, b_spec, b_spec, x0_spec, x0_spec],
        out_specs=[b_spec, b_spec],
        out_shape=out_shape,
        compiler_params=plgpu.CompilerParams(
            num_warps=num_warps, num_stages=num_stages),
        interpret=interpret,
    )(a_log, a_sign, b_log, b_sign, x0_log, x0_sign)


@functools.partial(
    jax.jit,
    static_argnames=("num_warps", "num_stages", "interpret"),
)
def matrix_scan_gpu_tree_call_zero_b(
    a_log: jax.Array,
    a_sign: jax.Array,
    x0_log: jax.Array,
    x0_sign: jax.Array,
    *,
    num_warps: int = 4,
    num_stages: int = 1,
    interpret: bool = False,
):
    """Zero-B tree-scan entry: a (G, T, d, d), x0 (G, 1, d, m), all f32,
    T a power of two.  Returns (x_log, x_sign): (G, T, d, m)."""
    g, t, d, _ = a_log.shape
    m = x0_log.shape[-1]
    grid = (g,)

    a_spec = pl.BlockSpec((1, t, d, d), lambda gi: (gi, 0, 0, 0))
    o_spec = pl.BlockSpec((1, t, d, m), lambda gi: (gi, 0, 0, 0))
    x0_spec = pl.BlockSpec((1, 1, d, m), lambda gi: (gi, 0, 0, 0))

    out_shape = [
        jax.ShapeDtypeStruct((g, t, d, m), jnp.float32),
        jax.ShapeDtypeStruct((g, t, d, m), jnp.float32),
    ]
    return pl.pallas_call(
        _matrix_scan_gpu_tree_kernel_zero_b,
        grid=grid,
        in_specs=[a_spec, a_spec, x0_spec, x0_spec],
        out_specs=[o_spec, o_spec],
        out_shape=out_shape,
        compiler_params=plgpu.CompilerParams(
            num_warps=num_warps, num_stages=num_stages),
        interpret=interpret,
    )(a_log, a_sign, x0_log, x0_sign)


# ---------------------------------------------------------------------------
# two_pass: per-tile tree scan -> carry stitch -> fixup, all CTAs parallel
# ---------------------------------------------------------------------------
def _matrix_scan_gpu_part_kernel(
    a_log_ref,
    a_sign_ref,
    b_log_ref,
    b_sign_ref,
    astar_log_ref,
    astar_sign_ref,
    s0_log_ref,
    s0_sign_ref,
):
    """Pass 1: tree-scan one (BT, d, *) tile in isolation, emitting the
    tile-local prefix transitions A* and zero-initialized states B*."""
    al = a_log_ref[0]  # (BT, d, d)
    asn = a_sign_ref[0]
    bl = b_log_ref[0]  # (BT, d, m)
    bsn = b_sign_ref[0]
    d, m = al.shape[-1], bl.shape[-1]

    a_star_l, a_star_s, b_star_l, b_star_s = tree_scan(
        _mat_combine, (al, asn, bl, bsn), mat_identity(d, m))
    astar_log_ref[0] = a_star_l
    astar_sign_ref[0] = a_star_s
    s0_log_ref[0] = b_star_l
    s0_sign_ref[0] = b_star_s


def _matrix_scan_gpu_part_kernel_zero_b(
    a_log_ref,
    a_sign_ref,
    astar_log_ref,
    astar_sign_ref,
):
    al = a_log_ref[0]  # (BT, d, d)
    asn = a_sign_ref[0]
    a_star_l, a_star_s = tree_scan(
        _prod_combine, (al, asn), prod_identity(al.shape[-1]))
    astar_log_ref[0] = a_star_l
    astar_sign_ref[0] = a_star_s


def _matrix_scan_gpu_fixup_kernel(
    astar_log_ref,
    astar_sign_ref,
    s0_log_ref,
    s0_sign_ref,
    xin_log_ref,
    xin_sign_ref,
    x_log_ref,
    x_sign_ref,
):
    """Pass 2: fold the tile's incoming state:  X = A* ∘ X_in ⊕ states⁰."""
    ax_l, ax_s = _fold_state(astar_log_ref[0], astar_sign_ref[0],
                             xin_log_ref[0, 0], xin_sign_ref[0, 0])
    x_l, x_s = _lse2(ax_l, ax_s, s0_log_ref[0], s0_sign_ref[0])
    x_log_ref[0] = x_l
    x_sign_ref[0] = x_s


def _matrix_scan_gpu_fixup_kernel_zero_b(
    astar_log_ref,
    astar_sign_ref,
    xin_log_ref,
    xin_sign_ref,
    x_log_ref,
    x_sign_ref,
):
    x_l, x_s = _fold_state(astar_log_ref[0], astar_sign_ref[0],
                           xin_log_ref[0, 0], xin_sign_ref[0, 0])
    x_log_ref[0] = x_l
    x_sign_ref[0] = x_s


def _carry_stitch(pa, pb, x0_log, x0_sign):
    """Scan per-tile (A*, B*) carries with the sharded-stitch combine.

    ``pa``: (G, K, d, d) / ``pb``: (G, K, d, m) (log, sign) Goom pairs as
    Gooms; returns each tile's incoming state planes (G, K, d, m).  This is
    literally ``sharded._carry_combine`` — the cross-device monoid combine
    — applied across CTAs inside one device."""
    from repro.core.goom import Goom
    from repro.core.ops import goom_add, lmme_reference
    from repro.kernels.sharded import _carry_combine

    ia, ib = jax.lax.associative_scan(
        _carry_combine(lmme_reference), (pa, pb), axis=1)
    x0 = Goom(x0_log, x0_sign)  # (G, 1, d, m)
    x_last = goom_add(lmme_reference(ia, x0), ib)  # state at each tile end
    xin_l = jnp.concatenate([x0_log, x_last.log_abs[:, :-1]], axis=1)
    xin_s = jnp.concatenate([x0_sign, x_last.sign[:, :-1]], axis=1)
    return xin_l, xin_s


def _prod_stitch(pa, x0_log, x0_sign):
    """Zero-B stitch: prefix products of the per-tile A* applied to x0."""
    from repro.core.goom import Goom
    from repro.core.ops import lmme_reference

    prods = jax.lax.associative_scan(
        lambda e, l: lmme_reference(l, e), pa, axis=1)
    x_last = lmme_reference(prods, Goom(x0_log, x0_sign))
    xin_l = jnp.concatenate([x0_log, x_last.log_abs[:, :-1]], axis=1)
    xin_s = jnp.concatenate([x0_sign, x_last.sign[:, :-1]], axis=1)
    return xin_l, xin_s


@functools.partial(
    jax.jit,
    static_argnames=("block_t", "num_warps", "num_stages", "interpret"),
)
def matrix_scan_gpu_two_pass_call(
    a_log: jax.Array,
    a_sign: jax.Array,
    b_log: jax.Array,
    b_sign: jax.Array,
    x0_log: jax.Array,
    x0_sign: jax.Array,
    *,
    block_t: int = 32,
    num_warps: int = 4,
    num_stages: int = 1,
    interpret: bool = False,
):
    """Two-pass grid-scan entry: a (G, T, d, d), b (G, T, d, m), x0
    (G, 1, d, m), all f32, T % block_t == 0 (block_t a power of two).
    Returns (x_log, x_sign): (G, T, d, m).
    """
    from repro.core.goom import Goom

    g, t, d, _ = a_log.shape
    m = b_log.shape[-1]
    k = t // block_t
    grid = (g, k)

    a_spec = pl.BlockSpec((1, block_t, d, d), lambda gi, ti: (gi, ti, 0, 0))
    b_spec = pl.BlockSpec((1, block_t, d, m), lambda gi, ti: (gi, ti, 0, 0))
    params = plgpu.CompilerParams(
        num_warps=num_warps, num_stages=num_stages)

    astar_l, astar_s, s0_l, s0_s = pl.pallas_call(
        _matrix_scan_gpu_part_kernel,
        grid=grid,
        in_specs=[a_spec, a_spec, b_spec, b_spec],
        out_specs=[a_spec, a_spec, b_spec, b_spec],
        out_shape=[
            jax.ShapeDtypeStruct((g, t, d, d), jnp.float32),
            jax.ShapeDtypeStruct((g, t, d, d), jnp.float32),
            jax.ShapeDtypeStruct((g, t, d, m), jnp.float32),
            jax.ShapeDtypeStruct((g, t, d, m), jnp.float32),
        ],
        compiler_params=params,
        interpret=interpret,
    )(a_log, a_sign, b_log, b_sign)

    pa = Goom(astar_l.reshape(g, k, block_t, d, d)[:, :, -1],
              astar_s.reshape(g, k, block_t, d, d)[:, :, -1])
    pb = Goom(s0_l.reshape(g, k, block_t, d, m)[:, :, -1],
              s0_s.reshape(g, k, block_t, d, m)[:, :, -1])
    xin_l, xin_s = _carry_stitch(pa, pb, x0_log, x0_sign)

    xin_spec = pl.BlockSpec((1, 1, d, m), lambda gi, ti: (gi, ti, 0, 0))
    return pl.pallas_call(
        _matrix_scan_gpu_fixup_kernel,
        grid=grid,
        in_specs=[a_spec, a_spec, b_spec, b_spec, xin_spec, xin_spec],
        out_specs=[b_spec, b_spec],
        out_shape=[
            jax.ShapeDtypeStruct((g, t, d, m), jnp.float32),
            jax.ShapeDtypeStruct((g, t, d, m), jnp.float32),
        ],
        compiler_params=params,
        interpret=interpret,
    )(astar_l, astar_s, s0_l, s0_s, xin_l, xin_s)


@functools.partial(
    jax.jit,
    static_argnames=("block_t", "num_warps", "num_stages", "interpret"),
)
def matrix_scan_gpu_two_pass_call_zero_b(
    a_log: jax.Array,
    a_sign: jax.Array,
    x0_log: jax.Array,
    x0_sign: jax.Array,
    *,
    block_t: int = 32,
    num_warps: int = 4,
    num_stages: int = 1,
    interpret: bool = False,
):
    """Zero-B two-pass entry: a (G, T, d, d), x0 (G, 1, d, m), all f32,
    T % block_t == 0 (block_t a power of two).  Returns (G, T, d, m)."""
    from repro.core.goom import Goom

    g, t, d, _ = a_log.shape
    m = x0_log.shape[-1]
    k = t // block_t
    grid = (g, k)

    a_spec = pl.BlockSpec((1, block_t, d, d), lambda gi, ti: (gi, ti, 0, 0))
    o_spec = pl.BlockSpec((1, block_t, d, m), lambda gi, ti: (gi, ti, 0, 0))
    params = plgpu.CompilerParams(
        num_warps=num_warps, num_stages=num_stages)

    astar_l, astar_s = pl.pallas_call(
        _matrix_scan_gpu_part_kernel_zero_b,
        grid=grid,
        in_specs=[a_spec, a_spec],
        out_specs=[a_spec, a_spec],
        out_shape=[
            jax.ShapeDtypeStruct((g, t, d, d), jnp.float32),
            jax.ShapeDtypeStruct((g, t, d, d), jnp.float32),
        ],
        compiler_params=params,
        interpret=interpret,
    )(a_log, a_sign)

    pa = Goom(astar_l.reshape(g, k, block_t, d, d)[:, :, -1],
              astar_s.reshape(g, k, block_t, d, d)[:, :, -1])
    xin_l, xin_s = _prod_stitch(pa, x0_log, x0_sign)

    xin_spec = pl.BlockSpec((1, 1, d, m), lambda gi, ti: (gi, ti, 0, 0))
    return pl.pallas_call(
        _matrix_scan_gpu_fixup_kernel_zero_b,
        grid=grid,
        in_specs=[a_spec, a_spec, xin_spec, xin_spec],
        out_specs=[o_spec, o_spec],
        out_shape=[
            jax.ShapeDtypeStruct((g, t, d, m), jnp.float32),
            jax.ShapeDtypeStruct((g, t, d, m), jnp.float32),
        ],
        compiler_params=params,
        interpret=interpret,
    )(astar_l, astar_s, xin_l, xin_s)
