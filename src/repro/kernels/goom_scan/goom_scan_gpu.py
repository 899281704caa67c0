"""Pallas-GPU kernels: prefix scan of a diagonal GOOM recurrence.

Same recurrence and combine algebra as the TPU kernel (``goom_scan.py``),
reshaped for a GPU launch.  Three time algorithms share the math:

``seq`` (``goom_scan_gpu_kernel_call``)
  the grid is ``(channel_tiles,)`` — one CTA per channel tile.  GPU grid
  steps are *parallel* CTAs, so the sequential time dimension cannot be a
  grid axis with a scratch carry; each CTA walks its time tiles with an
  in-kernel ``fori_loop``, threading the ``(1, BC)`` state carry through
  the loop in registers.  O(T) depth: the fallback for short T and the
  parity oracle for the parallel variants.

``tree`` (``goom_scan_gpu_tree_call``)
  still one CTA per channel tile, but the whole (power-of-two padded)
  time extent is one register tile scanned by the work-efficient Blelloch
  up/down-sweep (``tree.tree_scan``): 2(T-1) combines at depth 2·log2 T.

``two_pass`` (``goom_scan_gpu_two_pass_call``)
  for sequences longer than one register tile the grid becomes
  ``(channel_tiles, time_tiles)`` with *every* CTA independent: pass 1
  tree-scans each tile and emits its ``(A*, B*)`` compound; the per-tile
  carries are stitched with the same log-depth monoid combine
  ``kernels/sharded.py`` uses across devices (here across CTAs, at XLA
  level — time_tiles × C elements, negligible); pass 2 folds each tile's
  incoming state in.  Total depth O(log T), two HBM round-trips.

``num_warps`` / ``num_stages`` ride in via ``plgpu.CompilerParams``.
Lowering: Pallas's Triton path on CUDA devices; ``interpret=True`` runs
the identical bodies on CPU for CI parity (``pallas_gpu_interpret``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import triton as plgpu

from .goom_scan import _combine, _lse2
from .tree import diag_identity, tree_scan


def _scan_gpu_kernel(
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
        cl, cs = carry  # (1, BC) state entering this time tile
        ts = pl.ds(ti * block_t, block_t)
        al = a_log_ref[ts, :]  # (BT, BC)
        asn = a_sign_ref[ts, :]
        bl = b_log_ref[ts, :]
        bsn = b_sign_ref[ts, :]

        # In-tile inclusive scan of the (A, B) compound pairs.
        a_star_l, a_star_s, b_star_l, b_star_s = jax.lax.associative_scan(
            _combine, (al, asn, bl, bsn), axis=0
        )

        # Fold the carried state:  x = A* ⊙ x_carry ⊕ B*.
        x_l, x_s = _lse2(a_star_l + cl, a_star_s * cs, b_star_l, b_star_s)
        x_log_ref[ts, :] = x_l
        x_sign_ref[ts, :] = x_s
        return x_l[-1:], x_s[-1:]

    jax.lax.fori_loop(
        0, t_tiles, body, (x0_log_ref[...], x0_sign_ref[...]))


@functools.partial(
    jax.jit,
    static_argnames=("block_t", "block_c", "num_warps", "num_stages",
                     "interpret"),
)
def goom_scan_gpu_kernel_call(
    a_log: jax.Array,
    a_sign: jax.Array,
    b_log: jax.Array,
    b_sign: jax.Array,
    x0_log: jax.Array,
    x0_sign: jax.Array,
    *,
    block_t: int = 64,
    block_c: int = 128,
    num_warps: int = 4,
    num_stages: int = 1,
    interpret: bool = False,
):
    """Raw kernel entry: (T, C) planes + (1, C) initial state, all f32,
    T % block_t == 0 and C % block_c == 0.  Returns (x_log, x_sign): (T, C).
    """
    t, c = a_log.shape
    grid = (c // block_c,)

    ab_spec = pl.BlockSpec((t, block_c), lambda ci: (0, ci))
    x0_spec = pl.BlockSpec((1, block_c), lambda ci: (0, ci))

    out_shape = [
        jax.ShapeDtypeStruct((t, c), jnp.float32),
        jax.ShapeDtypeStruct((t, c), jnp.float32),
    ]
    return pl.pallas_call(
        functools.partial(_scan_gpu_kernel, t_tiles=t // block_t,
                          block_t=block_t),
        grid=grid,
        in_specs=[ab_spec, ab_spec, ab_spec, ab_spec, x0_spec, x0_spec],
        out_specs=[ab_spec, ab_spec],
        out_shape=out_shape,
        compiler_params=plgpu.CompilerParams(
            num_warps=num_warps, num_stages=num_stages),
        interpret=interpret,
    )(a_log, a_sign, b_log, b_sign, x0_log, x0_sign)


# ---------------------------------------------------------------------------
# tree: whole-T Blelloch scan, one CTA per channel tile
# ---------------------------------------------------------------------------
def _scan_gpu_tree_kernel(
    a_log_ref,
    a_sign_ref,
    b_log_ref,
    b_sign_ref,
    x0_log_ref,
    x0_sign_ref,
    x_log_ref,
    x_sign_ref,
):
    al = a_log_ref[...]  # (T, BC): the whole (pow2-padded) sequence
    asn = a_sign_ref[...]
    bl = b_log_ref[...]
    bsn = b_sign_ref[...]

    a_star_l, a_star_s, b_star_l, b_star_s = tree_scan(
        _combine, (al, asn, bl, bsn), diag_identity(al.shape[1]))

    # Fold the initial state:  x = A* ⊙ x0 ⊕ B*.
    x_l, x_s = _lse2(a_star_l + x0_log_ref[...], a_star_s * x0_sign_ref[...],
                     b_star_l, b_star_s)
    x_log_ref[...] = x_l
    x_sign_ref[...] = x_s


@functools.partial(
    jax.jit,
    static_argnames=("block_c", "num_warps", "num_stages", "interpret"),
)
def goom_scan_gpu_tree_call(
    a_log: jax.Array,
    a_sign: jax.Array,
    b_log: jax.Array,
    b_sign: jax.Array,
    x0_log: jax.Array,
    x0_sign: jax.Array,
    *,
    block_c: int = 128,
    num_warps: int = 4,
    num_stages: int = 1,
    interpret: bool = False,
):
    """Tree-scan entry: (T, C) planes + (1, C) initial state, all f32,
    T a power of two and C % block_c == 0.  Returns (x_log, x_sign): (T, C).
    """
    t, c = a_log.shape
    grid = (c // block_c,)

    ab_spec = pl.BlockSpec((t, block_c), lambda ci: (0, ci))
    x0_spec = pl.BlockSpec((1, block_c), lambda ci: (0, ci))

    out_shape = [
        jax.ShapeDtypeStruct((t, c), jnp.float32),
        jax.ShapeDtypeStruct((t, c), jnp.float32),
    ]
    return pl.pallas_call(
        _scan_gpu_tree_kernel,
        grid=grid,
        in_specs=[ab_spec, ab_spec, ab_spec, ab_spec, x0_spec, x0_spec],
        out_specs=[ab_spec, ab_spec],
        out_shape=out_shape,
        compiler_params=plgpu.CompilerParams(
            num_warps=num_warps, num_stages=num_stages),
        interpret=interpret,
    )(a_log, a_sign, b_log, b_sign, x0_log, x0_sign)


# ---------------------------------------------------------------------------
# two_pass: per-tile tree scan -> carry stitch -> fixup, all CTAs parallel
# ---------------------------------------------------------------------------
def _scan_gpu_part_kernel(
    a_log_ref,
    a_sign_ref,
    b_log_ref,
    b_sign_ref,
    astar_log_ref,
    astar_sign_ref,
    s0_log_ref,
    s0_sign_ref,
):
    """Pass 1: tree-scan one (BT, BC) tile in isolation.

    Emits the tile-local compound prefixes: ``A*`` (prefix products of a)
    and ``B*`` (the zero-initialized local states) — position BT-1 of each
    is this CTA's carry partial for the grid-level stitch."""
    al = a_log_ref[...]  # (BT, BC)
    asn = a_sign_ref[...]
    bl = b_log_ref[...]
    bsn = b_sign_ref[...]

    a_star_l, a_star_s, b_star_l, b_star_s = tree_scan(
        _combine, (al, asn, bl, bsn), diag_identity(al.shape[1]))
    astar_log_ref[...] = a_star_l
    astar_sign_ref[...] = a_star_s
    s0_log_ref[...] = b_star_l
    s0_sign_ref[...] = b_star_s


def _scan_gpu_fixup_kernel(
    astar_log_ref,
    astar_sign_ref,
    s0_log_ref,
    s0_sign_ref,
    xin_log_ref,
    xin_sign_ref,
    x_log_ref,
    x_sign_ref,
):
    """Pass 2: fold this tile's incoming state:  x = A* ⊙ x_in ⊕ states⁰."""
    x_l, x_s = _lse2(astar_log_ref[...] + xin_log_ref[...],
                     astar_sign_ref[...] * xin_sign_ref[...],
                     s0_log_ref[...], s0_sign_ref[...])
    x_log_ref[...] = x_l
    x_sign_ref[...] = x_s


@functools.partial(
    jax.jit,
    static_argnames=("block_t", "block_c", "num_warps", "num_stages",
                     "interpret"),
)
def goom_scan_gpu_two_pass_call(
    a_log: jax.Array,
    a_sign: jax.Array,
    b_log: jax.Array,
    b_sign: jax.Array,
    x0_log: jax.Array,
    x0_sign: jax.Array,
    *,
    block_t: int = 64,
    block_c: int = 128,
    num_warps: int = 4,
    num_stages: int = 1,
    interpret: bool = False,
):
    """Two-pass grid-scan entry: (T, C) planes + (1, C) initial state, all
    f32, T % block_t == 0 (block_t a power of two) and C % block_c == 0.
    Returns (x_log, x_sign): (T, C).
    """
    t, c = a_log.shape
    t_tiles = t // block_t
    grid = (c // block_c, t_tiles)

    tile_spec = pl.BlockSpec((block_t, block_c), lambda ci, ti: (ti, ci))
    plane_shape = [
        jax.ShapeDtypeStruct((t, c), jnp.float32),
        jax.ShapeDtypeStruct((t, c), jnp.float32),
    ]
    params = plgpu.CompilerParams(
        num_warps=num_warps, num_stages=num_stages)

    # Pass 1: every tile scanned independently (fully parallel grid).
    astar_l, astar_s, s0_l, s0_s = pl.pallas_call(
        _scan_gpu_part_kernel,
        grid=grid,
        in_specs=[tile_spec] * 4,
        out_specs=[tile_spec] * 4,
        out_shape=plane_shape * 2,
        compiler_params=params,
        interpret=interpret,
    )(a_log, a_sign, b_log, b_sign)

    # Stitch: the per-tile carries (A*, B*) at each tile's last position
    # obey the same monoid one level up — scan them with the log-depth
    # combine (the cross-CTA analogue of kernels/sharded.py's cross-device
    # carry combine), then fold x0 to get each tile's incoming state.
    pa_l = astar_l.reshape(t_tiles, block_t, c)[:, -1]
    pa_s = astar_s.reshape(t_tiles, block_t, c)[:, -1]
    pb_l = s0_l.reshape(t_tiles, block_t, c)[:, -1]
    pb_s = s0_s.reshape(t_tiles, block_t, c)[:, -1]
    ia_l, ia_s, ib_l, ib_s = jax.lax.associative_scan(
        _combine, (pa_l, pa_s, pb_l, pb_s), axis=0)
    xl_l, xl_s = _lse2(ia_l + x0_log, ia_s * x0_sign, ib_l, ib_s)
    xin_l = jnp.concatenate([x0_log, xl_l[:-1]], axis=0)  # (t_tiles, C)
    xin_s = jnp.concatenate([x0_sign, xl_s[:-1]], axis=0)

    # Pass 2: elementwise fixup, again fully parallel.
    xin_spec = pl.BlockSpec((1, block_c), lambda ci, ti: (ti, ci))
    return pl.pallas_call(
        _scan_gpu_fixup_kernel,
        grid=grid,
        in_specs=[tile_spec] * 4 + [xin_spec] * 2,
        out_specs=[tile_spec] * 2,
        out_shape=plane_shape,
        compiler_params=params,
        interpret=interpret,
    )(astar_l, astar_s, s0_l, s0_s, xin_l, xin_s)
