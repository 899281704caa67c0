"""Sequence-sharded GOOM prefix scans over a device mesh (shard_map).

Single-device scans cap the sequence length at one chip's memory.  This
module turns sequence length into a *scale-out* dimension: the time axis is
split over a mesh axis, each device runs the ordinary local scan (Pallas or
XLA — whatever the dispatch layer resolved) on its shard, and shards are
stitched with one small cross-device combine.

The decomposition (Heinsen's two-prefix-sum parallelization, arXiv
2311.06281; Martin & Cundy, arXiv 1709.04057) relies on the recurrence
being a monoid.  For ``X_t = A_t X_{t-1} ⊕ B_t`` over GOOMs the compound
of a whole shard is the pair

    A*_k = A_T ∘ ··· ∘ A_1           (∘ = LMME)
    B*_k = last state of the shard's zero-initialized local scan

and the shard-level recurrence ``X_k = A*_k X_{k-1} ⊕ B*_k`` is the *same*
monoid one level up.  Per device:

  1. local scan of the shard with zero initial state  -> states⁰_t, and the
     local prefix products A*_t (one extra local pass);
  2. ``all_gather`` of the P per-shard carries (A*, B*) over the sequence
     mesh axis — P tiny (d×d / d×m) GOOMs, a log-depth collective;
  3. an O(log P) associative scan over the gathered carries (the combine is
     LMME ∘ signed-LSE, so GOOM max-rescaling stays exact — no float
     round-trip anywhere);
  4. the stitch: ``X_t = A*_t ∘ X_in ⊕ states⁰_t`` where ``X_in`` is this
     shard's incoming prefix state (shard 0 uses the caller's ``x0``).

Everything is differentiable end-to-end: the local scans carry their own
custom VJPs, and ``all_gather`` / ``associative_scan`` / the stitch are
ordinary JAX.

Time lengths that don't divide the shard count are padded with identity
scan elements (A = I at log 0, B = exact zero at log -inf) and sliced back
— exact under the recurrence, same trick the kernel wrappers use for block
padding.

This module owns the *mechanics*; policy (which mesh, which axes, when to
fall back to single-device) lives in ``repro.core.engine``.  See
``docs/engine.md`` ("Sharded scans") for the worked 4-device example.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.goom import Goom, goom_zeros
from repro.core.ops import goom_add, goom_mul, lmme_reference


__all__ = [
    "ShardSpec",
    "seq_sharded_lmme",
    "seq_sharded_diagonal_scan",
    "seq_sharded_matrix_scan",
    "seq_sharded_cumulative_lmme",
    "seq_sharded_associative_scan",
]


@dataclasses.dataclass(frozen=True)
class ShardSpec:
    """Where sharded scans run: a mesh, the sequence axis, the batch axes.

    ``seq_axis`` is a single mesh axis name (the one collectives run over);
    ``batch_axes`` may name zero or more mesh axes for the leading batch dim
    (no collectives cross them — pure data parallelism).
    """

    mesh: Mesh
    seq_axis: str
    batch_axes: Tuple[str, ...] = ()

    @property
    def n_shards(self) -> int:
        return int(self.mesh.shape[self.seq_axis])

    def batch_size(self) -> int:
        n = 1
        for a in self.batch_axes:
            n *= int(self.mesh.shape[a])
        return n


def seq_sharded_lmme(a: Goom, b: Goom, *, spec: ShardSpec,
                     local_lmme: Callable) -> Goom:
    """LMME inside a shard_map over the whole mesh.

    A Pallas TPU kernel is never partitioned automatically: in a program
    over several devices it must run inside a shard_map.  The leading batch
    dim (time, for the GOOM layer's ``B·u``) is split over the sequence axis
    when it divides evenly, and the next (the batch) over the batch axes, as
    the scans below place them; an operand without those dims, or a dim
    that does not divide, is whole on every device."""
    batch = jnp.broadcast_shapes(a.shape[:-2], b.shape[:-2])
    seq = spec.seq_axis if batch and batch[0] % spec.n_shards == 0 else None
    bp = _batch_entry(spec, batch[1] if len(batch) > 1 else None)
    lead = (seq, bp)[:len(batch)]

    def spec_for(x: Goom):  # batch dims align from the right, and broadcast
        off = len(batch) - (x.ndim - 2)
        return P(*(lead[g] if g < len(lead) and x.shape[g - off] == batch[g]
                   else None for g in range(off, len(batch))))

    return jax.shard_map(
        local_lmme, mesh=spec.mesh, in_specs=(spec_for(a), spec_for(b)),
        out_specs=P(*lead), check_vma=False,
    )(a, b)


# ---------------------------------------------------------------------------
# small Goom helpers (leading-axis plumbing)
# ---------------------------------------------------------------------------
def _g_bcast(g: Goom, shape) -> Goom:
    return Goom(jnp.broadcast_to(g.log_abs, shape),
                jnp.broadcast_to(g.sign, shape))


def _g_concat(gs, axis=0) -> Goom:
    return Goom(jnp.concatenate([g.log_abs for g in gs], axis),
                jnp.concatenate([g.sign for g in gs], axis))


def _g_index(g: Goom, i) -> Goom:
    return Goom(jax.lax.dynamic_index_in_dim(g.log_abs, i, 0, keepdims=False),
                jax.lax.dynamic_index_in_dim(g.sign, i, 0, keepdims=False))


_g_zeros = goom_zeros  # exact-zero sentinel (log -inf), shared with core


def _g_eye(batch, d, dtype=jnp.float32) -> Goom:
    log = jnp.where(jnp.eye(d, dtype=bool), 0.0, -jnp.inf).astype(dtype)
    return _g_bcast(Goom(log, jnp.ones((d, d), dtype)), tuple(batch) + (d, d))


def _pad_time(g: Goom, pad: int, fill: Goom) -> Goom:
    """Append ``pad`` copies of the identity element ``fill`` (shape g[0])."""
    if pad == 0:
        return g
    tail = _g_bcast(fill, (pad,) + g.shape[1:])
    return _g_concat([g, tail], axis=0)


def _batch_entry(spec: ShardSpec, dim: Optional[int]):
    """PartitionSpec entry for the first batch dim (None if not shardable)."""
    axes = spec.batch_axes
    if not axes or dim is None or dim % spec.batch_size() != 0:
        return None
    return axes[0] if len(axes) == 1 else tuple(axes)


def _carry_combine(lmme: Callable[[Goom, Goom], Goom]):
    """The (A, B) monoid combine — identical algebra to core.scan's."""

    def combine(e, l):
        a_e, b_e = e
        a_l, b_l = l
        return lmme(a_l, a_e), goom_add(lmme(a_l, b_e), b_l)

    return combine


def _exclusive_prefix(pa: Goom, pb: Goom, eye: Goom, zero: Goom, idx):
    """This shard's incoming compound: identity for shard 0, else prefix."""
    pa_x = _g_concat([Goom(eye.log_abs[None], eye.sign[None]), pa[:-1]])
    pb_x = _g_concat([Goom(zero.log_abs[None], zero.sign[None]), pb[:-1]])
    return _g_index(pa_x, idx), _g_index(pb_x, idx)


# ---------------------------------------------------------------------------
# matrix recurrence:  X_t = A_t X_{t-1} ⊕ B_t
# ---------------------------------------------------------------------------
def seq_sharded_matrix_scan(
    a: Goom,
    b: Goom,
    x0: Optional[Goom],
    *,
    spec: ShardSpec,
    local_matrix_scan: Callable,
    local_cumulative_lmme: Callable,
    lmme: Callable[[Goom, Goom], Goom],
) -> Goom:
    """All states of the matrix GOOM recurrence, time-sharded over the mesh.

    a: (T, ..., d, d);  b: (T, ..., d, m);  x0: (..., d, m) or None.
    ``local_*`` are the dispatch-resolved single-device implementations that
    run on each shard; ``lmme`` is the resolved LMME used for the (large,
    batched) stitch.  The P-element carry combine uses the reference LMME —
    P tiny matrices, never a bottleneck, and the monoid is identical.
    """
    p = spec.n_shards
    t = b.shape[0]
    if t < p:
        return local_matrix_scan(a, b, x0)
    d = a.shape[-1]
    batch = jnp.broadcast_shapes(a.shape[1:-2], b.shape[1:-2])
    a = _g_bcast(a, (t,) + batch + (d, d))
    b = _g_bcast(b, (t,) + batch + b.shape[-2:])
    x0g = (_g_zeros(batch + b.shape[-2:]) if x0 is None
           else _g_bcast(x0, batch + b.shape[-2:]))

    pad = (-t) % p
    a = _pad_time(a, pad, _g_eye(batch, d))
    b = _pad_time(b, pad, _g_zeros(batch + b.shape[-2:]))

    bp = _batch_entry(spec, batch[0] if batch else None)
    nb = len(batch)
    sax = spec.seq_axis
    t_spec = P(sax, bp, *([None] * (nb - 1 + 2)))
    x_spec = P(bp, *([None] * (nb - 1 + 2)))

    def body(a_l: Goom, b_l: Goom, x0_l: Goom) -> Goom:
        states0 = local_matrix_scan(a_l, b_l, None)
        astar = local_cumulative_lmme(a_l)
        ga, gb = jax.lax.all_gather((astar[-1], states0[-1]), sax)
        pa, pb = jax.lax.associative_scan(
            _carry_combine(lmme_reference), (ga, gb), axis=0)
        idx = jax.lax.axis_index(sax)
        lb = x0_l.shape[:-2]
        a_in, b_in = _exclusive_prefix(
            pa, pb, _g_eye(lb, d), _g_zeros(x0_l.shape), idx)
        x_in = goom_add(lmme_reference(a_in, x0_l), b_in)
        return goom_add(lmme(astar, x_in), states0)

    out = jax.shard_map(
        body, mesh=spec.mesh,
        in_specs=(t_spec, t_spec, x_spec), out_specs=t_spec,
        check_vma=False,
    )(a, b, x0g)
    return out[:t] if pad else out


# ---------------------------------------------------------------------------
# prefix products:  A_t ··· A_1   (PSCAN(LMME), paper eq. 24)
# ---------------------------------------------------------------------------
def seq_sharded_cumulative_lmme(
    a: Goom,
    *,
    spec: ShardSpec,
    local_cumulative_lmme: Callable,
    lmme: Callable[[Goom, Goom], Goom],
) -> Goom:
    """All prefix products, time-sharded: one local pass + carry stitch."""
    p = spec.n_shards
    t = a.shape[0]
    if t < p:
        return local_cumulative_lmme(a)
    d = a.shape[-1]
    batch = a.shape[1:-2]
    pad = (-t) % p
    a = _pad_time(a, pad, _g_eye(batch, d))

    bp = _batch_entry(spec, batch[0] if batch else None)
    nb = len(batch)
    sax = spec.seq_axis
    t_spec = P(sax, bp, *([None] * (nb - 1 + 2)))

    def body(a_l: Goom) -> Goom:
        astar = local_cumulative_lmme(a_l)
        g = jax.lax.all_gather(astar[-1], sax)
        pref = jax.lax.associative_scan(
            lambda e, l: lmme_reference(l, e), g, axis=0)
        idx = jax.lax.axis_index(sax)
        lb = astar.shape[1:-2]
        eye = _g_eye(lb, d)
        pa_x = _g_concat([Goom(eye.log_abs[None], eye.sign[None]), pref[:-1]])
        p_in = _g_index(pa_x, idx)
        return lmme(astar, p_in)

    out = jax.shard_map(
        body, mesh=spec.mesh, in_specs=(t_spec,), out_specs=t_spec,
        check_vma=False,
    )(a)
    return out[:t] if pad else out


# ---------------------------------------------------------------------------
# diagonal recurrence:  x_t = a_t ⊙ x_{t-1} ⊕ b_t
# ---------------------------------------------------------------------------
def seq_sharded_diagonal_scan(
    a: Goom,
    b: Goom,
    x0: Optional[Goom],
    *,
    spec: ShardSpec,
    local_diagonal_scan: Callable,
) -> Goom:
    """Diagonal scan, time-sharded.  The per-shard decay compound is just the
    elementwise product of the shard's decays — a log-space cumsum — so the
    extra local pass the matrix scan needs collapses to one cumsum/cumprod.
    """
    p = spec.n_shards
    t = b.shape[0] if b.ndim else 1
    if t < p:
        return local_diagonal_scan(a, b, x0)
    shape = jnp.broadcast_shapes(a.shape, b.shape)
    a = _g_bcast(a, shape)
    b = _g_bcast(b, shape)
    x0g = (_g_zeros(shape[1:]) if x0 is None else _g_bcast(x0, shape[1:]))

    pad = (-t) % p
    ones = Goom(jnp.zeros(shape[1:], jnp.float32), jnp.ones(shape[1:], jnp.float32))
    a = _pad_time(a, pad, ones)
    b = _pad_time(b, pad, _g_zeros(shape[1:]))

    batch = shape[1:]
    bp = _batch_entry(spec, batch[0] if batch else None)
    sax = spec.seq_axis
    rest = [bp] + [None] * (len(batch) - 1) if batch else []
    t_spec = P(sax, *rest)
    x_spec = P(*rest)

    def body(a_l: Goom, b_l: Goom, x0_l: Goom) -> Goom:
        states0 = local_diagonal_scan(a_l, b_l, None)
        astar = Goom(jnp.cumsum(a_l.log_abs, axis=0),
                     jnp.cumprod(a_l.sign, axis=0))
        ga, gb = jax.lax.all_gather((astar[-1], states0[-1]), sax)

        def combine(e, l):
            a_e, b_e = e
            a_l_, b_l_ = l
            return goom_mul(a_l_, a_e), goom_add(goom_mul(a_l_, b_e), b_l_)

        pa, pb = jax.lax.associative_scan(combine, (ga, gb), axis=0)
        idx = jax.lax.axis_index(sax)
        lshape = x0_l.shape
        one = Goom(jnp.zeros(lshape, jnp.float32), jnp.ones(lshape, jnp.float32))
        a_in, b_in = _exclusive_prefix(pa, pb, one, _g_zeros(lshape), idx)
        x_in = goom_add(goom_mul(a_in, x0_l), b_in)
        x_in_b = _g_bcast(x_in, astar.shape)
        return goom_add(goom_mul(astar, x_in_b), states0)

    out = jax.shard_map(
        body, mesh=spec.mesh,
        in_specs=(t_spec, t_spec, x_spec), out_specs=t_spec,
        check_vma=False,
    )(a, b, x0g)
    return out[:t] if pad else out


# ---------------------------------------------------------------------------
# generic associative scan (selective-reset scan rides this)
# ---------------------------------------------------------------------------
def seq_sharded_associative_scan(fn, elems, *, spec: ShardSpec):
    """``jax.lax.associative_scan(fn, elems, axis=0)``, time-sharded.

    Works for any associative ``fn`` over a pytree with a leading time axis
    (the selective-reset monoid included: its combine is associative, so a
    shard-level bracketing computes the same result).  No identity element
    is known for an arbitrary monoid, so (a) the time length must divide the
    shard count — callers fall back to the local scan otherwise — and
    (b) shard 0's stitch is masked out with a ``where`` instead of combining
    with an identity.
    """
    leaves = jax.tree_util.tree_leaves(elems)
    t = leaves[0].shape[0]
    p = spec.n_shards
    if t % p != 0:
        raise ValueError(
            f"sharded associative scan needs T % n_shards == 0, got "
            f"T={t}, n_shards={p} (generic monoid: no identity to pad with)")
    sax = spec.seq_axis
    t_spec = P(sax)

    def body(elems_l):
        local = jax.lax.associative_scan(fn, elems_l, axis=0)
        summ = jax.tree.map(lambda x: x[-1], local)
        gathered = jax.lax.all_gather(summ, sax)
        pref = jax.lax.associative_scan(fn, gathered, axis=0)
        idx = jax.lax.axis_index(sax)
        prev = jax.tree.map(
            lambda x: jax.lax.dynamic_index_in_dim(
                x, jnp.maximum(idx - 1, 0), 0, keepdims=False),
            pref)
        t_l = jax.tree_util.tree_leaves(local)[0].shape[0]
        prev_b = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (t_l,) + x.shape), prev)
        stitched = fn(prev_b, local)
        return jax.tree.map(
            lambda l, s: jnp.where(idx == 0, l, s), local, stitched)

    return jax.shard_map(
        body, mesh=spec.mesh, in_specs=(t_spec,), out_specs=t_spec,
        check_vma=False,
    )(elems)
