"""Plain reference of the paper's deep GOOM RNN (§4.3), and its weights.

Imports nothing of the program.  Per layer, in float32 at the highest
matmul precision::

    h = LN(LN(x))                     the block's norm, then the layer's own
    u = h @ W_in                      (B, S, heads, d)
    x_t = A x_{t-1} + B u_t           per head, sequentially over t, with
                                      x_t kept as (log|x_t|, sign x_t)
    v_t = sign * exp(log|x_t| - max + 2)   the max over all heads and dims
    y = GLU(v C + u D) @ W_out ;  x <- x + y

then a final LN and the output head.  ``init_weights`` makes the weights
from a seed in one jitted call; the benchmark gives the same weights to the
program and to this reference.

A ``Precision`` says where values are rounded.  ``stated`` reads the one
the configuration states: activations, the residual stream and the
projections' outputs in its compute dtype, the projections' operands in it
too (accumulated in float32), the recurrence in its recurrence dtype.
``FLOAT32`` rounds nothing.  ``controls`` lower one of them each to the
next precision down.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.dtype(jnp.float32)
LOWER = {F32: jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.bfloat16): jnp.dtype(jnp.float8_e4m3fn)}


class Precision(NamedTuple):
    compute: Any = F32      # activations, residual stream, projections' outputs
    operands: Any = F32     # the projections' operands
    recurrence: Any = F32   # operands of the recurrence's products


FLOAT32 = Precision()


def stated(cfg: Dict[str, Any]) -> Precision:
    """The precision the configuration states."""
    c = jnp.dtype(cfg["compute_dtype"])
    return Precision(c, c, jnp.dtype(cfg["recurrence_dtype"]))


def controls(p: Precision) -> Dict[str, Precision]:
    """``p`` with its projections' operands, or its recurrence, one
    precision lower."""
    return {"operands": p._replace(operands=LOWER[p.operands]),
            "recurrence": p._replace(recurrence=LOWER[p.recurrence])}


def key_from_seed(seed: int) -> jax.Array:
    """A PRNG key for any non-negative seed (also past 32 bits)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def sizes(cfg: Dict[str, Any]) -> Dict[str, int]:
    d, hd = cfg["d_model"], cfg["head_dim"]
    return {"d": d, "hd": hd, "h": d // hd, "layers": cfg["n_layers"],
            "vocab": cfg["vocab"]}


def _weights(cfg: Dict[str, Any], key: jax.Array) -> Dict[str, jax.Array]:
    s = sizes(cfg)
    d, hd, h, n, v = s["d"], s["hd"], s["h"], s["layers"], s["vocab"]
    ks = iter(jax.random.split(key, 16))
    nrm = lambda shape: jax.random.normal(next(ks), shape, jnp.float32)
    eye = jnp.eye(hd, dtype=jnp.float32)
    return {
        "embed": 0.02 * nrm((v, d)),
        "head": nrm((d, v)) / d ** 0.5,
        "final_scale": 1.0 + 0.1 * nrm((d,)),
        "final_bias": 0.1 * nrm((d,)),
        "norm_scale": 1.0 + 0.1 * nrm((n, d)),
        "norm_bias": 0.1 * nrm((n, d)),
        "ln_scale": 1.0 + 0.1 * nrm((n, d)),
        "ln_bias": 0.1 * nrm((n, d)),
        "w_in": nrm((n, d, h, hd)) / d ** 0.5,
        "A": 0.9 * eye + 0.1 * nrm((n, h, hd, hd)) / hd ** 0.5,
        "B": 0.5 / hd ** 0.5 * nrm((n, h, hd, hd)),
        "C": 0.5 / hd ** 0.5 * nrm((n, h, hd, 2 * hd)),
        "D": 0.5 / hd ** 0.5 * nrm((n, h, hd, 2 * hd)),
        "w_out": nrm((n, h * hd, d)) / (h * hd) ** 0.5,
    }


def init_weights(cfg: Dict[str, Any], seed: int) -> Dict[str, jax.Array]:
    """Float32 weights from ``seed``, made on the default device in one call."""
    return jax.jit(partial(_weights, cfg))(key_from_seed(seed))


def _ln(x, scale, bias, eps=1e-5):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def _round(x, dtype):
    """``x`` rounded to ``dtype``, carried in float32."""
    return x if dtype == F32 else x.astype(dtype).astype(F32)


def _dot(x, w, dims, op, out=F32, batch=((), ())):
    """``x`` contracted with ``w`` over ``dims``: operands rounded to ``op``
    (float32 at the highest precision), accumulated in float32, the result
    rounded to ``out``."""
    if op == F32:
        y = jax.lax.dot_general(x, w, (dims, batch), precision=HIGHEST)
    else:
        y = jax.lax.dot_general(x.astype(op), w.astype(op), (dims, batch),
                                preferred_element_type=F32)
    return _round(y, out)


def _per_head(a, x, op, out=F32):
    """a (H, d, e) applied to x (..., H, d): (..., H, e), as sum_d a[h,d,e] x[..., h, d]."""
    lead = x.ndim - 2
    xt = jnp.moveaxis(x, -2, 0)                                          # (H, ..., d)
    y = _dot(xt, a, ((xt.ndim - 1,), (1,)), op, out, ((0,), (0,)))      # (H, ..., e)
    return jnp.moveaxis(y, 0, lead)


def _recurrence(a, bu, op):
    """x_t = A x_{t-1} + bu_t from x_0 = 0, in log space, one step at a time.

    a: (H, d, d); bu: (S, N, H, d).  Returns (log|x|, sign x), (S, N, H, d)."""
    n, h, d = bu.shape[1:]

    def step(state, b_t):
        lx, sx = state
        m = jax.lax.stop_gradient(jnp.max(lx, axis=-1, keepdims=True))
        y = _per_head(a.swapaxes(-1, -2), sx * jnp.exp(lx - m), op)
        la, sa = jnp.log(jnp.abs(y)) + m, jnp.sign(y)
        lb, sb = jnp.log(jnp.abs(b_t)), jnp.sign(b_t)
        top = jnp.maximum(la, lb)
        val = sa * jnp.exp(la - top) + sb * jnp.exp(lb - top)
        out = (top + jnp.log(jnp.abs(val)), jnp.where(val < 0, -1.0, 1.0))
        return out, out

    # x_0 = 0 as a log-magnitude far below any other (finite, so that the
    # gradient of the first step stays finite)
    zero = (jnp.full((n, h, d), -1e30, jnp.float32), jnp.ones((n, h, d), jnp.float32))
    _, (lx, sx) = jax.lax.scan(step, zero, bu)
    return lx, sx


def _layer(x, w, p: Precision):
    hd = w["A"].shape[-1]
    cd, op = p.compute, p.operands
    h = _round(_ln(x, w["norm_scale"], w["norm_bias"]), cd)
    h = _round(_ln(h, w["ln_scale"], w["ln_bias"]), cd)
    u = _dot(h, w["w_in"], ((2,), (0,)), op)                            # (N,S,H,d)
    bu = _per_head(w["B"].swapaxes(-1, -2), u, p.recurrence).transpose(1, 0, 2, 3)
    lx, sx = _recurrence(w["A"], bu, p.recurrence)                      # (S,N,H,d)
    lx, sx = lx.transpose(1, 0, 2, 3), sx.transpose(1, 0, 2, 3)         # (N,S,H,d)
    c = jax.lax.stop_gradient(jnp.max(lx, axis=(-2, -1), keepdims=True))
    vals = _round(sx * jnp.exp(lx - c + 2.0), cd)
    y = _round(_per_head(w["C"], vals, op, cd) + _per_head(w["D"], _round(u, cd), op, cd), cd)
    y = _round(y[..., :hd] * _round(jax.nn.sigmoid(y[..., hd:]), cd), cd)
    y = y.reshape(x.shape[:2] + (-1,))
    return _round(x + _dot(y, w["w_out"], ((2,), (0,)), op, cd), cd)


LAYER_KEYS = ("norm_scale", "norm_bias", "ln_scale", "ln_bias", "w_in", "A", "B",
              "C", "D", "w_out")


@partial(jax.jit, static_argnames=("prec",))
def hidden(w, tokens, prec: Precision = FLOAT32):
    """Final normed hidden states (N, S, d) of token rows (N, S)."""
    x = _round(w["embed"][tokens], prec.compute)
    stacked = {k: w[k] for k in LAYER_KEYS}

    def body(x, lw):
        return _layer(x, lw, prec), None

    x, _ = jax.lax.scan(body, x, stacked)
    return _round(_ln(x, w["final_scale"], w["final_bias"]), prec.compute)


@partial(jax.jit, static_argnames=("prec", "others", "block"))
def gaps(w, h_ref, targets, h_others=(), *, prec: Precision = FLOAT32,
         others: Tuple[Precision, ...] = (), block=64):
    """Per position (N, S): how far the reference's logit of the served
    token (``targets``) lies below the reference's best, and for each of
    ``h_others`` (hidden states at the precisions ``others``) how far the
    token that its logits put first lies below it.  Logits are made
    ``block`` positions at a time."""
    n, s, _ = h_ref.shape
    pad = (-s) % block
    padded = lambda a: jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
    nb = (s + pad) // block
    split = lambda a: padded(a).reshape((n, nb, block) + a.shape[2:]).swapaxes(0, 1)
    logits = lambda h, p: _dot(h, w["head"], ((2,), (0,)), p.operands)

    def one(_, xs):
        r, t, hs = xs
        ref = logits(r, prec)
        best = jnp.max(ref, axis=-1)
        at = lambda tok: jnp.take_along_axis(ref, tok[..., None], axis=-1)[..., 0]
        out = [best - at(t)]
        for h, p in zip(hs, others):
            out.append(best - at(jnp.argmax(logits(h, p), axis=-1)))
        return None, jnp.stack(out)

    _, g = jax.lax.scan(one, None, (split(h_ref), split(targets),
                                    tuple(split(h) for h in h_others)))
    # (nb, k, n, block) -> (k, n, S)
    return g.transpose(1, 2, 0, 3).reshape(len(others) + 1, n, nb * block)[:, :, :s]


def position_gaps(w, rows: Sequence[Tuple[List[int], List[int]]], prec: Precision,
                  others: Sequence[Precision] = (), shape=None):
    """Per served token of ``rows`` (``[(prompt, served tokens)]``): the
    gaps of ``gaps``, as a list per row of (k, tokens) arrays.  Rows are
    padded at the end to one length (the recurrence is causal, so padding
    leaves earlier positions alone), and to ``shape`` (rows, length) where
    given, so that every run compiles the same program."""
    n, length = shape or (len(rows), max(len(p) + len(t) - 1 for p, t in rows))
    tokens = np.zeros((n, length), np.int32)
    targets = np.zeros((n, length), np.int32)
    for i, (p, t) in enumerate(rows):
        seq = list(p) + list(t[:-1])
        tokens[i, :len(seq)] = seq
        targets[i, len(p) - 1:len(p) - 1 + len(t)] = t
    others = tuple(others)
    h_ref = hidden(w, tokens, prec)
    h_others = tuple(hidden(w, tokens, p) for p in others)
    g = np.asarray(gaps(w, h_ref, targets, h_others, prec=prec, others=others))
    return [g[:, i, len(p) - 1:len(p) - 1 + len(t)] for i, (p, t) in enumerate(rows)]
