"""Generalized orders of magnitude (GOOMs) — core representation.

The paper represents a real number x as a complex logarithm
``x' = log|x| + k*pi*i`` (complex64/complex128 on GPU).  On TPU we use the
*split representation*: a pytree ``Goom(log_abs, sign)`` where

  * ``log_abs`` is the real component (natural log of |x|), float32/float64;
  * ``sign``   is ``exp(i * imag)`` collapsed to a real plane in {+1.0, -1.0}.

The two are isomorphic (imag = k*pi  <=>  sign = (-1)^k); the split form is
what the MXU/VPU can actually consume.  A complex view is provided for
interop and for tests that cross-check against the paper's formulation.

Custom derivative redefinitions follow the paper:
  eq. (5)  d/dx abs(x)      := sign(x), with sign(0) := +1   (never zero)
  eq. (6)  d/dx log(x)      := 1 / (x + eps)                 (finite at 0)
  eq. (8)  d/dx' exp(x')    := exp(x') +/- eps               (never zero)
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "Goom",
    "to_goom",
    "from_goom",
    "goom_from_complex",
    "goom_to_complex",
    "safe_abs",
    "safe_log",
    "signed_exp",
    "finite_floor",
    "closeness",
    "LOG_ZERO",
]

# Sentinel for log(0).  Large negative, but comfortably inside float32 range so
# that arithmetic on it (adding two floors, etc.) cannot overflow to -inf and
# produce NaNs via inf - inf in LSE.  The paper (footnote 5) uses
# 2*log(SNN) ~= -174.7 for float32; we adopt the same convention per dtype.
_FINITE_FLOOR = {
    jnp.dtype(jnp.float32): float(2.0 * np.log(np.finfo(np.float32).tiny)),
    jnp.dtype(jnp.float64): float(2.0 * np.log(np.finfo(np.float64).tiny)),
    jnp.dtype(jnp.bfloat16): float(2.0 * np.log(np.finfo(np.float32).tiny)),
}

LOG_ZERO = _FINITE_FLOOR[jnp.dtype(jnp.float32)]  # convenience constant


def finite_floor(dtype) -> float:
    """The finite value used to represent log(0) for ``dtype`` (paper fn. 5).

    Unknown / low-precision dtypes (float16, integer promotions, ...) fall
    back to the float32 floor: a log-plane narrower than float32 cannot hold
    its own ``2*log(tiny)`` anyway, and the f32 floor is a valid exact-zero
    sentinel for every wider plane.
    """
    try:
        dt = jnp.dtype(dtype)
    except TypeError:
        dt = jnp.dtype(jnp.float32)
    return _FINITE_FLOOR.get(dt, _FINITE_FLOOR[jnp.dtype(jnp.float32)])


def _eps(dtype) -> float:
    return float(np.finfo(np.dtype(dtype)).eps)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class Goom:
    """Split-representation GOOM: real = sign * exp(log_abs).

    ``sign`` uses the convention sign(0) := +1 (paper: zero is non-negative).
    Both leaves always share shape; broadcasting happens in ops, not here.
    """

    log_abs: jax.Array
    sign: jax.Array

    #: Value-domain tag per flattened leaf, aligned with ``tree_flatten``
    #: order.  The static analyzer (``repro.analysis``) reads this to seed
    #: its jaxpr lattice: ``log_abs`` planes are log-space magnitudes,
    #: ``sign`` planes are the {+1,-1} channel.
    _goomcheck_domains = ("log", "sign")

    # -- pytree protocol ---------------------------------------------------
    def tree_flatten(self):
        return (self.log_abs, self.sign), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    # -- conveniences --------------------------------------------------------
    @property
    def shape(self):
        return jnp.shape(self.log_abs)

    @property
    def dtype(self):
        return jnp.result_type(self.log_abs)

    @property
    def ndim(self):
        return jnp.ndim(self.log_abs)

    def __getitem__(self, idx):
        return Goom(self.log_abs[idx], self.sign[idx])

    def reshape(self, *shape):
        return Goom(self.log_abs.reshape(*shape), self.sign.reshape(*shape))

    def astype(self, dtype):
        return Goom(self.log_abs.astype(dtype), self.sign.astype(dtype))

    def transpose(self, *axes):
        ax = axes if axes else None
        return Goom(jnp.transpose(self.log_abs, ax), jnp.transpose(self.sign, ax))

    @property
    def mT(self):
        return Goom(self.log_abs.mT, self.sign.mT)


# ---------------------------------------------------------------------------
# safe_abs — paper eq. (5): derivative is +/-1, never 0; sign(0) := +1.
# ---------------------------------------------------------------------------
@jax.custom_jvp
def safe_abs(x: jax.Array) -> jax.Array:
    return jnp.abs(x)


@safe_abs.defjvp
def _safe_abs_jvp(primals, tangents):
    (x,), (dx,) = primals, tangents
    s = jnp.where(x >= 0, jnp.ones_like(x), -jnp.ones_like(x))
    return jnp.abs(x), s * dx


def nonzero_sign(x: jax.Array) -> jax.Array:
    """sign(x) with sign(0) := +1, as a float plane in {+1, -1}."""
    return jnp.where(x >= 0, jnp.ones_like(x), -jnp.ones_like(x))


# ---------------------------------------------------------------------------
# safe_log — paper eq. (6): derivative 1/(x+eps); log(0) -> finite floor
# (or -inf if floor disabled).
# ---------------------------------------------------------------------------
@partial(jax.custom_jvp, nondiff_argnums=(1,))
def safe_log(x: jax.Array, use_floor: bool = False) -> jax.Array:
    out = jnp.log(x)
    if use_floor:
        floor = finite_floor(x.dtype)
        out = jnp.where(x == 0, jnp.asarray(floor, out.dtype), out)
        out = jnp.maximum(out, jnp.asarray(floor, out.dtype))
    return out


@safe_log.defjvp
def _safe_log_jvp(use_floor, primals, tangents):
    (x,), (dx,) = primals, tangents
    dt = jnp.result_type(x)  # x may be a python scalar: no .dtype attribute
    eps = jnp.asarray(_eps(dt), dt)
    return safe_log(x, use_floor), dx / (x + eps)


# ---------------------------------------------------------------------------
# signed_exp — complex exp of the GOOM, returning the real number
# sign*exp(log_abs); derivative redefined per paper eq. (8) so the real
# component of the derivative is never exactly zero.
# ---------------------------------------------------------------------------
@jax.custom_jvp
def _signed_exp(log_abs: jax.Array, sign: jax.Array) -> jax.Array:
    return sign * jnp.exp(log_abs)


@_signed_exp.defjvp
def _signed_exp_jvp(primals, tangents):
    log_abs, sign = primals
    d_log, d_sign = tangents
    y = sign * jnp.exp(log_abs)
    eps = jnp.asarray(_eps(log_abs.dtype), log_abs.dtype)
    shifted = y + jnp.where(y >= 0, eps, -eps)  # eq. (8): derivative never 0
    del d_sign  # sign plane is a constant {+1,-1}; no useful tangent.
    return y, shifted * d_log


def signed_exp(log_abs: jax.Array, sign: jax.Array) -> jax.Array:
    return _signed_exp(log_abs, sign)


# ---------------------------------------------------------------------------
# public maps
# ---------------------------------------------------------------------------
def to_goom(x: jax.Array, *, use_floor: bool = False) -> Goom:
    """Map a real array to its GOOM (paper eq. 4)."""
    x = jnp.asarray(x)
    if jnp.issubdtype(x.dtype, jnp.complexfloating):
        return goom_from_complex(x)
    dt = jnp.float32 if x.dtype == jnp.bfloat16 else x.dtype
    xf = x.astype(dt)
    return Goom(safe_log(safe_abs(xf), use_floor), nonzero_sign(xf))


def from_goom(g: Goom, dtype=None) -> jax.Array:
    """Map a GOOM back to a real array (paper eq. 7: take the real part)."""
    y = signed_exp(g.log_abs, g.sign)
    return y.astype(dtype) if dtype is not None else y


def goom_from_complex(z: jax.Array) -> Goom:
    """From the paper's complex formulation: x' = log|x| + k*pi*i."""
    # cos(imag) in {+1,-1} up to numerical error; snap to the convention.
    sign = jnp.where(jnp.cos(jnp.imag(z)) >= 0, 1.0, -1.0).astype(jnp.real(z).dtype)
    return Goom(jnp.real(z), sign)


def goom_to_complex(g: Goom) -> jax.Array:
    """To the paper's complex formulation (principal branch: imag in {0, pi})."""
    cdt = jnp.complex64 if g.dtype == jnp.float32 else jnp.complex128
    imag = jnp.where(g.sign < 0, jnp.asarray(np.pi, g.dtype), jnp.zeros_like(g.sign))
    return (g.log_abs + 1j * imag.astype(g.log_abs.dtype)).astype(cdt)


def goom_zeros(shape, dtype=jnp.float32, *, use_floor: bool = False) -> Goom:
    """GOOM representation of real 0 (log_abs = -inf, or the finite floor).

    The -inf sentinel (paper §3.1 option (a)) is exact: zeros never shadow
    genuinely tiny values.  The finite floor (option (b), paper fn. 5) keeps
    every value finite — preferred inside training graphs.
    """
    la = finite_floor(dtype) if use_floor else -jnp.inf
    return Goom(jnp.full(shape, la, dtype), jnp.ones(shape, dtype))


def goom_ones(shape, dtype=jnp.float32) -> Goom:
    return Goom(jnp.zeros(shape, dtype), jnp.ones(shape, dtype))


def closeness(got: Goom, want: Goom, *, cancel_margin: float | None = 12.0) -> dict:
    """How far two GOOM results lie apart, over the last axis (host numpy).

    ``norm_err``: the largest difference of values divided by their row's
    scale (the larger of the two rows' max log-magnitudes), well-posed
    under catastrophic cancellation.  Unless ``cancel_margin`` is None,
    entries within that many log-units of the row scale must also agree in
    log-magnitude (``log_ok``: within 1e-3 + 1e-4 |log|) and sign
    (``sign_mismatches``); below it an entry's log|sum| and even its sign
    are ill-conditioned for any float method, the reference included."""
    gl, gs = np.asarray(got.log_abs, np.float64), np.asarray(got.sign)
    wl, ws = np.asarray(want.log_abs, np.float64), np.asarray(want.sign)
    m = np.maximum(gl.max(-1, keepdims=True), wl.max(-1, keepdims=True))
    m = np.where(np.isfinite(m), m, 0.0)  # an all-zero row compares as zeros
    rep = {"finite": bool(np.isfinite(gl).all()),
           "norm_err": float(np.abs(gs * np.exp(gl - m)
                                    - ws * np.exp(wl - m)).max(initial=0.0))}
    if cancel_margin is not None:
        ok = wl > m - cancel_margin
        log_err = np.abs(gl[ok] - wl[ok])
        rep["max_log_err"] = float(log_err.max(initial=0.0))
        rep["log_ok"] = bool((log_err <= 1e-3 + 1e-4 * np.abs(wl[ok])).all())
        rep["sign_mismatches"] = int((gs[ok] != ws[ok]).sum())
    return rep
