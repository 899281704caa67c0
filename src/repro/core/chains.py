"""Experiment 1 (paper §4.1, Fig. 1): long chains of random matrix products.

``S_t = A_t S_{t-1}`` with ``A_t ~ N(0,1)^{d x d}``.  Over floats the chain
compounds magnitudes like ``sqrt(d)^t`` and overflows within ~``log(MAX)/
(0.5 log d)`` steps; over GOOMs the log-magnitude grows *linearly* and the
chain runs for as long as the log fits the component float — i.e. ~1e37 steps
for Complex64-equivalent GOOMs.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import engine
from .goom import Goom, safe_log, to_goom

__all__ = ["float_chain_survival", "goom_chain", "goom_chain_parallel",
           "chain_elements", "ChainResult"]


class ChainResult(NamedTuple):
    steps_survived: jax.Array  # first failing step (== n_steps if none failed)
    final_log_norm: jax.Array  # log Frobenius norm of the final state


def _is_catastrophic(x: jax.Array) -> jax.Array:
    """Non-finite anywhere, or total collapse to zero."""
    return jnp.logical_or(
        jnp.logical_not(jnp.all(jnp.isfinite(x))), jnp.all(x == 0)
    )


def float_chain_survival(key: jax.Array, d: int, n_steps: int, dtype=jnp.float32) -> ChainResult:
    """Run the chain over plain floats; report how many steps survive."""
    k0, k1 = jax.random.split(key)
    s0 = jax.random.normal(k0, (d, d), dtype)

    def step(carry, k):
        s, alive, steps = carry
        a = jax.random.normal(k, (d, d), dtype)
        s_new = a @ s
        failed = _is_catastrophic(s_new)
        alive_new = jnp.logical_and(alive, jnp.logical_not(failed))
        s = jnp.where(alive_new, s_new, s)
        steps = steps + alive_new.astype(jnp.int32)
        return (s, alive_new, steps), None

    keys = jax.random.split(k1, n_steps)
    (s, alive, steps), _ = jax.lax.scan(step, (s0, jnp.array(True), jnp.array(0)), keys)
    fro = jnp.sqrt(jnp.sum(jnp.square(s.astype(jnp.float32))))
    return ChainResult(steps, safe_log(fro))


def goom_chain(key: jax.Array, d: int, n_steps: int, dtype=jnp.float32) -> ChainResult:
    """Run the chain over GOOMs, sequentially (lax.scan of LMME)."""
    k0, k1 = jax.random.split(key)
    s0 = to_goom(jax.random.normal(k0, (d, d), dtype))

    def step(s, k):
        a = to_goom(jax.random.normal(k, (d, d), dtype))
        return engine.lmme(a, s), None

    keys = jax.random.split(k1, n_steps)
    s, _ = jax.lax.scan(step, s0, keys)
    # Catastrophic error in log-space = NaN or +inf (a -inf is an exact zero).
    ok = jnp.logical_not(
        jnp.logical_or(
            jnp.any(jnp.isnan(s.log_abs)), jnp.any(jnp.isposinf(s.log_abs))
        )
    )
    steps = jnp.where(ok, n_steps, 0).astype(jnp.int32)
    # log Frobenius norm straight from log-space (no overflow possible):
    m = jnp.max(s.log_abs)
    # the exp is dominated by the subtracted max (2*(x - m) <= 0)
    fro = 0.5 * safe_log(jnp.sum(jnp.exp(2.0 * (s.log_abs - m)))) + m  # goomcheck: disable=GC202
    return ChainResult(steps, fro)


def chain_elements(key: jax.Array, d: int, n_steps: int, dtype=jnp.float32) -> Goom:
    """``[S_0, A_1, .., A_n]``: the chain's start state and its matrices."""
    k0, k1 = jax.random.split(key)
    mats = jax.random.normal(k1, (n_steps, d, d), dtype)
    s0 = jax.random.normal(k0, (1, d, d), dtype)
    return to_goom(jnp.concatenate([s0, mats], axis=0))


def goom_chain_parallel(key: jax.Array, d: int, n_steps: int, dtype=jnp.float32) -> Goom:
    """All prefix states in parallel via PSCAN(LMME) (paper eq. 24 machinery)."""
    return engine.cumulative_lmme(chain_elements(key, d, n_steps, dtype))
