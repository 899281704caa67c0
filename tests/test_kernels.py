"""Pallas kernel tests: shape/dtype sweeps against the pure-jnp oracles.

Kernels run in interpret mode on CPU (the kernel body executes in Python);
on TPU the same BlockSpecs drive the MXU/VPU directly.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hyp_compat import given, settings, st  # degrades gracefully w/o hypothesis

from repro.core.goom import Goom, closeness, from_goom, to_goom
from repro.core.ops import lmme_naive, lmme_reference
from repro.core.scan import diagonal_scan
from repro.kernels.lmme.ops import lmme_pallas
from repro.kernels.goom_scan.goom_scan import goom_scan_kernel_call


# ---------------------------------------------------------------------------
# LMME kernel
# ---------------------------------------------------------------------------
def assert_goom_close(got, want, *, atol=1e-4, cancel_margin=12.0):
    """Compare GOOM results robustly to catastrophic cancellation.

    Entries whose |sum| is > cancel_margin log-units below their row scale
    are near-cancelling: log|sum| (and even the sign) of such entries is
    ill-conditioned for *any* float method, including the oracle.  Compare
    real-domain values normalized by the row scale, which is well-posed;
    away from cancellation, log-magnitudes and signs must agree tightly
    (``repro.core.goom.closeness``)."""
    rep = closeness(got, want, cancel_margin=cancel_margin)
    assert rep["norm_err"] <= atol, rep
    assert rep["log_ok"] and rep["sign_mismatches"] == 0, rep


@pytest.mark.parametrize("n,d,m", [(8, 8, 8), (16, 32, 8), (128, 128, 128),
                                   (130, 70, 50), (1, 256, 1)])
def test_lmme_pallas_matches_reference_shapes(n, d, m):
    key = jax.random.PRNGKey(0)
    ka, kb = jax.random.split(key)
    a = to_goom(jax.random.normal(ka, (n, d)))
    b = to_goom(jax.random.normal(kb, (d, m)))
    got = lmme_pallas(a, b, interpret=True)
    want = lmme_naive(a, b)
    assert_goom_close(got, want)


@pytest.mark.parametrize("batch", [(), (2,), (2, 3), (0,)])
def test_lmme_pallas_batched(batch):
    key = jax.random.PRNGKey(1)
    ka, kb = jax.random.split(key)
    a = to_goom(jax.random.normal(ka, batch + (16, 24)))
    b = to_goom(jax.random.normal(kb, batch + (24, 8)))
    got = lmme_pallas(a, b, interpret=True)
    want = lmme_naive(a, b)
    # cancellation-aware: raw allclose at 2e-5 flakes on the occasional
    # entry whose |sum| lands far below its row scale
    assert_goom_close(got, want)


def test_lmme_pallas_extreme_magnitudes():
    """Log-magnitudes far outside float range still contract correctly."""
    key = jax.random.PRNGKey(2)
    ka, kb = jax.random.split(key)
    a = to_goom(jax.random.normal(ka, (32, 32)))
    b = to_goom(jax.random.normal(kb, (32, 32)))
    big = Goom(a.log_abs + 30000.0, a.sign)     # exp would overflow any float
    small = Goom(b.log_abs - 45000.0, b.sign)
    got = lmme_pallas(big, small, interpret=True)
    want = lmme_naive(big, small)
    assert bool(jnp.all(jnp.isfinite(got.log_abs)))
    np.testing.assert_allclose(got.log_abs, want.log_abs, rtol=2e-4, atol=2e-4)


def test_lmme_pallas_exact_zero_rows():
    a = to_goom(jnp.zeros((8, 16)))
    b = to_goom(jax.random.normal(jax.random.PRNGKey(3), (16, 8)))
    got = lmme_pallas(a, b, interpret=True)
    assert bool(jnp.all(got.log_abs < -1e29))  # exact zeros stay zero


def test_lmme_pallas_gradients_match_reference():
    key = jax.random.PRNGKey(4)
    ka, kb = jax.random.split(key)
    av = jax.random.normal(ka, (8, 8))
    bv = jax.random.normal(kb, (8, 8))

    def f_pallas(av, bv):
        out = lmme_pallas(to_goom(av), to_goom(bv), interpret=True)
        return jnp.sum(out.log_abs)

    def f_ref(av, bv):
        out = lmme_reference(to_goom(av), to_goom(bv))
        return jnp.sum(out.log_abs)

    ga = jax.grad(f_pallas, argnums=(0, 1))(av, bv)
    gr = jax.grad(f_ref, argnums=(0, 1))(av, bv)
    for x, y in zip(ga, gr):
        np.testing.assert_allclose(x, y, rtol=1e-4, atol=1e-4)


@settings(max_examples=10, deadline=None)
@given(
    n=st.sampled_from([4, 8, 32]),
    d=st.sampled_from([4, 16, 64]),
    scale=st.floats(-100.0, 100.0),
)
def test_lmme_pallas_scale_invariance_property(n, d, scale):
    """LMME(c·A, B) == c ⊙ LMME(A, B) in log space (exactness of rescaling).

    Positive matrices: the property under test is the kernel's online
    rescaling, not cancellation conditioning — with mixed signs an
    eps·|scale| input perturbation can move a near-cancelling sum by an
    unbounded relative amount (that conditioning is covered by
    assert_goom_close in the shape tests)."""
    key = jax.random.PRNGKey(5)
    ka, kb = jax.random.split(key)
    a = to_goom(jnp.abs(jax.random.normal(ka, (n, d))) + 0.1)
    b = to_goom(jnp.abs(jax.random.normal(kb, (d, n))) + 0.1)
    out1 = lmme_pallas(Goom(a.log_abs + scale, a.sign), b, interpret=True)
    out0 = lmme_pallas(a, b, interpret=True)
    np.testing.assert_allclose(out1.log_abs, out0.log_abs + scale,
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_array_equal(out1.sign, out0.sign)


# ---------------------------------------------------------------------------
# goom_scan kernel
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("t,c,bt,bc", [(8, 8, 4, 8), (64, 16, 16, 8),
                                       (256, 512, 256, 512), (32, 8, 8, 8)])
def test_goom_scan_kernel_matches_diagonal_scan(t, c, bt, bc):
    key = jax.random.PRNGKey(7)
    k1, k2, k3 = jax.random.split(key, 3)
    a = to_goom(jnp.exp(-jnp.abs(jax.random.normal(k1, (t, c)))))  # decays
    b = to_goom(jax.random.normal(k2, (t, c)))
    x0 = to_goom(jax.random.normal(k3, (1, c)))

    x_log, x_sign = goom_scan_kernel_call(
        a.log_abs, a.sign, b.log_abs, b.sign, x0.log_abs, x0.sign,
        block_t=bt, block_c=bc, interpret=True,
    )
    want = diagonal_scan(a, b, x0=Goom(x0.log_abs[0], x0.sign[0]))
    np.testing.assert_allclose(x_log, want.log_abs, rtol=2e-4, atol=2e-4)
    np.testing.assert_array_equal(x_sign, want.sign)


def test_goom_scan_kernel_extreme_decay():
    """Decay products spanning thousands of log-units stay finite."""
    t, c = 64, 8
    key = jax.random.PRNGKey(8)
    log_a = -jnp.abs(jax.random.normal(key, (t, c))) * 100.0  # huge decay
    a = Goom(log_a, jnp.ones((t, c)))
    b = to_goom(jax.random.normal(jax.random.PRNGKey(9), (t, c)))
    x0 = to_goom(jnp.ones((1, c)))
    x_log, x_sign = goom_scan_kernel_call(
        a.log_abs, a.sign, b.log_abs, b.sign, x0.log_abs, x0.sign,
        block_t=16, block_c=8, interpret=True,
    )
    assert not bool(jnp.any(jnp.isnan(x_log)))
