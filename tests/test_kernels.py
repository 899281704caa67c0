"""Pallas kernel tests: shape/dtype sweeps against the pure-jnp oracles.

Kernels run in interpret mode on CPU (the kernel body executes in Python);
on TPU the same BlockSpecs drive the MXU/VPU directly.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hyp_compat import given, settings, st  # degrades gracefully w/o hypothesis

from repro.core.goom import Goom, closeness, from_goom, to_goom
from repro.core.ops import lmme_naive, lmme_reference
from repro.core.scan import diagonal_scan
from repro.kernels.lmme.ops import _lmme_planes, lmme_pallas
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


# A layer's per-head (H, 16, 16) matrices against one column per slot and
# position: where `a` is shared over batch dims, they fold into b's columns.
FOLD_H = 4
FOLD_CASES = {
    "decode": ((FOLD_H, 16, 16), (1, 32, FOLD_H, 16, 1)),
    "prefill_chunk": ((FOLD_H, 16, 16), (64, 1, FOLD_H, 16, 1)),
    "train": ((FOLD_H, 16, 16), (8, 2, FOLD_H, 16, 1)),
    "tail": ((FOLD_H, 16, 16), (1, 1, FOLD_H, 16, 1)),  # nothing to fold
    "no_broadcast": ((3, FOLD_H, 16, 16), (3, FOLD_H, 16, 1)),
}


def _fold_operands(case, scale, key=7):
    a_shape, b_shape = FOLD_CASES[case]
    ka, kb, ks = jax.random.split(jax.random.PRNGKey(key), 3)
    a = to_goom(jax.random.normal(ka, a_shape))
    b = to_goom(jax.random.normal(kb, b_shape))
    # e+scale on a, and e+-scale column by column on b: every column keeps
    # its own scale through the fold
    col = scale * jax.random.rademacher(ks, b_shape[:-2] + (1, b_shape[-1]),
                                        jnp.float32)
    return Goom(a.log_abs + scale, a.sign), Goom(b.log_abs + col, b.sign)


def _pallas_grids(jaxpr):
    """The grids of every pallas_call in ``jaxpr``, nested jaxprs included."""
    grids = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            grids.append(tuple(eqn.params["grid_mapping"].grid))
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    grids += _pallas_grids(sub)
    return grids


def _lmme_unfolded(a, b):
    """The wrapper without the fold: every plane copied over the joint
    batch, one padded problem per batch index, at the blocks ``lmme_pallas``
    picks for n = d = 16 and m = 1."""
    batch = jnp.broadcast_shapes(a.shape[:-2], b.shape[:-2])
    planes = [jnp.broadcast_to(x, batch + x.shape[-2:]).astype(jnp.float32)
              for x in (a.log_abs, a.sign, b.log_abs, b.sign)]
    return Goom(*_lmme_planes(*planes, 16, 128, 128, 4, 2, True, "tpu"))


@pytest.mark.parametrize("scale", [0.0, 200.0])
@pytest.mark.parametrize("case", sorted(FOLD_CASES))
def test_lmme_pallas_fold_matches_unfolded(case, scale):
    a, b = _fold_operands(case, scale)
    batch = jnp.broadcast_shapes(a.shape[:-2], b.shape[:-2])
    got = lmme_pallas(a, b, interpret=True)
    unfolded = _lmme_unfolded(a, b)
    assert got.shape == batch + (a.shape[-2], b.shape[-1])
    np.testing.assert_array_equal(got.log_abs, unfolded.log_abs)
    np.testing.assert_array_equal(got.sign, unfolded.sign)
    assert_goom_close(got, lmme_reference(a, b))


def test_lmme_pallas_fold_gradients_match_reference():
    key = jax.random.PRNGKey(8)
    ka, kb = jax.random.split(key)
    av = jax.random.normal(ka, (2, 16, 16))
    bv = jax.random.normal(kb, (3, 2, 2, 16, 1))

    def loss(lmme):
        return lambda av, bv: jnp.sum(lmme(to_goom(av), to_goom(bv)).log_abs)

    ga = jax.grad(loss(functools.partial(lmme_pallas, interpret=True)),
                  argnums=(0, 1))(av, bv)
    gr = jax.grad(loss(lmme_reference), argnums=(0, 1))(av, bv)
    for x, y in zip(ga, gr):
        np.testing.assert_allclose(x, y, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("case,grid", [("decode", (FOLD_H, 1, 1, 1)),
                                       ("tail", (FOLD_H, 1, 1, 1)),
                                       ("train", (FOLD_H, 1, 1, 1))])
def test_lmme_fold_launches_one_problem_per_head(case, grid):
    """The kernel grid's batch extent is H, not S*B*H: the fold engages.
    With ``a`` materialised over the batch it is one problem per copy."""
    from repro.core import engine

    a, b = _fold_operands(case, 0.0)
    batch = jnp.broadcast_shapes(a.shape[:-2], b.shape[:-2])
    a_full = Goom(jnp.broadcast_to(a.log_abs, batch + a.shape[-2:]),
                  jnp.broadcast_to(a.sign, batch + a.shape[-2:]))
    with engine.use_backend("pallas_interpret"):
        folded = jax.make_jaxpr(engine.lmme)(a, b).jaxpr
        unfolded = jax.make_jaxpr(engine.lmme)(a_full, b).jaxpr
    assert _pallas_grids(folded) == [grid]
    assert _pallas_grids(unfolded) == [(int(np.prod(batch)), 1, 1, 1)]


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
