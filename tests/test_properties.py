"""Property-based tests (hypothesis) for system invariants."""

import jax
import jax.numpy as jnp
import numpy as np
from hyp_compat import given, settings, st  # degrades gracefully w/o hypothesis

from repro.core.goom import Goom, from_goom, to_goom
from repro.core.ops import goom_add, goom_mul, goom_neg, lmme_naive
from repro.sharding.rules import make_rules

FINITE = st.floats(-1e3, 1e3, allow_nan=False).filter(lambda x: abs(x) > 1e-3)


# ---------------------------------------------------------------------------
# GOOM ring properties
# ---------------------------------------------------------------------------
@settings(max_examples=25, deadline=None)
@given(a=FINITE, b=FINITE, c=FINITE)
def test_goom_mul_associative_exact_in_log_space(a, b, c):
    ga, gb, gc = (to_goom(jnp.float32(x)) for x in (a, b, c))
    left = goom_mul(goom_mul(ga, gb), gc)
    right = goom_mul(ga, goom_mul(gb, gc))
    # log-space addition is associative to f32 rounding
    np.testing.assert_allclose(left.log_abs, right.log_abs, rtol=1e-6)
    assert left.sign == right.sign


@settings(max_examples=25, deadline=None)
@given(a=FINITE, b=FINITE)
def test_goom_add_commutative(a, b):
    ga, gb = to_goom(jnp.float32(a)), to_goom(jnp.float32(b))
    x = from_goom(goom_add(ga, gb))
    y = from_goom(goom_add(gb, ga))
    np.testing.assert_allclose(x, y, rtol=1e-6, atol=1e-6)


@settings(max_examples=25, deadline=None)
@given(a=FINITE)
def test_goom_neg_is_involution(a):
    g = to_goom(jnp.float32(a))
    gg = goom_neg(goom_neg(g))
    assert float(gg.log_abs) == float(g.log_abs)
    assert float(gg.sign) == float(g.sign)


@settings(max_examples=15, deadline=None)
@given(
    shift=st.floats(-1e6, 1e6),
    n=st.sampled_from([2, 4, 8]),
)
def test_lmme_shift_equivariance(shift, n):
    """LMME(e^s·A, B) = e^s · LMME(A, B): exact in log space for any shift —
    the property that gives GOOMs their unbounded dynamic range."""
    key = jax.random.PRNGKey(0)
    a = to_goom(jax.random.normal(key, (n, n)))
    b = to_goom(jax.random.normal(jax.random.fold_in(key, 1), (n, n)))
    base = lmme_naive(a, b)
    shifted = lmme_naive(Goom(a.log_abs + shift, a.sign), b)
    np.testing.assert_allclose(shifted.log_abs, base.log_abs + shift,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(shifted.sign, base.sign)


# ---------------------------------------------------------------------------
# sharding-rule invariants
# ---------------------------------------------------------------------------
_AX_NAMES = st.lists(
    st.sampled_from(["embed", "mlp", "heads", "vocab", "batch", None]),
    min_size=1, max_size=4,
)
_DIMS = st.lists(st.sampled_from([1, 2, 3, 4, 6, 8, 16, 28, 64]),
                 min_size=1, max_size=4)


@settings(max_examples=40, deadline=None)
@given(names=_AX_NAMES, dims=_DIMS)
def test_spec_never_reuses_mesh_axis_and_divides(names, dims):
    n = min(len(names), len(dims))
    names, dims = names[:n], dims[:n]
    mesh = jax.sharding.AbstractMesh((4, 2), ("data", "model"))
    rules = make_rules(mesh)
    spec = rules.spec(dims, names)
    sizes = {"data": 4, "model": 2}
    used = []
    for dim, entry in zip(dims, tuple(spec) + (None,) * (n - len(spec))):
        if entry is None:
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        shard = 1
        for a in axes:
            assert a not in used, "mesh axis assigned twice"
            used.append(a)
            shard *= sizes[a]
        assert dim % shard == 0, "uneven sharding in argument mode"


# ---------------------------------------------------------------------------
# data-pipeline invariants
# ---------------------------------------------------------------------------
@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), step=st.integers(0, 100))
def test_stream_deterministic_in_seed_and_step(seed, step):
    from repro.train.data import DataConfig, SyntheticStream

    cfg = DataConfig(task="markov", vocab=16, seq_len=8, global_batch=2,
                     seed=seed)
    a = SyntheticStream(cfg).generate(step)
    b = SyntheticStream(cfg).generate(step)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])


# ---------------------------------------------------------------------------
# optimizer invariant: step with zero grads only applies decay
# ---------------------------------------------------------------------------
@settings(max_examples=10, deadline=None)
@given(lr=st.floats(1e-5, 1e-1))
def test_adamw_zero_grad_moves_only_decayed(lr):
    from repro.train.optimizer import AdamW

    opt = AdamW(lambda s: jnp.asarray(lr), weight_decay=0.1)
    params = {"dense": {"w": jnp.ones(3)}, "norm": {"scale": jnp.ones(3)}}
    grads = jax.tree.map(jnp.zeros_like, params)
    state = opt.init(params)
    new, _ = opt.update(grads, state, params)
    # no-decay leaves unchanged; decayed leaves shrink
    np.testing.assert_array_equal(new["norm"]["scale"], params["norm"]["scale"])
    assert float(new["dense"]["w"][0]) < 1.0
