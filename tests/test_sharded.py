"""Sequence-sharded scan parity: multi-device engine vs single-device ref.

The multi-device cases need 8 host devices:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m pytest tests/test_sharded.py

(the CI multi-device job exports exactly that); without the flag they skip
and only the single-device fallback/config tests run.  Parity bars follow
the engine suite: strict 1e-5 log-space relative tolerance on well-posed
(positive-operand) problems — including the e±200 dynamic-range case —
and a looser bar where signed cancellation makes reassociation visible.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine
from repro.core.goom import Goom, to_goom

KEY = jax.random.PRNGKey(0)
NDEV = len(jax.devices())

needs8 = pytest.mark.skipif(
    NDEV < 8, reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")


def mesh18():
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:8]).reshape(1, 8), ("data", "seq"))


def ref_and_sharded(fn, *args):
    with engine.use_backend("xla_reference"):
        want = fn(*args)
        with engine.use_mesh(mesh18()):
            assert engine.active_seq_shards() == 8
            got = fn(*args)
    return want, got


def assert_log_close(got, want, rtol=1e-5):
    w = np.asarray(want.log_abs)
    g = np.asarray(got.log_abs)
    finite = np.isfinite(w)
    assert np.array_equal(np.isfinite(g), finite)
    rel = np.abs(g[finite] - w[finite]) / np.maximum(np.abs(w[finite]), 1.0)
    assert float(rel.max()) <= rtol, float(rel.max())


# ---------------------------------------------------------------------------
# single-device semantics (run everywhere)
# ---------------------------------------------------------------------------
def test_no_mesh_means_single_device():
    assert engine.active_seq_shards() == 1
    with engine.use_backend("reference"):
        assert engine.active_seq_shards() == 1


def test_explicit_shards_without_mesh_raises():
    with engine.use_backend("auto", seq_shards=4):
        with pytest.raises(ValueError, match="no mesh"):
            engine.active_seq_shards()


def test_use_mesh_none_disables():
    with engine.use_mesh(None):
        assert engine.active_seq_shards() == 1


def test_scan_logical_axes_in_rules():
    from jax.sharding import Mesh

    from repro.sharding.rules import make_rules

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    rules = make_rules(mesh)
    assert rules.mesh_axes_for("scan_seq") == ()          # opt-in: off
    assert rules.mesh_axes_for("scan_batch") == ("data",)
    rules = make_rules(mesh, overrides={"scan_seq": "model"})
    assert rules.mesh_axes_for("scan_seq") == ("model",)


def test_one_sized_seq_axis_falls_back():
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "seq"))
    with engine.use_mesh(mesh, seq_axis="seq"):
        assert engine.active_seq_shards() == 1
        a = to_goom(jax.random.normal(KEY, (6, 3, 3)) * 0.5)
        out = engine.cumulative_lmme(a)  # plain local path
        assert out.shape == (6, 3, 3)


def test_use_mesh_defaults_to_seq_axis_name():
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("seq", "other"))
    with engine.use_mesh(mesh):
        assert engine.get_config().seq_axis == "seq"
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    with engine.use_mesh(mesh):
        assert engine.get_config().seq_axis == "model"


@pytest.mark.parametrize("b_shape", [(4, 2, 3, 4, 1), (4, 1)])
def test_seq_sharded_lmme_plumbing(b_shape):
    """The shard_map around a kernel LMME: a leading batch dim splits over
    the sequence axis; without one every device takes the whole product."""
    from jax.sharding import Mesh

    from repro.core.ops import lmme_reference
    from repro.kernels.sharded import ShardSpec, seq_sharded_lmme

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "seq"))
    k1, k2 = jax.random.split(KEY)
    a = to_goom(jax.random.normal(k1, (3, 4, 4) if len(b_shape) > 2 else (4, 4)))
    b = to_goom(jax.random.normal(k2, b_shape))
    got = seq_sharded_lmme(a, b, spec=ShardSpec(mesh, "seq"),
                           local_lmme=lmme_reference)
    want = lmme_reference(a, b)
    np.testing.assert_array_equal(got.log_abs, want.log_abs)
    np.testing.assert_array_equal(got.sign, want.sign)


# ---------------------------------------------------------------------------
# multi-device parity (the acceptance bars)
# ---------------------------------------------------------------------------
@needs8
def test_matrix_scan_sharded_parity_batched_x0():
    k1, k2, k3 = jax.random.split(KEY, 3)
    a = to_goom(jnp.abs(jax.random.normal(k1, (64, 2, 4, 4))) * 0.6 + 0.05)
    b = to_goom(jnp.abs(jax.random.normal(k2, (64, 2, 4, 2))) * 0.6 + 0.05)
    x0 = to_goom(jnp.abs(jax.random.normal(k3, (2, 4, 2))) + 0.1)
    want, got = ref_and_sharded(engine.matrix_scan, a, b, x0)
    assert_log_close(got, want, rtol=1e-5)
    np.testing.assert_array_equal(got.sign, want.sign)


@needs8
def test_matrix_scan_sharded_parity_signed():
    k1, k2, k3 = jax.random.split(jax.random.fold_in(KEY, 1), 3)
    a = to_goom(jax.random.normal(k1, (32, 4, 4)) * 0.6)
    b = to_goom(jax.random.normal(k2, (32, 4, 2)) * 0.6)
    x0 = to_goom(jax.random.normal(k3, (4, 2)))
    want, got = ref_and_sharded(engine.matrix_scan, a, b, x0)
    # signed data: cancellation-adjacent elements reassociate (~1e-4)
    assert_log_close(got, want, rtol=1e-3)
    np.testing.assert_array_equal(got.sign, want.sign)


@needs8
def test_matrix_scan_sharded_non_divisible_length_pads():
    k1, k2 = jax.random.split(KEY)
    a = to_goom(jnp.abs(jax.random.normal(k1, (13, 3, 3))) + 0.1)
    b = to_goom(jnp.abs(jax.random.normal(k2, (13, 3, 1))) + 0.1)
    want, got = ref_and_sharded(engine.matrix_scan, a, b, None)
    assert got.shape == (13, 3, 1)
    assert_log_close(got, want, rtol=1e-5)


@needs8
def test_matrix_scan_shorter_than_mesh_falls_back_local():
    k1, k2 = jax.random.split(KEY)
    a = to_goom(jnp.abs(jax.random.normal(k1, (5, 3, 3))) + 0.1)
    b = to_goom(jnp.abs(jax.random.normal(k2, (5, 3, 1))) + 0.1)
    want, got = ref_and_sharded(engine.matrix_scan, a, b, None)
    assert_log_close(got, want, rtol=1e-5)


@needs8
def test_cumulative_lmme_sharded_parity_e200():
    """Acceptance bar: e±200 per-step magnitudes, 1e-5 log-space parity."""
    k1, k4 = jax.random.split(KEY)
    t, d = 48, 4
    shifts = 200.0 * jax.random.choice(k4, jnp.array([-1.0, 1.0]), (t, 1, 1))
    a0 = to_goom(jnp.abs(jax.random.normal(k1, (t, d, d))) + 0.1)
    a = Goom(a0.log_abs + shifts, a0.sign)
    want, got = ref_and_sharded(engine.cumulative_lmme, a)
    assert float(jnp.max(jnp.abs(want.log_abs))) > 200.0  # genuinely extreme
    assert_log_close(got, want, rtol=1e-5)


@needs8
def test_matrix_scan_sharded_parity_e200():
    k1, k2, k3, k4 = jax.random.split(KEY, 4)
    t, d, m = 24, 4, 2
    shifts = 200.0 * jax.random.choice(k4, jnp.array([-1.0, 1.0]), (t, 1, 1))
    a0 = to_goom(jnp.abs(jax.random.normal(k1, (t, d, d))) + 0.1)
    a = Goom(a0.log_abs + shifts, a0.sign)
    b = to_goom(jnp.abs(jax.random.normal(k2, (t, d, m))) + 0.1)
    x0 = to_goom(jnp.abs(jax.random.normal(k3, (d, m))) + 0.1)
    want, got = ref_and_sharded(engine.matrix_scan, a, b, x0)
    assert float(jnp.max(jnp.abs(want.log_abs))) > 200.0
    assert_log_close(got, want, rtol=1e-5)


@needs8
def test_diagonal_scan_sharded_parity():
    k1, k2, k3 = jax.random.split(KEY, 3)
    a = to_goom(jnp.exp(-jnp.abs(jax.random.normal(k1, (48, 2, 5)))))
    b = to_goom(jax.random.normal(k2, (48, 2, 5)))
    x0 = to_goom(jax.random.normal(k3, (2, 5)))
    want, got = ref_and_sharded(engine.diagonal_scan, a, b, x0)
    assert_log_close(got, want, rtol=1e-5)
    np.testing.assert_array_equal(got.sign, want.sign)


@needs8
def test_diagonal_scan_sharded_no_x0_odd_length():
    k1, k2 = jax.random.split(KEY)
    a = to_goom(jnp.exp(-jnp.abs(jax.random.normal(k1, (19, 3)))))
    b = to_goom(jax.random.normal(k2, (19, 3)))
    want, got = ref_and_sharded(engine.diagonal_scan, a, b, None)
    assert got.shape == (19, 3)
    assert_log_close(got, want, rtol=1e-5)


@needs8
def test_sharded_gradients_match_reference():
    k1, k2, k3 = jax.random.split(KEY, 3)
    t, d, m = 16, 3, 2
    a = to_goom(jnp.abs(jax.random.normal(k1, (t, d, d))) + 0.1)
    b = to_goom(jnp.abs(jax.random.normal(k2, (t, d, m))) + 0.1)
    x0 = to_goom(jnp.abs(jax.random.normal(k3, (d, m))) + 0.1)

    def loss(al, bl):
        out = engine.matrix_scan(Goom(al, a.sign), Goom(bl, b.sign), x0)
        return jnp.sum(jnp.where(jnp.isfinite(out.log_abs), out.log_abs, 0.0))

    with engine.use_backend("xla_reference"):
        gr = jax.jit(jax.grad(loss, argnums=(0, 1)))(a.log_abs, b.log_abs)
        with engine.use_mesh(mesh18()):
            gs = jax.jit(jax.grad(loss, argnums=(0, 1)))(a.log_abs, b.log_abs)
    for x, y in zip(gs, gr):
        assert np.all(np.isfinite(x))
        np.testing.assert_allclose(x, y, rtol=1e-4, atol=1e-4)


@needs8
def test_selective_reset_scan_sharded_parity_no_resets():
    """With a never-firing threshold (|cos| can't exceed 1) the reset monoid
    degenerates to exact matrix products — sharded must match strictly."""
    from repro.core.scan import colinearity_select, orthonormal_reset

    mats = to_goom(jax.random.normal(jax.random.fold_in(KEY, 9), (16, 3, 3)) * 2.0)
    with engine.use_backend("xla_reference"):
        want, wflags = engine.selective_reset_scan(
            mats, colinearity_select(1.01), orthonormal_reset())
        with engine.use_mesh(mesh18()):
            got, gflags = engine.selective_reset_scan(
                mats, colinearity_select(1.01), orthonormal_reset())
    assert not np.any(wflags) and not np.any(gflags)
    assert_log_close(got, want, rtol=1e-4)


@needs8
def test_selective_reset_scan_sharded_with_resets_stays_finite():
    """When resets DO fire, the reset *positions* are bracketing-dependent
    (the select condition looks at interim compounds, and the sharded tree
    materializes different ones) — so assert behavior, not bit-parity:
    resets fire, states stay finite, no overflow."""
    from repro.core.scan import colinearity_select, orthonormal_reset

    mats = to_goom(jax.random.normal(jax.random.fold_in(KEY, 9), (16, 3, 3)) * 2.0)
    with engine.use_mesh(mesh18(), backend="xla_reference"):
        got, gflags = engine.selective_reset_scan(
            mats, colinearity_select(0.995), orthonormal_reset())
    assert bool(np.any(gflags))  # the data does trigger resets
    assert not np.any(np.isnan(got.log_abs))
    assert not np.any(np.isposinf(got.log_abs))


@needs8
def test_sharded_under_jit_and_batch_axes():
    """The train-step shape: engine resolves inside jit, batch dim sharded
    over "data" via the scan_batch rule path (use_mesh batch_axis)."""
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("data", "seq"))
    k1, k2 = jax.random.split(KEY)
    a = to_goom(jnp.abs(jax.random.normal(k1, (32, 4, 3, 3))) * 0.5 + 0.05)
    b = to_goom(jnp.abs(jax.random.normal(k2, (32, 4, 3, 1))) * 0.5 + 0.05)
    with engine.use_backend("xla_reference"):
        want = engine.matrix_scan(a, b, None)
        with engine.use_mesh(mesh, seq_axis="seq", batch_axis="data"):
            assert engine.active_seq_shards() == 4
            got = jax.jit(engine.matrix_scan)(a, b)
    assert_log_close(got, want, rtol=1e-5)


@needs8
def test_sharded_local_pallas_interpret_matches_reference():
    """The local scans inside shard bodies can be the Pallas kernels."""
    k1, k2 = jax.random.split(KEY)
    a = to_goom(jnp.abs(jax.random.normal(k1, (16, 2, 2))) + 0.1)
    b = to_goom(jnp.abs(jax.random.normal(k2, (16, 2, 1))) + 0.1)
    with engine.use_backend("xla_reference"):
        want = engine.matrix_scan(a, b, None)
    with engine.use_backend("pallas_interpret"):
        with engine.use_mesh(mesh18()):
            got = engine.matrix_scan(a, b, None)
    assert_log_close(got, want, rtol=1e-4)


@needs8
@pytest.mark.parametrize("s", [16, 3])
def test_lmme_under_mesh_matches_local(s):
    """engine.lmme with sequence shards active (the GOOM layer's B·u):
    split over the sequence axis when S divides, replicated otherwise."""
    k1, k2 = jax.random.split(KEY)
    a = to_goom(jax.random.normal(k1, (3, 4, 4)))
    b = to_goom(jax.random.normal(k2, (s, 2, 3, 4, 1)))
    want, got = ref_and_sharded(engine.lmme, a, b)
    assert_log_close(got, want)


@needs8
@pytest.mark.parametrize("a_shape,b_shape,spec", [
    ((3, 4, 4), (16, 2, 3, 4, 1), ("seq", "data")),     # B·u: S over seq, B over data
    ((2, 3, 4, 4), (16, 2, 3, 4, 1), ("seq", "data")),  # A carries the batch dim
    ((16, 1, 3, 4, 4), (16, 2, 3, 4, 1), ("seq", "data")),  # A broadcasts over B
    ((3, 4, 4), (6, 3, 3, 4, 1), (None, None)),         # neither divides
])
def test_lmme_under_data_seq_mesh(a_shape, b_shape, spec):
    """On a data x seq mesh the B·u LMME splits time over 'seq' and the
    batch over 'data': no device computes another replica's batch."""
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4), ("data", "seq"))
    k1, k2 = jax.random.split(KEY)
    a = to_goom(jax.random.normal(k1, a_shape))
    b = to_goom(jax.random.normal(k2, b_shape))
    with engine.use_backend("xla_reference"):
        want = engine.lmme(a, b)
        with engine.use_mesh(mesh, seq_axis="seq", batch_axis="data"):
            got = jax.jit(engine.lmme)(a, b)
    assert_log_close(got, want)
    sharding = got.log_abs.sharding
    assert sharding.is_equivalent_to(
        jax.sharding.NamedSharding(mesh, P(*spec)), got.ndim)


def test_host_mesh_takes_the_first_n_devices():
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(n_devices=1)
    assert dict(mesh.shape) == {"data": 1, "model": 1}
    assert mesh.devices.flat[0] == jax.devices()[0]
    if NDEV >= 8:
        mesh = make_host_mesh(seq_shards=4, n_devices=8)
        assert dict(mesh.shape) == {"data": 2, "model": 4}
