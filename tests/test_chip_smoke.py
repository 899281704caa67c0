"""The float64 host references and closeness reports of ``chip_smoke.py``
(the witnesses its chip checks hold kernels and the XLA reference to),
checked here against the XLA reference on small inputs."""

import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine
from repro.core.chains import chain_elements, goom_chain_parallel
from repro.core.goom import Goom, to_goom

_spec = importlib.util.spec_from_file_location(
    "chip_smoke", Path(__file__).resolve().parents[1] / "chip_smoke.py")
cs = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(cs)

KEY = jax.random.PRNGKey(0)


def _ref(fn, *args):
    with engine.use_backend("xla_reference"):
        return jax.jit(fn)(*args)


def _case(name):
    k1, k2 = jax.random.split(KEY)
    if name == "diagonal_scan":
        args = (cs._decays(k1, (64, 8)), cs._normal(k2, (64, 8)))
        return engine.diagonal_scan, args, cs.host_diagonal_scan, False
    if name == "matrix_scan":
        args = (cs._positive(k1, (64, 3, 16, 16)), cs._positive(k2, (64, 3, 16, 4)))
        return engine.matrix_scan, args, cs.host_matrix_scan, True
    args = (cs._positive(k1, (64, 2, 16, 16)),)  # batched
    return engine.cumulative_lmme, args, cs.host_prefix_products, False


@pytest.mark.parametrize("name", ["diagonal_scan", "matrix_scan",
                                  "cumulative_lmme"])
def test_host_float64_recurrences_match_reference(name):
    fn, args, host, swap = _case(name)
    exact = host(*args)
    ok, rep = cs.against_f64({"reference": _ref(fn, *args)}, exact, swap, 1e-5)
    assert ok, rep
    assert rep["f32_floor"] <= rep["reference"] + 1e-7


@pytest.mark.parametrize("name", ["diagonal_scan", "matrix_scan",
                                  "cumulative_lmme"])
def test_dropped_carry_fails_against_float64(name):
    """A control: the second half scanned without the first half's carry
    is far outside any bound the smoke run sets."""
    fn, args, host, swap = _case(name)
    cut = args[0].shape[0] // 2
    lo = _ref(fn, *[x[:cut] for x in args])
    hi = _ref(fn, *[x[cut:] for x in args])
    out = Goom(jnp.concatenate([lo.log_abs, hi.log_abs]),
               jnp.concatenate([lo.sign, hi.sign]))
    ok, rep = cs.against_f64({"dropped": out}, host(*args), swap, 1e-2)
    assert not ok and rep["dropped"] > 0.1, rep


def test_host_chain_matches_reference_and_keeps_its_scale():
    """The chain's float64 reference carries its scale apart: at 2,048
    steps the product is ~e^2700, far past float64's range."""
    n = 2048
    exact = cs.host_prefix_products(chain_elements(KEY, 4, n - 1))
    assert float(np.max(exact.log_abs[-1])) > 710
    with engine.use_backend("xla_reference"):
        got = jax.jit(goom_chain_parallel, static_argnums=(1, 2))(KEY, 4, n - 1)
    rep = cs.chain_close(got, exact)
    assert rep["pass"], rep
    assert rep["max_scale_rel_err"] <= 1e-5


def test_goom_close_reports_log_and_sign_checks():
    want = to_goom(jnp.array([[1.0, -2.0, 3e-9]]))
    flipped = Goom(want.log_abs, want.sign.at[0, 1].set(1.0))
    assert cs.goom_close(want, want)["pass"]
    rep = cs.goom_close(flipped, want)
    assert not rep["pass"] and rep["sign_mismatches"] == 1
