"""The Pallas-TPU kernels compile for a TPU v5e, at the model's widths.

Nothing here runs on a chip: each case compiles the ``pallas_tpu`` path of
an engine op against a described (not attached) ``v5e:2x2`` topology, so
Mosaic refuses here what it would refuse on the chip -- an op it cannot
lower, a block that overflows VMEM -- at no chip time.  Shapes are those of
goom-rnn-124m (48 heads of 16x16) and the paper's long matrix chains.

The topology is described inside a fixture, never while a module is
imported: only one process may load the TPU compiler library, and the
tests run under several xdist workers.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import engine
from repro.core.goom import Goom

H, D = 48, 16  # goom-rnn-124m: d_model 768 = 48 heads x head_dim 16
V5E_HBM_BYTES = 16 * 2**30


def _lmme(al, asn, bl, bsn):
    return engine.lmme(Goom(al, asn), Goom(bl, bsn))


def _diagonal_scan(al, asn, bl, bsn):
    return engine.diagonal_scan(Goom(al, asn), Goom(bl, bsn))


def _matrix_scan(al, asn, bl, bsn):
    return engine.matrix_scan(Goom(al, asn), Goom(bl, bsn))


def _cumulative_lmme(al, asn):
    return engine.cumulative_lmme(Goom(al, asn))


def _pair(a_shape, b_shape=None):
    return [a_shape, a_shape] + ([b_shape, b_shape] if b_shape else [])


CASES = {
    # GOOM layer B.u LMME: one 64-token prefill chunk, and a decode step
    # over 8 slots -- (H, d, d) x (S, B, H, d, 1)
    "lmme_prefill_chunk": (_lmme, _pair((H, D, D), (64, 1, H, D, 1))),
    "lmme_decode": (_lmme, _pair((H, D, D), (1, 8, H, D, 1))),
    "diagonal_scan": (_diagonal_scan, _pair((4096, 768), (4096, 768))),
    # sequence-sharded GOOM layer: batch 8 in the state columns
    "matrix_scan": (_matrix_scan, _pair((256, H, D, D), (256, H, D, 8))),
    "cumulative_lmme": (_cumulative_lmme, _pair((8192, D, D))),
}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler library, or it is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """An entry compiled without a chip cannot be read back: keep these
    compiles out of the persistent cache."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.mark.parametrize("case", sorted(CASES))
def test_pallas_tpu_compiles_for_v5e(case, one_chip, no_compile_cache):
    fn, shapes = CASES[case]
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
    with engine.use_backend("pallas_tpu"):
        compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < V5E_HBM_BYTES
