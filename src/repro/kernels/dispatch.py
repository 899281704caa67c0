"""Backend registry + resolution for the scan engine.

Two layers:

**Resolution** maps a *requested* backend (user/config intent) to a
*resolved* backend (what actually runs), given the platform and operand
dtype:

  requested        platform   dtype        resolved
  ---------        --------   -----        --------
  auto             tpu        f32          pallas_tpu
  auto             gpu        f32          pallas_gpu     (Triton lowering)
  auto             tpu/gpu    f64/other    xla_reference  (kernels are f32)
  auto             cpu        any          xla_reference
  pallas           tpu        any->f32     pallas_tpu
  pallas           gpu        any->f32     pallas_gpu
  pallas           cpu        any->f32     pallas_interpret
  reference        any        any          xla_reference

Every concrete name may also be requested literally (forced), which is what
the parity tests do: ``pallas_interpret`` runs the TPU-shaped kernels and
``pallas_gpu_interpret`` the GPU-shaped ones, both under ``interpret=True``
on any host (the CI ``gpu-interpret`` job).

**Registry**: implementations are registered per ``(op, backend)`` with
:func:`register_impl` — a factory ``(resolved, BlockConfig) -> callable``.
Adding a backend is one registration per op, not an edit to an enumerated
if-chain; third-party/experimental backends can call
:func:`register_backend` to extend the concrete set.

The platform is read once per process (:func:`current_platform` is cached)
— never per call, and never inside a trace; the engine additionally stamps
it on each config push (see ``repro.core.engine``).

This module owns the kernel-facing callables (padding and chunking live in
``kernels/*/ops.py``); the user-facing API with config overrides is
``repro.core.engine``.  Nothing outside ``kernels/`` should ever pass
``matmul=`` or block sizes.
"""

from __future__ import annotations

import functools
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.goom import Goom
from repro.core.ops import lmme_reference
from repro.core import scan as _scan

from .blocks import BlockConfig, OPS
from .goom_scan import goom_scan_pallas, matrix_scan_pallas
from .lmme import lmme_pallas

__all__ = ["BACKENDS", "CONCRETE_BACKENDS", "OPS", "current_platform",
           "resolve_backend", "register_impl", "register_backend",
           "registered_backends", "registered_impls", "get_impl"]

CONCRETE_BACKENDS = ["xla_reference", "pallas_tpu", "pallas_interpret",
                     "pallas_gpu", "pallas_gpu_interpret"]
BACKENDS = ("auto", "pallas", "reference") + tuple(CONCRETE_BACKENDS)


@functools.lru_cache(maxsize=None)
def current_platform() -> str:
    """The process's default JAX platform ("cpu" / "gpu" / "tpu").

    Cached on first use: backend resolution must not re-read
    ``jax.default_backend()`` per call (it walks the backend registry and,
    under tracing, would make resolution depend on trace-time state)."""
    return jax.default_backend()


def resolve_backend(requested: str, *, platform: Optional[str] = None,
                    dtype=jnp.float32) -> str:
    """Resolve a requested backend name to a concrete registered one.

    ``platform`` defaults to the cached process platform; the engine passes
    the platform it stamped at config-push time, tests pass it explicitly
    to cover the whole resolution matrix without monkeypatching JAX."""
    if requested in ("reference", "xla_reference"):
        return "xla_reference"
    if requested in CONCRETE_BACKENDS:
        return requested  # forced: trust the caller (tests, debugging)
    if platform is None:
        platform = current_platform()
    if requested == "pallas":
        if platform == "tpu":
            return "pallas_tpu"
        if platform == "gpu":
            return "pallas_gpu"
        return "pallas_interpret"
    if requested != "auto":
        raise ValueError(f"unknown backend {requested!r}; one of {BACKENDS}")
    if jnp.dtype(dtype) == jnp.dtype(jnp.float32):
        if platform == "tpu":
            return "pallas_tpu"
        if platform == "gpu":
            return "pallas_gpu"
    return "xla_reference"


# ---------------------------------------------------------------------------
# the registry: (op, backend) -> factory(resolved, BlockConfig) -> callable
# ---------------------------------------------------------------------------
_Factory = Callable[[str, BlockConfig], Callable]
_REGISTRY: Dict[Tuple[str, str], _Factory] = {}


def register_impl(op: str, *backends: str):
    """Decorator: register a factory for ``op`` on each named backend."""

    def deco(factory: _Factory) -> _Factory:
        for backend in backends:
            _REGISTRY[(op, backend)] = factory
        return factory

    return deco


def register_backend(name: str, impls: Dict[str, _Factory]) -> None:
    """Extend the concrete backend set at runtime (experimental backends).

    ``impls`` maps op name -> factory; every engine op must be covered so
    resolution can never land on a hole."""
    missing = set(OPS) - set(impls)
    if missing:
        raise ValueError(f"backend {name!r} missing impls for {sorted(missing)}")
    if name not in CONCRETE_BACKENDS:
        CONCRETE_BACKENDS.append(name)
    for op, factory in impls.items():
        _REGISTRY[(op, name)] = factory


def registered_backends(op: str) -> Tuple[str, ...]:
    """The backends with a registered implementation of ``op``."""
    return tuple(b for (o, b) in _REGISTRY if o == op)


def registered_impls() -> Tuple[Tuple[str, str], ...]:
    """Every registered ``(op, backend)`` pair, sorted.

    This is the enumeration the static analyzer (``repro.analysis``)
    walks: each pair is traced under abstract shapes and its jaxpr
    checked against the GOOM numerical-safety rules."""
    return tuple(sorted(_REGISTRY))


def _pallas_flags(resolved: str) -> Tuple[str, bool]:
    """(kernel variant, interpret?) for a pallas_* backend name."""
    variant = "gpu" if resolved.startswith("pallas_gpu") else "tpu"
    interpret = resolved in ("pallas_interpret", "pallas_gpu_interpret")
    return variant, interpret


_PALLAS = ("pallas_tpu", "pallas_interpret", "pallas_gpu",
           "pallas_gpu_interpret")


def _launch_kw(blocks: BlockConfig, variant: str) -> dict:
    return {} if variant == "tpu" else {
        "num_warps": blocks.num_warps or 4,
        "num_stages": blocks.num_stages or 1,
    }


# -- lmme -------------------------------------------------------------------
@register_impl("lmme", "xla_reference")
def _lmme_ref(resolved: str, blocks: BlockConfig):
    return lmme_reference


@register_impl("lmme", *_PALLAS)
def _lmme_pallas(resolved: str, blocks: BlockConfig):
    variant, interpret = _pallas_flags(resolved)
    kw = _launch_kw(blocks, variant)

    def f(a: Goom, b: Goom) -> Goom:
        return lmme_pallas(
            a, b,
            block_n=blocks.block_n, block_m=blocks.block_m,
            block_d=blocks.block_d,
            interpret=interpret, variant=variant, **kw,
        )

    return f


# -- diagonal scan ----------------------------------------------------------
def _broadcast_goom(g: Goom, shape) -> Goom:
    return Goom(jnp.broadcast_to(g.log_abs, shape),
                jnp.broadcast_to(g.sign, shape))


@register_impl("diagonal_scan", "xla_reference")
def _diagonal_scan_ref(resolved: str, blocks: BlockConfig):
    def ref(a: Goom, b: Goom, x0: Optional[Goom] = None) -> Goom:
        # match the kernel wrappers: a/b broadcast to a common shape
        # (associative_scan itself requires identical operand shapes)
        shape = jnp.broadcast_shapes(a.shape, b.shape)
        x0b = None if x0 is None else _broadcast_goom(x0, shape[1:])
        return _scan.diagonal_scan(
            _broadcast_goom(a, shape), _broadcast_goom(b, shape), x0b)

    return ref


@register_impl("diagonal_scan", *_PALLAS)
def _diagonal_scan_pallas(resolved: str, blocks: BlockConfig):
    variant, interpret = _pallas_flags(resolved)
    kw = _launch_kw(blocks, variant)

    def f(a: Goom, b: Goom, x0: Optional[Goom] = None) -> Goom:
        return goom_scan_pallas(
            a, b, x0,
            block_t=blocks.block_t, block_c=blocks.block_c,
            algo=blocks.algo or "auto",
            interpret=interpret, variant=variant, **kw,
        )

    return f


# -- matrix scan ------------------------------------------------------------
def _matrix_ref_chunked(a: Goom, b: Goom, x0: Optional[Goom], chunk: int) -> Goom:
    """Reference matrix scan, chunked over time for bounded memory.

    Within a chunk the full O(log L) associative scan runs; the entering
    state is carried sequentially across chunks (same recurrence algebra as
    the fused kernel's carry, so results match the plain reference).
    """
    t = b.shape[0]
    batch = jnp.broadcast_shapes(a.shape[1:-2], b.shape[1:-2])
    a = _broadcast_goom(a, (t,) + batch + a.shape[-2:])
    b = _broadcast_goom(b, (t,) + batch + b.shape[-2:])
    if x0 is not None:
        x0 = _broadcast_goom(x0, batch + b.shape[-2:])
    if t <= chunk or t % chunk:
        return _scan.matrix_scan(a, b, x0, matmul=lmme_reference)
    nc = t // chunk

    def resh(g: Goom) -> Goom:
        return Goom(g.log_abs.reshape((nc, chunk) + g.shape[1:]),
                    g.sign.reshape((nc, chunk) + g.shape[1:]))

    if x0 is None:
        x0 = Goom(jnp.full(b.shape[1:], -jnp.inf, jnp.float32),
                  jnp.ones(b.shape[1:], jnp.float32))

    @jax.checkpoint
    def outer(carry: Goom, ab):
        a_k, b_k = ab
        states = _scan.matrix_scan(a_k, b_k, carry, matmul=lmme_reference)
        return states[-1], states

    _, states_c = jax.lax.scan(outer, x0, (resh(a), resh(b)))
    return Goom(states_c.log_abs.reshape((t,) + states_c.shape[2:]),
                states_c.sign.reshape((t,) + states_c.shape[2:]))


@register_impl("matrix_scan", "xla_reference")
def _matrix_scan_ref(resolved: str, blocks: BlockConfig):
    chunk = blocks.block_t or 128

    def ref(a: Goom, b: Goom, x0: Optional[Goom] = None) -> Goom:
        return _matrix_ref_chunked(a, b, x0, chunk)

    return ref


@register_impl("matrix_scan", *_PALLAS)
def _matrix_scan_pallas(resolved: str, blocks: BlockConfig):
    variant, interpret = _pallas_flags(resolved)
    kw = _launch_kw(blocks, variant)

    def f(a: Goom, b: Goom, x0: Optional[Goom] = None) -> Goom:
        return matrix_scan_pallas(
            a, b, x0,
            block_t=blocks.block_t, algo=blocks.algo or "auto",
            interpret=interpret, variant=variant, **kw,
        )

    return f


# -- cumulative lmme --------------------------------------------------------
@register_impl("cumulative_lmme", "xla_reference")
def _cumulative_lmme_ref(resolved: str, blocks: BlockConfig):
    def ref(a: Goom) -> Goom:
        return _scan.cumulative_lmme(a, matmul=lmme_reference)

    return ref


@register_impl("cumulative_lmme", *_PALLAS)
def _cumulative_lmme_pallas(resolved: str, blocks: BlockConfig):
    variant, interpret = _pallas_flags(resolved)
    kw = _launch_kw(blocks, variant)

    def f(a: Goom) -> Goom:
        # A_t···A_1 == matrix recurrence with B = 0 and X_0 = I: the fused
        # kernel's zero-B path computes it without ever materializing a B
        # operand (b=None below — only the (d, d) identity is built).
        d = a.shape[-1]
        eye = Goom(
            jnp.where(jnp.eye(d, dtype=bool), 0.0, -jnp.inf).astype(jnp.float32),
            jnp.ones((d, d), jnp.float32),
        )
        return matrix_scan_pallas(
            a, None, eye,
            block_t=blocks.block_t, algo=blocks.algo or "auto",
            interpret=interpret, variant=variant, **kw,
        )

    return f


# ---------------------------------------------------------------------------
# impl lookup
# ---------------------------------------------------------------------------
def _make(op: str, resolved: str, blocks: Optional[BlockConfig],
          shapes: Optional[Tuple[int, ...]]):
    if blocks is None:
        from . import autotune  # lazy: autotune imports dispatch for timing

        blocks = autotune.cached_blocks(op, resolved, shapes)
    try:
        factory = _REGISTRY[(op, resolved)]
    except KeyError:
        raise KeyError(
            f"no implementation registered for op {op!r} on backend "
            f"{resolved!r}; registered: {registered_backends(op)}") from None
    return factory(resolved, blocks), blocks


def get_impl(op: str, resolved: str, blocks: Optional[BlockConfig] = None,
             shard=None, shapes: Optional[Tuple[int, ...]] = None):
    """Return the callable implementing ``op`` on the resolved backend.

    ``blocks`` (a :class:`BlockConfig`) pins the tiling; ``None`` consults
    the persisted autotune cache for ``(op, resolved, device_kind,
    shape-bucket(shapes))`` and falls back to the static defaults — this is
    how autotuned winners reach every call site without any caller naming
    a block size.

    ``shard`` (a ``repro.kernels.sharded.ShardSpec`` or None) selects the
    sequence-sharded multi-device path: the local implementation above runs
    per device inside ``shard_map``, with a cross-shard LMME-monoid carry
    combine stitching the time shards together.  ``lmme`` is not a scan: it
    runs inside a shard_map of its own, split over its leading batch dim
    (a Pallas kernel cannot be partitioned automatically).  Code already
    inside a shard body must take the local implementation (``shard=None``).
    """
    base, blocks = _make(op, resolved, blocks, shapes)
    if shard is None:
        return base
    from . import sharded  # lazy: keeps single-device imports collective-free

    if op == "lmme":
        def f(a, b):
            return sharded.seq_sharded_lmme(a, b, spec=shard, local_lmme=base)

        return f

    if op == "diagonal_scan":
        def f(a, b, x0=None):
            return sharded.seq_sharded_diagonal_scan(
                a, b, x0, spec=shard, local_diagonal_scan=base)

        return f
    lmme_impl, _ = _make("lmme", resolved, None, None)
    if op == "matrix_scan":
        cum, _ = _make("cumulative_lmme", resolved, blocks, None)

        def f(a, b, x0=None):
            return sharded.seq_sharded_matrix_scan(
                a, b, x0, spec=shard, local_matrix_scan=base,
                local_cumulative_lmme=cum, lmme=lmme_impl)

        return f
    assert op == "cumulative_lmme", op

    def f(a):
        return sharded.seq_sharded_cumulative_lmme(
            a, spec=shard, local_cumulative_lmme=base, lmme=lmme_impl)

    return f
