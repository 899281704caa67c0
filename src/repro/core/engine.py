"""Scan engine: one API for every GOOM recurrence, with backend dispatch.

Every model, experiment, benchmark, and serving path routes its recurrences
through this module.  Callers never pass ``matmul=`` or block sizes — they
pick a *backend* (usually implicitly, via ``auto``) and the engine selects
the implementation, handling padding/unpadding and chunking internally.

Public ops
----------
  ``lmme(a, b)``                     log-matmul-exp (paper eq. 9)
  ``diagonal_scan(a, b, x0)``        x_t = a_t ⊙ x_{t-1} ⊕ b_t
  ``matrix_scan(a, b, x0)``          X_t = A_t X_{t-1} ⊕ B_t   (fused kernel)
  ``diagonal_scan_carry(...)`` /     stateful (states, carry) variants for
  ``matrix_scan_carry(...)``         chunked ingestion (serving prefill)
  ``cumulative_lmme(a)``             PSCAN(LMME): A_t ··· A_1  (paper eq. 24)
  ``selective_reset_scan(...)``      paper §5, with the engine's LMME inside

Backend selection
-----------------
Requested (via ``use_backend`` / ``set_default_backend``, default ``auto``)
resolves to a concrete backend per-call:

  ========= ========== ============ =================================
  requested platform   log dtype    resolved
  ========= ========== ============ =================================
  auto      tpu        float32      ``pallas_tpu``      (compiled)
  auto      gpu        float32      ``pallas_gpu``      (Triton)
  auto      tpu/gpu    float64      ``xla_reference``   (kernels are f32)
  auto      cpu        any          ``xla_reference``
  pallas    tpu        any          ``pallas_tpu``
  pallas    gpu        any          ``pallas_gpu``
  pallas    cpu        any          ``pallas_interpret`` (debug/parity)
  reference any        any          ``xla_reference``
  ========= ========== ============ =================================

Every concrete name may also be requested literally to force a path
(parity tests force ``pallas_interpret`` / ``pallas_gpu_interpret`` on
CPU).  The platform is resolved *once per config push* and cached on the
config entry — never re-read per call or inside a trace.

Block configs
-------------
Tiling is per ``(op, backend)`` (``repro.kernels.blocks.BlockConfig``),
resolved in precedence order: ``use_blocks()`` overrides > the persisted
autotune cache (``engine.autotune()`` / ``repro.kernels.autotune``) >
static defaults.  No caller ever names a block size.

Overrides
---------
    from repro.core import engine

    with engine.use_backend("pallas"):          # scoped
        states = engine.matrix_scan(a, b)

    with engine.use_blocks(matrix_scan={"block_t": 64}):
        states = engine.matrix_scan(a, b)       # pinned tiling

    engine.set_default_backend("reference")     # process-wide default

    engine.autotune()   # sweep tilings for the resolved backend, persist

``use_backend`` affects *tracing*: a ``jax.jit``-compiled function captures
the backend that was active when it was first traced — construct jitted
step functions under the backend you intend to serve with.

Sharded scans
-------------
Every scan op also runs multi-device: batch-sharded through the usual
``sharding.rules`` logical axes, and *sequence-sharded* via ``shard_map``
(each device scans its time-shard locally, per-shard carries are combined
cross-device with the LMME monoid, then stitched — see
``repro.kernels.sharded`` and docs/engine.md).  Activation, in precedence
order:

  1. ``use_mesh(mesh, seq_axis=...)`` — explicit mesh;
  2. active ``sharding.rules`` whose ``scan_seq`` logical axis maps to a
     mesh axis (``scan_batch`` supplies the batch axes);
  3. otherwise — or with ``seq_shards=1``, or a 1-sized sequence axis —
     single-device (``seq_shards="auto"`` falls back silently; an explicit
     shard count without a mesh raises).

Like backends, the sharding context is captured at trace time.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
from typing import Any, Callable, Dict, Mapping, Optional, Tuple, Union

import jax

from .goom import Goom
from . import scan as _scan

__all__ = [
    "EngineConfig",
    "use_backend",
    "use_blocks",
    "use_mesh",
    "set_default_backend",
    "get_config",
    "resolved_backend",
    "active_seq_shards",
    "autotune",
    "lmme",
    "diagonal_scan",
    "diagonal_scan_carry",
    "matrix_scan",
    "matrix_scan_carry",
    "cumulative_lmme",
    "selective_reset_scan",
]

# (op, backend-pattern, BlockConfig) override entries; "*" matches every
# backend.  Later entries win (use_blocks scopes append).
_BlockEntry = Tuple[str, str, Any]


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Engine-wide knobs.  Block tiling lives in per-(op, backend)
    ``BlockConfig`` tables (see ``use_blocks``), not here."""

    backend: str = "auto"
    # platform the backend resolves against, stamped once at config-push
    # time (None on the import-time default: resolved lazily through the
    # cached dispatch.current_platform(), never re-read per call).
    platform: Optional[str] = None
    # block-config override entries, appended by use_blocks scopes
    blocks: Tuple[_BlockEntry, ...] = ()
    # -- sharded scans (see module docstring) -------------------------------
    mesh: Optional[Any] = None          # jax.sharding.Mesh; None -> rules
    seq_axis: Optional[str] = None      # mesh axis carrying the time shards
    batch_axis: Union[None, str, Tuple[str, ...]] = None
    seq_shards: Union[str, int] = "auto"  # "auto" | 1 (off) | mesh axis size


_DEFAULT = EngineConfig()
_STACK: list = []


def _current_platform() -> str:
    from repro.kernels import dispatch

    return dispatch.current_platform()


def get_config() -> EngineConfig:
    return _STACK[-1] if _STACK else _DEFAULT


def set_default_backend(backend: str) -> None:
    """Set the process-wide default backend (outside any ``use_backend``)."""
    global _DEFAULT
    _DEFAULT = dataclasses.replace(_DEFAULT, backend=backend,
                                   platform=_current_platform())


@contextlib.contextmanager
def use_backend(backend: str = "auto", **overrides):
    """Scoped backend/config override (see module docstring for names).

    The platform is resolved here, once per push — backend resolution
    inside the scope (including at trace time) reuses the stamped value."""
    overrides.setdefault("platform", _current_platform())
    cfg = dataclasses.replace(get_config(), backend=backend, **overrides)
    _STACK.append(cfg)
    try:
        yield cfg
    finally:
        _STACK.pop()


@contextlib.contextmanager
def use_blocks(_backend: str = "*", **per_op):
    """Scoped per-op block-config overrides.

    Keyword names are engine ops, values are dicts of ``BlockConfig``
    fields (or ``BlockConfig`` instances)::

        with engine.use_blocks(matrix_scan={"block_t": 64},
                               lmme={"block_n": 256, "block_d": 512}):
            ...

    The positional ``_backend`` restricts the override to one concrete
    backend name (default ``"*"`` = whatever backend resolves).  Overrides
    nest: inner scopes win field-by-field over outer scopes, which win over
    the autotune cache and the static defaults.  Nothing outside
    ``kernels/`` names a block size except through this context manager.
    """
    from repro.kernels.blocks import BlockConfig, OPS

    entries = []
    for op, fields in per_op.items():
        if op not in OPS:
            raise ValueError(f"unknown engine op {op!r}; one of {OPS}")
        cfg = fields if isinstance(fields, BlockConfig) else BlockConfig(**fields)
        entries.append((op, _backend, cfg))
    base = get_config()
    cfg = dataclasses.replace(base, blocks=base.blocks + tuple(entries))
    _STACK.append(cfg)
    try:
        yield cfg
    finally:
        _STACK.pop()


@contextlib.contextmanager
def use_mesh(mesh, *, seq_axis: Optional[str] = None,
             batch_axis: Union[None, str, Tuple[str, ...]] = None,
             seq_shards: Union[str, int] = "auto", **overrides):
    """Scoped mesh for sequence-sharded scans (see module docstring).

    ``seq_axis`` defaults to the mesh axis named ``"seq"`` when present,
    else the *last* mesh axis (the TP/SP axis on the production meshes).
    ``mesh=None`` explicitly restores single-device scans inside the scope.
    """
    if mesh is not None and seq_axis is None:
        names = tuple(mesh.axis_names)
        seq_axis = "seq" if "seq" in names else names[-1]
    overrides.setdefault("platform", _current_platform())
    cfg = dataclasses.replace(
        get_config(), mesh=mesh, seq_axis=seq_axis, batch_axis=batch_axis,
        seq_shards=1 if mesh is None else seq_shards, **overrides)
    _STACK.append(cfg)
    try:
        yield cfg
    finally:
        _STACK.pop()


def _block_overrides(cfg: EngineConfig, op: str, resolved: str,
                     shapes: Optional[Tuple[int, ...]]):
    """Merge the active use_blocks entries for (op, resolved), or None.

    None tells dispatch to consult the autotune cache, then defaults;
    explicit entries merge field-by-field *on top of* that same base, so
    pinning one field keeps the autotuned values of the others."""
    matches = [entry for (o, b, entry) in cfg.blocks
               if o == op and b in ("*", resolved)]
    if not matches:
        return None
    from repro.kernels.autotune import cached_blocks
    from repro.kernels.blocks import merge

    out = cached_blocks(op, resolved, shapes)  # cache winner or defaults
    for entry in matches:
        out = merge(out, entry)
    return out


def resolved_backend(dtype=None) -> str:
    """The concrete backend the current config resolves to for ``dtype``."""
    from repro.kernels import dispatch  # lazy: keeps `import repro.core` light

    import jax.numpy as jnp

    cfg = get_config()
    return dispatch.resolve_backend(
        cfg.backend, platform=cfg.platform,
        dtype=jnp.float32 if dtype is None else dtype,
    )


def _resolved_shard():
    """The ShardSpec the current config resolves to, or None (single-device).

    Precedence: explicit ``use_mesh`` config > active ``sharding.rules``
    (``scan_seq`` / ``scan_batch`` logical axes) > None.
    """
    cfg = get_config()
    if cfg.seq_shards == 1:
        return None
    mesh, seq_axis, batch_axis = cfg.mesh, cfg.seq_axis, cfg.batch_axis
    if mesh is None:
        from repro.sharding import rules as _rules

        active = _rules.current_rules()
        if active is not None:
            seq = active.mesh_axes_for("scan_seq")
            if seq:
                mesh = active.mesh
                seq_axis = seq[0]
                if batch_axis is None:
                    batch_axis = active.mesh_axes_for("scan_batch")
    if mesh is None or seq_axis is None:
        if isinstance(cfg.seq_shards, int) and cfg.seq_shards > 1:
            raise ValueError(
                f"seq_shards={cfg.seq_shards} requested but no mesh is "
                "active (use engine.use_mesh or sharding rules with a "
                "scan_seq mapping)")
        return None
    from repro.kernels.sharded import ShardSpec

    n = int(mesh.shape[seq_axis])
    if cfg.seq_shards not in ("auto", n):
        raise ValueError(
            f"seq_shards={cfg.seq_shards} does not match mesh axis "
            f"{seq_axis!r} of size {n}")
    if n == 1:
        return None
    if isinstance(batch_axis, str):
        batch_axes: Tuple[str, ...] = (batch_axis,)
    else:
        batch_axes = tuple(batch_axis or ())
    return ShardSpec(mesh, seq_axis, batch_axes)


def active_seq_shards() -> int:
    """How many sequence shards the current config resolves to (1 = local).

    Model code uses this to pick scan layouts — e.g. handing the engine one
    full-length scan (shardable) instead of a sequential loop over chunks.
    """
    shard = _resolved_shard()
    return 1 if shard is None else shard.n_shards


def _impl(op: str, dtype, shapes: Optional[Tuple[int, ...]] = None, *,
          local: bool = False) -> Callable:
    """The implementation of ``op`` for the current config; ``local`` for
    code that already runs inside a shard body."""
    from repro.kernels import dispatch

    cfg = get_config()
    resolved = dispatch.resolve_backend(cfg.backend, platform=cfg.platform,
                                        dtype=dtype)
    return dispatch.get_impl(op, resolved,
                             blocks=_block_overrides(cfg, op, resolved, shapes),
                             shard=None if local else _resolved_shard(),
                             shapes=shapes)


# ---------------------------------------------------------------------------
# autotuning
# ---------------------------------------------------------------------------
def autotune(
    ops: Optional[Tuple[str, ...]] = None,
    *,
    backend: Optional[str] = None,
    shapes: Optional[Mapping[str, Tuple[int, ...]]] = None,
    reps: int = 3,
    cache_path: Optional[str] = None,
    verbose: bool = False,
) -> Dict[str, dict]:
    """Sweep candidate tilings and persist winners to the autotune cache.

    ``ops`` defaults to every engine op; ``backend`` defaults to what the
    current config resolves to (so ``engine.autotune()`` on a GPU host
    tunes ``pallas_gpu``); ``shapes`` maps op -> problem dims
    (see ``kernels.autotune.DEFAULT_SHAPES`` for the conventions).  Winners
    are keyed by ``(op, backend, device_kind, shape-bucket)`` and consumed
    automatically by every subsequent engine call on matching shapes — see
    docs/engine.md for the cache file format.  Returns per-op reports."""
    from repro.kernels import autotune as _autotune
    from repro.kernels.blocks import OPS

    backend = backend or resolved_backend()
    reports = {}
    for op in ops or OPS:
        reports[op] = _autotune.autotune_op(
            op, backend, (shapes or {}).get(op), reps=reps, path=cache_path,
            verbose=verbose)
        if verbose:
            r = reports[op]
            print(f"autotune[{op}/{backend}]: {r['blocks']} "
                  f"({r['ms']:.3f} ms) -> {r['key']}")
    return reports


# ---------------------------------------------------------------------------
# public ops
# ---------------------------------------------------------------------------
def lmme(a: Goom, b: Goom) -> Goom:
    """LMME over GOOMs: (..., n, d) ∘ (..., d, m), batch dims broadcast."""
    hint = (a.shape[-2], a.shape[-1], b.shape[-1])
    return _impl("lmme", a.dtype, hint)(a, b)


def diagonal_scan(a: Goom, b: Goom, x0: Optional[Goom] = None) -> Goom:
    """All states of x_t = a_t ⊙ x_{t-1} ⊕ b_t over the leading axis."""
    shape = jax.numpy.broadcast_shapes(a.shape, b.shape)
    hint = (shape[0], math.prod(shape[1:]) if shape[1:] else 1)
    return _impl("diagonal_scan", a.dtype, hint)(a, b, x0)


def matrix_scan(a: Goom, b: Goom, x0: Optional[Goom] = None) -> Goom:
    """All states of X_t = A_t X_{t-1} ⊕ B_t (fused PSCAN∘LMME on Pallas)."""
    hint = (a.shape[0], a.shape[-1], b.shape[-1])
    return _impl("matrix_scan", a.dtype, hint)(a, b, x0)


def _carry_out(states: Goom) -> Tuple[Goom, Goom]:
    return states, states[-1]


def diagonal_scan_carry(
    a: Goom, b: Goom, x0: Optional[Goom] = None
) -> Tuple[Goom, Goom]:
    """Carry-in/carry-out diagonal scan: ``(states, final_state)``.

    The stateful form of :func:`diagonal_scan` for chunked ingestion
    (serving prefill, streaming): feed a chunk with the previous chunk's
    carry as ``x0`` and thread the returned carry into the next call —
    the concatenated chunk states equal one full-length scan, because the
    recurrence algebra folds ``x0`` exactly (see ``core.scan``)."""
    return _carry_out(diagonal_scan(a, b, x0))


def matrix_scan_carry(
    a: Goom, b: Goom, x0: Optional[Goom] = None
) -> Tuple[Goom, Goom]:
    """Carry-in/carry-out matrix scan: ``(states, final_state)``.

    Chunked-ingestion form of :func:`matrix_scan` — same carry-threading
    contract as :func:`diagonal_scan_carry`."""
    return _carry_out(matrix_scan(a, b, x0))


def cumulative_lmme(a: Goom) -> Goom:
    """All prefix products A_t ··· A_1 (paper eq. 24's scan)."""
    hint = (a.shape[0], a.shape[-1])
    return _impl("cumulative_lmme", a.dtype, hint)(a)


def selective_reset_scan(
    a: Goom,
    select_fn: Callable[[Goom], jax.Array],
    reset_fn: Callable[[Goom], Goom],
    *,
    reset_only_state_compounds: bool = True,
) -> Tuple[Goom, jax.Array]:
    """Selective-resetting scan (paper §5) with the engine's LMME inside.

    The reset combine is data-dependent control flow that XLA's associative
    scan already handles; the engine routes its inner matrix products to the
    backend-selected LMME, which is where the flops are.  Under an active
    mesh the whole associative scan is sequence-sharded (the reset combine
    rides the same shard decomposition); note the reset *positions* are
    bracketing-dependent — the select condition inspects interim compounds,
    and the sharded tree materializes different ones — so sharded and local
    runs are equivalent selective-reset trajectories, not bit-identical
    (single-device scans already have this property across tree shapes).
    Lengths that don't divide the shard count fall back to the local scan —
    the reset monoid has no identity element to pad with.
    """
    shard = _resolved_shard()
    in_shards = (shard is not None and a.shape[0] % shard.n_shards == 0
                 and a.shape[0] >= shard.n_shards)
    if in_shards:
        from repro.kernels import sharded as _sharded

        def assoc(fn, elems, axis=0, _spec=shard):
            assert axis == 0, axis
            return _sharded.seq_sharded_associative_scan(fn, elems, spec=_spec)
    else:
        assoc = jax.lax.associative_scan

    return _scan.selective_reset_scan(
        a, select_fn, reset_fn,
        matmul=_impl("lmme", a.dtype, local=in_shards),
        reset_only_state_compounds=reset_only_state_compounds,
        assoc_scan=assoc,
    )
