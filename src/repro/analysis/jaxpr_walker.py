"""Jaxpr layer: an abstract interpreter enforcing GOOM numerical safety.

The walker runs over a traced computation (``jax.make_jaxpr`` output),
propagating an :class:`~repro.analysis.lattice.AbsVal` per value, and
reports the GC1xx rules:

  GC101  exp of a log magnitude with no dominating max-subtraction
  GC102  narrowing float cast of a log-space value
  GC103  bare ``log`` primitive (i.e. not inside ``safe_log``)
  GC104  reduction over linear values exp'd from unrescaled logs
  GC105  impure primitives (host callbacks) in the hot path

Descent policy
--------------
``pjit`` / ``scan`` / ``remat`` / ``cond`` / ``custom_vjp_call_jaxpr``
bodies are walked (``jnp.cumsum`` lowers to a ``pjit``, so descent is
mandatory); ``custom_jvp_call`` is **not** descended for domain rules —
it is the sanctioned wrapper boundary (``safe_log`` / ``signed_exp`` /
``safe_abs`` are ``custom_jvp`` functions, and any log/exp inside one is
by definition wrapped).  The wrapper's *output* domain is classified
from the primitives its body contains (log -> log-space, exp -> linear).
``pallas_call`` kernel bodies are skipped entirely: kernel numerics are
covered by the e±200 parity suites, and Pallas refs don't fit the value
lattice.  A separate exhaustive pass (descending everything except
``pallas_call``) scans for impure primitives.
"""

from __future__ import annotations

import pathlib
from typing import Callable, Dict, List, Optional, Sequence

from .lattice import AbsVal, TokenSource, UNKNOWN, join
from .registry import RULES
from .report import Finding

__all__ = ["walk_jaxpr", "trace_and_walk", "default_relativize"]

_IMPURE = frozenset({
    "debug_callback", "debug_print", "io_callback", "pure_callback", "callback",
    "outside_call", "host_callback_call",
})
# reductions that collapse an axis in linear space
_LINEAR_REDUCTIONS = frozenset({"reduce_sum", "dot_general"})
# structural prims: domain/provenance pass straight through
_MAX_PRIMS = frozenset({"reduce_max", "cummax"})


def default_relativize(file_name: str) -> str:
    """Map an absolute traceback path to the repo-relative rule path."""
    p = pathlib.PurePosixPath(pathlib.Path(file_name).as_posix())
    parts = p.parts
    for marker in ("repro",):
        if marker in parts:
            i = len(parts) - 1 - parts[::-1].index(marker)
            if i + 1 < len(parts):
                return "/".join(parts[i + 1:])
    return p.name


def _user_frame(eqn):
    try:
        from jax._src import source_info_util

        frame = source_info_util.user_frame(eqn.source_info.traceback)
        if frame is not None:
            return frame.file_name, frame.start_line
    except Exception:
        pass
    return None, 0


def _is_literal(v) -> bool:
    return hasattr(v, "val") and not hasattr(v, "count")


def _is_float_dtype(dt) -> bool:
    # jax.dtypes.issubdtype, not np.issubdtype: bf16/f8 are ml_dtypes
    # extension types that numpy does not consider np.floating
    import numpy as np

    from jax import dtypes as jax_dtypes

    return jax_dtypes.issubdtype(np.dtype(dt), np.floating)


def _float_aval(v) -> bool:
    dt = getattr(getattr(v, "aval", None), "dtype", None)
    return dt is not None and _is_float_dtype(dt)


def _sub_jaxprs(params):
    """All (closed or open) jaxprs reachable from an eqn's params."""
    from jax._src.core import Jaxpr, ClosedJaxpr

    out = []

    def rec(x):
        if isinstance(x, (Jaxpr, ClosedJaxpr)):
            out.append(x)
        elif isinstance(x, (tuple, list)):
            for c in x:
                rec(c)

    for v in params.values():
        rec(v)
    return out


def _prim_names(jaxpr) -> set:
    """Primitive names reachable in a jaxpr (recursively)."""
    inner = getattr(jaxpr, "jaxpr", jaxpr)
    names = set()
    for eqn in inner.eqns:
        names.add(eqn.primitive.name)
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in _sub_jaxprs(eqn.params):
            names |= _prim_names(sub)
    return names


class _Walker:
    def __init__(self, target: str, relativize: Callable[[str], str],
                 tokens: Optional[TokenSource] = None):
        self.target = target
        self.relativize = relativize
        self.tokens = tokens or TokenSource()
        self.findings: List[Finding] = []

    # -- reporting -----------------------------------------------------------
    def _emit(self, rule: str, eqn, message: str):
        file_name, line = _user_frame(eqn)
        self.findings.append(Finding(
            rule=rule, severity=RULES[rule].severity,
            file=self.relativize(file_name) if file_name else "<unknown>",
            line=line, message=message, target=self.target))

    # -- env -----------------------------------------------------------------
    def run(self, closed, in_vals: Sequence[AbsVal]) -> List[AbsVal]:
        jaxpr = closed.jaxpr
        env: Dict = {}
        if len(in_vals) != len(jaxpr.invars):
            raise ValueError(
                f"{self.target}: seeded {len(in_vals)} domains for "
                f"{len(jaxpr.invars)} jaxpr inputs")
        for var, val in zip(jaxpr.invars, in_vals):
            env[var] = val
        for var in jaxpr.constvars:
            env[var] = UNKNOWN
        self._walk(jaxpr, env)
        return [self._read(env, v) for v in jaxpr.outvars]

    def _read(self, env, v) -> AbsVal:
        if _is_literal(v):
            return UNKNOWN
        return env.get(v, UNKNOWN)

    def _operands(self, env, eqn) -> List[AbsVal]:
        """Non-literal float operands (what domain joins range over)."""
        return [self._read(env, v) for v in eqn.invars
                if not _is_literal(v) and _float_aval(v)]

    def _descend(self, sub, env_vals: Sequence[AbsVal]) -> List[AbsVal]:
        from jax._src.core import ClosedJaxpr

        inner = sub.jaxpr if isinstance(sub, ClosedJaxpr) else sub
        env: Dict = {}
        n = len(inner.invars)
        vals = list(env_vals)[-n:] if len(env_vals) >= n else (
            list(env_vals) + [UNKNOWN] * (n - len(env_vals)))
        for var, val in zip(inner.invars, vals):
            env[var] = val
        for var in inner.constvars:
            env[var] = UNKNOWN
        self._walk(inner, env)
        return [self._read(env, v) for v in inner.outvars]

    # -- the interpreter -----------------------------------------------------
    def _walk(self, jaxpr, env):
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            outs = self._eqn(eqn, env, name)
            for var, val in zip(eqn.outvars, outs):
                env[var] = val

    def _eqn(self, eqn, env, name) -> List[AbsVal]:
        n_out = len(eqn.outvars)
        vals = self._operands(env, eqn)
        j = join(vals)

        if name in _IMPURE:
            return [UNKNOWN] * n_out  # reported by the impurity pass

        if name == "log":
            self._emit("GC103", eqn,
                       "bare `log` primitive — not inside safe_log "
                       "(paper eq. 6: the derivative must be floored)")
            return [AbsVal(domain="log",
                           origin=frozenset({self.tokens.fresh()}))] * n_out

        if name == "exp":
            escape = j.domain == "log" and not j.rescaled
            if escape:
                self._emit("GC101", eqn,
                           "exp of a log-space magnitude with no dominating "
                           "max-subtraction: overflow escape from GOOM "
                           "space")
            return [AbsVal(domain="linear", from_log=escape,
                           origin=j.origin)] * n_out

        if name == "convert_element_type":
            import numpy as np

            import jax.numpy as jnp

            new = np.dtype(eqn.params.get("new_dtype", np.float32))
            old_dt = getattr(eqn.invars[0].aval, "dtype", None)
            if (j.domain == "log" and old_dt is not None
                    and _is_float_dtype(old_dt) and _is_float_dtype(new)
                    and jnp.finfo(new).bits < jnp.finfo(np.dtype(old_dt)).bits):
                self._emit("GC102", eqn,
                           f"log-space value demoted {np.dtype(old_dt).name}"
                           f"->{new.name}: log carries need full f32 "
                           "precision")
            return [j] * n_out

        if name in _MAX_PRIMS:
            return [AbsVal(domain=j.domain, rescaled=j.rescaled,
                           origin=j.origin,
                           max_of=j.origin | j.max_of)] * n_out

        if name == "sub" and len(eqn.invars) == 2:
            a = self._read(env, eqn.invars[0])
            b = self._read(env, eqn.invars[1])
            rescaled = bool(b.max_of & a.origin) or j.rescaled
            return [AbsVal(domain=j.domain, rescaled=rescaled,
                           from_log=j.from_log, origin=j.origin,
                           max_of=frozenset())] * n_out

        if name in _LINEAR_REDUCTIONS:
            if any(v.from_log for v in vals):
                self._emit("GC104", eqn,
                           f"`{name}` over linear values exp'd from an "
                           "unrescaled log magnitude: bypasses the "
                           "max-rescaled LSE/LMME monoid")
            if name == "dot_general":
                return [AbsVal(domain="linear",
                               from_log=any(v.from_log for v in vals))] * n_out
            return [j] * n_out

        if name == "pjit" and str(eqn.params.get("name", "")).startswith("cum"):
            # jnp.cumsum & friends lower to a pjit-wrapped scan: treat the
            # whole thing as one reduction rather than descending.
            if any(v.from_log for v in vals):
                self._emit("GC104", eqn,
                           f"cumulative reduction ({eqn.params['name']}) "
                           "over linear values exp'd from an unrescaled "
                           "log magnitude")
            return [j] * n_out

        if name == "custom_jvp_call":
            # Sanctioned wrapper boundary: classify the output domain from
            # the body's primitives; never descend for domain rules.
            sub = eqn.params.get("call_jaxpr")
            prims = _prim_names(sub) if sub is not None else set()
            if "log" in prims and "exp" not in prims:
                return [AbsVal(domain="log",
                               origin=frozenset({self.tokens.fresh()}))] * n_out
            if "exp" in prims:
                return [AbsVal(domain="linear")] * n_out
            return [j] * n_out

        if name == "cond":
            branches = eqn.params.get("branches", ())
            arg_vals = [self._read(env, v) for v in eqn.invars[1:]]
            outs = [self._descend(b, arg_vals) for b in branches]
            if outs:
                return [join([o[i] for o in outs if i < len(o)])
                        for i in range(n_out)]
            return [j] * n_out

        if name != "pallas_call":
            for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
                sub = eqn.params.get(key)
                if sub is not None and not callable(sub):
                    arg_vals = [self._read(env, v) for v in eqn.invars]
                    outs = self._descend(sub, arg_vals)
                    if len(outs) >= n_out:
                        return outs[-n_out:]
                    return outs + [UNKNOWN] * (n_out - len(outs))

        # generic propagation: join the float operands
        return [j] * n_out


def _scan_impure(jaxpr, walker: _Walker):
    inner = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in inner.eqns:
        if eqn.primitive.name in _IMPURE:
            walker._emit("GC105", eqn,
                         f"impure primitive `{eqn.primitive.name}` in the "
                         "jitted hot path (host round-trip per dispatch)")
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in _sub_jaxprs(eqn.params):
            _scan_impure(sub, walker)


def walk_jaxpr(closed, in_vals: Sequence[AbsVal], *, target: str,
               relativize: Callable[[str], str] = default_relativize,
               tokens: Optional[TokenSource] = None) -> List[Finding]:
    """Run the domain walker + the impurity pass over a ClosedJaxpr."""
    w = _Walker(target, relativize, tokens)
    w.run(closed, in_vals)
    _scan_impure(closed, w)
    return w.findings


def trace_and_walk(fn, args, in_vals: Sequence[AbsVal], *, target: str,
                   relativize: Callable[[str], str] = default_relativize,
                   tokens: Optional[TokenSource] = None) -> List[Finding]:
    """``jax.make_jaxpr`` the callable on abstract args, then walk it."""
    import jax

    closed = jax.make_jaxpr(fn)(*args)
    return walk_jaxpr(closed, in_vals, target=target,
                      relativize=relativize, tokens=tokens)
