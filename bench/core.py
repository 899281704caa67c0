"""What every cell shares: files by name, the device check, compile counting,
the result line and the comparison report."""

from __future__ import annotations

import importlib.util
import json
import math
import os
import sys
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"


class NoDevice(Exception):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_spec() -> Dict[str, Any]:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def find(entries: List[Dict[str, Any]], name: str, what: str) -> Dict[str, Any]:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"no {what} named {name!r} in BENCHMARK.json")


def load_json(rel: str) -> Dict[str, Any]:
    return json.loads((ROOT / rel).read_text())


def traffic_file(name: str) -> Dict[str, Any]:
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def load_module(path: Path, name: str):
    """A module from a file whose name need not be an identifier."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver(name: str):
    return importlib.import_module(f"bench.drivers.{name}")


def metric_reader(name: str):
    return load_module(BENCH / "metrics" / f"{name}.py", f"bench_metric_{name}")


def peaks(device_kind: str) -> Dict[str, float]:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in bench/peaks.json")
    return table[device_kind]


def require_chips(n: int):
    """The devices of the cell: ``n`` TPU chips, or NoDevice."""
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoDevice(f"JAX found no devices: {e}")
    if devs[0].platform != "tpu":
        raise NoDevice(f"JAX found {devs[0].platform}, not a TPU")
    if len(devs) < n:
        raise NoDevice(f"the cell needs {n} chips, JAX found {len(devs)}")
    return devs[:n]


def device_info(devs) -> Dict[str, Any]:
    d = devs[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def memory_peak_bytes(devs) -> int:
    """The fullest chip's peak: its arrays' peak, plus the peak it reserved
    for programs' temporaries, which a TPU counts apart from the bytes in
    use (a kernel's padded operands live there)."""
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return peak


class CompileCounter:
    """Counts traces and backend compiles while ``armed``: a program first
    met inside the measured window shows up here."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _secs, **_kw):
        if self.armed and name in self.EVENTS:
            self.count += 1


def quantile(values: List[float], q: float) -> float:
    """The q-quantile of ``values`` by linear interpolation between order
    statistics (numpy's default); an infinite value sorts last."""
    xs = sorted(values)
    if not xs:
        return math.nan
    pos = q * (len(xs) - 1)
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    if math.isinf(xs[hi]) or math.isinf(xs[lo]):
        return xs[hi] if pos > lo else xs[lo]
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


class Checks:
    """The numbers ``correct`` rests on, each beside its limit."""

    def __init__(self):
        self.items: Dict[str, Dict[str, float]] = {}

    def add(self, name: str, value: float, limit: float) -> None:
        self.items[name] = {"value": value, "limit": limit}

    @property
    def ok(self) -> bool:
        return bool(self.items) and all(
            math.isfinite(c["value"]) and c["value"] <= c["limit"]
            for c in self.items.values())


def finite(x: float) -> Optional[float]:
    return x if isinstance(x, (int, float)) and math.isfinite(x) else None


def emit(result: Dict[str, Any], checks: Checks) -> None:
    """The checks as the last lines of standard error, then the result as
    the last line of standard output, with the checks as its last key."""
    for name, c in checks.items.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    line = dict(result)
    line["checks"] = {k: {"value": finite(v["value"]), "limit": v["limit"]}
                      for k, v in checks.items.items()}
    print(json.dumps(line), flush=True)


def now() -> float:
    return time.monotonic()


def cache_dir() -> str:
    """JAX's persistent compilation cache inside the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), small programs included, so that
    only a cell's first run compiles."""
    import jax

    from repro import compile_cache

    path = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    os.makedirs(path, exist_ok=True)
    return path
