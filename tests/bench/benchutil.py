"""Shared set-up of the benchmark's tests: import paths, ``bench/run.py``
as a module, and the CPU-sized overrides of each cell."""

from __future__ import annotations

import importlib.util
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

_spec = importlib.util.spec_from_file_location("bench_run", ROOT / "bench" / "run.py")
run = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(run)

PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
SERVE_SMOKE = {
    "config": {"d_model": 64, "n_layers": 2, "head_dim": 8, "vocab": 256, "scan_chunk": 16},
    "traffic": {"rate_per_s": 2.0, "prompt": {"median": 10, "sigma": 0.5, "min": 3, "max": 24},
                "output": {"median": 12, "sigma": 0.5, "min": 4, "max": 20},
                "engine": {"max_slots": 4, "chunk": 8, "page_len": 48, "max_queue": 4096},
                "drain_s": 60, "trace_start_s": 0.5, "trace_s": 1.0},
    "peaks": PEAKS,
}


def with_traffic(base, **traffic):
    out = {k: (dict(v) if isinstance(v, dict) else v) for k, v in base.items()}
    out["traffic"] = dict(out["traffic"], **traffic)
    return out
