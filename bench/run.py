"""Run one cell of the benchmark and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  The cell (``BENCHMARK.json``'s ``workloads``)
names a configuration file and a traffic mix (``bench/traffic/<mix>.json``),
whose ``driver`` (``bench/drivers/<driver>.py``) builds the system from the
seed, warms up, measures for ``--seconds`` and checks the answers against
the plain reference.  With ``--trace 1`` the window is profiled and the
cell's per-layer metrics are read from ``bench/metrics/<metric>.py``;
otherwise its end-to-end metrics are printed.

Exits 3, printing no result, where JAX finds no TPU or fewer chips than the
cell asks for.  The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and
``breakdown`` when traced), with ``checks`` last: each number the answers
were compared by, beside its limit.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import math  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import core  # noqa: E402


def cell_metrics(spec, cell, kind):
    """The cell's metrics of one kind: listed for it, or for every cell."""
    out = []
    for m in spec[kind]:
        if cell["name"] in m.get("workloads", [cell["name"]]):
            out.append(m)
    return out


def run_cell(name, seed, seconds, trace, *, require_chip=True, overrides=None):
    """Run one cell; returns the result line (a dict) and the checks.

    ``overrides`` (tests only) replaces entries of the configuration and the
    traffic mix, e.g. to run at a size a CPU holds."""
    spec = core.load_spec()
    cell = core.find(spec["workloads"], name, "workload")
    config = core.find(spec["configs"], cell["config"], "config")
    cfg = core.load_json(config["file"])
    mix = core.traffic_file(cell["traffic"])
    overrides = overrides or {}
    cfg.update(overrides.get("config", {}))
    mix.update(overrides.get("traffic", {}))

    if require_chip:
        devices = core.require_chips(cell["chips"])
    else:
        import jax

        devices = jax.devices()[:cell["chips"]]
    if require_chip:
        core.cache_dir()
    trace_dir = str(ROOT / ".bench_trace" / name)
    ctx = {"config": cfg, "traffic": mix, "seed": seed, "seconds": seconds,
           "trace": bool(trace), "trace_dir": trace_dir, "devices": devices,
           "t_start": T_START}
    res = core.driver(mix["driver"]).run(ctx)
    checks = res["checks"]

    line = {"correct": checks.ok, "attempted": res["attempted"], "failed": res["failed"]}
    metrics = {}
    if not trace:
        for m in cell_metrics(spec, cell, "end_to_end"):
            metrics[m["name"]] = {"value": res["e2e"][m["name"]], "unit": m["unit"]}
    device = dict(core.device_info(devices), memory_peak_bytes=res["memory_peak_bytes"])
    if trace:
        from bench import trace as tracing

        ops = tracing.device_ops(tracing.xplane_file(trace_dir))
        summary = tracing.summarize(ops, res["trace_window_s"],
                                    re.compile(mix["kernel_pattern"]))
        shutil.rmtree(trace_dir, ignore_errors=True)
        kind = devices[0].device_kind
        ctx = {"config": cfg, "traffic": mix, "trace": summary,
               "peaks": overrides.get("peaks") or core.peaks(kind),
               "counters": res.get("counters"), "work": res.get("work")}
        for m in cell_metrics(spec, cell, "per_layer"):
            v = core.metric_reader(m["name"]).read(ctx)
            if v is not None and math.isfinite(v):
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        line["breakdown"] = {"device_ops": summary["device_ops"],
                             "idle_gaps": summary["idle_gaps"]}
    line["metrics"] = metrics
    line["device"] = device
    line.update(res.get("extra", {}))
    control = {k: v for k, v in res.get("control", {}).items() if v is not None}
    if control:
        line["control"] = control
    return line, checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="bench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must not be negative")
    try:
        line, checks = run_cell(args.workload, args.seed, args.seconds, args.trace)
    except core.NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    core.emit(line, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
