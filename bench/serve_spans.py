"""The serving cell's span and counter metrics and its time-to-first-token
account, from one traced run.

    python3 bench/serve_spans.py --workload rnn124m-longgen --seed <n> --seconds 51

From the root of a checkout, on the chip.  Runs the cell through its
driver (``bench/drivers/serve_stream.py``: the same set-up, schedule, load
generator and traced window as ``bench/run.py --trace 1``, without the
reference check) and prints one JSON line:

* ``metrics``: the per-layer metrics of ``bench/metrics/`` that read the
  program's spans and counters (``SPAN_METRICS``), over the window's
  requests (``ServeMetrics.request_log()``), the change in
  ``Engine.prefill_stats()`` and the trace;
* ``ttft_account``: per request, the client's time to first token less
  the load generator's lateness, front door, queue wait and admission;
  the median and p90 of that remainder, in ms, and of each part;
* ``breakdown``: device seconds per program, the longest idle gaps, each
  named by the engine thread's spans (``bench/spans.py``), and how many
  of each span the trace holds.

``--trace-start-s`` and ``--trace-s`` move and size the traced span
(default: the mix's), ``--keep-trace <file>`` copies its ``.xplane.pb``.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from collections import Counter  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import core, spans, trace as tracing  # noqa: E402
from bench.drivers import serve_stream  # noqa: E402
from bench.traffic import schedule  # noqa: E402
from repro.serve.metrics import REQUEST_EVENTS  # noqa: E402

SPAN_METRICS = ("frontdoor_p90_ms.serve", "queue_wait_p90_ms.serve",
                "admission_p90_ms.serve", "prompt_tokens_per_dispatch",
                "admission_device_share.serve")
PARTS = ("late", "frontdoor", "queue_wait", "admission")


def _p50_p90(values):
    return {"p50": core.quantile(values, 0.5), "p90": core.quantile(values, 0.9)}


def ttft_account(recs, log, t0):
    """Match the window's logged requests to the load generator's records
    (both in order of sending) and split each client TTFT, in ms."""
    log = sorted((e for e in log if all(k in e for k in REQUEST_EVENTS)),
                 key=lambda e: e["received"])
    sent = sorted((r for r in recs if r["sent"] is not None and r["events"]),
                  key=lambda r: r["sent"])
    if len(log) != len(sent):
        raise RuntimeError(f"{len(log)} requests logged in full, {len(sent)} answered")
    parts = {k: [] for k in PARTS + ("client_ttft", "remainder")}
    skew = 0.0
    for entry, r in zip(log, sent):
        skew = max(skew, abs(entry["received"] - t0 - r["sent"]) * 1e3)
        client = (r["events"][0][0] - r["t"]) * 1e3
        split = {"late": (r["sent"] - r["t"]) * 1e3}
        split.update({k: spans.phase_ms(entry, k) for k in PARTS[1:]})
        parts["client_ttft"].append(client)
        parts["remainder"].append(client - sum(split.values()))
        for k, v in split.items():
            parts[k].append(v)
    out = {k: _p50_p90(v) for k, v in parts.items()}
    out["requests"] = len(log)
    out["send_to_read_max_ms"] = skew
    return out


def run(name, seed, seconds, *, trace_start_s=None, trace_s=None, keep=None,
        require_chip=True, overrides=None):
    spec = core.load_spec()
    cell = core.find(spec["workloads"], name, "workload")
    cfg = core.load_json(core.find(spec["configs"], cell["config"], "config")["file"])
    mix = core.traffic_file(cell["traffic"])
    overrides = overrides or {}
    cfg.update(overrides.get("config", {}))
    mix.update(overrides.get("traffic", {}))
    if require_chip:
        devices = core.require_chips(cell["chips"])
        core.cache_dir()
    else:
        import jax

        devices = jax.devices()[:cell["chips"]]
    trace_dir = str(ROOT / ".bench_trace" / f"{name}.spans")
    ctx = {"config": cfg, "traffic": mix, "seed": seed, "seconds": seconds,
           "trace": True, "trace_dir": trace_dir, "devices": devices, "t_start": T_START}
    start = trace_start_s if trace_start_s is not None else min(mix["trace_start_s"], seconds / 3)
    span = {"dir": trace_dir, "start_s": start,
            "seconds": trace_s if trace_s is not None else min(mix["trace_s"], seconds - start)}
    srv = serve_stream.Server(ctx)
    try:
        sched = schedule(mix, seed, seconds, cfg["vocab"])
        before = srv.engine.prefill_stats()
        t0, recs, counters, _, traced, stalls = srv.measure(sched, seconds, mix["drain_s"], span)
        prefill = {k: v - before[k] for k, v in srv.engine.prefill_stats().items()}
        log = [e for e in srv.server.gateway.metrics.request_log() if e["submitted"] >= t0]
    finally:
        srv.stop()

    path = tracing.xplane_file(trace_dir)
    if keep:
        Path(keep).parent.mkdir(parents=True, exist_ok=True)
        shutil.copyfile(path, keep)
    ops = tracing.device_ops(path)
    summary = tracing.summarize(ops, traced[1] - traced[0], re.compile(mix["kernel_pattern"]))
    summary["programs"] = spans.programs(spans.device_programs(path))
    host = spans.host_spans(path)
    gaps = spans.labelled_gaps(ops, host)
    shutil.rmtree(trace_dir, ignore_errors=True)

    peaks = overrides.get("peaks") or core.peaks(devices[0].device_kind)
    mctx = {"config": cfg, "traffic": mix, "trace": summary, "peaks": peaks,
            "counters": counters, "requests": log, "prefill": prefill}
    metrics = {m: core.metric_reader(m).read(mctx) for m in SPAN_METRICS}
    return {
        "metrics": metrics,
        "ttft_account": ttft_account(recs, log, t0),
        "prefill": prefill,
        "device": dict(core.device_info(devices), busy_s=summary["busy_s"],
                       window_s=summary["window_s"]),
        "breakdown": {"programs": summary["programs"], "idle_gaps": gaps,
                      "host_spans": dict(Counter(name for name, *_ in host))},
        "stalls": stalls,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="bench/serve_spans.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace-start-s", type=float)
    ap.add_argument("--trace-s", type=float)
    ap.add_argument("--keep-trace")
    args = ap.parse_args(argv)
    try:
        out = run(args.workload, args.seed, args.seconds, trace_start_s=args.trace_start_s,
                  trace_s=args.trace_s, keep=args.keep_trace)
    except core.NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
