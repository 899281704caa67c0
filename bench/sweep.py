"""Find a serving cell's knee: the highest arrival rate whose backlog does
not grow over a window.  One set-up, then one window per rate, each with
the cell's mix at that rate, in one process on the chip::

    python3 bench/sweep.py --workload rnn124m-longgen --seed 5 --seconds 51 \\
        --rates 0.2,0.4,0.6,0.8

Prints one JSON line per rate: requests due and answered, the tails of time
to first token and per output token, the largest queue seen, and the time
to first token of the last third of the requests over the first third (a
backlog that grows shows as a ratio well above 1).  The rate of a cell is
fixed in its mix's file from this once; the benchmark never searches.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

T_START = time.monotonic()

from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import core  # noqa: E402
from bench.drivers.serve_stream import Server, latencies  # noqa: E402
from bench.traffic import schedule  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="bench/sweep.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    spec = core.load_spec()
    cell = core.find(spec["workloads"], args.workload, "workload")
    cfg = core.load_json(core.find(spec["configs"], cell["config"], "config")["file"])
    mix = core.traffic_file(cell["traffic"])
    try:
        devices = core.require_chips(cell["chips"])
    except core.NoDevice as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 3
    core.cache_dir()
    srv = Server({"config": cfg, "traffic": mix, "seed": args.seed, "devices": devices})
    print(f"set-up {core.now() - T_START:.1f} s", file=sys.stderr, flush=True)
    try:
        for rate in (float(r) for r in args.rates.split(",")):
            sched = schedule(dict(mix, rate_per_s=rate), args.seed, args.seconds, cfg["vocab"])
            t0, recs, counters, queue_max, _, _ = srv.measure(sched, args.seconds, mix["drain_s"])
            ok, ttft, tpot = latencies(sched, recs, sched[-1]["t"] + mix["drain_s"])
            third = max(1, len(ttft) // 3)
            head = sorted(ttft[:third])[third // 2]
            tail = sorted(ttft[-third:])[third // 2]
            print(json.dumps({
                "rate_per_s": rate, "due": len(sched), "answered": sum(ok),
                "ttft_p50_ms": core.quantile(ttft, 0.5) * 1e3,
                "ttft_p90_ms": core.quantile(ttft, 0.9) * 1e3,
                "tpot_p50_ms": core.quantile(tpot, 0.5) * 1e3,
                "tpot_p90_ms": core.quantile(tpot, 0.9) * 1e3,
                "queue_max": queue_max, "ttft_last_over_first_third": tail / head,
                "tokens_per_dispatch": counters["decode_steps"] / max(1, counters["dispatches"]),
                "drained_s": core.now() - t0,
            }), flush=True)
    finally:
        srv.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
