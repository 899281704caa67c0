"""Open-loop streaming traffic through the HTTP front door of ``serve.Engine``.

Set-up builds the weights from the seed (``bench/reference``'s generator,
one jitted call), the program's model, ``Engine``, ``Gateway`` and the HTTP
server on a free local port, and sends warm-up requests down the same path
until every program the mix uses is compiled.  The window hands the mix's
schedule to ``bench/loadgen.py``, a process of its own that never imports
JAX, and waits for every request due in the window to be answered.  Once
the server is stopped and its state freed, the reference, at the precision
the configuration states, recomputes every answered request, and two
numbers of the gap by which a served token's logit lies below the
reference's best are held to the mix's limits (``served_numbers``).
"""

from __future__ import annotations

import asyncio
import gc
import json
import math
import subprocess
import sys
import time
from typing import Any, Dict, List

import numpy as np

from bench import core, loadgen, trace as tracing
from bench.traffic import schedule


def lm_config(cfg: Dict[str, Any]):
    """The program's model configuration, from the benchmark's file."""
    import jax.numpy as jnp

    from repro.models.blocks import BlockCfg, GroupCfg
    from repro.models.goom_layer import GoomSSMCfg
    from repro.models.model import LMConfig

    goom = GoomSSMCfg(d_model=cfg["d_model"], head_dim=cfg["head_dim"],
                      chunk=cfg["scan_chunk"])
    blk = BlockCfg(mixer="goom_ssm", channel="none", goom=goom, norm="ln")
    lm = LMConfig(name=cfg["name"], family="ssm", vocab=cfg["vocab"],
                  d_model=cfg["d_model"], n_layers=cfg["n_layers"],
                  groups=(GroupCfg(period=(blk,), n_periods=cfg["n_layers"]),),
                  final_norm="ln", sub_quadratic=True)
    for key in ("param_dtype", "compute_dtype"):
        if jnp.dtype(getattr(lm, key)) != jnp.dtype(cfg[key]):
            raise ValueError(f"the program runs {key} {getattr(lm, key)}, the file says {cfg[key]}")
    return lm


def program_params(w: Dict[str, Any]) -> Dict[str, Any]:
    """The benchmark's weights in the program's parameter tree."""
    return {
        "embed": w["embed"],
        "final_norm": {"scale": w["final_scale"], "bias": w["final_bias"]},
        "group_0": {"b0": {
            "mixer_norm": {"scale": w["norm_scale"], "bias": w["norm_bias"]},
            "mixer": {
                "ln": {"scale": w["ln_scale"], "bias": w["ln_bias"]},
                "in_proj": {"w": w["w_in"]},
                "A": w["A"], "B": w["B"], "C": w["C"], "D": w["D"],
                "out_proj": {"w": w["w_out"]},
            },
        }},
        "lm_head": {"w": w["head"]},
    }


def _warmup_plan(eng, vocab: int, chunk: int, rng) -> List[List[Dict[str, Any]]]:
    """Rounds of requests that reach every program of the serving path: a
    prompt of two chunks (chunk step, page snapshot, fused full-chunk
    admission), the same prompt again (prefix hit), a prompt with a tail
    (single-token steps, fused tail admission), one shorter than a chunk
    and one of exactly a chunk; the later rounds queue behind a full
    engine, so decode runs at both horizons."""
    two = rng.integers(0, vocab, 2 * chunk).tolist()
    rounds = [[{"t": 0.0, "prompt": two, "max_tokens": 12}]]
    for r in range(2):
        batch = [{"t": 0.0, "prompt": two, "max_tokens": 12}]
        for p in (chunk + chunk // 2 + 3, max(2, chunk // 4), chunk):
            batch.append({"t": 0.0, "prompt": rng.integers(0, vocab, p).tolist(),
                          "max_tokens": 10 + 2 * r})
        while len(batch) < eng.max_slots + 2:
            batch.append({"t": 0.0, "prompt": rng.integers(0, vocab, 3).tolist(),
                          "max_tokens": 9})
        rounds.append(batch)
    return rounds


def _requests_ok(rec: Dict[str, Any], req: Dict[str, Any]) -> bool:
    return (rec["error"] is None and rec["finish"] == "length"
            and len(rec["tokens"]) == req["max_tokens"])


class Server:
    """The program behind its HTTP front door, warmed up, and the load
    generator's process, started during set-up."""

    def __init__(self, ctx: Dict[str, Any]):
        import jax

        from bench.reference import goom_rnn as ref
        from repro.models.common import unzip
        from repro.models.model import DecoderLM
        from repro.serve.api.gateway import Gateway
        from repro.serve.api.server import BackgroundServer
        from repro.serve.scheduler import Engine

        cfg, mix, seed = ctx["config"], ctx["traffic"], ctx["seed"]
        eng_cfg = mix["engine"]
        self.ctx = ctx
        self.counter = core.CompileCounter()
        model = DecoderLM(lm_config(cfg))
        params = program_params(ref.init_weights(cfg, seed))
        want = jax.tree.structure(jax.eval_shape(
            lambda: unzip(model.init(jax.random.PRNGKey(0)))[0]))
        if jax.tree.structure(params) != want:
            raise ValueError("the benchmark's weights do not fit the program's parameter tree")
        self.engine = Engine(model, params, max_slots=eng_cfg["max_slots"],
                             page_len=eng_cfg["page_len"], chunk=eng_cfg["chunk"],
                             backend="auto")
        self.server = BackgroundServer(
            Gateway(self.engine, max_queue=eng_cfg["max_queue"])).start()
        self.gen = None
        for batch in _warmup_plan(self.engine, cfg["vocab"], eng_cfg["chunk"],
                                  np.random.default_rng(seed % (2 ** 63))):
            recs = asyncio.run(loadgen.main_async(self._plan(batch, core.now(), 600.0)))
            bad = [r for r, q in zip(recs, batch) if not _requests_ok(r, q)]
            if bad:
                raise RuntimeError(f"warm-up request failed: {bad[0]}")
        self._spawn()

    def _plan(self, requests, t0, drain_s):
        return {"host": "127.0.0.1", "port": self.server.port, "t0": t0,
                "drain_s": drain_s, "requests": requests}

    def _spawn(self):
        self.gen = subprocess.Popen([sys.executable, str(core.BENCH / "loadgen.py")],
                                    stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)

    def measure(self, sched, seconds, drain_s, trace=None):
        """Send ``sched`` from a window starting now; returns the window's
        start, the requests' records, the decode counters' change over the
        window, the largest queue seen, the traced span (or None), and two
        readings of host stalls: the longest this thread overslept a nap in
        the window, and the longest pause of Python's garbage collector in
        this process up to the drain's end."""
        eng = self.engine
        if self.gen is None:
            self._spawn()
        t0 = core.now() + 0.2
        self.counter.armed = True
        self.gen.stdin.write(json.dumps(self._plan(sched, t0, drain_s)) + "\n")
        self.gen.stdin.flush()
        stats0 = eng.decode_stats()
        queue, traced = [], None
        stalls = {"oversleep": 0.0, "gc": 0.0, "gc_start": 0.0}

        def on_gc(phase, _info):
            if phase == "start":
                stalls["gc_start"] = time.perf_counter()
            else:
                stalls["gc"] = max(stalls["gc"], time.perf_counter() - stalls["gc_start"])

        def wait_until(t):
            while core.now() < t:
                queue.append(eng.n_waiting)
                nap = min(0.5, max(0.0, t - core.now()))
                a = core.now()
                time.sleep(nap)
                stalls["oversleep"] = max(stalls["oversleep"], core.now() - a - nap)

        gc.callbacks.append(on_gc)

        if trace is not None:
            t_on = t0 + trace["start_s"]
            wait_until(t_on)
            with tracing.capture(trace["dir"]):
                a = core.now()
                wait_until(t_on + trace["seconds"])
                traced = (a - t0, core.now() - t0)
        wait_until(t0 + seconds)
        stats1 = eng.decode_stats()
        out, _ = self.gen.communicate(timeout=sched[-1]["t"] + drain_s + 60)
        gc.callbacks.remove(on_gc)
        self.gen = None
        self.counter.armed = False
        counters = {k: stats1[k] - stats0[k] for k in ("decode_steps", "dispatches")}
        stalls = {"oversleep_max_ms": stalls["oversleep"] * 1e3, "gc_max_ms": stalls["gc"] * 1e3}
        return t0, json.loads(out), counters, max(queue, default=0), traced, stalls

    def stop(self):
        if self.gen is not None and self.gen.poll() is None:
            self.gen.kill()
            self.gen.wait()
        self.server.stop()


def latencies(sched, recs, end):
    """Per request due in the window: time to first token and time per
    output token after it, in seconds.  A request not answered in full
    waited until ``end`` (seconds from the window's start) for both."""
    ok = [_requests_ok(r, q) for r, q in zip(recs, sched)]
    ttft, tpot = [], []
    for r, good in zip(recs, ok):
        if good:
            first, last = r["events"][0][0], r["events"][-1][0]
            ttft.append(first - r["t"])
            tpot.append((last - first) / (len(r["tokens"]) - 1))
        else:
            ttft.append(end - r["t"])
            tpot.append(end - r["t"])
    return ok, ttft, tpot


def token_gaps(recs) -> Dict[str, float]:
    """The longest wait between two streamed tokens of one request, in ms,
    and when it ended (seconds from the window's start).  A stall of the
    host holds every stream at once; so does an admission that keeps
    decode waiting."""
    gaps = [(b[0] - a[0], b[0]) for r in recs for a, b in zip(r["events"], r["events"][1:])]
    if not gaps:
        return {}
    longest = max(gaps)
    return {"token_gap_max_ms": longest[0] * 1e3, "token_gap_at_s": longest[1]}


def served_numbers(per_row, early: int, above: float) -> Dict[str, List[float]]:
    """The numbers ``correct`` rests on, from ``position_gaps``' rows (one
    list entry per reference row: the served tokens, then each control):
    ``logit_gap``, the widest gap over each request's first ``early`` served
    tokens; ``gap_share``, the share of all served tokens whose gap exceeds
    ``above``; ``widest``, the widest gap over all of them (reported only:
    late in long requests two correct references, float32 and bfloat16,
    drift apart by as much, PERF.md)."""
    kinds = range(per_row[0].shape[0])
    every = [np.concatenate([g[k] for g in per_row]) for k in kinds]
    return {"logit_gap": [float(max(g[k][:early].max() for g in per_row)) for k in kinds],
            "gap_share": [float((e > above).mean()) for e in every],
            "widest": [float(e.max()) for e in every]}


def run(ctx: Dict[str, Any]) -> Dict[str, Any]:
    from bench.reference import goom_rnn as ref

    cfg, mix, seed, seconds = ctx["config"], ctx["traffic"], ctx["seed"], ctx["seconds"]
    srv = Server(ctx)
    try:
        sched = schedule(mix, seed, seconds, cfg["vocab"])
        trace = None
        if ctx["trace"]:
            start = min(mix["trace_start_s"], seconds / 3)
            trace = {"dir": ctx["trace_dir"], "start_s": start,
                     "seconds": min(mix["trace_s"], seconds - start)}
        t0, recs, counters, queue_max, traced, stalls = srv.measure(
            sched, seconds, mix["drain_s"], trace)
        setup_s = t0 - ctx["t_start"]
        peak = core.memory_peak_bytes(ctx["devices"])
        compiles = srv.counter.count
    finally:
        srv.stop()
    del srv
    gc.collect()

    ok, ttft, tpot = latencies(sched, recs, sched[-1]["t"] + mix["drain_s"])
    late = max((r["sent"] - r["t"] for r in recs if r["sent"] is not None), default=0.0)
    stalls.update(token_gaps(recs))
    print(f"serve: {len(sched)} requests due, {sum(ok)} answered in full; "
          f"generator at most {late * 1e3:.1f} ms late; largest queue {queue_max}; "
          f"decode {counters['decode_steps']} steps in {counters['dispatches']} dispatches; "
          f"stalls {stalls}", file=sys.stderr, flush=True)

    # ---- correctness: the reference over every answered request
    checks = core.Checks()
    checks.add("lost_requests", float(len(sched) - sum(ok)), 0.0)
    checks.add("compiles_in_window", float(compiles), 0.0)
    rows = [(q["prompt"], r["tokens"]) for r, q, good in zip(recs, sched, ok) if good]
    prec = ref.stated(cfg)
    others = ref.controls(prec) if mix.get("control") else {}
    numbers = {"logit_gap": [math.inf], "gap_share": [math.inf]}
    if rows:
        t_ref = core.now()
        per_row = ref.position_gaps(
            ref.init_weights(cfg, seed), rows, prec, list(others.values()),
            shape=(len(sched), mix["prompt"]["max"] + mix["output"]["max"] - 1))
        numbers = served_numbers(per_row, mix["check_tokens"], mix["gap_above"])
        print(f"reference: {len(rows)} requests, {sum(len(t) for _, t in rows)} served "
              f"tokens, {core.now() - t_ref:.1f} s; widest gap over every served token "
              f"{numbers['widest'][0]!r}", file=sys.stderr, flush=True)
    control = {}
    for k, name in enumerate(others, start=1):
        for number in ("logit_gap", "gap_share"):
            control[f"{number}.{name}"] = numbers[number][k]
        print(f"control {name}: logit_gap {numbers['logit_gap'][k]!r}, gap_share "
              f"{numbers['gap_share'][k]!r}", file=sys.stderr, flush=True)
    for number in ("logit_gap", "gap_share"):
        checks.add(number, numbers[number][0], mix["limits"][number])

    result = {
        "attempted": len(sched),
        "failed": len(sched) - sum(ok),
        "e2e": {"setup_s": setup_s,
                "ttft_p90_ms": core.quantile(ttft, 0.9) * 1e3,
                "tpot_p90_ms": core.quantile(tpot, 0.9) * 1e3},
        "memory_peak_bytes": peak,
        "extra": {"requests": len(sched), "generator_late_ms": late * 1e3,
                  "queue_max": queue_max, "stalls": stalls},
        "control": control,
        "counters": counters,
        "checks": checks,
    }
    if traced is not None:
        lo, hi = traced
        tokens = 0
        for r, q in zip(recs, sched):
            tokens += sum(n for t, n in r["events"] if lo <= t < hi)
            if r["events"] and lo <= r["events"][0][0] < hi:
                tokens += len(q["prompt"])
        result["trace_window_s"] = hi - lo
        result["work"] = {"tokens": tokens}
    return result
