"""Serving demo: concurrent HTTP clients against the streaming front door.

Run:  PYTHONPATH=src python examples/serve_lm.py --arch olmo-1b --tokens 32

Part 1 boots the full serving stack in-process — ``serve.Engine`` on its
own thread behind the asyncio HTTP server (``repro.serve.api``) — and
drives it the way production traffic would: more concurrent streaming
clients than decode slots, token-by-token SSE consumption, and a
``/status`` snapshot at the end.  The same server is what
``python -m repro.serve.api`` exposes standalone.  Part 2 runs the
legacy lockstep static batch (``serve.steps.generate``) for comparison —
the path the decode_32k / long_500k dry-run cells lower for the
production mesh.
"""

import argparse
import threading
import time

import jax

from repro.configs import get_config
from repro.models.common import unzip
from repro.models.model import DecoderLM
from repro.serve import Engine, generate, slot_cache_bytes
from repro.serve.api import BackgroundServer, Gateway
from repro.serve.api import client as api


def _client(host, port, i, prompt, n_tokens, out, t_start):
    """One streaming client: consume SSE tokens, retry on 429."""
    while True:
        try:
            toks = []
            for event in api.stream_completion(
                    host, port, {"prompt": prompt, "max_tokens": n_tokens}):
                choice = event["choices"][0]
                toks.append(choice["token"])
                if choice["finish_reason"] is not None:
                    out[i] = (toks, choice["finish_reason"],
                              time.perf_counter() - t_start)
            return
        except api.RetryLater as e:
            print(f"  client {i}: 429, retrying in {e.retry_after}s")
            time.sleep(e.retry_after)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--chunk", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--tokens", type=int, default=32)
    args = ap.parse_args()

    cfg = get_config(args.arch, smoke=True)
    model = DecoderLM(cfg)
    params, _ = unzip(model.init(jax.random.PRNGKey(0)))

    page_len = args.prompt_len + args.tokens
    sb = slot_cache_bytes(model, args.slots, page_len)
    print(f"== HTTP front door: {args.requests} streaming clients on "
          f"{args.slots} slots x page {page_len} "
          f"({sb['per_slot']/2**10:.0f} KiB/slot)")

    eng = Engine(model, params, max_slots=args.slots, page_len=page_len,
                 chunk=args.chunk)
    srv = BackgroundServer(Gateway(eng, max_queue=2 * args.requests)).start()
    print(f"serving on http://{srv.host}:{srv.port} "
          f"(standalone: python -m repro.serve.api)")
    try:
        t0 = time.perf_counter()
        out = [None] * args.requests
        threads = []
        for i in range(args.requests):
            # staggered workload: prompts and budgets vary per request
            p = args.prompt_len - (i % 3)
            n = max(2, args.tokens - 4 * i)
            prompt = jax.random.randint(jax.random.PRNGKey(i), (p,), 0,
                                        cfg.vocab)
            threads.append(threading.Thread(
                target=_client,
                args=(srv.host, srv.port, i, list(map(int, prompt)), n,
                      out, t0), daemon=True))
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        t_eng = time.perf_counter() - t0
        n_tok = sum(len(toks) for toks, _, _ in out)
        print(f"server: {n_tok} tokens to {args.requests} clients in "
              f"{t_eng*1e3:.0f} ms ({n_tok/t_eng:.0f} tok/s aggregate)")
        for i, (toks, reason, dt) in enumerate(out):
            print(f"  req {i}: {len(toks):3d} tokens ({reason}) in "
                  f"{dt*1e3:6.0f} ms — {toks[:8]}"
                  f"{' ...' if len(toks) > 8 else ''}")
        snap = api.get_status(srv.host, srv.port)
        lat = snap["latency_ms"]
        print(f"/status: {snap['requests']['finished']} finished, "
              f"engine step p50 {lat['engine_step']['p50']:.1f} ms, "
              f"ttft p50 {lat['ttft']['p50']:.0f} ms, "
              f"request p50 {lat['request']['p50']:.0f} ms")
    finally:
        srv.stop()

    print(f"\n== legacy lockstep batch: {args.requests} x {args.tokens} tokens")
    prompts = jax.random.randint(
        jax.random.PRNGKey(99), (args.requests, args.prompt_len), 0, cfg.vocab)
    t0 = time.perf_counter()
    seqs = generate(model, params, prompts, n_tokens=args.tokens,
                    max_len=page_len)
    jax.block_until_ready(seqs)
    t_leg = time.perf_counter() - t0
    n_tok = args.requests * args.tokens
    print(f"legacy: {n_tok} tokens in {t_leg*1e3:.0f} ms "
          f"({n_tok/t_leg:.0f} tok/s; every sequence decodes to the max)")


if __name__ == "__main__":
    main()
