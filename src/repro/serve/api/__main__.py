"""Entry point: ``python -m repro.serve.api --arch olmo-1b --port 8000``.

Builds a (randomly initialized) smoke model unless ``--full`` is given,
wraps it in Engine -> Gateway -> ServeAPI, and serves until interrupted.
Try it::

    PYTHONPATH=src python -m repro.serve.api --port 8000 &
    curl -N localhost:8000/v1/completions -d \
      '{"prompt": [3, 1, 4, 1, 5], "max_tokens": 8, "stream": true}'
    curl localhost:8000/status
"""

from __future__ import annotations

import argparse
import asyncio

from ... import compile_cache
from .gateway import Gateway
from .server import ServeAPI, build_engine


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(prog="python -m repro.serve.api")
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--full", action="store_true",
                    help="full-size config (default: smoke shapes)")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8000)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--page-len", type=int, default=256)
    ap.add_argument("--chunk", type=int, default=16)
    ap.add_argument("--max-queue", type=int, default=32)
    ap.add_argument("--backend", default="auto")
    args = ap.parse_args(argv)

    compile_cache.enable()
    eng, cfg = build_engine(
        args.arch, smoke=not args.full, max_slots=args.slots,
        page_len=args.page_len, chunk=args.chunk, backend=args.backend)
    gateway = Gateway(eng, max_queue=args.max_queue).start()
    print(f"serving {cfg.name} on http://{args.host}:{args.port} "
          f"({args.slots} slots x page {args.page_len}, "
          f"queue watermark {args.max_queue})")

    async def _serve():
        api = await ServeAPI(gateway, args.host, args.port).start()
        print(f"POST /v1/completions (SSE with \"stream\": true) | "
              f"GET /status — port {api.port}")
        try:
            await api.serve_forever()
        except asyncio.CancelledError:
            pass
        finally:
            await api.stop()

    try:
        asyncio.run(_serve())
    except KeyboardInterrupt:
        pass
    finally:
        gateway.stop()


if __name__ == "__main__":
    main()
