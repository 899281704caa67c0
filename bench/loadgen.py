"""Open-loop streaming load generator, in a process of its own (no JAX).

Reads one plan from standard input::

    {"host", "port", "t0": <time.monotonic() of the window's start>,
     "drain_s": <seconds past the last arrival to wait for answers>,
     "requests": [{"t", "prompt", "max_tokens"}, ...]}

sends each request at ``t0 + t`` as a streaming ``POST /v1/completions``,
and writes one JSON list to standard output: per request the scheduled and
actual send times, the arrival time and size of every streamed batch of
tokens, the tokens, and the finish reason or error.  CLOCK_MONOTONIC is
shared by the processes of one machine, so times compare across them.
One thread, one event loop, one connection per request.
"""

from __future__ import annotations

import asyncio
import json
import sys
import time


async def one(host: str, port: int, t0: float, req: dict) -> dict:
    rec = {"t": req["t"], "sent": None, "events": [], "tokens": [],
           "finish": None, "error": None}
    delay = t0 + req["t"] - time.monotonic()
    if delay > 0:
        await asyncio.sleep(delay)
    rec["sent"] = time.monotonic() - t0
    body = json.dumps({"prompt": req["prompt"], "max_tokens": req["max_tokens"],
                       "stream": True}).encode()
    writer = None
    try:
        reader, writer = await asyncio.open_connection(host, port)
        writer.write(
            (f"POST /v1/completions HTTP/1.1\r\nHost: bench\r\n"
             f"Content-Type: application/json\r\nContent-Length: {len(body)}\r\n"
             f"Connection: close\r\n\r\n").encode() + body)
        await writer.drain()
        status = (await reader.readline()).decode("latin-1").split()
        if len(status) < 2 or status[1] != "200":
            rec["error"] = f"HTTP {' '.join(status[1:]) or 'no status'}"
            return rec
        while (await reader.readline()) not in (b"\r\n", b"\n", b""):
            pass
        while True:
            line = await reader.readline()
            if not line:
                rec["error"] = rec["error"] or "stream ended without [DONE]"
                return rec
            line = line.strip()
            if not line.startswith(b"data:"):
                continue
            payload = line[5:].strip()
            if payload == b"[DONE]":
                return rec
            ev = json.loads(payload)
            choice = ev["choices"][0]
            tok = choice.get("token")
            if tok is not None:
                now = time.monotonic() - t0
                if rec["events"] and rec["events"][-1][0] == now:
                    rec["events"][-1][1] += 1
                else:
                    rec["events"].append([now, 1])
                rec["tokens"].append(int(tok))
            if choice.get("finish_reason"):
                rec["finish"] = choice["finish_reason"]
    except (OSError, ValueError, KeyError) as e:
        rec["error"] = f"{type(e).__name__}: {e}"
        return rec
    finally:
        if writer is not None:
            writer.close()


async def main_async(plan: dict) -> list:
    t0 = plan["t0"]
    last = max((r["t"] for r in plan["requests"]), default=0.0)
    tasks = [asyncio.create_task(one(plan["host"], plan["port"], t0, r))
             for r in plan["requests"]]
    timeout = max(0.0, t0 + last + plan["drain_s"] - time.monotonic())
    done, pending = await asyncio.wait(tasks, timeout=timeout)
    for t in pending:
        t.cancel()
    out = []
    for task, req in zip(tasks, plan["requests"]):
        if task in done:
            out.append(task.result())
        else:
            out.append({"t": req["t"], "sent": None, "events": [], "tokens": [],
                        "finish": None, "error": "no answer before the drain ended"})
    return out


def main() -> None:
    plan = json.loads(sys.stdin.readline())
    out = asyncio.run(main_async(plan))
    sys.stdout.write(json.dumps(out) + "\n")
    sys.stdout.flush()


if __name__ == "__main__":
    main()
