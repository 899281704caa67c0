"""HTTP front door conformance against a real server on an ephemeral port.

One module-scoped ``BackgroundServer`` (smoke olmo, 2 slots, queue
watermark 3) backs every test: SSE wire framing, stream/non-stream
parity against per-sequence reference decodes, mid-stream disconnect
evicting the slot, deterministic 429 + ``Retry-After`` under
saturation, the ``/status`` schema, error paths, deadlines over HTTP,
and the end-to-end acceptance run (more concurrent streaming clients
than slots, one of them disconnecting mid-stream and one retrying
after a 429 — every survivor must match its reference decode).
"""

import json
import socket
import threading
import time

import pytest

from repro.serve import Engine, Request
from repro.serve.api import BackgroundServer, Gateway, build_engine
from repro.serve.api import client as api_client
from repro.serve.api.sse import SSEDecoder, completion_chunk, encode_event

MAX_SLOTS = 2
PAGE_LEN = 64
MAX_QUEUE = 3
LONG = 40  # budget long enough that saturation outlives the assertions


class _Server:
    def __init__(self):
        self.engine, self.cfg = build_engine(
            "olmo-1b", smoke=True, max_slots=MAX_SLOTS, page_len=PAGE_LEN,
            chunk=4)
        self.gateway = Gateway(self.engine, max_queue=MAX_QUEUE)
        self.srv = BackgroundServer(self.gateway).start()
        self.host, self.port = self.srv.host, self.srv.port
        # per-sequence references from a solo engine over the same params
        self.solo = Engine(self.engine.model, self.engine.params,
                           max_slots=1, page_len=PAGE_LEN, chunk=4)
        self._refs = {}

    def ref(self, prompt, n):
        key = (tuple(prompt), n)
        if key not in self._refs:
            uid = f"ref{len(self._refs)}"
            self._refs[key] = self.solo.run(
                [Request(uid=uid, prompt=list(prompt),
                         max_new_tokens=n)])[uid]
        return self._refs[key]

    def wait_idle(self, timeout=60.0):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if not self.engine.has_work and self.gateway.queue_depth() == 0:
                return
            time.sleep(0.01)
        raise TimeoutError("server did not drain")


@pytest.fixture(scope="module")
def server():
    s = _Server()
    # warm the jitted paths so per-test latencies are decode-bound
    api_client.completion(s.host, s.port,
                          {"prompt": [1, 2, 3], "max_tokens": 2})
    yield s
    s.srv.stop()


PROMPT = [3, 1, 4, 1, 5, 9]


# ---------------------------------------------------------------------------
# wire protocol: framing, parity, /status schema, error paths
# ---------------------------------------------------------------------------
def test_sse_unit_framing_roundtrip():
    """Pure-unit check: encoder output survives arbitrary re-chunking."""
    events = [completion_chunk("u", 7, 0), completion_chunk("u", 8, 1, "length")]
    wire = b"".join(encode_event(e) for e in events) + b"data: [DONE]\n\n"
    for chunk_size in (1, 3, 7, len(wire)):
        dec = SSEDecoder()
        payloads = []
        for lo in range(0, len(wire), chunk_size):
            payloads.extend(dec.feed(wire[lo:lo + chunk_size]))
        assert payloads[-1] == "[DONE]"
        assert [json.loads(p)["choices"][0]["token"]
                for p in payloads[:-1]] == [7, 8]


def test_nonstream_completion_matches_reference(server):
    server.wait_idle()
    out = api_client.completion(server.host, server.port,
                                {"prompt": PROMPT, "max_tokens": 8})
    choice = out["choices"][0]
    assert choice["tokens"] == server.ref(PROMPT, 8)
    assert choice["finish_reason"] == "length"
    assert out["object"] == "text_completion"
    assert out["usage"] == {"prompt_tokens": len(PROMPT),
                            "completion_tokens": 8,
                            "total_tokens": len(PROMPT) + 8}


def test_stream_matches_reference_token_by_token(server):
    server.wait_idle()
    events = list(api_client.stream_completion(
        server.host, server.port, {"prompt": PROMPT, "max_tokens": 8}))
    toks = [e["choices"][0]["token"] for e in events]
    assert toks == server.ref(PROMPT, 8)
    # exactly the last event is terminal; indices count up from 0
    assert [e["choices"][0]["finish_reason"] for e in events] == \
        [None] * 7 + ["length"]
    assert [e["token_index"] for e in events] == list(range(8))
    assert all(e["object"] == "text_completion" for e in events)


def test_sse_raw_wire_framing(server):
    """Bytes on the socket: header block, ``data: {...}\\n\\n`` chunks,
    terminal ``data: [DONE]\\n\\n`` — checked without the client helper."""
    server.wait_idle()
    body = json.dumps({"prompt": PROMPT, "max_tokens": 4,
                       "stream": True}).encode()
    with socket.create_connection((server.host, server.port), 10) as sock:
        sock.settimeout(30)
        sock.sendall(b"POST /v1/completions HTTP/1.1\r\nHost: t\r\n"
                     b"Content-Length: %d\r\n"
                     b"Connection: close\r\n\r\n" % len(body) + body)
        raw = b""
        while True:
            got = sock.recv(65536)
            if not got:
                break
            raw += got
    head, _, payload = raw.partition(b"\r\n\r\n")
    assert head.startswith(b"HTTP/1.1 200 OK")
    assert b"content-type: text/event-stream" in head.lower()
    assert payload.endswith(b"data: [DONE]\n\n")
    frames = payload.split(b"\n\n")
    assert frames[-1] == b""  # stream ends on a frame boundary
    frames = frames[:-1]
    assert all(f.startswith(b"data: ") for f in frames)
    chunks = [json.loads(f[len(b"data: "):]) for f in frames[:-1]]
    assert [c["choices"][0]["token"] for c in chunks] == server.ref(PROMPT, 4)
    assert chunks[-1]["choices"][0]["finish_reason"] == "length"


def test_status_schema_and_healthz(server):
    server.wait_idle()
    assert api_client.request_json(server.host, server.port, "GET",
                                   "/healthz") == {"ok": True}
    snap = api_client.get_status(server.host, server.port)
    assert set(snap) >= {"uptime_s", "requests", "throughput",
                         "latency_ms", "busy_slots", "engine",
                         "prefix_cache", "decode", "prefill"}
    assert set(snap["requests"]) == {"submitted", "finished", "rejected",
                                     "by_finish_reason"}
    assert set(snap["throughput"]) == {"tokens_total", "tokens_per_s",
                                       "requests_per_s", "steps_total"}
    for series in ("engine_step", "ttft", "request", "queue_wait",
                   "admission", "frontdoor"):
        assert set(snap["latency_ms"][series]) == {"p50", "p90", "p99"}
    eng = snap["engine"]
    assert eng["max_slots"] == MAX_SLOTS
    assert eng["queue_limit"] == MAX_QUEUE
    assert eng["page_len"] == PAGE_LEN
    assert 0.0 <= eng["slot_occupancy"] <= 1.0
    # the module fixture's warmup + earlier tests have finished work
    assert snap["requests"]["finished"] >= 1
    assert snap["throughput"]["tokens_total"] >= 1
    assert snap["latency_ms"]["engine_step"]["p50"] > 0
    assert snap["latency_ms"]["admission"]["p50"] > 0
    # prefix-cache gauges (satellite: hit rate / tokens saved / occupancy)
    pc = snap["prefix_cache"]
    assert set(pc) == {"enabled", "lookups", "hits", "hit_rate",
                       "hit_tokens", "prefill_tokens_saved", "nodes",
                       "evicted", "page_size", "pages"}
    assert set(pc["pages"]) == {"total", "used", "free", "occupancy"}
    assert pc["enabled"] is True
    assert pc["lookups"] >= 1  # warmup + this module's completions
    assert 0.0 <= pc["hit_rate"] <= 1.0
    assert 0.0 <= pc["pages"]["occupancy"] <= 1.0
    assert pc["pages"]["used"] + pc["pages"]["free"] == pc["pages"]["total"]
    assert eng["page_size"] == snap["prefix_cache"]["page_size"]
    assert eng["prefix_reuse"] is True
    # multi-step decode gauges (satellite: dispatches / host syncs /
    # tokens-per-dispatch, live from Engine.decode_stats())
    dec = snap["decode"]
    assert set(dec) == {"dispatches", "decode_steps", "tokens_per_dispatch",
                        "host_syncs", "syncs_per_token", "horizon_max",
                        "last_horizon"}
    assert dec["dispatches"] >= 1  # warmup + earlier tests decoded
    assert dec["decode_steps"] >= dec["dispatches"]
    assert dec["tokens_per_dispatch"] >= 1.0
    assert dec["horizon_max"] >= 1
    assert 1 <= dec["last_horizon"] <= dec["horizon_max"]
    assert dec["host_syncs"] >= 1
    assert dec["syncs_per_token"] <= 1.0
    # admission counters, live from Engine.prefill_stats()
    pre = snap["prefill"]
    assert set(pre) == {"admissions", "prompt_tokens", "chunk_calls",
                        "tail_calls", "finisher_calls"}
    assert pre["admissions"] == pre["finisher_calls"] >= 1
    assert pre["prompt_tokens"] >= pre["admissions"]


def test_request_log_is_bounded_and_feeds_the_phase_latencies():
    """Pure-unit check of ``ServeMetrics``' per-request log: it keeps the
    last ``window`` requests, and ``/status``' phase series read it."""
    from repro.serve import ServeMetrics

    m = ServeMetrics(window=3)
    for i in range(5):
        t = 10.0 * i
        for event, dt in (("received", 0.0), ("submitted", 0.001),
                          ("admit_start", 0.041), ("admitted", 0.091),
                          ("first_taken", 0.092), ("first_frame", 0.094)):
            m.stamp(f"r{i}", event, t + dt)
    log = m.request_log()
    assert [e["uid"] for e in log] == ["r2", "r3", "r4"]
    lat = m.snapshot()["latency_ms"]
    for series, ms in (("queue_wait", 40.0), ("admission", 50.0),
                       ("frontdoor", 3.0)):
        assert lat[series]["p50"] == pytest.approx(ms)


def test_request_log_events_are_in_order(server):
    """Each request's log: HTTP read <= handed to the engine thread <=
    popped by the engine <= first token on the host <= first token taken
    by the front door <= its SSE frame drained."""
    from repro.serve.metrics import REQUEST_EVENTS

    server.wait_idle()
    events = list(api_client.stream_completion(
        server.host, server.port, {"prompt": PROMPT, "max_tokens": 3}))
    assert len(events) == 3
    log = server.gateway.metrics.request_log()
    uid = events[0]["id"]
    mine = [e for e in log if e["uid"] == uid]
    assert len(mine) == 1 and set(mine[0]) == {"uid", *REQUEST_EVENTS}
    for entry in log:
        times = [entry[k] for k in REQUEST_EVENTS if k in entry]
        assert times == sorted(times), entry


def test_status_prefix_hits_after_shared_prefix_traffic(server):
    """Two completions sharing a long prefix: the second hits, and the
    gauges in /status move (hit-rate visible over the wire)."""
    server.wait_idle()
    shared = list(range(1, 13))  # 3 pages at chunk=4
    api_client.completion(server.host, server.port,
                          {"prompt": shared + [40], "max_tokens": 2})
    pre = api_client.get_status(server.host,
                                server.port)["prefix_cache"]
    api_client.completion(server.host, server.port,
                          {"prompt": shared + [50, 51], "max_tokens": 2})
    server.wait_idle()
    post = api_client.get_status(server.host,
                                 server.port)["prefix_cache"]
    assert post["hits"] > pre["hits"]
    assert post["prefill_tokens_saved"] > pre["prefill_tokens_saved"]
    assert post["nodes"] >= 1 and post["pages"]["used"] >= post["nodes"]


def test_error_paths(server):
    server.wait_idle()
    host, port = server.host, server.port
    with pytest.raises(api_client.APIError) as e:
        api_client.completion(host, port, {"prompt": [], "max_tokens": 4})
    assert e.value.status == 400
    with pytest.raises(api_client.APIError) as e:
        api_client.completion(host, port,
                              {"prompt": PROMPT, "max_tokens": PAGE_LEN})
    assert e.value.status == 400 and "page_len" in str(e.value)
    with pytest.raises(api_client.APIError) as e:
        api_client.completion(host, port, {"max_tokens": 4})  # no prompt
    assert e.value.status == 400
    with pytest.raises(api_client.APIError) as e:
        api_client.request_json(host, port, "GET", "/v1/completions")
    assert e.value.status == 405
    with pytest.raises(api_client.APIError) as e:
        api_client.request_json(host, port, "GET", "/nope")
    assert e.value.status == 404
    # malformed JSON body
    with socket.create_connection((host, port), 10) as sock:
        sock.settimeout(10)
        sock.sendall(b"POST /v1/completions HTTP/1.1\r\nHost: t\r\n"
                     b"Content-Length: 9\r\nConnection: close\r\n\r\n"
                     b"not json!")
        assert b"HTTP/1.1 400" in sock.recv(65536)


def test_deadline_over_http_times_out(server):
    server.wait_idle()
    out = api_client.completion(
        server.host, server.port,
        {"prompt": PROMPT, "max_tokens": LONG, "deadline_ms": 1})
    choice = out["choices"][0]
    assert choice["finish_reason"] == "timeout"
    # partial output only — and still a prefix of the reference decode
    assert len(choice["tokens"]) < LONG
    ref = server.ref(PROMPT, LONG)
    assert choice["tokens"] == ref[:len(choice["tokens"])]


# ---------------------------------------------------------------------------
# lifecycle under load: disconnect eviction, 429 backpressure, e2e
# ---------------------------------------------------------------------------
def test_mid_stream_disconnect_evicts_slot(server):
    server.wait_idle()
    before = server.gateway.metrics.snapshot()["requests"][
        "by_finish_reason"].get("cancelled", 0)
    gen = api_client.stream_completion(
        server.host, server.port, {"prompt": PROMPT, "max_tokens": LONG})
    first = next(gen)  # at least one token arrived: the slot is live
    assert first["choices"][0]["token"] == server.ref(PROMPT, LONG)[0]
    assert server.engine.n_active >= 1
    gen.close()  # client hangs up mid-stream
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline and server.engine.n_active:
        time.sleep(0.005)
    assert server.engine.n_active == 0, "disconnect did not evict the slot"
    server.wait_idle()
    after = server.gateway.metrics.snapshot()["requests"][
        "by_finish_reason"].get("cancelled", 0)
    assert after == before + 1


def _hold_stream(server, results, i, budget=LONG):
    """Worker: stream one completion to the end (no retry)."""
    try:
        toks = [e["choices"][0]["token"] for e in api_client.stream_completion(
            server.host, server.port,
            {"prompt": PROMPT, "max_tokens": budget})]
        results[i] = toks
    except Exception as e:  # surfaced by the asserting test
        results[i] = e


def test_saturation_answers_429_with_retry_after(server):
    """Deterministic backpressure: fill every slot and the whole waiting
    queue with long streams, then the next request must bounce."""
    server.wait_idle()
    n_hold = MAX_SLOTS + MAX_QUEUE
    base = server.gateway.metrics.snapshot()["requests"]["submitted"]
    results = [None] * n_hold
    threads = [threading.Thread(target=_hold_stream,
                                args=(server, results, i), daemon=True)
               for i in range(n_hold)]
    # stagger the holders so each lands below the watermark (a burst
    # would trip admission control on the holders themselves): final
    # state is exactly MAX_SLOTS decoding + MAX_QUEUE waiting
    for i, t in enumerate(threads):
        t.start()
        deadline = time.monotonic() + 30
        while (time.monotonic() < deadline
               and server.gateway.metrics.snapshot()["requests"]["submitted"]
               < base + i + 1):
            time.sleep(0.002)
    assert server.gateway.queue_depth() >= MAX_QUEUE
    with pytest.raises(api_client.RetryLater) as e:
        api_client.completion(server.host, server.port,
                              {"prompt": PROMPT, "max_tokens": 2})
    assert e.value.retry_after >= 1
    rejected = server.gateway.metrics.snapshot()["requests"]["rejected"]
    assert rejected >= 1
    for t in threads:
        t.join(timeout=120)
    ref = server.ref(PROMPT, LONG)
    for r in results:
        assert r == ref  # saturation never corrupted the held streams


def _retrying_stream(server, results, i, prompt, budget):
    """Worker: stream with 429-retry (bounded) — the well-behaved client."""
    for _ in range(200):
        try:
            toks = [e["choices"][0]["token"]
                    for e in api_client.stream_completion(
                        server.host, server.port,
                        {"prompt": prompt, "max_tokens": budget})]
            results[i] = ("ok", toks)
            return
        except api_client.RetryLater as e:
            results[i] = ("retrying", e.retry_after)
            time.sleep(min(e.retry_after, 0.25))
        except Exception as e:
            results[i] = ("error", e)
            return
    results[i] = ("error", RuntimeError("still 429 after 200 tries"))


def test_e2e_concurrent_clients_disconnect_and_retry(server):
    """Acceptance: 6 streaming clients against 2 slots — all complete
    with reference-exact tokens; a 7th disconnects mid-stream and an
    8th is driven through an explicit 429-then-retry cycle."""
    server.wait_idle()
    jobs = [(PROMPT[:1 + (i % 5)], 6 + 3 * (i % 4)) for i in range(6)]
    refs = [server.ref(p, n) for p, n in jobs]
    results = [None] * 6
    threads = [threading.Thread(target=_retrying_stream,
                                args=(server, results, i, p, n), daemon=True)
               for i, (p, n) in enumerate(jobs)]
    # one misbehaving client: connect, take two events, vanish
    disconnector = api_client.stream_completion(
        server.host, server.port, {"prompt": PROMPT, "max_tokens": LONG})
    next(disconnector)
    for t in threads:
        t.start()
    next(disconnector)
    disconnector.close()
    # one explicitly throttled client: force a 429 first, then retry
    saw_429 = False
    for _ in range(400):
        try:
            out = api_client.completion(
                server.host, server.port,
                {"prompt": PROMPT, "max_tokens": 4})
            break
        except api_client.RetryLater as e:
            saw_429 = True
            time.sleep(min(e.retry_after, 0.1))
    else:
        pytest.fail("throttled client never got through")
    assert out["choices"][0]["tokens"] == server.ref(PROMPT, 4)
    for t in threads:
        t.join(timeout=180)
    for (p, n), ref, res in zip(jobs, refs, results):
        assert res is not None and res[0] == "ok", res
        assert res[1] == ref, (p, n)
    server.wait_idle()
    assert server.engine.n_active == 0
    assert server.engine._alloc.n_used == 0
    # the fleet was bigger than the slot count the whole way through
    assert len(jobs) > MAX_SLOTS
    del saw_429  # informative only: saturation timing may let it through
