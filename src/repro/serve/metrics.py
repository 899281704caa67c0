"""Serving metrics: counters, latency percentiles, a per-request log and
the serving path's profiler spans.

``ServeMetrics`` is the one mutable stats object the serving stack
shares: the gateway's engine thread records step/admission timings, the
async HTTP handlers record rejections and time-to-first-token, and the
``/status`` endpoint serializes a consistent ``snapshot()``.  Everything
is windowed host-side state — bounded deques and integer counters under
one lock — so recording never touches the device or allocates per event.

Latency percentiles are computed over sliding windows (last ``window``
events) rather than reservoir samples: serving dashboards care about
*recent* tail latency, and the windows are small enough to sort on every
snapshot.

``span(name, **ids)`` marks one stretch of host work at a layer boundary
(``serve.http``, ``serve.admit``, ``serve.decode``, ...) in the JAX
profiler's host plane, on the device trace's clock; with no profiler
running it costs one context-manager entry.  Names are ``serve.``
literals; identifiers (``uid=``, ``k=``) go in keyword arguments.
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict, deque
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation

#: A request's events in the per-request log, in the order they happen:
#: HTTP request read starts, gateway hands it to the engine thread, the
#: engine pops it from its queue, its first token is on the host (end of
#: admission), the front door takes that token off the stream, and its
#: SSE frame is drained to the socket.
REQUEST_EVENTS = ("received", "submitted", "admit_start", "admitted",
                  "first_taken", "first_frame")


def span(name: str, **ids: Any) -> TraceAnnotation:
    """A profiler span over a ``with`` block: ``name`` is a ``serve.``
    literal, ``ids`` (``uid=``, ``k=``) land as the event's arguments."""
    return TraceAnnotation(name, **ids)


def percentiles(values, pcts=(50, 90, 99)) -> Dict[str, float]:
    """``{"p50": ..., ...}`` in the values' own unit (empty -> zeros)."""
    out = {}
    vals = sorted(values)
    for p in pcts:
        if not vals:
            out[f"p{p}"] = 0.0
        else:
            idx = min(len(vals) - 1, int(len(vals) * p / 100))
            out[f"p{p}"] = float(vals[idx])
    return out


class ServeMetrics:
    """Thread-safe serving stats: counters + windowed latency percentiles.

    Recorded events:

    * ``record_submitted / record_rejected`` — admission outcomes (a
      rejection is the 429 backpressure path, never seen by the engine);
    * ``record_step(seconds, n_active)`` — host time of one
      ``Engine.step()`` (admission, decode dispatch and flush), the
      ``serve.step`` span;
    * ``record_first_token(seconds)`` — per-request time-to-first-token
      (submit -> first streamed token);
    * ``record_finished(reason, n_tokens, seconds)`` — terminal event
      with the request's total latency; ``reason`` is the engine's
      ``finish_reason`` (length/stop/timeout/cancelled);
    * ``stamp(uid, event)`` — one of a request's ``REQUEST_EVENTS`` in
      the per-request log (``time.monotonic``, the last ``window``
      requests), from which ``snapshot()`` derives queue wait,
      admission and front-door latencies.
    """

    def __init__(self, window: int = 2048):
        self._lock = threading.Lock()
        self._started = time.monotonic()
        self.n_submitted = 0
        self.n_rejected = 0
        self.n_steps = 0
        self.n_tokens = 0
        self.finish_reasons: Dict[str, int] = {}
        self._step_s: deque = deque(maxlen=window)
        self._ttft_s: deque = deque(maxlen=window)
        self._request_s: deque = deque(maxlen=window)
        self._busy_slots = 0  # n_active at the last recorded step
        self._window = window
        self._log: "OrderedDict[Any, Dict[str, float]]" = OrderedDict()

    # -- recording (any thread) --------------------------------------------
    def record_submitted(self) -> None:
        with self._lock:
            self.n_submitted += 1

    def record_rejected(self) -> None:
        with self._lock:
            self.n_rejected += 1

    def record_step(self, seconds: float, n_active: int) -> None:
        """Host time of one ``Engine.step()`` (the gateway's ``serve.step``
        span: admission, decode dispatch and flush), for
        ``latency_ms.engine_step``."""
        with self._lock:
            self.n_steps += 1
            self._step_s.append(seconds)
            self._busy_slots = n_active

    def record_first_token(self, seconds: float) -> None:
        with self._lock:
            self._ttft_s.append(seconds)

    def record_tokens(self, n: int) -> None:
        with self._lock:
            self.n_tokens += n

    def record_finished(self, reason: str, n_tokens: int,
                        seconds: Optional[float] = None) -> None:
        with self._lock:
            self.finish_reasons[reason] = self.finish_reasons.get(reason,
                                                                  0) + 1
            if seconds is not None:
                self._request_s.append(seconds)

    def stamp(self, uid, event: str, t: Optional[float] = None) -> None:
        """Log ``event`` (one of ``REQUEST_EVENTS``) of request ``uid`` at
        ``t`` (``time.monotonic()`` when not given).  The log keeps the
        last ``window`` requests; the first event of a uid opens its entry."""
        if t is None:
            t = time.monotonic()
        with self._lock:
            entry = self._log.get(uid)
            if entry is None:
                entry = self._log[uid] = {}
                if len(self._log) > self._window:
                    self._log.popitem(last=False)
            entry[event] = t

    # -- reading -------------------------------------------------------------
    def request_log(self) -> List[Dict[str, Any]]:
        """The per-request log, oldest request first: one dict per
        request, ``uid`` and the times (``time.monotonic``) of the
        events logged so far."""
        with self._lock:
            return [dict(ev, uid=uid) for uid, ev in self._log.items()]

    def _phases_ms(self) -> Dict[str, List[float]]:
        """Per logged request, in ms: ``queue_wait`` (submitted -> popped
        by the engine), ``admission`` (popped -> first token on the host),
        ``frontdoor`` (HTTP read -> submitted, plus first token taken ->
        its SSE frame drained).  Caller holds the lock."""
        out: Dict[str, List[float]] = {"queue_wait": [], "admission": [],
                                       "frontdoor": []}
        for ev in self._log.values():
            if "submitted" in ev and "admit_start" in ev:
                out["queue_wait"].append(
                    (ev["admit_start"] - ev["submitted"]) * 1e3)
            if "admit_start" in ev and "admitted" in ev:
                out["admission"].append(
                    (ev["admitted"] - ev["admit_start"]) * 1e3)
            if all(k in ev for k in ("received", "submitted", "first_taken",
                                     "first_frame")):
                out["frontdoor"].append(
                    (ev["submitted"] - ev["received"]
                     + ev["first_frame"] - ev["first_taken"]) * 1e3)
        return out

    def snapshot(self) -> dict:
        """One consistent stats dict (the ``/status`` payload core)."""
        with self._lock:
            uptime = max(time.monotonic() - self._started, 1e-9)
            n_finished = sum(self.finish_reasons.values())
            phases = self._phases_ms()
            return {
                "uptime_s": uptime,
                "requests": {
                    "submitted": self.n_submitted,
                    "finished": n_finished,
                    "rejected": self.n_rejected,
                    "by_finish_reason": dict(self.finish_reasons),
                },
                "throughput": {
                    "tokens_total": self.n_tokens,
                    "tokens_per_s": self.n_tokens / uptime,
                    "requests_per_s": n_finished / uptime,
                    "steps_total": self.n_steps,
                },
                "latency_ms": {
                    "engine_step": percentiles(
                        [s * 1e3 for s in self._step_s]),
                    "ttft": percentiles([s * 1e3 for s in self._ttft_s]),
                    "request": percentiles(
                        [s * 1e3 for s in self._request_s]),
                    **{k: percentiles(v) for k, v in phases.items()},
                },
                "busy_slots": self._busy_slots,
            }
