"""The program's own marks in a profiler trace: host spans and program names.

``bench/trace.py`` reduces the device plane's ``XLA Ops`` line.  This
module reads what the serving path adds on the same clock:

* ``host_spans``: the ``serve.`` spans (``repro.serve.metrics.span``) on
  the ``/host:CPU`` plane, one line per host thread;
* ``device_programs``: the device plane's ``XLA Modules`` line, one event
  per run of a compiled program (``jit_decode_k8(<fingerprint>)``);
* ``programs``: device seconds per program name, instances summed;
* ``labelled_gaps``: the longest idle gaps between device operations,
  each named by the engine thread's spans open over most of it
  (``serve.step>serve.admit>serve.first_token_sync``, or ``no span``),
  then by the device operations on either side, as ``trace.summarize``
  names them.

It also holds the per-request phases of ``ServeMetrics.request_log()``
that the span metrics of ``bench/metrics/`` take percentiles of.
"""

from __future__ import annotations

import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from bench import core
from bench.trace import DEVICE_PLANE, Op, short

HOST_PLANE = "/host:CPU"
MODULES_LINE = "XLA Modules"
PREFIX = "serve."
FRONT_DOOR = ("serve.http", "serve.sse_first")   # the event loop's spans

# (name, start_ns, end_ns, host line index)
Span = Tuple[str, float, float, int]

_PROGRAM = re.compile(r"^(?:jit_)?(.*?)(?:\(\d+\))?$")


def _planes(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path).planes


def host_spans(path: str) -> List[Span]:
    """The ``serve.`` spans of the host plane, by start time."""
    out: List[Span] = []
    for plane in _planes(path):
        if plane.name != HOST_PLANE:
            continue
        for i, ln in enumerate(plane.lines):
            for ev in ln.events:
                if ev.name.startswith(PREFIX):
                    out.append((ev.name, float(ev.start_ns),
                                float(ev.start_ns + ev.duration_ns), i))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def device_programs(path: str) -> Dict[int, List[Op]]:
    """The runs of compiled programs on each TPU chip, by chip index."""
    out: Dict[int, List[Op]] = {}
    for plane in _planes(path):
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        runs = out.setdefault(int(m.group(1)), [])
        for ln in plane.lines:
            if ln.name == MODULES_LINE:
                runs.extend((ev.name, float(ev.start_ns),
                             float(ev.start_ns + ev.duration_ns)) for ev in ln.events)
    return out


def program_name(event: str) -> str:
    """``jit_decode_k8(1234)`` -> ``decode_k8``."""
    return _PROGRAM.match(event).group(1)


def programs(runs: Dict[int, List[Op]]) -> List[List]:
    """``[name, seconds]`` per program, instances summed, averaged over
    the chips, longest first."""
    n = max(len(runs), 1)
    total: Dict[str, float] = {}
    for evs in runs.values():
        for name, s, e in evs:
            key = program_name(name)
            total[key] = total.get(key, 0.0) + (e - s)
    return [[k, v / n * 1e-9] for k, v in sorted(total.items(), key=lambda kv: -kv[1])]


def engine_spans(spans: Sequence[Span]) -> List[Span]:
    """The spans of the engine thread: the host line holding the most
    spans other than the front door's (a span open when the trace started
    is not in it, so a short trace may hold no ``serve.step``)."""
    counts: Dict[int, int] = {}
    for name, _, _, line in spans:
        if name not in FRONT_DOOR:
            counts[line] = counts.get(line, 0) + 1
    if not counts:
        return []
    line = max(counts, key=counts.get)
    return [s for s in spans if s[3] == line]


def label(start: float, end: float, spans: Iterable[Span]) -> str:
    """The spans open over more than half of ``[start, end)``, outermost
    first, joined by ``>``; ``no span`` where none is."""
    half = 0.5 * (end - start)
    cover = [s for s in spans if min(s[2], end) - max(s[1], start) > half]
    cover.sort(key=lambda s: (s[1], -s[2]))
    return ">".join(s[0] for s in cover) or "no span"


def gaps(ops: Dict[int, List[Op]]) -> List[Tuple[float, float, str, str]]:
    """Every stretch of a chip with no operation running, between its
    first and last operation: ``(start_ns, end_ns, op before, op after)``."""
    out = []
    for evs in ops.values():
        end, last = None, None
        for name, s, e in sorted(evs, key=lambda o: o[1]):
            if end is not None and s > end:
                out.append((end, s, last, name))
            if end is None or e > end:
                end, last = e, name
    return out


def labelled_gaps(ops: Dict[int, List[Op]], spans: Sequence[Span],
                  top: int = 10) -> List[List]:
    """The ``top`` longest idle gaps as ``[label, seconds]``, the label
    naming the engine thread's spans over the gap, then its neighbours:
    ``serve.step>serve.admit | after copy, before broadcast.2.clone``."""
    eng = engine_spans(spans)
    longest = sorted(gaps(ops), key=lambda g: g[0] - g[1])[:top]
    return [[f"{label(s, e, eng)} | after {short(a)}, before {short(b)}", (e - s) * 1e-9]
            for s, e, a, b in longest]


# -- per-request phases of the request log (ServeMetrics.request_log) --------

def phase_ms(entry: Dict[str, float], phase: str) -> Optional[float]:
    """One phase of a logged request, in ms, or None where its events
    are missing: ``queue_wait`` (handed to the engine thread -> popped
    from its queue), ``admission`` (popped -> first token on the host),
    ``frontdoor`` (HTTP read -> handed over, plus first token taken off
    the stream -> its SSE frame drained)."""
    try:
        if phase == "queue_wait":
            return (entry["admit_start"] - entry["submitted"]) * 1e3
        if phase == "admission":
            return (entry["admitted"] - entry["admit_start"]) * 1e3
        if phase == "frontdoor":
            return (entry["submitted"] - entry["received"]
                    + entry["first_frame"] - entry["first_taken"]) * 1e3
    except KeyError:
        return None
    raise ValueError(f"no phase {phase!r}")


def phase_p90_ms(requests: Optional[Sequence[Dict[str, float]]],
                 phase: str) -> Optional[float]:
    """p90 of a phase over the requests that logged it (None if none)."""
    vals = [v for v in (phase_ms(r, phase) for r in requests or ()) if v is not None]
    return core.quantile(vals, 0.9) if vals else None
