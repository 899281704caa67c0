"""From a profiler trace to device busy time, kernel time and exposed
collectives.

``capture`` writes one JAX profiler trace of a window; ``device_ops`` reads
the operations that ran on each chip out of its ``.xplane.pb``; ``summarize``
reduces them.  Busy time is the union of the operations' intervals on a
chip, averaged over the chips; a collective is exposed where it runs and no
other operation does.
"""

from __future__ import annotations

import contextlib
import glob
import os
import re
import shutil
from typing import Dict, Iterable, List, Sequence, Tuple

# (name, start_ns, end_ns)
Op = Tuple[str, float, float]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(r"^(all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all)")


@contextlib.contextmanager
def capture(logdir: str):
    """Profile the block into ``logdir`` (emptied first), Python tracing off."""
    import jax

    shutil.rmtree(logdir, ignore_errors=True)
    os.makedirs(logdir, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(logdir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def xplane_file(logdir: str) -> str:
    found = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return found[-1]


def device_ops(path: str, line: str = OPS_LINE) -> Dict[int, List[Op]]:
    """The operations of each TPU chip in the trace, by chip index."""
    from jax.profiler import ProfileData

    out: Dict[int, List[Op]] = {}
    for plane in ProfileData.from_file(path).planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        ops = out.setdefault(int(m.group(1)), [])
        for ln in plane.lines:
            if ln.name != line:
                continue
            for ev in ln.events:
                ops.append((ev.name, float(ev.start_ns), float(ev.start_ns + ev.duration_ns)))
    return out


_SUFFIX = re.compile(r"\.\d+$")


def short(name: str) -> str:
    """An operation's name without its HLO text and instance number:
    ``%lmme_kernel_call.390 = (...) custom-call(...)`` -> ``lmme_kernel_call``."""
    return _SUFFIX.sub("", name.split(" = ", 1)[0].lstrip("%"))


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def length(intervals: Sequence[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: Sequence[Tuple[float, float]],
             b: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Parts of the disjoint sorted intervals ``a`` that no interval of the
    disjoint sorted ``b`` covers."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def summarize(ops: Dict[int, List[Op]], window_s: float,
              kernel: "re.Pattern[str]", top: int = 10) -> Dict:
    """Device time over a traced window of ``window_s`` seconds, averaged
    over the chips: ``busy_s``, ``kernel_s`` (operations whose name matches
    ``kernel``), ``collective_s`` and ``collective_exposed_s``, and the
    ``top`` operations by time (instances summed under their short name;
    collectives are known by it, kernels by ``kernel`` on the full name)
    and the longest gaps between operations."""
    n = max(len(ops), 1)
    busy = kern = coll = exposed = 0.0
    by_name: Dict[str, float] = {}
    gaps: List[Tuple[str, float]] = []
    for evs in ops.values():
        spans = union((s, e) for _, s, e in evs)
        busy += length(spans)
        kern += length(union((s, e) for name, s, e in evs if kernel.search(name)))
        c = union((s, e) for name, s, e in evs if COLLECTIVE.match(short(name)))
        other = union((s, e) for name, s, e in evs if not COLLECTIVE.match(short(name)))
        coll += length(c)
        exposed += length(subtract(c, other))
        for name, s, e in evs:
            key = short(name)
            by_name[key] = by_name.get(key, 0.0) + (e - s)
        ordered = sorted(evs, key=lambda o: o[1])
        end, last = None, None
        for name, s, e in ordered:
            if end is not None and s > end:
                gaps.append((f"after {short(last)}, before {short(name)}", (s - end) * 1e-9))
            if end is None or e > end:
                end, last = e, name
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": window_s,
        "busy_s": busy / n * 1e-9,
        "kernel_s": kern / n * 1e-9,
        "collective_s": coll / n * 1e-9,
        "collective_exposed_s": exposed / n * 1e-9,
        "device_ops": [[k, v / n * 1e-9] for k, v in ranked],
        "idle_gaps": [[k, v] for k, v in sorted(gaps, key=lambda g: -g[1])[:top]],
        "n_ops": sum(len(v) for v in ops.values()),
        "chips": len(ops),
    }
