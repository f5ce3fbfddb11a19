"""Reduce the program's own ``dx.*`` spans in a profiler trace.

The program writes ``jax.profiler.TraceAnnotation`` spans named ``dx.*``
on its flush path (``repro.plan.spans``); a ``dx.sync.*`` or ``dx.h2d.*``
span carries the bytes it moved as the stat ``bytes``. Inside the
benchmark's ``window`` span this gives:

    spans         per span name: ``count``, ``total_s`` (clipped to the
                  window), ``bytes`` (sum of the stat) and ``idle_s``, the
                  first device's idle seconds while a span of that name
                  was open, at any depth
    idle_by_span  the first device's idle seconds, split by the innermost
                  ``dx.`` span open on the host at each instant (the one
                  that started last), ``outside`` where none was open; the
                  parts sum to the idle time
    window_s      length of the ``window`` span

A program without such spans gives empty ``spans`` and all idle time
``outside``; the readers of ``bench/metrics`` then report nothing.

``of(run)`` reduces the trace of one ``--trace 1`` run: the newest trace
file under the checkout's ``.bench_out``, taken only if its ``window`` span
is the one the run's summary (``trace_reduce``) measured; where none is, a
line on standard error says why. Run as a script it prints the reduction
of one trace file as JSON.
"""
from __future__ import annotations

import functools
import gzip
import heapq
import json
import sys
from pathlib import Path

import trace_reduce

PREFIX = "dx."
OUTSIDE = "outside"
ROOT = Path(__file__).resolve().parent.parent


def _program_spans(planes, lo: float, hi: float) -> list:
    """(start, end, name, bytes) of the host's ``dx.`` events that overlap
    ``[lo, hi]``, clipped to it."""
    out = []
    for p in planes:
        if not p.name.startswith("/host:"):
            continue
        for ln in p.lines:
            for e in ln.events:
                if not e.name.startswith(PREFIX):
                    continue
                start, end = e.start_ns, e.start_ns + e.duration_ns
                if not (start < hi and (end > lo or start >= lo)):
                    continue
                nbytes = dict(getattr(e, "stats", ())).get("bytes", 0)
                out.append((max(start, lo), min(end, hi), e.name,
                            int(nbytes)))
    return out


def _overlap(a: list, b: list) -> float:
    """Total length of the intersection of two sorted lists of disjoint
    ``[start, end]`` intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_by_span(spans: list, idle: list) -> dict:
    """Split each idle interval exactly by the innermost span open over
    it: the latest-started (an earlier end breaks a tie, as a nested span
    ends first). ``spans``: (start, end, name, ...); ``idle``: sorted
    disjoint ``[start, end]``. Returns {name: length}, ``outside`` where no
    span was open."""
    cuts = sorted({x for s, e, *_ in spans for x in (s, e)}
                  | {x for iv in idle for x in iv})
    opening = sorted(spans, key=lambda sp: (sp[0], sp[1]))
    heap, out, j, k = [], {}, 0, 0
    for a, b in zip(cuts, cuts[1:]):
        while j < len(opening) and opening[j][0] <= a:
            s, e, name = opening[j][:3]
            heapq.heappush(heap, (-s, e, name))
            j += 1
        while heap and heap[0][1] <= a:
            heapq.heappop(heap)
        while k < len(idle) and idle[k][1] <= a:
            k += 1
        # idle bounds are cuts, so [a, b] lies inside one gap or outside all
        if k < len(idle) and idle[k][0] <= a:
            name = heap[0][2] if heap else OUTSIDE
            out[name] = out.get(name, 0.0) + (b - a)
    return out


def reduce_program(planes) -> dict:
    planes = list(planes)
    windows = [(s, e) for s, e, n in trace_reduce._host_spans(planes)
               if n == trace_reduce.WINDOW]
    if not windows:
        raise ValueError("trace holds no 'window' span")
    lo, hi = windows[0]
    devices = trace_reduce._device_planes(planes)
    if not devices:
        raise ValueError("trace holds no device plane")
    busy = trace_reduce.union(trace_reduce._op_events(devices[0]), lo, hi)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    idle = [[s, e] for s, e in zip(edges[0::2], edges[1::2]) if e > s]
    spans = _program_spans(planes, lo, hi)
    by_name = {}
    for s, e, name, nbytes in spans:
        by_name.setdefault(name, []).append((s, e, nbytes))
    stats = {}
    for name, evs in sorted(by_name.items()):
        stats[name] = {
            "count": len(evs),
            "total_s": sum(e - s for s, e, _ in evs) / 1e9,
            "bytes": sum(b for *_, b in evs),
            "idle_s": _overlap(trace_reduce.union(evs, lo, hi), idle) / 1e9,
        }
    split = idle_by_span(spans, idle)
    return {"spans": stats,
            "idle_by_span": {k: v / 1e9 for k, v in
                             sorted(split.items(), key=lambda kv: -kv[1])},
            "window_s": (hi - lo) / 1e9}


def _planes(path):
    from jax.profiler import ProfileData
    if str(path).endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read()).planes
    return ProfileData.from_file(str(path)).planes


def reduce_file(path) -> dict:
    """Reduce an ``.xplane.pb`` file, or a gzipped one (``.gz``)."""
    return reduce_program(_planes(path))


@functools.lru_cache(maxsize=1)
def _reduce_cached(path: str, mtime_ns: int) -> dict:
    return reduce_file(path)


def of(run, root: Path = ROOT) -> dict | None:
    """The program's spans in this run's trace, or None where the run has
    no trace of its own to read."""
    if not run.trace:
        return None
    found = sorted((root / ".bench_out").rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime_ns)
    if not found:
        return _missing(f"no trace file under {root / '.bench_out'}")
    path = found[-1]
    try:
        prog = _reduce_cached(str(path), path.stat().st_mtime_ns)
    except (OSError, ValueError) as e:   # unreadable, or not a run's trace
        return _missing(f"{path}: {e}")
    if prog["window_s"] != run.trace["window_s"]:
        return _missing(f"{path} is another run's trace")
    return prog


def _missing(why: str) -> None:
    """A traced run whose own trace cannot be read: say so, report
    nothing."""
    print(f"program_trace: {why}; the program's metrics are left out",
          file=sys.stderr)
    return None


def windows(prog: dict | None) -> int:
    """Flush windows in the trace: the number of ``dx.flush`` spans."""
    if not prog:
        return 0
    return prog["spans"].get("dx.flush", {}).get("count", 0)


if __name__ == "__main__":
    print(json.dumps(reduce_file(sys.argv[1]), indent=1))
