"""Reduce a JAX profiler trace (``.xplane.pb``) to device busy and idle time.

    busy_s        union of the intervals in which an operation ran on a
                  device, inside the benchmark's ``window`` span, averaged
                  over the devices
    window_s      length of the ``window`` span
    device_ops    seconds per device operation, summed over devices and
                  divided by their number, named ``module/op`` (the jitted
                  program and the HLO op's name, both without their ids)
    collective_s  the part of ``device_ops`` spent in collectives
    idle_gaps     the longest gaps between device operations on the first
                  device, each labelled by the benchmark span that was open
                  on the host at the gap's midpoint (``host`` where none was)

Run as a script it prints the reduction of one trace file as JSON.
"""
from __future__ import annotations

import bisect
import gzip
import json
import re
import sys
from typing import Iterable

WINDOW = "window"
HOST_SPANS = ("submit", "flush_async", "wait", "drain")
COLLECTIVE = re.compile(r"all[-_]to[-_]all|all[-_]gather|all[-_]reduce|"
                        r"reduce[-_]scatter|collective[-_]permute|"
                        r"\bsend\b|\brecv\b", re.I)
_SUFFIX = re.compile(r"(\.\d+)+$")
_OP_NAME = re.compile(r"^%?([\w.\-]+)\s*=")
_MODULE_ID = re.compile(r"\(\d+\)$")
TOP = 10


_DEVICE = re.compile(r"^/device:(TPU|GPU):\d+$")


def _device_planes(planes) -> list:
    """One plane per chip (not the host's, nor a runtime's custom plane)."""
    return [p for p in planes if _DEVICE.match(p.name)]


def op_name(text: str) -> str:
    """``%fusion.4 = f32[...] fusion(...)`` -> ``fusion``."""
    m = _OP_NAME.match(text)
    return _SUFFIX.sub("", m.group(1) if m else text.split(" ")[0])


def _op_events(plane) -> list:
    """(start_ns, end_ns, ``module/op``) of the device's operations: the
    ``XLA Ops`` line where the trace has one, otherwise every line but the
    module and step summaries."""
    lines = list(plane.lines)
    ops = [ln for ln in lines if ln.name == "XLA Ops"]
    if not ops:
        ops = [ln for ln in lines
               if ln.name not in ("XLA Modules", "Steps", "Framework Ops",
                                  "Framework Name Scope")]
    modules = sorted((e.start_ns, e.start_ns + e.duration_ns,
                      _MODULE_ID.sub("", e.name))
                     for ln in lines if ln.name == "XLA Modules"
                     for e in ln.events)
    starts = [m[0] for m in modules]
    out = []
    for ln in ops:
        for e in ln.events:
            i = bisect.bisect_right(starts, e.start_ns) - 1
            mod = modules[i][2] if i >= 0 and \
                e.start_ns < modules[i][1] else "?"
            out.append((e.start_ns, e.start_ns + e.duration_ns,
                        f"{mod}/{op_name(e.name)}"))
    return out


def union(intervals: Iterable[tuple], lo: float, hi: float) -> list:
    """Merged, clipped ``(start, end)`` intervals inside ``[lo, hi]``."""
    merged = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e, *_ in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _host_spans(planes) -> list:
    out = []
    for p in planes:
        if not p.name.startswith("/host:"):
            continue
        for ln in p.lines:
            for e in ln.events:
                if e.name == WINDOW or e.name in HOST_SPANS:
                    out.append((e.start_ns, e.start_ns + e.duration_ns,
                                e.name))
    return out


def _label(spans: list, t: float) -> str:
    best = None
    for s, e, name in spans:
        if name != WINDOW and s <= t < e and (best is None or s > best[0]):
            best = (s, name)
    return best[1] if best else "host"


def reduce_planes(planes) -> dict:
    planes = list(planes)
    spans = _host_spans(planes)
    windows = [(s, e) for s, e, n in spans if n == WINDOW]
    if not windows:
        raise ValueError("trace holds no 'window' span")
    lo, hi = windows[0]
    devices = _device_planes(planes)
    if not devices:
        raise ValueError("trace holds no device plane")
    busy, per_op, gaps = 0.0, {}, []
    for i, plane in enumerate(devices):
        events = _op_events(plane)
        merged = union(events, lo, hi)
        busy += sum(e - s for s, e in merged)
        for s, e, name in events:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                per_op[name] = per_op.get(name, 0.0) + d
        if i == 0:
            edges = [lo] + [x for iv in merged for x in iv] + [hi]
            for s, e in zip(edges[0::2], edges[1::2]):
                if e > s:
                    gaps.append((_label(spans, (s + e) / 2), e - s))
    n = len(devices)
    ops = sorted(((k, v / n / 1e9) for k, v in per_op.items()),
                 key=lambda kv: -kv[1])
    gaps.sort(key=lambda g: -g[1])
    return {
        "busy_s": busy / n / 1e9,
        "window_s": (hi - lo) / 1e9,
        "devices": n,
        "collective_s": sum(v for k, v in ops
                            if COLLECTIVE.search(k.split("/")[-1])),
        "device_ops": [[k, v] for k, v in ops[:TOP]],
        "idle_gaps": [[k, v / 1e9] for k, v in gaps[:TOP]],
    }


def reduce_file(path) -> dict:
    """Reduce an ``.xplane.pb`` file, or a gzipped one (``.gz``)."""
    from jax.profiler import ProfileData
    if str(path).endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return reduce_planes(
                ProfileData.from_serialized_xspace(f.read()).planes)
    return reduce_planes(ProfileData.from_file(str(path)).planes)


if __name__ == "__main__":
    print(json.dumps(reduce_file(sys.argv[1]), indent=1))
