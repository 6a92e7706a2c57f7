"""Reduce a ``jax.profiler`` trace (``.xplane.pb``) of one window to device
numbers: busy and idle time, device time per program and per operation
(its own time, less the operations nested in it, as a loop's body),
collective time and the part of it no other operation overlaps, and the
longest idle gaps with what the host was doing in them.

The window is the host span the benchmark opens around its measured work
(``bench.window``, a ``jax.profiler.TraceAnnotation``); device events are
clipped to it.  Only ``jax`` is needed to read the file (``ProfileData``),
so no TPU library loads to reduce a trace.
"""
from __future__ import annotations

import heapq
from collections import defaultdict

WINDOW = "bench.window"
NAME_CHARS = 120
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")


def _union(intervals):
    """Merged, sorted [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _length(merged) -> int:
    return sum(e - s for s, e in merged)


def _overlap(a, b) -> int:
    """Total overlap of two merged interval lists."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def load(path: str) -> dict:
    """The trace as plain data: {plane name: {line name: [(event name,
    start ns, end ns)]}}."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    return {p.name: {line.name: [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                                 for ev in line.events] for line in p.lines}
            for p in data.planes}


def _events(events, lo, hi):
    for name, s, e in events:
        if e > lo and s < hi:
            yield name, max(s, lo), min(e, hi)


def _device_planes(planes: dict, num_devices: int):
    names = sorted(n for n, lines in planes.items()
                   if n.startswith("/device:") and "XLA Ops" in lines)
    if len(names) < num_devices:
        raise ValueError(f"trace has {len(names)} device planes with XLA ops; "
                         f"{num_devices} expected")
    return [planes[n] for n in names[:num_devices]]


def _host_spans(planes: dict):
    """Every host event: (name, start, end)."""
    return [ev for n, lines in planes.items() if n.startswith("/host:")
            for events in lines.values() for ev in events]


def _innermost(host, points):
    """For each of ``points`` (ascending), the name of the shortest host
    span other than the window around it (start <= t < end), or None.  One
    sweep: spans join a heap keyed on their length as the sweep reaches
    their start, and one that has ended leaves it once it reaches the top,
    as no later point lies inside it either."""
    spans = sorted((s, e, n) for n, s, e in host if n != WINDOW)
    heap, out, i = [], [], 0
    for t in points:
        while i < len(spans) and spans[i][0] <= t:
            s, e, n = spans[i]
            heapq.heappush(heap, (e - s, n, e))
            i += 1
        while heap and heap[0][2] <= t:
            heapq.heappop(heap)
        out.append(heap[0][1] if heap else None)
    return out


def _self_times(events):
    """[name, start, end, self ns, leaf] of each event of a line whose
    events nest (a loop op holds the ops of its body): its own time is its
    time less its children's, and a leaf has no children."""
    out, stack = [], []
    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][2] <= s:
            stack.pop()
        row = [name, s, e, e - s, True]
        if stack:
            stack[-1][3] -= e - s
            stack[-1][4] = False
        stack.append(row)
        out.append(row)
    return out


def _is_collective(name: str) -> bool:
    low = name.lower()
    return any(c in low for c in COLLECTIVES)


def reduce(path: str, num_devices: int = 1, top: int = 10) -> dict:
    """The reduction of the trace file at ``path``."""
    return reduce_planes(load(path), num_devices, top)


def reduce_planes(planes: dict, num_devices: int = 1, top: int = 10) -> dict:
    """The reduction of a trace given as ``load`` returns it."""
    host = _host_spans(planes)
    wins = [(s, e) for n, s, e in host if n == WINDOW]
    if not wins:
        raise ValueError(f"no {WINDOW!r} host span in the trace")
    lo, hi = wins[0]
    window_ns = hi - lo
    busy, ops, modules, coll, exposed = [], defaultdict(float), {}, [], []
    gaps = []
    for lines in _device_planes(planes, num_devices):
        op_iv, coll_iv, other_iv = [], [], []
        for name, s, e, own, leaf in _self_times(_events(lines["XLA Ops"], lo, hi)):
            ops[name] += own / 1e9 / num_devices
            op_iv.append((s, e))
            if _is_collective(name):
                coll_iv.append((s, e))
            elif leaf:
                other_iv.append((s, e))
        merged = _union(op_iv)
        busy.append(_length(merged))
        if "XLA Modules" in lines:
            for name, s, e in _events(lines["XLA Modules"], lo, hi):
                m = modules.setdefault(name, {"count": 0, "seconds": 0.0})
                m["count"] += 1.0 / num_devices
                m["seconds"] += (e - s) / 1e9 / num_devices
        c = _union(coll_iv)
        coll.append(_length(c))
        exposed.append(_length(c) - _overlap(c, _union(other_iv)))
        prev = lo
        for s, e in merged + [[hi, hi]]:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
    # label each idle gap by the innermost host span around its midpoint
    idle = defaultdict(float)
    gaps.sort(key=lambda g: g[0] + g[1])
    for (s, e), name in zip(gaps, _innermost(host, [(s + e) // 2 for s, e in gaps])):
        idle[name or "host: no span"] += (e - s) / 1e9 / num_devices
    mean = lambda xs: sum(xs) / len(xs) / 1e9
    return {
        "window_s": window_ns / 1e9,
        "busy_s": mean(busy),
        "ops": dict(ops),
        "modules": modules,
        "collective_s": mean(coll),
        "collective_exposed_s": mean(exposed),
        "breakdown": {
            # an op's name is its HLO instruction; its head names it enough
            "device_ops": [[n[:NAME_CHARS], v]
                           for n, v in sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
            "idle_gaps": [[n, v] for n, v in sorted(idle.items(), key=lambda kv: -kv[1])[:top]],
        },
    }
