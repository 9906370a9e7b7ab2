"""Reduction from a profiler trace to device busy time, idle time, per-op
time and the longest idle gaps.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps
what the reduction needs, as plain lists:

    {"devices": {plane: [[op, start_ns, dur_ns], ...]},
     "host":    [[name, start_ns, dur_ns], ...]}

``devices`` holds each device plane's ``XLA Ops`` line (an op's name is
its whole HLO text); ``host`` is the host thread line that holds the
benchmark's own spans.  ``reduce`` works on
that form only, so it can be checked on a small recorded trace.

The window is the host span ``bench.window``.  Busy time is the union of
the op intervals inside it, per device, averaged over the devices that ran
an op; the idle share is 1 - busy / window.  An idle gap is a stretch of
the window in which the first device ran no op; it is labelled by the
innermost host span open at its midpoint, inside the window span.
"""
from __future__ import annotations

import collections
import glob
import os

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"


def op_name(hlo: str) -> str:
    """``%fusion.12 = f32[8,128]{...} fusion(...)`` -> ``fusion.12``: an
    event's name is the op's whole HLO text, operands included."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def op_label(hlo: str) -> str:
    """The op's name and result shape, for the breakdown."""
    head, _, rest = hlo.partition(" = ")
    return f"{head.lstrip('%')} {rest.split('{', 1)[0].split(' ', 1)[0]}".strip()


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        [e.name, float(e.start_ns), float(e.duration_ns)]
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [[e.name, float(e.start_ns), float(e.duration_ns)]
                       for e in line.events]
                if any(e[0] == WINDOW for e in evs):
                    host = evs
    return {"devices": devices, "host": host}


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def window(tr: dict):
    spans = [e for e in tr["host"] if e[0] == WINDOW]
    if not spans:
        raise ValueError("trace has no bench.window span")
    _, s, d = spans[0]
    return s, s + d


def _leaves(events):
    """The events that enclose no other event of their line (a while loop's
    event encloses the ops of its body)."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out = []
    for i, e in enumerate(evs):
        nxt = evs[i + 1] if i + 1 < len(evs) else None
        if nxt is None or nxt[1] >= e[1] + e[2]:
            out.append(e)
    return out


def _clipped(events, w0, w1):
    for name, s, d in events:
        a, b = max(s, w0), min(s + d, w1)
        if b > a:
            yield name, a, b


def reduce(tr: dict, top: int = 10) -> dict:
    """busy_s, window_s, idle_share, per-op seconds and labelled idle gaps."""
    w0, w1 = window(tr)
    win = (w1 - w0) * 1e-9
    busy, ops = [], collections.Counter()
    first = None
    for plane in sorted(tr["devices"]):
        iv = [(a, b) for _, a, b in _clipped(tr["devices"][plane], w0, w1)]
        if not iv:
            continue
        merged = _union(iv)
        busy.append(sum(b - a for a, b in merged) * 1e-9)
        if first is None:
            first = merged
        for name, a, b in _clipped(_leaves(tr["devices"][plane]), w0, w1):
            ops[op_label(name)] += (b - a) * 1e-9
    if not busy:
        raise ValueError("no device op ran in the traced window")
    busy_s = sum(busy) / len(busy)
    gaps = collections.Counter()
    edges = [w0] + [x for iv in first for x in iv] + [w1]
    spans = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    host = sorted((e for e in tr["host"] if e[0] != WINDOW),
                  key=lambda e: (e[1], -e[2]))
    mids = [(a + b) / 2 for a, b in spans]
    for (a, b), label in zip(spans, _labels(host, mids)):
        gaps[label] += (b - a) * 1e-9
    return {"busy_s": busy_s, "window_s": win,
            "idle_share": 1.0 - busy_s / win,
            "device_ops": [[k, v] for k, v in ops.most_common(top)],
            "idle_gaps": [[k, v] for k, v in gaps.most_common(top)],
            "devices": len(busy)}


def _labels(host, times):
    """For each of the ascending ``times``: the innermost host span open
    then, with its innermost enclosing benchmark span when that is another.
    Spans of one thread nest, so a stack sweep finds them."""
    stack, out, j = [], [], 0
    for t in times:
        while j < len(host) and host[j][1] <= t:
            e = host[j]
            while stack and stack[-1][1] + stack[-1][2] < e[1]:
                stack.pop()
            stack.append(e)
            j += 1
        while stack and stack[-1][1] + stack[-1][2] < t:
            stack.pop()
        if not stack:
            out.append(WINDOW)
            continue
        inner = stack[-1]
        outer = next((e for e in reversed(stack)
                      if e[0].startswith("bench.")), None)
        if outer is not None and outer is not inner:
            out.append(f"{outer[0]} > {inner[0]}")
        else:
            out.append(inner[0])
    return out


def kernel_time(tr: dict, prefix: str):
    """Seconds and event count of the ops whose name starts with ``prefix``
    (a Pallas kernel's op takes the name of its jitted wrapper), inside the
    window, summed over devices."""
    w0, w1 = window(tr)
    total, count = 0.0, 0
    for events in tr["devices"].values():
        for name, a, b in _clipped(events, w0, w1):
            if op_name(name).startswith(prefix):
                total += (b - a) * 1e-9
                count += 1
    return total, count
