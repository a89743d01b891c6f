"""Reduction of a profiler trace to device busy time, per-op device time
and idle gaps attributed to the host span open during them.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into plain
event lists; everything after that works on those lists alone, so the
arithmetic is checked on small hand-made traces.  Times are nanoseconds
on the trace's clock, on which the profiler puts host and device events
alike.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict

# the device plane's line of single operations, and of whole programs
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def load(trace_dir: str, host_spans: set[str]) -> dict:
    """Events of the newest trace under ``trace_dir``:

    ``ops`` and ``modules``: ``{device plane name: [(name, start, dur)]}``;
    ``spans``: ``[(name, start, dur)]`` of the host events whose name is in
    ``host_spans`` (the benchmark's own annotations), and ``host``: every
    event of the host thread that opened them."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no trace under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    out = {"ops": {}, "modules": {}, "spans": [], "host": []}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key:
                    out[key][plane.name] = [(op_name(e.name), e.start_ns,
                                             e.duration_ns)
                                            for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [(e.name, e.start_ns, e.duration_ns) for e in line.events]
                mine = [e for e in evs if e[0] in host_spans]
                if mine:
                    out["spans"].extend(mine)
                    out["host"].extend(evs)
    return out


def op_name(text: str) -> str:
    """An operation's name without its HLO text: ``%fusion.3 = f32[...]
    fusion(...)`` is ``fusion.3``; a program's name is left as it is."""
    if " = " in text:
        text = text.split(" = ", 1)[0]
    return text.lstrip("%")


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted ``(start, end)`` intervals."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy, lo: float, hi: float) -> list[tuple[float, float]]:
    """Idle intervals of ``[lo, hi]`` between merged busy intervals."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def innermost(host, t: float) -> str:
    """Name of the deepest host event open at time ``t`` (the one that
    started last among those covering it), or ``"host idle"``."""
    best = None
    for name, s, d in host:
        if s <= t < s + d and (best is None or s >= best[1]):
            best = (name, s)
    return best[0] if best else "host idle"


def reduce(tr: dict, top: int = 10) -> dict:
    """Busy and idle time over the window spanned by the benchmark's host
    spans, averaged over the devices that ran an operation.

    ``busy_s``: union of operation intervals; ``window_s``: the window;
    ``device_ops``: the ``top`` operations by summed device time;
    ``idle_gaps``: the ``top`` longest idle intervals, each named by the
    host event open at its midpoint; ``modules``: ``{program name:
    (seconds, runs)}`` summed over devices."""
    if not tr["spans"]:
        raise ValueError("the trace holds none of the benchmark's spans")
    lo = min(s for _, s, _ in tr["spans"])
    hi = max(s + d for _, s, d in tr["spans"])
    devices = [p for p, evs in tr["ops"].items() if evs]
    busy_ns, op_ns, op_counts, idle = 0.0, defaultdict(float), defaultdict(int), []
    for plane in devices:
        evs = [(n, s, d) for n, s, d in tr["ops"][plane] if s + d > lo and s < hi]
        for n, s, d in evs:
            op_ns[n] += d
            op_counts[n] += 1
        merged = union(clip([(s, s + d) for _, s, d in evs], lo, hi))
        busy_ns += sum(e - s for s, e in merged)
        idle.extend(gaps(merged, lo, hi))
    modules: dict = defaultdict(lambda: [0.0, 0])
    for plane, evs in tr["modules"].items():
        for n, s, d in evs:
            if s + d > lo and s < hi:
                modules[n][0] += d * 1e-9
                modules[n][1] += 1
    idle.sort(key=lambda g: g[0] - g[1])
    n_dev = max(1, len(devices))
    return {
        "busy_s": busy_ns * 1e-9 / n_dev,
        "window_s": (hi - lo) * 1e-9,
        "device_ops": [[n, t * 1e-9 / n_dev] for n, t in
                       sorted(op_ns.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[innermost(tr["host"], (s + e) / 2), (e - s) * 1e-9]
                      for s, e in idle[:top]],
        "op_ns": dict(op_ns),
        "op_counts": dict(op_counts),
        "modules": {n: tuple(v) for n, v in modules.items()},
        "n_devices": len(devices),
    }


def op_time(red: dict, prefix: str) -> tuple[float, int]:
    """(summed device seconds per device, event count) of the operations
    whose name starts with ``prefix`` (``kmeans_lloyd_step`` takes
    ``kmeans_lloyd_step.6``)."""
    ns = sum(v for n, v in red["op_ns"].items() if n.startswith(prefix))
    cnt = sum(v for n, v in red["op_counts"].items() if n.startswith(prefix))
    n_dev = max(1, red["n_devices"])
    return ns * 1e-9 / n_dev, cnt // n_dev
