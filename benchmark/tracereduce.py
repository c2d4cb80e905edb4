"""From a jax.profiler capture to the device numbers of a traced run.

`events_from_dir` reads the `.xplane.pb` the capture wrote into a small
JSON-able record: per device plane, the operations of its "XLA Ops" line
(name, start and duration in seconds from the capture's start), and the
host's events that have a duration. `reduce` turns that record into

    busy_s       union of the intervals in which an operation ran on a
                 device, averaged over the devices that ran any
    device_op_s  sum of the durations of the outermost operations (the
                 "XLA Ops" line nests a loop's body inside the loop),
                 averaged likewise
    device_s     device_op_s per pass of the traced round
    idle_share   1 - busy_s / window_s
    breakdown    the ten operations with the most self time (duration
                 less the operations nested in it), named by the HLO
                 instruction, and the ten longest idle gaps, each named
                 by the host event that overlaps it most

A record with no device operation reduces to None: the metrics that read
it are then left out of the line.
"""

from __future__ import annotations

import glob
import os

DEVICE_PREFIX = "/device:"
OPS_LINES = ("XLA Ops",)
TOP = 10


def events_from_dir(logdir: str) -> dict:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        return {"device": {}, "host": [], "lines": {}}
    pd = ProfileData.from_file(paths[-1])
    device: dict = {}
    host: list = []
    lines: dict = {}
    for plane in pd.planes:
        plane_lines = list(plane.lines)
        lines[plane.name] = [ln.name for ln in plane_lines]
        if plane.name.startswith(DEVICE_PREFIX):
            ops = [ln for ln in plane_lines if ln.name in OPS_LINES]
            evs = [
                [e.name, e.start_ns / 1e9, e.duration_ns / 1e9]
                for ln in ops for e in ln.events
            ]
            if evs:
                device[plane.name] = evs
        elif plane.name.startswith("/host:"):
            host += [
                [e.name, e.start_ns / 1e9, e.duration_ns / 1e9]
                for ln in plane_lines for e in ln.events if e.duration_ns > 0
            ]
    return {"device": device, "host": host, "lines": lines}


def union(intervals: list) -> list:
    """Merged [start, end] intervals, sorted."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def self_times(evs: list) -> tuple[float, dict]:
    """(sum of outermost durations, {op name: self time})."""
    outer = 0.0
    selfs: dict = {}
    stack: list = []  # [end, name]
    for name, s, d in sorted(evs, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        short = name.split(" = ")[0]
        if stack:
            parent = stack[-1][1]
            selfs[parent] = selfs.get(parent, 0.0) - min(d, stack[-1][0] - s)
        else:
            outer += d
        selfs[short] = selfs.get(short, 0.0) + d
        stack.append([s + d, short])
    return outer, selfs


def _label(gap: tuple, host: list) -> str:
    s, e = gap
    best, name = 0.0, "host: no event"
    for n, hs, hd in host:
        overlap = min(e, hs + hd) - max(s, hs)
        if overlap > best:
            best, name = overlap, f"host: {n}"
    return name


def reduce(events: dict, n_passes: int, window_s: float) -> "dict | None":
    planes = {k: v for k, v in events["device"].items() if v}
    if not planes or window_s <= 0:
        return None
    busy, op_s, totals, gaps = [], [], {}, []
    for evs in planes.values():
        clipped = [(max(0.0, s), min(window_s, s + d)) for _, s, d in evs]
        merged = union([c for c in clipped if c[1] > c[0]])
        busy.append(sum(e - s for s, e in merged))
        outer, selfs = self_times(evs)
        op_s.append(outer)
        for name, t in selfs.items():
            totals[name] = totals.get(name, 0.0) + t
        edges = [0.0] + [x for iv in merged for x in iv] + [window_s]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    busy_s = sum(busy) / len(busy)
    device_op_s = sum(op_s) / len(op_s)
    longest = sorted(gaps, key=lambda g: g[1] - g[0], reverse=True)[:TOP]
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "device_op_s": device_op_s,
        "device_s": device_op_s / n_passes if n_passes else None,
        "idle_share": 1.0 - busy_s / window_s,
        "breakdown": {
            "device_ops": sorted(([n, t / len(planes)] for n, t in totals.items()),
                                 key=lambda x: x[1], reverse=True)[:TOP],
            "idle_gaps": [[_label(g, events["host"]), g[1] - g[0]] for g in longest],
        },
    }
