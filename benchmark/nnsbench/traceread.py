"""Reduction of a ``jax.profiler`` trace to the device's busy seconds,
its busiest operations and its longest idle gaps. The trace is first
turned into plain lists (``load``), so the same arithmetic runs on the
small synthetic trace ``--selftest`` keeps.

Plain form: ``{"planes": [{"name": str, "lines": [{"name": str,
"events": [[name, start_ns, duration_ns], ...]}]}]}``."""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)")
OP_LINES = ("XLA Ops", "XLA Modules")     # first that exists is used
WINDOW_SPAN = "bench.trace_window"
SPAN_PREFIX = "bench."


def op_kind(name: str) -> str:
    """A device event's name as the trace gives it is the operation's
    whole HLO line (``%convert_reduce_fusion.32 = (f32[32,256]...``); an
    unrolled model has one such line a layer. Kept: the operation's name
    without ``%`` and without its trailing number, so that the layers'
    copies of one fusion add up under one name."""
    return re.sub(r"\.\d+$", "", name.split(" = ", 1)[0].lstrip("%"))


def load(trace_dir: str) -> dict:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    planes = []
    for plane in data.planes:
        device = DEVICE_PLANE.match(plane.name)
        lines = []
        for line in plane.lines:
            if device and line.name not in OP_LINES:
                continue
            events = [[op_kind(ev.name) if device else ev.name,
                       int(ev.start_ns), int(ev.duration_ns)]
                      for ev in line.events
                      if device or ev.name.startswith(SPAN_PREFIX)]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def describe(trace_dir: str) -> list:
    """Every plane and line with its event count: for a look by hand."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    out = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        for line in plane.lines:
            evs = list(line.events)
            out.append((plane.name, line.name, len(evs),
                        evs[0].name if evs else ""))
    return out


def union(intervals):
    """Merge [start, end) intervals; returns them sorted and disjoint."""
    merged = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return merged


def _clip(intervals, lo, hi):
    return [[max(a, lo), min(b, hi)] for a, b in intervals
            if min(b, hi) > max(a, lo)]


def reduce(trace: dict, top: int = 10) -> dict:
    """busy_s (averaged over the device planes), window_s, the ``top``
    device operations by summed seconds, and the ``top`` idle-gap causes:
    each gap on the first device goes to the harness span that covers
    most of it, else to ``unattributed``."""
    spans = []                     # harness spans on the host: name, lo, hi
    window = None
    devices = []
    for plane in trace["planes"]:
        if DEVICE_PLANE.match(plane["name"]):
            by_name = {ln["name"]: ln["events"] for ln in plane["lines"]}
            for want in OP_LINES:
                if want in by_name:
                    devices.append((plane["name"], by_name[want]))
                    break
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name == WINDOW_SPAN:
                    window = [start, start + dur]
                elif name.startswith(SPAN_PREFIX):
                    spans.append((name, start, start + dur))
    if not devices:
        raise ValueError("the trace holds no device plane with an "
                         f"operations line {OP_LINES}")
    if window is None:
        lo = min(e[1] for _, evs in devices for e in evs)
        hi = max(e[1] + e[2] for _, evs in devices for e in evs)
        window = [lo, hi]
    lo, hi = window
    busy, ops = [], {}
    first_busy = None
    for _, events in sorted(devices):
        merged = _clip(union([s, s + d] for _, s, d in events), lo, hi)
        busy.append(sum(b - a for a, b in merged))
        if first_busy is None:
            first_busy = merged
        for name, s, d in events:
            a, b = max(s, lo), min(s + d, hi)
            if b > a:
                ops[name] = ops.get(name, 0) + (b - a)
    n = len(devices)
    gaps = {}
    edge = lo
    for a, b in first_busy + [[hi, hi]]:
        if a > edge:
            cover = {}
            for name, s, e in spans:
                o = min(e, a) - max(s, edge)
                if o > 0:
                    cover[name] = cover.get(name, 0) + o
            best = max(cover, key=cover.get) if cover else None
            who = best if best and cover[best] * 2 >= (a - edge) \
                else "unattributed"
            gaps[who] = gaps.get(who, 0) + (a - edge)
        edge = max(edge, b)

    def ranked(d):
        return [[k, v / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {"busy_s": sum(busy) / n / 1e9, "window_s": (hi - lo) / 1e9,
            "devices": n,
            "device_ops": [[k, v / n] for k, v in ranked(ops)],
            "idle_gaps": ranked(gaps)}


def idle_pct(reduced) -> float | None:
    """Share of the traced stretch in which no operation ran on the
    device, from ``reduce``'s result; None where there is no trace."""
    if not reduced or reduced["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])
