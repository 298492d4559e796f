"""The per-pipeline report: proctime / interlatency / framerate per element.

≙ the GstShark tracers the reference leans on (tools/tracing/README.md:
proctime, interlatency, framerate, queue-level) — but built in, since
this runtime owns its scheduler. Enable per pipeline::

    tracer = pipeline.enable_tracing()
    pipeline.run()
    print(tracer.report(pipeline))

It keeps no stamp and no hook of its own: the frame's birth is its
:class:`~.context.TraceContext` (``t0_ns``, wall clock), and the
per-hop record is ``spans.chain_span``, which feeds :meth:`Tracer.arrive`
for a pipeline that has tracing enabled — with ``NNS_TPU_OBS=0`` too
(the rings and the e2e histograms then stay off).

Semantics:
  * proctime      — time spent inside each element's chain (already
                    accumulated in Element.stats; surfaced here)
  * interlatency  — time from a buffer's FIRST entry into the pipeline
                    to its arrival at each element
  * framerate     — buffers/sec observed at each element
  * queue-level   — live fill of each queue element at report time
  * percentiles   — p50/p95/p99 of each series from a bounded
                    reservoir (O(1) per buffer, fixed memory), so tail
                    latency — the number a serving stack is judged on —
                    is observable beyond mean/peak
"""
from __future__ import annotations

import threading
import time
from typing import Any, Dict

from ..edge.broker import live_broker_stats
from ..tensors.transfer import transfer_stats
from .metrics import Reservoir


def _wire_summary(st: Dict[str, Any]) -> Dict[str, Any]:
    """Condense an element's wire_* counters (edge/wire.py) into the
    per-link block report() exposes; {} when the element never touched
    a socket, so non-networked elements stay uncluttered."""
    out: Dict[str, Any] = {}
    for key in ("wire_bytes_out", "wire_bytes_in",
                "wire_msgs_out", "wire_msgs_in"):
        if st.get(key):
            out[key[5:]] = st[key]
    raw, enc = st.get("wire_raw_bytes_out", 0), st.get("wire_enc_bytes_out", 0)
    if raw and enc:
        out["compress_ratio"] = round(raw / enc, 3)
    frames_out = st.get("wire_frames_out", 0)
    if frames_out:
        out["frames_out"] = frames_out
        out["pack_us_avg"] = round(
            st.get("wire_pack_ns", 0) / frames_out / 1e3, 2)
        msgs = st.get("wire_msgs_out", 0)
        if msgs:
            out["frames_per_msg"] = round(frames_out / msgs, 2)
    if st.get("wire_frames_in"):
        out["frames_in"] = st["wire_frames_in"]
    if st.get("wire_delta_keyframes") or st.get("wire_delta_diffs"):
        # delta codec sender: how much temporal redundancy the link shed
        out["delta"] = {
            "keyframes": st.get("wire_delta_keyframes", 0),
            "diffs": st.get("wire_delta_diffs", 0),
            "promotions": st.get("wire_delta_promotions", 0),
            "bytes_saved": st.get("wire_delta_bytes_saved", 0)}
    if st.get("wire_delta_keyframes_in") or st.get("wire_delta_diffs_in"):
        out["delta_in"] = {
            "keyframes": st.get("wire_delta_keyframes_in", 0),
            "diffs": st.get("wire_delta_diffs_in", 0)}
    return out


def _session_summary(st: Dict[str, Any], el=None) -> Dict[str, Any]:
    """Condense an element's session_* counters (edge/session.py) into
    the per-link delivery-guarantee block: sent/delivered, replays,
    dup-drops, DECLARED losses, ack traffic, heartbeat RTT. {} for
    sessionless elements so existing reports are unchanged. The numbers
    are exact by construction — the chaos harness asserts
    sent == delivered + declared_lost (+ in-flight) from this block."""
    out: Dict[str, Any] = {}
    for key, val in st.items():
        if key.startswith("session_") and val:
            out[key[8:]] = val
    pongs = st.get("session_pongs", 0)
    if pongs:
        out["rtt_us_avg"] = round(
            st.get("session_rtt_ns", 0) / pongs / 1e3, 1)
        out.pop("rtt_ns", None)
    # live (non-counter) gauges: ring fill, attached sessions, frames
    # awaiting a correlated result — whatever the element exposes
    info = getattr(el, "session_info", None)
    if callable(info):
        try:
            out.update(info() or {})
        except Exception:  # noqa: BLE001 — reporting must never raise
            pass
    return out


def _fusion_block(pipeline, report: Dict[str, Dict[str, Any]]
                  ) -> Dict[str, Any]:
    """Aggregate fusion-compiler stats: one sub-entry per FusedSegment
    (member count, jit cache hits/misses, p50 of the device-program
    dispatch latency observed as ``fusion/<name>``) plus pipeline
    totals. {} on unfused pipelines so existing reports are unchanged."""
    segments: Dict[str, Any] = {}
    for name, el in pipeline.elements.items():
        if not getattr(el, "IS_FUSED_SEGMENT", False):
            continue
        st = el.stats.snapshot()
        seg = {
            "elements": st.get("fused_elements", 0),
            "members": [m.name for m in getattr(el, "members", [])],
            "jit_hits": st.get("jit_hits", 0),
            "jit_misses": st.get("jit_misses", 0),
            # chips one dispatch of this segment's program spans: the
            # hit/miss and dispatch-latency numbers are per-PROGRAM
            # (per-mesh), not per-chip — a sharded batch is one
            # dispatch, so dividing by devices would undercount
            "devices": st.get("devices", 1) or 1,
        }
        # the dispatch-latency series is internal plumbing; fold it
        # into the segment entry instead of a top-level row
        series = report.pop(f"fusion/{name}", None)
        if series is not None:
            seg["dispatch_us_p50"] = series["interlatency_us_p50"]
            seg["dispatch_us_p95"] = series["interlatency_us_p95"]
        segments[name] = seg
    if not segments:
        return {}
    return {
        "segments": len(segments),
        "fused_elements": sum(s["elements"] for s in segments.values()),
        "jit_hits": sum(s["jit_hits"] for s in segments.values()),
        "jit_misses": sum(s["jit_misses"] for s in segments.values()),
        "devices": max(s["devices"] for s in segments.values()),
        "per_segment": segments,
    }


def _transfer_block(pipeline) -> Dict[str, Any]:
    """The overlapped-execution view: per-element in-flight window
    stats (occupancy, overlap ratio — from each element's
    ``transfer_report()``) plus the bidirectional coalescing service's
    achieved depths (upload/download frames-per-RPC). {} when nothing
    overlapped or coalesced, so existing reports are unchanged."""
    out: Dict[str, Any] = {}
    windows: Dict[str, Any] = {}
    for name, el in pipeline.elements.items():
        rep = getattr(el, "transfer_report", None)
        if callable(rep):
            try:
                r = rep()
            except Exception:  # noqa: BLE001 — reporting never raises
                continue
            if r:
                windows[name] = r
    if windows:
        out["windows"] = windows
        ratios = [w["overlap_ratio"] for w in windows.values()
                  if w.get("overlap_ratio")]
        if ratios:
            out["overlap_ratio"] = round(max(ratios), 2)
        # window stats are per-MESH: a sharded in-flight frame is one
        # slot across every chip its program spans, so the
        # occupancy/blocked numbers must not be read per-chip — surface
        # the widest span so the block is self-describing. Always
        # present (1 = per-chip), matching the fusion block.
        out["devices"] = max(int(w.get("devices", 1) or 1)
                             for w in windows.values())
    for direction, st in transfer_stats().items():
        if st.get("rpcs"):
            out[direction] = {
                "rpcs": st["rpcs"], "frames": st["frames"],
                "arrays": st["arrays"],
                "coalesce_avg": round(st["frames_per_rpc_avg"], 2),
            }
    return out


class _Agg:
    """O(1)-memory running aggregate (sum/max/first/last) plus a
    bounded reservoir (which also counts) for tail percentiles."""

    __slots__ = ("total", "peak", "first_ns", "last_ns", "res")

    def __init__(self, now_ns: int):
        self.total = 0
        self.peak = 0
        self.first_ns = now_ns
        self.last_ns = now_ns
        self.res = Reservoir()


class Tracer:
    """What ``Pipeline.enable_tracing()`` returns."""

    def __init__(self):
        # per-series aggregates; the lock keeps fan-in elements (mux
        # fed from several queue threads) from losing counts
        self._agg: Dict[str, _Agg] = {}
        self._lock = threading.Lock()

    def arrive(self, name: str, ctx, ts_ns: int) -> None:
        """A frame born at ``ctx.t0_ns`` reached element ``name`` at
        ``ts_ns`` (both wall clock; called by the span layer's hop).
        Clamped at 0 as ``observe_e2e`` is: a context adopted off the
        wire carries another host's clock."""
        self._add(name, max(0, ts_ns - ctx.t0_ns), ts_ns)

    def observe(self, series: str, value_ns: float) -> None:
        """Feed a named scalar series (ns) from outside the buffer path —
        e.g. the serve scheduler's per-request queue delay and per-batch
        latency. Reported alongside elements with the same field names
        (the ``interlatency_us_*`` columns carry the observed value)."""
        self._add(series, value_ns, time.time_ns())

    def _add(self, key: str, lat: float, now_ns: int) -> None:
        with self._lock:
            agg = self._agg.get(key)
            if agg is None:
                agg = self._agg[key] = _Agg(now_ns)
            agg.total += lat
            if lat > agg.peak:
                agg.peak = lat
            agg.res.add(lat)
            agg.last_ns = now_ns

    def report(self, pipeline=None) -> Dict[str, Dict[str, Any]]:
        out: Dict[str, Dict[str, Any]] = {}
        with self._lock:
            for name, a in self._agg.items():
                n, pct = a.res.n, a.res.percentiles()
                dt_ns = a.last_ns - a.first_ns
                out[name] = {
                    "buffers": n,
                    "interlatency_us_avg": a.total / n / 1e3,
                    "interlatency_us_max": a.peak / 1e3,
                    "interlatency_us_p50": pct["p50"] / 1e3,
                    "interlatency_us_p95": pct["p95"] / 1e3,
                    "interlatency_us_p99": pct["p99"] / 1e3,
                    "framerate_fps": ((n - 1) * 1e9 / dt_ns
                                      if dt_ns > 0 else 0.0),
                }
        if pipeline is not None:
            for name, el in pipeline.elements.items():
                entry = out.setdefault(name, {})
                # one consistent point-in-time copy per element: a
                # mid-flight chain bump can't tear buffers/proctime
                st = el.stats.snapshot()
                if st.get("buffers"):
                    entry["proctime_us_avg"] = (st["proctime_ns"] /
                                                st["buffers"] / 1e3)
                # fault accounting: only shown when something actually
                # happened, so healthy reports stay uncluttered
                for key in ("dropped", "retries", "restarts", "shed"):
                    if st.get(key):
                        entry[key] = st[key]
                w = _wire_summary(st)
                if w:
                    entry["wire"] = w
                s = _session_summary(st, el)
                if s:
                    entry["session"] = s
                q = getattr(el, "_q", None)
                if q is not None and hasattr(q, "qsize"):
                    entry["queue_level"] = q.qsize()
                rep = getattr(el, "router_report", None)
                if callable(rep):
                    r = rep()
                    if r:
                        entry["router"] = r
            fusion = _fusion_block(pipeline, out)
            if fusion:
                out["fusion"] = fusion
            transfer = _transfer_block(pipeline)
            if transfer:
                out["transfer"] = transfer
        # control-plane counters: any live in-process discovery broker
        # (register/query/error totals) surfaces next to the elements
        broker = live_broker_stats()
        if broker:
            out["broker"] = broker
        return out
