"""Bounded, lock-cheap per-thread span rings.

The hot path (one record per element hop per frame) touches no shared
lock: each thread appends fixed-shape tuples to its own bounded
``deque`` (C-level append, maxlen eviction). The global registry of
rings is only locked when a NEW thread records its first span and when
a dump snapshots the fleet — never per frame.

A span is the tuple::

    (name, cat, ts_ns, dur_ns, trace_id, span_id, parent_id, tid)

with wall-clock (epoch) timestamps so spans recorded in different
processes align in one Chrome trace. ``NNS_TPU_OBS=0`` turns the whole
layer off.

The bridge to ``jax.profiler``: a profiler trace counts from its
session's start on the device's clock, so the rings' epoch stamps
cannot be laid beside it — the spans have to be IN the trace.
:func:`region` (work a thread does) is a ``TraceAnnotation`` for its
whole extent; :func:`record_span` (a wait measured after the fact)
with ``prof=`` drops an end-stamped marker annotation carrying
``dur_ns``, from which a reader rebuilds ``[end - dur, end]``. Every
annotation carries ``trace`` / ``span`` / ``parent`` (and whatever
else the site names, e.g. ``element``) as metadata, so the spans of
one frame share an identifier in the xplane as they do in the ring.
Profiler names are ``nns.<layer>.<what>``. Outside a profiler session
an annotation is a flag test in C++; ``chain_span`` is not bridged.
"""
from __future__ import annotations

import os
import re
import threading
import time
from collections import deque
from typing import List, Optional, Tuple

from .context import TraceContext, _BASE, _IDS, next_id

# per-thread ring capacity: at ~6 spans per frame per process this
# holds many seconds of a fast pipeline's history; tune via env
RING_SPANS = int(os.environ.get("NNS_TPU_OBS_RING", "8192"))

ENABLED = os.environ.get("NNS_TPU_OBS", "1").lower() \
    not in ("0", "false", "off")


def enabled() -> bool:
    return ENABLED


def set_enabled(on: bool) -> None:
    global ENABLED
    ENABLED = bool(on)


_tls = threading.local()
_rings: List[Tuple[int, str, deque]] = []     # (tid, thread name, ring)
_rings_lock = threading.Lock()


def _new_ring() -> deque:
    """Slow path of ``_ring()``: first span on this thread."""
    r = deque(maxlen=RING_SPANS)
    _tls.ring = r
    t = threading.current_thread()
    with _rings_lock:
        _rings.append((t.ident or 0, t.name, r))
    return r


def _ring() -> deque:
    # try/except over getattr: the hit path is free on modern CPython
    # and this runs once per recorded span
    try:
        return _tls.ring
    except AttributeError:
        return _new_ring()


def snapshot() -> List[tuple]:
    """Every live span, all threads: [(tid, span), ...]. Copying under
    the registry lock keeps concurrent appends safe (deque iteration
    over a mutating deque is not)."""
    with _rings_lock:
        rings = list(_rings)
    out = []
    for tid, _name, ring in rings:
        out.extend((tid, s) for s in list(ring))
    return out


def thread_names() -> dict:
    with _rings_lock:
        return {tid: name for tid, name, _ in _rings}


def clear() -> None:
    """Test hook: drop every recorded span (rings stay registered)."""
    with _rings_lock:
        for _tid, _name, ring in _rings:
            ring.clear()


# -- recording ----------------------------------------------------------

_annotation = None     # jax.profiler.TraceAnnotation, bound on first use


def _bind_annotation():
    """Bound lazily (as ``_observe_e2e`` is): ``obs/`` imports without
    jax, and the profiler module loads only once a span is bridged."""
    global _annotation
    from jax.profiler import TraceAnnotation
    _annotation = TraceAnnotation
    return TraceAnnotation


def record_span(name: str, cat: str, ts_ns: int, dur_ns: int,
                ctx: Optional[TraceContext] = None,
                parent: Optional[int] = None,
                prof: Optional[str] = None, **meta) -> int:
    """Record one span; with a context the span parents onto the
    context's current span and becomes the new current (the linear
    causality chain). ``prof`` names the marker annotation that carries
    the span into a running profiler trace (module docstring). Returns
    the span id (0 when recording is off)."""
    if not ENABLED:
        return 0
    sid = _BASE | (next(_IDS) & 0xFFFFFF)   # next_id(), inlined (hot)
    try:
        ring = _tls.ring
    except AttributeError:
        ring = _new_ring()
    if ctx is not None:
        p = ctx.span_id if parent is None else parent
        trace_id = ctx.trace_id
        ctx.span_id = sid
    else:
        p = 0 if parent is None else parent
        trace_id = 0
    ring.append((name, cat, ts_ns, dur_ns, trace_id, sid, p))
    if prof is not None:
        with (_annotation or _bind_annotation())(
                prof, dur_ns=dur_ns, trace=trace_id, span=sid, parent=p,
                **meta):
            pass
    return sid


class _Region:
    """One open :func:`region`: ring tuple on exit, profiler annotation
    for the whole extent. ``account`` is what the thread's compile
    events are charged to while the region is the innermost one that
    has any (``obs/load.py``); None on every other region."""

    __slots__ = ("name", "cat", "ctx", "ann", "sid", "trace_id", "parent",
                 "t0", "dur_ns", "account")

    def __init__(self, name, cat, ctx, prof, meta):
        self.name, self.cat, self.ctx = name, cat, ctx
        self.account = None
        self.sid = _BASE | (next(_IDS) & 0xFFFFFF)
        try:
            stack = _tls.open
        except AttributeError:
            stack = _tls.open = []
        if ctx is not None:
            self.trace_id, self.parent = ctx.trace_id, ctx.span_id
        elif stack:
            self.trace_id, self.parent = stack[-1].trace_id, stack[-1].sid
        else:
            self.trace_id = self.parent = 0
        self.ann = (_annotation or _bind_annotation())(
            prof, trace=self.trace_id, span=self.sid, parent=self.parent,
            **meta)

    def __enter__(self):
        _tls.open.append(self)
        self.t0 = time.time_ns()
        self.ann.__enter__()
        return self

    def __exit__(self, *exc):
        self.ann.__exit__(*exc)
        dur = self.dur_ns = time.time_ns() - self.t0
        _tls.open.pop()
        try:
            ring = _tls.ring
        except AttributeError:
            ring = _new_ring()
        ring.append((self.name, self.cat, self.t0, dur, self.trace_id,
                     self.sid, self.parent))
        if self.ctx is not None:
            self.ctx.span_id = self.sid
        return False

    def note(self, **meta) -> None:
        """Metadata known only once the work is under way (a loaded
        tree's bytes, a trace's equations), added to the open
        annotation; the ring keeps no metadata."""
        self.ann.set_metadata(**meta)

    def charge(self, account) -> "_Region":
        """Make ``account`` what :func:`open_account` answers while this
        region is the innermost one that has any."""
        self.account = account
        return self


class _Off:
    """``region()`` with recording off: nothing built, nothing kept."""

    dur_ns = 0       # what callers add to a context's accumulators
    account = None   # a caller's account is never charged
    sid = t0 = 0

    def __enter__(self):
        return self

    def note(self, **meta) -> None:
        pass

    def charge(self, account) -> "_Off":
        return self

    def __exit__(self, *exc):
        return False


_OFF = _Off()


def region(prof: str, cat: str, ctx: Optional[TraceContext] = None,
           name: Optional[str] = None, **meta):
    """Context manager around work a thread DOES (dispatch, a transfer
    call, an admission). On exit the ring gets the span under ``name``
    (default: ``prof``), parented on ``ctx`` when given (and advancing
    it), else on the thread's open region; for its whole extent it is
    also the profiler annotation ``prof`` with ``meta`` as metadata."""
    if not ENABLED:
        return _OFF
    return _Region(name or prof, cat, ctx, prof, meta)


def open_account():
    """The ``account`` of the innermost open region of this thread that
    has one, or None: whom a compile event that fires now is charged
    to."""
    try:
        stack = _tls.open
    except AttributeError:
        return None
    for r in reversed(stack):
        if r.account is not None:
            return r.account
    return None


def record_root(name: str, ctx: TraceContext) -> int:
    """The source-stamp root span (zero duration, no parent): children
    recorded downstream always find their parent in the dump."""
    if not ENABLED:
        return 0
    sid = next_id()
    _ring().append((name, "source", ctx.t0_ns, 0, ctx.trace_id, sid, 0))
    ctx.span_id = sid
    return sid


def identifier(name: str) -> str:
    """``name`` as the identifier :func:`named_program` makes of it."""
    return re.sub(r"\W", "_", name)


def named_program(name: str, fn):
    """``fn`` under the stable name its jitted program carries in a
    profiler trace: ``jax.jit`` names the module ``jit_<__name__>``, so
    the ``XLA Modules`` line reads ``jit_nns_<layer>_<what>`` whatever
    closure or lambda built it. ``name`` is made an identifier (an
    element or model name may hold ``-`` or ``.``). ``fn`` itself is
    left alone: module-level functions are shared."""
    def program(*args, **kwargs):
        return fn(*args, **kwargs)
    program.__name__ = program.__qualname__ = identifier(name)
    return program


_observe_e2e = None    # metrics.observe_e2e, bound on first sink frame


def traced(element) -> bool:
    """Whether the element's pipeline has tracing enabled
    (``Pipeline.enable_tracing()``): with ``ENABLED``, the two things
    that turn the stamp and the hop on."""
    pipeline = element.pipeline
    return pipeline is not None and pipeline.tracer is not None


def chain_span(element, ctx: TraceContext, ts_ns: int, dur_ns: int) -> None:
    """The per-element hop: one span per buffer through ``chain()``,
    attributed to compute, and the arrival in the pipeline's report
    (``obs/report.py``) when it has tracing enabled. Sinks additionally
    settle the frame's end-to-end histogram. ``ctx`` is what
    ``ensure_ctx`` gave ``chain()`` on entry. ``record_span`` is
    inlined: this is the single hottest call in the whole obs plane
    (once per element per frame)."""
    pipeline = element.pipeline
    if pipeline is not None and pipeline.tracer is not None:
        pipeline.tracer.arrive(element.name, ctx, ts_ns)
    if not ENABLED:                  # tracing alone: no ring, no e2e
        return
    sid = _BASE | (next(_IDS) & 0xFFFFFF)
    try:
        ring = _tls.ring
    except AttributeError:
        ring = _new_ring()
    ring.append((element.name, "element", ts_ns, dur_ns,
                 ctx.trace_id, sid, ctx.span_id))
    ctx.span_id = sid
    ctx.c_ns += dur_ns
    if not element.src_pads:         # terminal: the frame settles here
        global _observe_e2e
        if _observe_e2e is None:
            from .metrics import observe_e2e as _obs
            _observe_e2e = _obs
        _observe_e2e(element, ctx, ts_ns + dur_ns)
