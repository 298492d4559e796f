"""Generic plumbing elements: queue, tee, capsfilter, identity, appsrc,
appsink, fakesink (the GStreamer core-element analogs the reference's
pipelines lean on, e.g. ``queue`` for thread boundaries and ``tee`` for
fan-out in composite pipelines, README.md multi-model examples)."""
from __future__ import annotations

import queue as _pyqueue
import threading
import time
from typing import Callable, List, Optional

from ..obs import context as _obs_ctx
from ..obs import spans as _obs_spans
from ..tensors.buffer import Buffer, Chunk
from ..tensors.caps import Caps
from ..utils.log import logger
from .element import (Element, SinkElement, SrcElement, TransferError,
                      TransformElement)
from .events import CapsEvent, EosEvent, Event
from .pad import FlowError, Pad, PadDirection
from .registry import register_element

_SENTINEL = object()


class _NativeQueueAdapter:
    """queue.Queue facade over the C++ MPMC ring (csrc/nns_ring.cc) —
    the native thread-boundary the reference gets from GStreamer's C
    queue. Waiting happens in native condition variables, off the GIL."""

    def __init__(self, capacity: int):
        from ..native.lib import NativeRing
        self._ring = NativeRing(capacity)
        self.closed = False

    def put(self, item) -> None:
        self._ring.push(item, -1)

    def put_nowait(self, item) -> None:
        if not self._ring.push(item, 0):
            raise _pyqueue.Full

    def get(self, timeout: Optional[float] = None):
        item = self._ring.pop(-1 if timeout is None else
                              max(0, int(timeout * 1000)))
        if item is None:
            raise _pyqueue.Empty
        return item

    def get_nowait(self):
        return self.get(timeout=0)

    def close(self) -> None:
        self.closed = True
        self._ring.close()

    def qsize(self) -> int:
        return len(self._ring)


def _record_queue_wait(name: str, item) -> None:
    """A buffer left a queue (``queue``'s worker pop, ``appsrc``'s src
    loop pop): turn its entry stamp into the queue-wait span and the
    context's queue attribution."""
    if not _obs_spans.ENABLED:
        return
    qt = item.extras.pop(_obs_ctx.QT_KEY, None)
    ctx = item.extras.get(_obs_ctx.CTX_KEY)
    if qt is None or ctx is None:
        return
    wait = max(0, time.time_ns() - qt)
    _obs_spans.record_span(name, "queue", qt, wait, ctx,
                           prof="nns.queue.wait", element=name)
    ctx.q_ns += wait


@register_element("queue")
class Queue(Element):
    """Thread boundary with a bounded buffer queue.

    Backpressure: upstream ``chain`` blocks when the queue is full
    (matching gst queue defaults). GStreamer leaky semantics:
    ``leaky=upstream`` drops the incoming buffer when full;
    ``leaky=downstream`` evicts the oldest queued buffer to make room.

    ``backend=auto`` (default) uses the native C++ ring for the common
    non-leaky case, building libnnstpu from ``csrc/`` on first use, and
    the python queue only where that build cannot run (no toolchain) —
    so a clean clone and a tree with ``build/`` present run the SAME
    queue. ``python``/``native`` force one; :attr:`active_backend` says
    which is in use. Leaky modes always use the python queue (eviction
    needs its internals).
    """

    SINK_TEMPLATES = {"sink": None}
    SRC_TEMPLATES = {"src": None}
    PROPS = {"max-size-buffers": 16, "leaky": "none", "backend": "auto"}
    SPAN_POINTS = ("queue-wait",)

    def __init__(self, name=None, **props):
        super().__init__(name, **props)
        self._q = self._make_q()
        self._thread: Optional[threading.Thread] = None
        self._running = False

    def _make_q(self):
        cap = max(1, self.max_size_buffers)
        if self.backend in ("auto", "native") and self.leaky == "none":
            from ..native.lib import native_available
            # builds from csrc/ on first use (once per process; a no-op
            # `make` when the library is fresh): what runs must not
            # depend on whether a git-ignored build/ happens to exist
            if native_available():
                return _NativeQueueAdapter(cap)
            if self.backend == "native":
                raise RuntimeError(
                    f"{self.name}: backend=native but libnnstpu is not "
                    "built (run `make native`)")
        elif self.backend == "native":
            raise ValueError(
                f"{self.name}: leaky queues need backend=python")
        return _pyqueue.Queue(maxsize=cap)

    @property
    def active_backend(self) -> str:
        """``"native"`` (C++ ring) or ``"python"`` — what this queue
        actually runs on, whatever ``backend`` asked for."""
        return ("native" if isinstance(self._q, _NativeQueueAdapter)
                else "python")

    def set_property(self, key: str, value) -> None:
        super().set_property(key, value)
        if key.replace("_", "-") in ("max-size-buffers", "leaky", "backend"):
            # properties may be applied after __init__ (launch parser);
            # rebuild then — but never once the worker owns the queue.
            # During Element.__init__ (constructor kwargs) _q does not
            # exist yet: skip — Queue.__init__ builds it exactly once.
            if "_q" not in self.__dict__:
                return
            if getattr(self, "_running", False):
                raise RuntimeError(
                    f"{self.name}: cannot reconfigure a running queue")
            self._q = self._make_q()

    def start(self) -> None:
        super().start()
        if isinstance(self._q, _NativeQueueAdapter) and self._q.closed:
            # a closed ring stays closed: a restarted element (rapid
            # start/stop cycles) gets a live one. Here and not in
            # stop(): a producer still in its push loop after stop()
            # must keep meeting the closed ring (push returns 'closed');
            # a live one, with no worker to drain it, would fill and
            # block that producer for good.
            self._q = self._make_q()
        self._running = True
        self._thread = threading.Thread(
            target=self._worker, name=f"queue:{self.name}", daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._running = False
        super().stop()
        if isinstance(self._q, _NativeQueueAdapter):
            # the C++ ring has real shutdown: close() wakes BOTH blocked
            # producers (push returns 'closed') and the worker's pop.
            # The sentinel dance below can lose a race against a
            # producer re-filling the freed slot, wedging that producer
            # in the native cv forever (observed under CPU load).
            self._q.close()
        else:
            try:
                self._q.put_nowait(_SENTINEL)
            except _pyqueue.Full:
                try:
                    self._q.get_nowait()
                    self._q.put_nowait(_SENTINEL)
                except (_pyqueue.Empty, _pyqueue.Full):
                    pass
        if self._thread is not None and self._thread is not threading.current_thread():
            self._thread.join(timeout=5.0)
            self._thread = None

    def chain(self, pad: Pad, item) -> None:
        if isinstance(item, Event):
            self._q.put(item)  # events are serialized: never dropped
            return
        # the queue bypasses Element.chain (no do_chain), so its hop is
        # taken here (stats['buffers'] is counted by the worker on pop —
        # counting here too would double it)
        traced = _obs_spans.traced(self)
        if _obs_spans.ENABLED or traced:
            now = time.time_ns()
            if _obs_spans.ENABLED:
                # entry stamp: the worker's pop turns it into the
                # queue-wait span (+ queue attribution on the context)
                item.extras[_obs_ctx.QT_KEY] = now
            if traced:
                ctx = _obs_ctx.ensure_ctx(item)
                if ctx is not None:
                    self.pipeline.tracer.arrive(self.name, ctx, now)
        if self.leaky == "upstream":
            # GStreamer leaky=upstream: drop the incoming buffer when full
            try:
                self._q.put_nowait(item)
            except _pyqueue.Full:
                pass
        elif self.leaky == "downstream":
            # GStreamer leaky=downstream: evict the oldest queued BUFFER;
            # events keep their queue position (they are never dropped)
            while True:
                try:
                    self._q.put_nowait(item)
                    return
                except _pyqueue.Full:
                    dropped = False
                    with self._q.mutex:
                        for i, old in enumerate(self._q.queue):
                            if not isinstance(old, Event):
                                del self._q.queue[i]
                                dropped = True
                                # wake producers blocked in put(): mutex IS
                                # the not_full condition's lock
                                self._q.not_full.notify()
                                break
                    if not dropped:
                        # only events queued: block until the worker drains
                        self._q.put(item)
                        return
        else:
            self._q.put(item)  # blocking: backpressure

    def _worker(self) -> None:
        while self._running:
            try:
                item = self._q.get()
            except _pyqueue.Empty:
                break  # native ring closed and drained
            if item is _SENTINEL:
                break
            try:
                if isinstance(item, Event):
                    if isinstance(item, CapsEvent):
                        self.sinkpad.set_caps(item.caps)
                        self.set_src_caps(item.caps)
                    else:
                        self.forward_event(item)
                else:
                    self.stats.add(buffers=1, bytes=item.nbytes)
                    _record_queue_wait(self.name, item)
                    self.srcpad.push(item)
            except FlowError:
                break
            except Exception as exc:  # noqa: BLE001
                logger.exception("%s: error in queue worker", self.name)
                self.post_error(exc)
                break


@register_element("tee")
class Tee(Element):
    """1-to-N fan-out. Buffers are shared, not copied: chunks are
    immutable by convention (device arrays are immutable anyway)."""

    SINK_TEMPLATES = {"sink": None}
    SRC_TEMPLATES = {"src_%u": None}

    def do_chain(self, pad: Pad, buf: Buffer) -> None:
        for p in self.src_pads.values():
            if p.is_linked:
                p.push(buf)

    def on_sink_caps(self, pad: Pad, caps: Caps) -> None:
        self.set_src_caps(caps)


@register_element("capsfilter")
class CapsFilter(TransformElement):
    """Pass-through that restricts negotiation to its ``caps`` property."""

    PROPS = {"caps": ""}

    def transform(self, buf: Buffer) -> Buffer:
        return buf

    def transform_caps(self, incaps: Caps) -> Optional[Caps]:
        if not self.caps:
            return incaps
        want = Caps(self.caps) if isinstance(self.caps, str) else self.caps
        out = incaps.intersect(want)
        if out.is_empty():
            raise ValueError(
                f"{self.name}: caps {incaps} do not satisfy filter {want}")
        return out.fixate() if not out.is_fixed() else out

    def static_transfer(self, in_caps):
        """Input ∩ ``caps`` property; a fixed caps property alone pins
        an otherwise-unknown upstream."""
        if in_caps.get("sink") is None and self.caps:
            want = Caps(self.caps) if isinstance(self.caps, str) else self.caps
            if want.is_fixed():
                return {"src": want}
            return {"src": None}
        return super().static_transfer(in_caps)


@register_element("identity")
class Identity(TransformElement):
    PROPS = {"silent": True}

    def transform(self, buf: Buffer) -> Buffer:
        if not self.silent:
            logger.info("%s: buffer pts=%s chunks=%d", self.name, buf.pts, len(buf))
        return buf


@register_element("appsrc")
class AppSrc(SrcElement):
    """Application-driven source: the app thread calls ``push_buffer`` /
    ``end_stream``; the src loop relays into the pipeline."""

    PROPS = {"caps": "", "max-buffers": 64}
    SPAN_POINTS = ("source-root", "queue-wait", "chain")

    def __init__(self, name=None, **props):
        super().__init__(name, **props)
        self._q: _pyqueue.Queue = self._make_q()

    def _make_q(self) -> _pyqueue.Queue:
        return _pyqueue.Queue(maxsize=max(1, int(self.max_buffers)))

    def set_property(self, key: str, value) -> None:
        super().set_property(key, value)
        # the launch parser applies properties after __init__: rebuild
        # the entry queue then, as Queue does (during Element.__init__
        # _q does not exist yet; once started, the app thread may be
        # blocked on the queue that would be dropped)
        if key.replace("_", "-") == "max-buffers" and "_q" in self.__dict__:
            if self._started:
                raise RuntimeError(
                    f"{self.name}: cannot reconfigure a running appsrc")
            self._q = self._make_q()

    def push_buffer(self, buf: Buffer) -> None:
        if _obs_spans.ENABLED:
            # the frame is born HERE, not when the src loop pops it: the
            # wait in this entry queue belongs to its latency
            if _obs_ctx.ctx_of(buf) is None:
                _obs_spans.record_root(self.name, _obs_ctx.stamp(buf))
            buf.extras[_obs_ctx.QT_KEY] = time.time_ns()
        self._q.put(buf)

    def end_stream(self) -> None:
        self._q.put(_SENTINEL)

    def negotiate_src_caps(self) -> Optional[Caps]:
        return Caps(self.caps) if self.caps else None

    def create(self) -> Optional[Buffer]:
        while not self._stop_evt.is_set():
            try:
                item = self._q.get(timeout=0.1)
            except _pyqueue.Empty:
                continue
            if item is _SENTINEL:
                return None
            _record_queue_wait(self.name, item)
            return item
        return None


@register_element("appsink")
class AppSink(SinkElement):
    """Collecting sink with an optional new-data callback
    (≙ tensor_sink's ``new-data`` signal, ref: gsttensor_sink.c)."""

    PROPS = {"max-buffers": 0, "emit-signals": True}

    def __init__(self, name=None, **props):
        super().__init__(name, **props)
        self.buffers: List[Buffer] = []
        self.callback: Optional[Callable[[Buffer], None]] = None
        self._lock = threading.Lock()

    def connect(self, callback: Callable[[Buffer], None]) -> None:
        self.callback = callback

    def render(self, buf: Buffer) -> None:
        with self._lock:
            self.buffers.append(buf)
            if self.max_buffers > 0 and len(self.buffers) > self.max_buffers:
                self.buffers.pop(0)
        if self.callback is not None:
            self.callback(buf)

    def pop_all(self) -> List[Buffer]:
        with self._lock:
            out, self.buffers = self.buffers, []
            return out


@register_element("tensortestsrc")
class TensorTestSrc(SrcElement):
    """Synthetic tensor source (≙ videotestsrc feeding tensor_converter in
    reference test pipelines). Generates frames matching its ``caps``
    property with a chosen fill pattern; PTS synthesized from framerate."""

    # device=true pre-stages a pool of frames in HBM and cycles them, so
    # the stream is device-resident from the source on (MLPerf-offline
    # style): downstream device elements see zero H2D cost, isolating
    # the runtime's own per-buffer overhead from the host link.
    # unique=true additionally adds the frame counter to each pooled
    # frame ON DEVICE (one tiny fused op, no host bytes), so every
    # emitted frame is distinct — a remote transport that caches repeat
    # executions by (executable, args) cannot serve pool repeats from
    # cache and fake downstream throughput. Off by default: it perturbs
    # frame CONTENT, which belongs to benchmark configs, not to
    # pipelines that verify pattern semantics.
    PROPS = {"caps": "", "pattern": "counter", "seed": 0, "is-live": False,
             "device": False, "pool-size": 4, "unique": False}

    def __init__(self, name=None, **props):
        super().__init__(name, **props)
        self._config = None
        self._count = 0
        self._rng = None
        self._pool = None
        self._uniq = None

    def static_src_caps(self) -> Optional[Caps]:
        """Fixated ``caps`` property (required for this source)."""
        if not self.caps:
            raise TransferError(f"{self.name}: 'caps' property is required")
        return super().static_src_caps()

    def negotiate_src_caps(self) -> Optional[Caps]:
        if not self.caps:
            raise ValueError(f"{self.name}: 'caps' property is required")
        caps = Caps(self.caps)
        if not caps.is_fixed():
            caps = caps.fixate()
        self._config = caps.to_config()
        return caps

    def _make_frame(self, count: int):
        import numpy as np
        arrays = []
        for info in self._config.info:
            dt = info.type.np_dtype
            if self.pattern == "zeros":
                arr = np.zeros(info.shape, dtype=dt)
            elif self.pattern == "ones":
                arr = np.ones(info.shape, dtype=dt)
            elif self.pattern == "random":
                if np.issubdtype(np.dtype(dt), np.integer):
                    ii = np.iinfo(dt)
                    arr = self._rng.integers(ii.min, ii.max, info.shape,
                                             dtype=dt, endpoint=True)
                else:
                    arr = self._rng.random(info.shape).astype(dt)
            else:  # counter
                arr = np.full(info.shape, count).astype(dt)
            arrays.append(arr)
        return arrays

    def create(self) -> Optional[Buffer]:
        import numpy as np
        if self._rng is None:
            self._rng = np.random.default_rng(self.seed)
        if self.device:
            if self._pool is None:
                import jax
                n = max(1, int(self.pool_size))
                self._pool = [
                    [Chunk(jax.device_put(a)) for a in self._make_frame(i)]
                    for i in range(n)]
                if self.unique:
                    self._uniq = jax.jit(lambda a, s: a + s)
            chunks = self._pool[self._count % len(self._pool)]
            if self._uniq is not None:
                chunks = [Chunk(self._uniq(
                    c.raw, np.asarray(self._count % 199 + 1).astype(c.dtype)))
                    for c in chunks]
        else:
            chunks = [Chunk(a) for a in self._make_frame(self._count)]
        cfg = self._config
        dur = cfg.frame_duration_ns()
        pts = self._count * dur if dur else self._count
        self._count += 1
        if self.is_live and dur:
            import time as _t
            _t.sleep(dur / 1e9)
        return Buffer(chunks, pts=pts, duration=dur)


@register_element("fakesink")
class FakeSink(SinkElement):
    PROPS = {"dump": False}

    def render(self, buf: Buffer) -> None:
        if self.dump:
            logger.info("%s: pts=%s %r", self.name, buf.pts, buf)
