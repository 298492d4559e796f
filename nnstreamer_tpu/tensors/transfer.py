"""Bidirectional coalescing host<->device transfer service.

The transfer layer the async overlapped executor sits on:

  * **download** — the original coalescing D2H fetcher: frames enqueue
    their outputs with :func:`submit_fetch` and leave immediately
    carrying :class:`PendingHost` handles; one fetcher thread drains
    everything queued into one batched ``jax.device_get`` per RPC.
  * **upload** — the symmetric H2D side: :func:`submit_upload` enqueues
    host arrays for a device and returns :class:`PendingDevice`
    handles; one uploader thread drains everything queued into one
    batched ``jax.device_put`` per RPC (grouped per target device), so
    the H2D legs of consecutive in-flight frames share round trips —
    the "double-buffered H2D" leg of the overlap window.
  * **in-flight window** — :class:`InFlightWindow`, the per-link bound
    on frames between dispatch and completion. ``acquire`` blocks the
    dispatching chain thread when the window is full, which is exactly
    the backpressure the upstream ``queue`` element needs to see.

Why coalescing (both directions): every transfer call has a fixed cost
(a host sync, a dispatch) whatever its payload. Batching N frames'
arrays into one call amortizes that cost N ways; the adaptive
Nagle-style linger below lets stragglers join without ever delaying a
lone frame by more than 5% of the measured call time. What either buys
on a chip local to the process is not measured (ROADMAP Design 2).

``transfer_stats()`` reports both directions; ``fetch_stats()`` the
download side alone. A pipeline's ``report()`` (``obs/report.py``)
surfaces the same numbers in its ``transfer`` block together with each
element's window occupancy and overlap ratio.

The reference has no analog (host pointers are free there); this is the
TPU-native cost model talking (SURVEY.md §7 hard part (b): device
residency, materialize only at host boundaries — here even the
materialization is pipelined and batched, in both directions).
"""
from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from ..obs import spans as _obs_spans
from ..utils import flowmarks as flow

# cap on arrays per RPC so one giant drain can't add unbounded latency
# to the frames queued behind it
_MAX_ARRAYS_PER_RPC = 256

# test hook: added per-call latency (seconds) simulating a slow
# transfer. Applied inside the transfer threads only — never on a chain
# thread — so it models the transfer, not the host. 0.0 = off.
_sim_rtt_s = 0.0


def set_simulated_rtt_ms(ms: float) -> None:
    """Inject ``ms`` of artificial round-trip latency into every
    transfer call (both directions). Test knob for reproducing slow
    transfers on a local backend; production leaves it at 0."""
    global _sim_rtt_s
    _sim_rtt_s = max(0.0, float(ms)) / 1e3


class _Ticket:
    """One frame's transfer: a list of arrays -> their counterparts on
    the other side of the link."""

    __slots__ = ("arrays", "results", "error", "device", "_evt")

    def __init__(self, arrays: List[Any], device: Any = None):
        self.arrays: Optional[List[Any]] = arrays
        self.results: Optional[List[Any]] = None
        self.error: Optional[BaseException] = None
        self.device = device           # upload target; None for download
        self._evt = threading.Event()

    @property
    def done(self) -> bool:
        return self._evt.is_set()

    def _deliver(self, results: Optional[List[Any]],
                 error: Optional[BaseException] = None) -> None:
        self.results = results
        self.error = error
        self.arrays = None  # the transfer thread's refs go; buffer
        self._evt.set()     # lifetime is now governed by the handles

    def wait(self) -> List[Any]:
        self._evt.wait()
        if self.error is not None:
            raise self.error
        assert self.results is not None
        return self.results


class _Coalescer:
    """One direction of the link: a queue of tickets drained by a
    single daemon thread, one batched RPC per drain. Subclasses name
    the thread and provide :meth:`_rpc`."""

    THREAD_NAME = "nns-transfer"
    SPAN = "nns.transfer"         # the span around each batched call

    def __init__(self):
        self._q: List[_Ticket] = []
        self._cv = threading.Condition()
        self._thread: Optional[threading.Thread] = None
        # achieved-depth accounting: frames (tickets) per RPC is THE
        # number that says whether the service actually amortizes the
        # link round trip (1.0 = degenerated to frame-at-a-time)
        self._stats = {"rpcs": 0, "frames": 0, "arrays": 0}

    # direction-specific batched transfer; raises to trigger the
    # per-ticket retry isolation in _run
    def _rpc(self, tickets: List[_Ticket], flat: List[Any]) -> List[Any]:
        raise NotImplementedError

    def stats(self, reset: bool = False) -> dict:
        with self._cv:
            out = dict(self._stats)
            if reset:
                self._stats.update(rpcs=0, frames=0, arrays=0)
        out["frames_per_rpc_avg"] = (
            out["frames"] / out["rpcs"] if out["rpcs"] else 0.0)
        return out

    def _account(self, n_tickets: int, n_arrays: int) -> None:
        with self._cv:
            self._stats["rpcs"] += 1
            self._stats["frames"] += n_tickets
            self._stats["arrays"] += n_arrays

    def _ensure_thread(self) -> None:
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._run, name=self.THREAD_NAME, daemon=True)
            self._thread.start()

    def submit(self, ticket: _Ticket) -> None:
        with self._cv:
            self._ensure_thread()
            self._q.append(ticket)
            self._cv.notify()

    def _grab_batch(self) -> List[_Ticket]:
        """Pop a device-uniform run of tickets up to the per-RPC array
        cap. Mixed target devices can't share one RPC: the run stops at
        the first ticket bound elsewhere (it leads the next drain)."""
        grab: List[_Ticket] = []
        n = 0
        with self._cv:
            while self._q and n < _MAX_ARRAYS_PER_RPC:
                if grab and self._q[0].device is not grab[0].device:
                    break
                t = self._q.pop(0)
                grab.append(t)
                n += len(t.arrays or ())
        return grab

    def _run(self) -> None:
        import time as _time

        last_rpc = 0.0
        while True:
            with self._cv:
                while not self._q:
                    self._cv.wait()
            # adaptive linger (Nagle-style): on a slow link, draining the
            # instant the first ticket lands races the pipeline's refill
            # — the consumer frees queue slots only when THIS delivery
            # runs, so tickets submitted a millisecond after the drain
            # wait a whole extra round trip. A pause of 5% of the last
            # RPC (capped 4 ms) lets stragglers join. The worst case is
            # bounded by construction: the pause never exceeds 5% of the
            # measured RPC time, so even a fast link moving big payloads
            # pays <=5% slower cadence, repaid by any batching gain at
            # all; tiny-payload RPCs (the latency-sensitive case) have
            # tiny durations and skip the pause entirely. Skipped when
            # the backlog already fills an RPC — waiting could not
            # deepen that batch, only delay it.
            linger = min(0.004, last_rpc * 0.05)
            if linger > 0.0005:
                with self._cv:
                    backlog = sum(len(t.arrays or ()) for t in self._q)
                if backlog < _MAX_ARRAYS_PER_RPC:
                    _time.sleep(linger)
            grab = self._grab_batch()
            if not grab:
                continue
            flat = [a for t in grab for a in (t.arrays or ())]
            t0 = _time.perf_counter()
            try:
                with _obs_spans.region(
                        self.SPAN, "transfer", arrays=len(flat),
                        bytes=sum(a.nbytes for a in flat)):
                    if _sim_rtt_s > 0.0:
                        _time.sleep(_sim_rtt_s)
                    results = self._rpc(grab, flat)
                last_rpc = _time.perf_counter() - t0
                self._account(len(grab), len(flat))
            except BaseException:  # noqa: BLE001 - isolate per frame below
                # one poisoned array (donated buffer, transient RPC error)
                # must not fail every frame sharing the RPC: retry each
                # ticket alone so only the genuinely bad frame errors out.
                # The failed round trip still cost a full RTT: count it
                # (0 frames delivered) so frames_per_rpc_avg cannot read
                # BETTER than reality on an unhealthy link; account each
                # retry before delivering so a resolve-then-reset caller
                # never sees counts land after its reset. The failed
                # attempt still measured real link time — keep the
                # linger's RPC estimate live through error storms.
                last_rpc = _time.perf_counter() - t0
                self._account(0, 0)
                for t in grab:
                    t1 = _time.perf_counter()
                    try:
                        res1 = self._rpc([t], list(t.arrays or []))
                        last_rpc = _time.perf_counter() - t1
                        self._account(1, len(t.arrays or ()))
                        t._deliver(res1)
                    except BaseException as exc:  # noqa: BLE001
                        self._account(0, 0)
                        t._deliver(None, exc)
                continue
            i = 0
            for t in grab:
                k = len(t.arrays or ())
                t._deliver(results[i:i + k])
                i += k


class _Downloader(_Coalescer):
    """D2H: one batched ``jax.device_get`` per RPC."""

    THREAD_NAME = "nns-fetch"
    SPAN = "nns.transfer.fetch"

    def _rpc(self, tickets: List[_Ticket], flat: List[Any]) -> List[Any]:
        import jax
        return list(jax.device_get(flat))


class _Uploader(_Coalescer):
    """H2D: one batched ``jax.device_put`` per RPC. _grab_batch keeps
    each drain device-uniform, so the whole flat list ships in one
    call."""

    THREAD_NAME = "nns-upload"
    SPAN = "nns.transfer.upload"

    def _rpc(self, tickets: List[_Ticket], flat: List[Any]) -> List[Any]:
        import jax
        return list(jax.device_put(flat, tickets[0].device))


_downloader = _Downloader()
_uploader = _Uploader()


class PendingHost:
    """A device array whose host copy is in flight.

    Shape/dtype are known immediately (from the array's aval, no sync);
    :meth:`resolve` blocks until the coalescer's ``device_get`` lands.
    One ticket is shared by every output of a frame. ``dev`` keeps the
    device array reachable so device-side consumers stay in HBM without
    waiting; it is dropped at first resolution.
    """

    __slots__ = ("_ticket", "_index", "dev", "shape", "dtype")

    def __init__(self, ticket: _Ticket, index: int, dev):
        self._ticket = ticket
        self._index = index
        self.dev = dev
        self.shape = tuple(dev.shape)
        self.dtype = np.dtype(dev.dtype)

    @property
    def ndim(self) -> int:
        return len(self.shape)

    @property
    def done(self) -> bool:
        return self._ticket.done

    def resolve(self) -> np.ndarray:
        out = self._ticket.wait()[self._index]
        self.dev = None
        return out


class PendingDevice:
    """A host array whose device copy is in flight — the upload mirror
    of :class:`PendingHost`. ``host`` keeps the source array reachable
    until the upload lands; shape/dtype are free."""

    __slots__ = ("_ticket", "_index", "host", "shape", "dtype")

    def __init__(self, ticket: _Ticket, index: int, host):
        self._ticket = ticket
        self._index = index
        self.host = host
        self.shape = tuple(host.shape)
        self.dtype = np.dtype(host.dtype)

    @property
    def done(self) -> bool:
        return self._ticket.done

    def resolve(self) -> Any:
        out = self._ticket.wait()[self._index]
        self.host = None
        return out


def submit_fetch(outputs: Sequence[Any]) -> List[Any]:
    """Enqueue one coalesced fetch for all device-resident outputs of a
    frame; host arrays pass through untouched. Returns the outputs with
    device arrays replaced by :class:`PendingHost` handles."""
    import jax

    dev_idx = [i for i, o in enumerate(outputs)
               if isinstance(o, jax.Array)]
    if not dev_idx:
        return list(outputs)
    ticket = _Ticket([outputs[i] for i in dev_idx])
    _downloader.submit(ticket)
    wrapped = list(outputs)
    for slot, i in enumerate(dev_idx):
        wrapped[i] = PendingHost(ticket, slot, outputs[i])
    return wrapped


def submit_upload(inputs: Sequence[Any], device: Any) -> List[Any]:
    """Enqueue one coalesced upload of all host-resident inputs of a
    frame to ``device``; device arrays pass through untouched. Returns
    the inputs with host arrays replaced by :class:`PendingDevice`
    handles. Frames queued while an upload RPC is in flight share the
    next one — consecutive in-flight frames' H2D legs overlap."""
    import jax

    host_idx = [i for i, x in enumerate(inputs)
                if not isinstance(x, (jax.Array, PendingHost, PendingDevice))]
    if not host_idx:
        return list(inputs)
    ticket = _Ticket([np.asarray(inputs[i]) for i in host_idx],
                     device=device)
    _uploader.submit(ticket)
    wrapped = list(inputs)
    for slot, i in enumerate(host_idx):
        wrapped[i] = PendingDevice(ticket, slot, np.asarray(inputs[i]))
    return wrapped


def resolve(x: Any) -> Any:
    """Materialize ``x`` if it is a pending transfer; identity
    otherwise."""
    return x.resolve() if isinstance(x, (PendingHost, PendingDevice)) else x


def fetch_stats(reset: bool = False) -> dict:
    """Download-side counters: rpcs / frames / arrays since start (or
    last reset) plus ``frames_per_rpc_avg``, the achieved batching depth
    — the observability hook for "is the RTT actually being amortized".
    (Historical name; the upload mirror is in :func:`transfer_stats`.)"""
    return _downloader.stats(reset=reset)


def transfer_stats(reset: bool = False) -> Dict[str, dict]:
    """Both directions' coalescer counters, keyed ``download`` /
    ``upload`` — the service half of the pipeline report's ``transfer``
    block (the per-element half is each window's report)."""
    return {"download": _downloader.stats(reset=reset),
            "upload": _uploader.stats(reset=reset)}


class InFlightWindow:
    """The per-link bound on frames between dispatch and completion.

    ``acquire`` blocks the dispatching chain thread while ``limit``
    frames are in flight — backpressure that propagates into the
    upstream queue element exactly like a slow synchronous invoke
    would, so bounded-queue flow control keeps working under overlap.
    ``release`` is called by the completer once the frame has been
    pushed downstream (or accounted dropped).

    The occupancy/overlap accounting lives here because the window IS
    the overlap: ``overlap_ratio`` is total in-flight frame-seconds
    over the dispatch-to-last-completion wall span — 1.0 means serial
    (no overlap won), ``limit`` means the window ran full depth.

    ``devices`` records how many chips one slot's dispatch spans: the
    budget is per-mesh, so a batch sharded across an 8-chip mesh still
    occupies exactly ONE slot (it is one XLA dispatch with one
    completion), not ``len(mesh.devices)`` — a window of K means K
    outstanding programs regardless of how wide each program is. The
    value is reporting-only; it never scales the limit.
    """

    def __init__(self, limit: int, devices: int = 1):
        self.limit = max(1, int(limit))
        self.devices = max(1, int(devices))
        self._cv = threading.Condition()
        self._inflight = 0
        self._peak = 0
        self._acquires = 0
        self._occupancy_sum = 0       # inflight depth sampled per acquire
        self._blocked_ns = 0
        self._inflight_ns = 0         # sum of per-frame dispatch->release
        self._first_ns: Optional[int] = None
        self._last_ns: Optional[int] = None

    @flow.acquires("window-slot")
    def acquire(self, timeout: Optional[float] = None, ctx=None,
                element: str = "") -> Optional[int]:
        """Take a window slot; returns the dispatch timestamp (ns) to
        hand back to :meth:`release`, or None on timeout. With the
        frame's trace context the blocked time is also that frame's
        window-wait span on the calling (chain) thread, charged to its
        queue attribution."""
        import time as _time
        t_wall = _time.time_ns() if ctx is not None else 0
        t0 = _time.perf_counter_ns()
        with self._cv:
            while self._inflight >= self.limit:
                if not self._cv.wait(timeout):
                    return None
            now = _time.perf_counter_ns()
            self._blocked_ns += now - t0
            self._inflight += 1
            self._acquires += 1
            self._occupancy_sum += self._inflight
            if self._inflight > self._peak:
                self._peak = self._inflight
            if self._first_ns is None:
                self._first_ns = now
        if ctx is not None:
            _obs_spans.record_span(f"{element}:window_wait", "queue", t_wall,
                                   now - t0, ctx,
                                   prof="nns.filter.window_wait",
                                   element=element)
            ctx.q_ns += now - t0
        return now

    @flow.settles("window-slot")
    def release(self, t_dispatch_ns: int) -> None:
        import time as _time
        now = _time.perf_counter_ns()
        with self._cv:
            self._inflight -= 1
            self._inflight_ns += now - t_dispatch_ns
            self._last_ns = now
            self._cv.notify_all()

    def idle(self) -> bool:
        with self._cv:
            return self._inflight == 0

    def wait_idle(self, timeout: Optional[float] = None) -> bool:
        import time as _time
        deadline = None if timeout is None \
            else _time.monotonic() + timeout
        with self._cv:
            while self._inflight > 0:
                left = None if deadline is None \
                    else deadline - _time.monotonic()
                if left is not None and left <= 0:
                    return False
                if not self._cv.wait(left if left is not None else 1.0):
                    return False
            return True

    def report(self) -> Dict[str, Any]:
        with self._cv:
            span = ((self._last_ns - self._first_ns)
                    if self._first_ns is not None
                    and self._last_ns is not None else 0)
            return {
                "window": self.limit,
                "devices": self.devices,
                "in_flight": self._inflight,
                "in_flight_peak": self._peak,
                "occupancy_avg": round(
                    self._occupancy_sum / self._acquires, 2)
                    if self._acquires else 0.0,
                "overlap_ratio": round(self._inflight_ns / span, 2)
                    if span > 0 else 0.0,
                "blocked_ms": round(self._blocked_ns / 1e6, 2),
            }
