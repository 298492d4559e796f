"""FusedSegment: one element standing in for a run of device-capable
members, executing their composed ``device_fn`` programs as a single
cached ``jax.jit`` per caps signature.

Dataflow after rewiring (planner.apply_fusion): the upstream element
pushes into the segment's sink pad; the segment pushes one buffer per
input buffer from its src pad — member activations never leave the
device between stages, so a frame crosses the host↔device link once in
and once out instead of once per element.

Caps negotiation is NOT re-implemented: the members' internal pad
links are left intact, so the segment replays the incoming CAPS event
through the head member's chain and lets the members' own
``on_sink_caps`` cascade settle it (the tail's src pad is unlinked, so
the cascade stops at the segment boundary). Whatever the unfused chain
would have negotiated, the fused segment negotiates — by construction.

Fault integration: the segment adopts the run's (uniform) ``on-error``
policy and the strongest member circuit-breaker settings. A failure
inside the compiled program records on the breaker and re-raises, so
``Element.chain`` applies the policy exactly as it would for a member;
an open breaker sheds frames with the filter's QosEvent retry-after
convention. Stats live in the locked :class:`utils.atomic.Counters`
(chain thread writes, user thread reads) so racecheck stays clean.
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Callable, List, Optional

from ..obs import context as _obs_ctx
from ..obs import spans as _obs_spans
from ..pipeline.element import TransformElement
from ..pipeline.events import CapsEvent, QosEvent
from ..pipeline.pad import Pad
from ..tensors.buffer import Buffer, Chunk
from ..tensors.transfer import submit_fetch
from ..utils.log import logger
from ..utils.xla_cache import ensure_compile_cache


class FusedSegment(TransformElement):
    """Composite element executing fused member programs on device.

    Constructed only by the fusion planner — it is deliberately not
    registered for launch strings (a launch string describes the
    *unfused* graph; fusion is a start-time placement decision).
    """

    ELEMENT_NAME = "fused_segment"
    SINK_TEMPLATES = {"sink": None}
    SRC_TEMPLATES = {"src": None}
    # stop()/start() drops only the jit cache; programs rebuild from
    # the bound member fns, so on-error=restart is lossless
    RESTART_SAFE = True
    IS_FUSED_SEGMENT = True

    def __init__(self, members: List, fns: List[Callable],
                 name: Optional[str] = None, **props):
        assert len(members) == len(fns) and members, "empty fused run"
        # the run has a uniform policy (planner breaks runs otherwise);
        # adopt it so chain-level error handling matches the members'
        props.setdefault("on-error", str(getattr(members[0], "on_error",
                                                 "fail")))
        super().__init__(name, **props)
        self.members = list(members)
        self._fns = list(fns)
        # a member asking for prefetch-host meant "ship my output via
        # the coalescing fetcher"; mid-segment outputs no longer leave
        # the device, but the SEGMENT's output does — honor the intent
        # there
        self._prefetch = any(bool(getattr(m, "prefetch_host", False))
                             for m in members)
        # per-caps-signature compiled programs; only the segment's
        # streaming thread touches it (one segment = one thread)
        self._programs: dict = {}
        # the run's (uniform — the planner breaks runs on a mesh-spec
        # change) device mesh: when set, the fused program pins a
        # batch-major layout at every member boundary and inputs are
        # committed to the mesh before dispatch, so a fused run stays
        # mesh-resident end to end instead of collapsing to one chip
        self._mesh = next(
            (m for m in (getattr(getattr(e, "fw", None), "mesh", None)
                         for e in members) if m is not None), None)
        self.stats.update(jit_hits=0, jit_misses=0, jit_prewarmed=0, shed=0,
                          breaker_opened=0, fused_elements=len(members),
                          devices=(len(self._mesh.devices.ravel())
                                   if self._mesh is not None else 1))
        # strongest member breaker settings win; 0 threshold = no breaker
        self._breaker = None
        self.breaker_threshold = max(
            (int(getattr(m, "breaker_threshold", 0) or 0) for m in members),
            default=0)
        resets = [float(getattr(m, "breaker_reset_ms", 0) or 0)
                  for m in members
                  if int(getattr(m, "breaker_threshold", 0) or 0) > 0]
        self.breaker_reset_ms = min(resets) if resets else 1000.0
        retries = [float(getattr(m, "breaker_retry_after_ms", 0) or 0)
                   for m in members]
        self.breaker_retry_after_ms = max(retries) if retries else 100.0
        # overlapped execution: the widest member window wins (the run
        # was device-capable end to end, so one window governs the fused
        # program); reorder stays on unless EVERY member opted out
        self.in_flight = max(
            (int(getattr(m, "in_flight", 1) or 1) for m in members),
            default=1)
        self.reorder = all(bool(getattr(m, "reorder", True))
                           for m in members)
        self.reorder_deadline_ms = max(
            (float(getattr(m, "reorder_deadline_ms", 1000.0) or 1000.0)
             for m in members), default=1000.0)
        self._overlap = None
        # completion errors are latched by the completer and re-raised
        # on the NEXT frame's chain (so Element.chain applies the
        # on-error policy on the chain thread, one frame late); two
        # roles store the field — completer sets, chain clears — so a
        # plain store is not enough: the lock makes the handoff atomic
        self._err_lock = threading.Lock()
        self._pending_error: Optional[BaseException] = None

    # -- lifecycle --------------------------------------------------------
    def start(self) -> None:
        super().start()
        if int(self.breaker_threshold) > 0:
            from ..fault.breaker import CircuitBreaker
            self._breaker = CircuitBreaker(
                threshold=int(self.breaker_threshold),
                reset_s=float(self.breaker_reset_ms) / 1e3,
                name=self.name, on_transition=self._on_breaker_transition)
        else:
            self._breaker = None
        self._overlap = None
        if int(self.in_flight) > 1:
            from ..elements.overlap import OverlapExecutor
            self._overlap = OverlapExecutor(
                int(self.in_flight),
                complete_cb=self._complete_frame,
                error_cb=self._complete_error,
                push_cb=self.push,
                name=self.name,
                reorder=bool(self.reorder),
                reorder_deadline_s=float(self.reorder_deadline_ms) / 1e3,
                devices=(len(self._mesh.devices.ravel())
                         if self._mesh is not None else 1))
        # a run without a filter member (transform ! decoder) compiles
        # here first
        ensure_compile_cache()
        self._prewarm_from_cache()

    def _cache_key(self) -> str:
        """Segment identity for the persistent compile cache: the
        member names (launch-string stable) — the same fused run in a
        resurrected replica maps to the same signature bucket."""
        return "+".join(m.name for m in self.members)

    def _prewarm_from_cache(self) -> None:
        """Compile (and execute once, on zeros) every caps signature
        this segment's previous incarnations served, so the first real
        frame hits a warm program (fleet/cache.py)."""
        from ..fleet import cache as compile_cache
        cc = compile_cache.active()
        if cc is None:
            return
        import jax
        import numpy as np
        for sig, _donate in cc.signatures("fusion", self._cache_key()):
            if sig in self._programs:
                continue
            try:
                arrays = [np.zeros(shape, dtype) for shape, dtype in sig]
                if self._mesh is not None:
                    from ..parallel.sharding import place_batch
                    arrays = place_batch(arrays, self._mesh)
                exe = self._compile()
                jax.block_until_ready(exe(arrays))
                self._programs[sig] = exe
                self.stats.inc("jit_prewarmed")
            except (TypeError, ValueError) as exc:
                # a stale signature fails at trace time and only costs
                # its own replay; a device-side failure propagates
                logger.warning("%s: cached fused signature %s no longer "
                               "traces, skipped: %s", self.name, sig, exc)

    def _record_signature(self, sig) -> None:
        from ..fleet import cache as compile_cache
        cc = compile_cache.active()
        if cc is None:
            return
        try:
            cc.record("fusion", self._cache_key(), sig)
        except Exception as exc:  # cache IO must never fail the chain
            logger.warning("%s: compile-cache record failed: %s",
                           self.name, exc)

    def drain(self) -> None:
        super().drain()
        if self._overlap is not None:
            self._overlap.flush()

    def stop(self) -> None:
        super().stop()
        if self._overlap is not None:
            self._overlap.flush()
            self._overlap.stop()
        self._programs.clear()

    def _on_breaker_transition(self, old: str, new: str) -> None:
        from ..fault.breaker import OPEN
        if new == OPEN:
            self.stats.inc("breaker_opened")
        logger.warning("%s: circuit breaker %s -> %s", self.name, old, new)
        self.post_message("warning", breaker=new, breaker_from=old,
                          retry_after_ms=float(self.breaker_retry_after_ms))

    # -- negotiation ------------------------------------------------------
    def on_sink_caps(self, pad: Pad, caps) -> None:
        """Replay the CAPS event through the members' own negotiation
        (their internal links are intact; the tail's unlinked src pad
        ends the cascade), then forward the tail's answer."""
        head, tail = self.members[0], self.members[-1]
        head.chain(head.sinkpad, CapsEvent(caps))
        out = None
        for p in tail.src_pads.values():
            if p.caps is not None:
                out = p.caps
                break
        if out is None:
            raise ValueError(
                f"{self.name}: member negotiation produced no caps for "
                f"{caps} (members: {[m.name for m in self.members]})")
        self.set_src_caps(out)

    # -- dataflow ---------------------------------------------------------
    def do_chain(self, pad: Pad, buf: Buffer) -> None:
        if self._overlap is not None:
            # a completion error latched by the completer surfaces HERE,
            # one frame late, so Element.chain applies the segment's
            # on-error policy on the chain thread exactly as it would
            # for a synchronous failure (the failed frame itself was
            # already accounted dropped by _complete_error)
            with self._err_lock:
                err, self._pending_error = self._pending_error, None
            if err is not None:
                raise err
        if self._breaker is not None and not self._breaker.allow():
            self._shed_frame(buf)
            return
        arrays = [c.raw for c in buf.chunks]
        sig = tuple((tuple(a.shape), str(a.dtype)) for a in arrays)
        if self._mesh is not None:
            # commit inputs batch-major before dispatch; arrays the
            # serve scheduler already placed pass through untouched
            from ..parallel.sharding import place_batch
            arrays = place_batch(arrays, self._mesh)
        t0 = time.perf_counter_ns()
        exe = self._programs.get(sig)
        missed = exe is None
        if missed:
            self.stats.inc("jit_misses")
            exe = self._compile()
        else:
            self.stats.inc("jit_hits")
        try:
            # jit tracing/compilation errors surface here on the chain
            # thread in BOTH modes; with a window the device execution
            # itself is still in flight when this returns
            with (self._overlap.dispatching(buf)
                  if self._overlap is not None else contextlib.nullcontext()):
                outs = exe(arrays)
        except Exception:
            # device program failed (trace or dispatch): count it on
            # the breaker, then let Element.chain apply the segment's
            # on-error policy — exactly the member path's fault flow
            if self._breaker is not None:
                self._breaker.record_failure()
            raise
        self._programs[sig] = exe
        if missed:
            self._record_signature(sig)
        dt = time.perf_counter_ns() - t0
        tracer = getattr(self.pipeline, "tracer", None)
        if tracer is not None:
            tracer.observe(f"fusion/{self.name}", dt)
        if self._overlap is not None:
            t_disp = self._overlap.window.acquire(
                ctx=_obs_ctx.ctx_of(buf), element=self.name)
            try:
                self._overlap.submit(buf, outs, t_disp)
            except BaseException:
                # never strand the slot on a failed enqueue: the
                # completer will not see this frame
                self._overlap.window.release(t_disp)
                raise
            return
        if self._breaker is not None:
            self._breaker.record_success()
        self.push(buf.with_chunks(self._out_chunks(outs)))

    def _out_chunks(self, outs) -> List[Chunk]:
        if not isinstance(outs, (list, tuple)):
            outs = [outs]
        if self._prefetch:
            outs = submit_fetch(outs)
        return [Chunk(o) for o in outs]

    # -- completer side (in-flight window) --------------------------------
    def _complete_frame(self, entry) -> Buffer:
        """Materialize one in-flight fused program's outputs; raises the
        deferred device error, routed to :meth:`_complete_error`. No
        donation for segment programs: member activations alias through
        the fused XLA program already; input donation would invalidate
        upstream-owned device buffers."""
        import jax
        outs = jax.block_until_ready(entry.payload)
        if self._breaker is not None:
            self._breaker.record_success()
        return entry.buf.with_chunks(self._out_chunks(outs))

    def _complete_error(self, entry, exc: BaseException) -> None:
        """Per-frame accounting for a deferred device failure, then
        latch the error for the chain thread to re-raise."""
        if self._breaker is not None:
            self._breaker.record_failure()
        self.stats.inc("dropped")
        logger.warning("%s: fused program failed at completion (frame "
                       "dropped): %s", self.name, exc)
        with self._err_lock:
            if self._pending_error is None:
                self._pending_error = exc

    def handle_event(self, pad: Pad, event) -> None:
        if self._overlap is not None:
            # serialized events must not overtake in-flight frames
            self._overlap.flush()
        super().handle_event(pad, event)

    def transfer_report(self) -> dict:
        """Window occupancy / overlap stats for the pipeline report's
        ``transfer`` block; {} when running synchronously."""
        return self._overlap.report() if self._overlap is not None else {}

    def _compile(self):
        import jax
        fns = self._fns
        mesh = self._mesh
        if mesh is not None and len(mesh.devices.ravel()) > 1:
            from ..parallel.sharding import batch_sharding

            def pin(arrs):
                # batch-major at every member boundary: without the
                # constraint XLA may re-layout mid-program activations
                # around a tensor-parallel member and pay an all-gather
                # at the next batch-parallel stage
                return [jax.lax.with_sharding_constraint(
                            a, batch_sharding(
                                mesh, a.ndim,
                                a.shape[0] if a.ndim else 0))
                        for a in arrs]

            def program(arrs):
                arrs = pin(arrs)
                for fn in fns:
                    arrs = fn(arrs)
                    if not isinstance(arrs, (list, tuple)):
                        arrs = [arrs]
                    arrs = pin(arrs)
                return arrs
        else:
            def program(arrs):
                for fn in fns:
                    arrs = fn(arrs)
                return arrs

        # one jax.jit object per caps signature: jit would retrace a
        # shared object silently, which would skew the hit/miss stats
        # the trace report promises
        return jax.jit(_obs_spans.named_program(
            "nns_fused_" + self.name, program))

    def _shed_frame(self, buf: Buffer) -> None:
        self.stats.inc("shed")
        self.stats.inc("dropped")
        self.send_upstream_event(QosEvent(
            proportion=2.0,
            period_ns=int(float(self.breaker_retry_after_ms) * 1e6),
            timestamp=buf.pts))
