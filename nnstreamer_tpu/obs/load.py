"""The load path's account: where a filter's seconds between ``start()``
and its first buffer went, and which program each compile event
belongs to.

The spans are ``obs/spans.py`` regions like every other (category
``load``: ``nns.load.start``, ``.model``, ``.place``, ``.program``,
``.trace``, ``.first_buffer``, with ``nns.filter.prepare`` under its
program), so the ring holds them and a running profiler shows them. What
this module adds is the charge: JAX reports tracing, lowering and
backend compilation (or the persistent cache's retrieval) through
``jax.monitoring``, on the thread that compiles and with no word of
whose program it was. One process-wide listener (:func:`install`; JAX's
listeners cannot be taken back, so it is one-way) adds each event to the
:class:`Account` of the innermost open ``nns.load.program`` /
``nns.filter.prepare`` region of that thread (``spans.open_account``)
and does nothing when none is open: a user's own ``jax.jit`` and
another thread's compile are charged to nobody. (``nns.load.model``
has an account too, for a model file that makes its weights with a
``jax.jit`` of its own: no record, since it is no program the filter
built, but ``model_jit`` in the report.) A closed region leaves one
record in its backend's
:class:`LoadLog`, whose :meth:`~LoadLog.report` is the ``load`` block of
``tensor_filter``'s ``transfer_report()``.

An event is kept as the interval it covered (its end is the listener's
call, its start that minus the duration), and a record's seconds are
the length of the intervals' union: ``jax.jit`` reports the trace of
every nested ``jit`` inside its own, and ``nns.load.trace`` times the
model's trace around JAX's own event, so a plain sum would count those
seconds twice.
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

from . import events, spans

# jax.monitoring duration events -> the Account field they are kept in
_DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "retrieval",
}
_COUNTS = {
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "misses",
}

_install_lock = threading.Lock()
_installed = False


def install() -> None:
    """Register the listener, once per process (the jax backend's first
    ``open()``)."""
    global _installed
    with _install_lock:
        if _installed:
            return
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_listener(_on_event)
        _installed = True


def _on_duration(event: str, duration: float, **_) -> None:
    field = _DURATIONS.get(event)
    if field is None:
        return
    account = spans.open_account()
    if account is None:
        return
    if field == "retrieval":
        account.retrieval_s += duration
    else:
        end = time.time()
        getattr(account, field).append((end - duration, end))


def _on_event(event: str, **_) -> None:
    field = _COUNTS.get(event)
    if field is None:
        return
    account = spans.open_account()
    if account is not None:
        setattr(account, field, getattr(account, field) + 1)


class Account:
    """What one open ``nns.load.program`` / ``nns.filter.prepare``
    region has been charged: the wall-clock intervals (seconds) of its
    trace, lowering and backend-compile events, the cache's hits, misses
    and retrieval seconds, and the seconds of the prepare region under
    it."""

    __slots__ = ("trace", "lower", "compile", "retrieval_s", "hits",
                 "misses", "prepare_s")

    def __init__(self):
        self.trace: List[Tuple[float, float]] = []
        self.lower: List[Tuple[float, float]] = []
        self.compile: List[Tuple[float, float]] = []
        self.retrieval_s = 0.0
        self.hits = self.misses = 0
        self.prepare_s = 0.0

    def seconds(self) -> Dict[str, Any]:
        """The charge in seconds, as a record and ``model_jit`` give it."""
        return {"trace_s": covered(self.trace),
                "lower_s": covered(self.lower),
                "compile_s": covered(self.compile),
                # "off": the persistent cache neither held the program
                # nor kept it (disabled, or under JAX's thresholds)
                "cache": "hit" if self.hits else
                         "miss" if self.misses else "off",
                "retrieval_s": self.retrieval_s}


def covered(intervals: Iterable[Tuple[float, float]]) -> float:
    """Seconds inside at least one of ``intervals``."""
    total, reach = 0.0, float("-inf")
    for lo, hi in sorted(intervals):
        if hi > reach:
            total += hi - max(lo, reach)
            reach = hi
    return total


def signature_text(sig: Sequence[Tuple[Sequence[int], str]]) -> str:
    """``float32[4,16], int32[8]`` for a program's input signature."""
    return ", ".join(f"{dtype}[{','.join(map(str, shape))}]"
                     for shape, dtype in sig)


class LoadLog:
    """One backend's load: the seconds of its ``nns.load.model`` and
    ``nns.load.place`` spans and one record per program it built.
    ``serving`` is set by the element once its first buffer is done: a
    program built from then on is a recompile on that element's frame
    path (``at: "frame"``) and goes out as a ``recompile`` event."""

    def __init__(self):
        self.seconds: Dict[str, float] = {}
        self.jitted: Dict[str, Dict[str, Any]] = {}
        self.programs: List[Dict[str, Any]] = []
        self.serving = ""

    @property
    def at(self) -> str:
        return "frame" if self.serving else "load"

    @contextlib.contextmanager
    def phase(self, what: str, **meta):
        """``nns.load.<what>`` (``model``, ``place``), its seconds kept
        for the report as ``<what>_s``; yields the region, for metadata
        that is known only afterwards. What JAX compiles inside it (a
        model file that makes its weights with a ``jax.jit`` of its
        own) is no program of the filter's and gets no record: it is
        kept as ``<what>_jit``, so that the span's seconds can be told
        apart."""
        account = Account()
        with spans.region("nns.load." + what, "load",
                          **meta).charge(account) as region:
            yield region
        if region.account is not account:        # recording is off
            return
        self.seconds[what + "_s"] = \
            self.seconds.get(what + "_s", 0.0) + region.dur_ns / 1e9
        if account.trace or account.lower or account.compile:
            self.jitted[what + "_jit"] = account.seconds()

    @contextlib.contextmanager
    def program(self, name: str, sig, donate: Sequence[int]):
        """``nns.load.program`` around the building of one program, from
        the cache's miss to the return of its first call. The record is
        left when the body did not raise."""
        account, at = Account(), self.at
        signature = signature_text(sig)
        with spans.region(
                "nns.load.program", "load", program=name,
                signature=signature, donate=",".join(map(str, donate)),
                at=at).charge(account) as region:
            yield region
        if region.account is not account:        # recording is off
            return
        self.programs.append(
            _record(region, account, name, signature, donate, at))
        if at == "frame":
            events.emit(
                "recompile", source=self.serving,
                message=f"{name} rebuilt on the frame path for "
                        f"{signature} in {region.dur_ns / 1e9:.3f} s",
                program=name, signature=signature)

    @contextlib.contextmanager
    def trace(self):
        """``nns.load.trace`` around the model's Python trace; its
        extent joins the open program's trace intervals, JAX's own
        event inside it included."""
        with spans.region("nns.load.trace", "load") as region:
            yield region
        account = spans.open_account()
        if account is not None and region.dur_ns:
            account.trace.append(
                (region.t0 / 1e9, (region.t0 + region.dur_ns) / 1e9))

    @contextlib.contextmanager
    def prepare(self, **meta):
        """``nns.filter.prepare`` around ``jit_nns_filter_prepare``'s
        building and call: a record of its own, and ``prepare_s`` of the
        program it was run for."""
        outer, account = spans.open_account(), Account()
        with spans.region("nns.filter.prepare", "filter",
                          **meta).charge(account) as region:
            yield region
        if region.account is not account:
            return
        if outer is not None:
            outer.prepare_s += region.dur_ns / 1e9
        self.programs.append(_record(
            region, account, "jit_nns_filter_prepare", "", (), self.at))

    def report(self) -> Optional[Dict[str, Any]]:
        """``{"model_s", "place_s", "programs": [records]}`` and, where
        the model file compiled programs of its own, ``"model_jit"``
        (their trace, lower and compile seconds inside ``model_s``); or
        None where nothing was recorded (``NNS_TPU_OBS=0``)."""
        if not self.seconds:
            return None
        return {"model_s": self.seconds.get("model_s", 0.0),
                "place_s": self.seconds.get("place_s", 0.0),
                **{k: dict(v) for k, v in self.jitted.items()},
                "programs": [dict(r) for r in self.programs]}


def _record(region, account: Account, program: str, signature: str,
            donate: Sequence[int], at: str) -> Dict[str, Any]:
    return {"program": program, "signature": signature,
            "donate": list(donate), "at": at,
            "wall_s": region.dur_ns / 1e9, **account.seconds(),
            "prepare_s": account.prepare_s}


def phase_seconds(block: Dict[str, Any]) -> Dict[str, float]:
    """A load block's seconds by phase (``model``, ``place``, ``trace``,
    ``lower``, ``compile``, ``prepare``, ``first_buffer``, ``total``):
    the program phases summed over the records with ``at: "load"``; a
    phase not reached yet is left out."""
    out = {"model": block["model_s"], "place": block["place_s"]}
    loaded = [r for r in block["programs"] if r["at"] == "load"]
    for phase in ("trace", "lower", "compile", "prepare"):
        out[phase] = sum(r[phase + "_s"] for r in loaded)
    for phase in ("first_buffer", "total"):
        if block.get(phase + "_s") is not None:
            out[phase] = block[phase + "_s"]
    return out
