"""tensor_filter — the inference element.

≙ gst/nnstreamer/tensor_filter/tensor_filter.c (+ tensor_filter_common.c):
property parsing, framework auto-detection, model-vs-caps verification,
invoke dispatch, rolling latency/throughput statistics, input/output
combination, async generative output, suspend watchdog, shared-model key.

TPU-native specifics: chunks handed to the backend may already be
device-resident (HBM); outputs stay device-resident until a host boundary.
The hot path is one cached-executable dispatch (SURVEY.md §3.2 analog).
"""
from __future__ import annotations

import collections
import contextlib
import threading
import time
from typing import Any, List, Optional

import numpy as np

from ..filters.base import (Accelerator, FilterEvent, FilterProperties,
                            HeldStateNotCheckpointable, InvokeDrop)
from ..filters.registry import (detect_framework, find_filter,
                                shared_model_get, shared_model_insert,
                                shared_model_release)
from ..tensors.buffer import Buffer, Chunk
# module scope, not per-frame: submit_fetch runs on every prefetch-host
# frame on the hot path
from ..tensors.transfer import submit_fetch
from ..tensors.caps import Caps
from ..tensors.info import TensorInfo, TensorsConfig, TensorsInfo
from ..tensors.types import TensorFormat
from ..obs import context as _obs_ctx
from ..obs import events as _obs_events
from ..obs import spans as _obs_spans
from ..pipeline.element import Element, TransferError
from ..pipeline.events import Event, QosEvent
from ..pipeline.pad import Pad
from ..pipeline.registry import register_element
from ..utils.log import logger
from ..utils.watchdog import Watchdog

# rolling window for the latency property
# (≙ GST_TF_STAT_MAX_RECENT, tensor_filter.c)
_MAX_RECENT = 10

# latency re-report thresholds (≙ tensor_filter.c:106-118): re-post when
# the estimate grows past reported×(1+5%) or improves by more than 25%
_LATENCY_REPORT_HEADROOM = 1.05
_LATENCY_IMPROVE_THRESHOLD = 0.75


def infer_batch_dim(sel: TensorsInfo, model: TensorsInfo) -> Optional[int]:
    """The stream's uniform leading batch dim over the model input, or
    None when the stream is not model-plus-one-leading-dim."""
    if len(sel) != len(model):
        return None
    b = None
    for s, m in zip(sel, model):
        if s.type != m.type or len(s.shape) != len(m.shape) + 1 \
                or tuple(s.shape[1:]) != tuple(m.shape):
            return None
        if b is None:
            b = int(s.shape[0])
        elif int(s.shape[0]) != b:
            return None
    return b


@register_element("tensor_filter")
class TensorFilter(Element):
    """Runs a model on every buffer through a filter backend
    (``framework``).
    ``framework=jax`` computes what the model's parameters alone
    determine once per load, not once per buffer: an equation of the
    traced program all of whose operands are parameter leaves, literals
    or results of such equations (flax's float32 kernels converted to
    the module's bfloat16, a decoder's projection weights cut, padded
    and re-laid for the product that reads them) runs on the device in
    one program, ``jit_nns_filter_prepare``, and the per-buffer program
    ``jit_nns_filter_<model>`` is built from the same trace without
    those equations (``filters/prepare.py``); every input signature,
    mesh mode and a fused segment share the one set of results. It is
    redone when the parameters are replaced (``reload_model()``, the
    resume after ``suspend``). The loaded tree stays on the device as
    the source, so the results' bytes come on top (ViT-H/14: 2.53 GB of
    float32 + 1.26 GB of bfloat16). ``transfer_report()`` carries
    ``prepared_equations`` (the equations moved to the load),
    ``prepared_leaves`` and ``prepared_bytes`` (the leaves among them
    held a second time in a narrower dtype; all 0 where the leaves alone
    determine nothing: such a model gets ``jax.jit`` of its ``apply_fn``
    and its own arrays as before) and, read off the same trace,
    ``kernel_calls``: the Pallas kernels the program calls, name -> call
    sites; the span ring and a profiler trace hold one
    ``nns.filter.prepare`` span per load with ``leaves``, ``equations``,
    ``bytes_in`` and ``bytes_out``. No property selects it: an equation
    on constants gives the same bits whenever it is run.
    The load accounts for itself (``obs/load.py``): ``nns.load.*`` spans
    from ``start()`` to the first buffer, every compile event charged to
    the program that caused it, and ``transfer_report()["load"]``
    (``load_report()``) with the seconds by phase and one record per
    program built; a program built after the first buffer is a
    recompile on the frame path and goes out as a ``recompile`` event."""

    SINK_TEMPLATES = {"sink": "other/tensors"}
    SRC_TEMPLATES = {"src": "other/tensors"}
    # under overlap-depth>0 the executor adds dispatch/complete spans
    SPAN_POINTS = ("chain", "window-wait", "dispatch", "complete")
    PROPS = {
        "framework": "auto",
        "model": "",
        "input": "", "inputtype": "", "inputname": "",
        "output": "", "outputtype": "", "outputname": "",
        "accelerator": "",
        "custom": "",
        "latency": 0,            # 1 = enable latency property updates
        "throughput": 0,
        "invoke-dynamic": False,
        "invoke-async": False,
        "suspend": 0,            # idle ms before model unload; 0 = off
        "shared-tensor-filter-key": "",
        "input-combination": "",
        "output-combination": "",
        # start async device->host copies of outputs at invoke time, so
        # a downstream host boundary (decoder/serializer) finds the data
        # already in flight instead of paying the full D2H round-trip
        # latency per frame. Off by default: chained device-resident
        # elements should NOT force transfers.
        "prefetch-host": False,
        # circuit breaker on the backend path (fault/breaker.py):
        # breaker-threshold consecutive invoke failures open it — frames
        # are then SHED (serve rows answered with MsgKind.SHED +
        # retry-after, upstream throttled via QosEvent) instead of each
        # paying a doomed invoke; after breaker-reset-ms one probe
        # half-opens it. 0 = disabled (default).
        "breaker-threshold": 0,
        "breaker-reset-ms": 1000.0,
        "breaker-retry-after-ms": 50.0,
        # K-frame in-flight invoke window (elements/overlap.py): keep up
        # to K frames between dispatch and completion, completing each on
        # a dedicated completer thread instead of blocking the chain
        # thread, so frame N+1's H2D and dispatch overlap frame N's
        # compute and D2H. 1 = synchronous (default). Requires a
        # backend with async dispatch (SUPPORTS_DISPATCH, e.g. jax);
        # otherwise the filter logs a notice and stays synchronous.
        "in-flight": 1,
        # restore PTS order before push() when in-flight > 1 (bounded
        # reorder buffer with a stall deadline). Disable only when every
        # downstream consumer is order-insensitive — pipelint WARNs if an
        # aggregator/trainer/rate sits downstream without it.
        "reorder": True,
        # how long the reorder buffer dams the pipeline waiting for a
        # missing frame before abandoning the gap
        "reorder-deadline-ms": 1000.0,
        # donate input device buffers to the dispatched executable
        # (XLA input/output aliasing): the H2D staging buffer is reused
        # for the outputs, halving HBM traffic per frame. Only honored
        # on device platforms that support donation (tpu/gpu) and only
        # for buffers this filter itself uploaded; device-resident
        # inputs owned by upstream elements are never donated.
        "donate-input": False,
        # run one zero-filled invoke at caps negotiation so the XLA
        # compile (tens of seconds for a big model) happens before the
        # first real frame instead of stalling it (no reference analog:
        # its backends don't JIT; on TPU cold-start hygiene is a
        # framework concern). Only effective for sync invokes on STATIC
        # caps: async/dynamic/flexible streams have no fixed invoke
        # signature to warm (async backends such as the LLM filter warm
        # through their own prefill path) — requesting it there logs a
        # notice and does nothing.
        "warmup": False,
    }

    def __init__(self, name=None, **props):
        super().__init__(name, **props)
        self.fw = None
        self._fw_owned = True
        self._in_info: Optional[TensorsInfo] = None
        self._out_info: Optional[TensorsInfo] = None
        self._recent_latency = collections.deque(maxlen=_MAX_RECENT)
        self._invoke_count = 0
        self._total_latency_ns = 0
        # dispatch-to-return timing, distinct from dispatch-to-completion
        # (_recent_latency): under an in-flight window the former is the
        # chain-thread cost (near-zero by design), the latter the real
        # model + transfer latency. Both are surfaced; QoS uses completion.
        self._recent_dispatch = collections.deque(maxlen=_MAX_RECENT)
        self._dispatch_count = 0
        self._total_dispatch_ns = 0
        # latency fields are written by the chain thread (sync path) AND
        # the completer thread (windowed path): one leaf lock covers the
        # deques/counters (racecheck: rmw from two roles needs it)
        self._stats_lock = threading.Lock()
        self._overlap = None               # OverlapExecutor when K > 1
        self._start_time = None
        # the load as the element sees it (load_report()): its
        # nns.load.start spans, the first buffer's entry into do_chain
        # (wall clock, ns) and how long it took to complete
        self._load_spans: List[Any] = []
        self._first_chain_ns: Optional[int] = None
        self._first_buffer_ns: Optional[int] = None
        self._watchdog: Optional[Watchdog] = None
        self._in_combi: Optional[List[int]] = None
        self._out_combi: Optional[List[str]] = None
        self._batch: Optional[int] = None  # batched-invoke leading dim
        self._reported_latency_us: Optional[float] = None
        self._throttle_period_ns = 0       # from downstream QoS events
        self._next_accept_ts: Optional[int] = None
        self._breaker = None
        # checkpoint/: framework state recovered by restore_state,
        # applied once the framework is open (start())
        self._fw_restore = None
        self.stats.update({"invoke_errors": 0, "frames_dropped": 0,
                           "qos_dropped": 0, "shed": 0,
                           "breaker_opened": 0})

    # -- framework lifecycle ---------------------------------------------
    @contextlib.contextmanager
    def _loading(self):
        """``nns.load.start``: ``start()`` whole, and before it the
        backend's ``open()`` where something asks for the framework
        earlier (the fusion planner, ``plan_out_caps``); the load counts
        from the first of them."""
        with _obs_spans.region("nns.load.start", "load", element=self.name,
                               framework=self.framework) as span:
            yield
        self._load_spans.append(span)

    def _open_fw(self) -> None:
        if self.fw is not None:
            return
        from ..utils.models import resolve
        # model:// and mlagent://model/ URIs resolve through the model
        # registry (≙ ml_agent.c URI resolution); plain paths untouched
        models = tuple(resolve(m) for m in self.model.split(",") if m) \
            if self.model else ()
        fw_name = self.framework
        if fw_name in ("auto", ""):
            fw_name = detect_framework(models)
        props = FilterProperties(
            framework=fw_name,
            model_files=models,
            # empty accelerator property = framework default (TPU), like the
            # reference's auto mode; an explicit "false"/"cpu" opts out
            accelerators=(tuple(Accelerator.parse(self.accelerator))
                          if self.accelerator else (Accelerator.DEFAULT,)),
            custom_properties=self.custom,
            invoke_dynamic=self.invoke_dynamic,
            invoke_async=self.invoke_async,
            shared_key=self.shared_tensor_filter_key or None,
            latency_report=bool(self.latency),
        )
        if self.input and self.inputtype:
            props.input_info = TensorsInfo.make(self.inputtype, self.input)
        if self.output and self.outputtype:
            props.output_info = TensorsInfo.make(self.outputtype, self.output)

        fw = None
        if props.shared_key:
            # consult the registry BEFORE loading: one HBM copy of the weights
            fw = shared_model_get(props.shared_key)
            self._fw_owned = False
        if fw is None:
            fw = find_filter(fw_name)()
            # from start(): inside its span; asked for earlier (the
            # fusion planner, plan_out_caps): the load begins here
            with contextlib.nullcontext() if self._started \
                    else self._loading():
                fw.open(props)
            if props.shared_key:
                fw = shared_model_insert(props.shared_key, fw)
        self.fw = fw
        self._fw_props = props
        mi_in, mi_out = fw.get_model_info()
        self._in_info = props.input_info or mi_in
        self._out_info = props.output_info or mi_out
        if self.invoke_async:
            fw.set_async_dispatcher(self._dispatch_async)
            fw.on_async_error = self._account_invoke_error
        if self.suspend > 0:
            self._watchdog = Watchdog(self.suspend / 1000.0, self._on_idle)
        if self._in_combi is None and self.input_combination:
            self._in_combi = [int(i) for i in self.input_combination.split(",")]
        if self._out_combi is None and self.output_combination:
            self._out_combi = [t.strip() for t in self.output_combination.split(",")]

    RESTART_SAFE = True  # stop/start re-opens the framework cleanly

    def start(self) -> None:
        with self._loading():
            super().start()
            self._open_fw()
            if self._fw_restore is not None:
                state, snap_dir = self._fw_restore
                if hasattr(self.fw, "restore_state"):
                    self.fw.restore_state(state, snap_dir)
                self._fw_restore = None
            self._start_time = time.monotonic()
            if int(self.breaker_threshold) > 0:
                from ..fault.breaker import CircuitBreaker
                self._breaker = CircuitBreaker(
                    threshold=int(self.breaker_threshold),
                    reset_s=float(self.breaker_reset_ms) / 1e3,
                    name=self.name,
                    on_transition=self._on_breaker_transition)
            else:
                self._breaker = None
            self._overlap = None
            window = int(self.in_flight)
            if window > 1:
                if self.invoke_async:
                    logger.info("%s: in-flight=%d ignored — invoke-async "
                                "backends manage their own in-flight frames",
                                self.name, window)
                elif not getattr(self.fw, "SUPPORTS_DISPATCH", False):
                    logger.info("%s: in-flight=%d ignored — framework %s "
                                "has no async dispatch; staying synchronous",
                                self.name, window, self.fw.NAME)
                else:
                    from .overlap import OverlapExecutor
                    mesh = getattr(self.fw, "mesh", None)
                    devices = len(mesh.devices.ravel()) \
                        if mesh is not None else 1
                    self._overlap = OverlapExecutor(
                        window,
                        complete_cb=self._complete_frame,
                        error_cb=self._complete_error,
                        push_cb=self.push,
                        name=self.name,
                        reorder=bool(self.reorder),
                        reorder_deadline_s=float(
                            self.reorder_deadline_ms) / 1e3,
                        devices=devices)

    def drain(self) -> None:
        """During a deliberate drain the filter may sit idle for longer
        than the suspend window while upstream flushes its queues —
        quiesce the idle watchdog so the model is not unloaded right
        before the flushed tail arrives and needs it. (The pipeline
        stops after the drain, so the quiesce is never resumed: destroy
        in stop() cleans up.)"""
        super().drain()
        if self._overlap is not None:
            self._overlap.flush()
        if self._watchdog is not None:
            self._watchdog.quiesce()

    # -- checkpoint/restore (checkpoint/) ---------------------------------
    CHECKPOINTABLE = ("whatever the loaded framework exposes (e.g. the "
                      "llm backend's continuous-batching streams); NOT "
                      "the device-resident state a jax model carries "
                      "between buffers (a fifth item of get_model()): a "
                      "snapshot of such a filter raises "
                      "HeldStateNotCheckpointable")

    def snapshot_state(self, snap_dir):
        # delegation, not ownership: the element itself keeps nothing
        # between frames, but a framework may carry cross-invoke state.
        # The llm backend's continuous batching knows how to snapshot
        # its own; the jax backend's held state (a model's recurrent
        # state, on the device, advanced by every buffer) does not yet,
        # and a snapshot that left it out would restore a stream that
        # reads its documents' later buffers from an empty state
        if self.fw is not None and hasattr(self.fw, "snapshot_state"):
            return self.fw.snapshot_state(snap_dir)
        held = self._held_state()
        if held is not None:
            raise HeldStateNotCheckpointable(
                f"{self.name}: the model carries {held['leaves']} state "
                f"arrays ({held['bytes']} bytes) on the device between "
                "buffers, which checkpoint/ cannot snapshot yet; drain "
                "the stream to a document's end and snapshot a pipeline "
                "whose filter is stopped, or leave this element out")
        if self._fw_restore is not None:
            return self._fw_restore[0]  # restored, never started: re-emit
        return None

    def restore_state(self, state, snap_dir):
        self._fw_restore = (state, snap_dir)

    def stop(self) -> None:
        super().stop()
        if self._overlap is not None:
            # settle every in-flight frame before the framework closes;
            # the (stopped) executor is kept so post-run trace reports
            # still see the window/overlap numbers
            self._overlap.flush()
            self._overlap.stop()
        if self._watchdog is not None:
            self._watchdog.destroy()
        if self.fw is not None:
            key = self.shared_tensor_filter_key
            if key:
                shared_model_release(key)
            elif self._fw_owned:
                self.fw.close()
            self.fw = None
        self._load_spans = []
        self._first_chain_ns = self._first_buffer_ns = None

    # -- negotiation ------------------------------------------------------
    def _infer_batch(self, sel: TensorsInfo) -> Optional[int]:
        """If the stream is the model input plus one leading (outermost)
        batch dim on every tensor, return that batch size.

        TPU-first batched invoke: tensor_aggregator (or a batched source)
        stacks N frames; the whole stack goes through ONE executable
        dispatch, which is how the MXU earns its keep — the reference has
        no analog (its backends are handed exactly the model shape).
        Only backends declaring SUPPORTS_BATCH negotiate this; others keep
        the fail-fast caps mismatch error."""
        if not getattr(self.fw, "SUPPORTS_BATCH", False):
            return None
        if self._in_info is None:
            return None
        return infer_batch_dim(sel, self._in_info)

    def on_sink_caps(self, pad: Pad, caps: Caps) -> None:
        self._open_fw()
        cfg = caps.to_config()
        self._batch = None
        if self._in_info is not None and cfg.format == TensorFormat.STATIC:
            sel = cfg.info
            if self._in_combi:
                sel = TensorsInfo(cfg.info[i] for i in self._in_combi)
            if len(sel) and not sel.is_equal(self._in_info):
                self._batch = self._infer_batch(sel)
                if self._batch is None:
                    raise ValueError(
                        f"{self.name}: model input {self._in_info!r} does not match "
                        f"negotiated stream caps {sel!r}. Check tensor_converter/"
                        "tensor_transform output dims, or set input/inputtype "
                        "properties explicitly.")
        elif self._in_info is None:
            # push-path: derive model info from caps (SET_INPUT_INFO analog)
            self._in_info = cfg.info
            out = self.fw.set_input_info(cfg.info)
            if out is not None:
                self._out_info = out
        if self.invoke_dynamic or self._out_info is None:
            out_cfg = TensorsConfig(TensorsInfo(), TensorFormat.FLEXIBLE,
                                    cfg.rate_n, cfg.rate_d)
        else:
            out_info = self._out_info.copy()
            if self._batch is not None:
                out_info = TensorsInfo(
                    TensorInfo(i.name, i.type, (self._batch,) + tuple(i.shape))
                    for i in out_info)
            out_cfg = TensorsConfig(out_info, TensorFormat.STATIC,
                                    cfg.rate_n, cfg.rate_d)
        self.set_src_caps(Caps.from_config(out_cfg))
        if self.warmup:
            if self.invoke_async or self.invoke_dynamic \
                    or cfg.format != TensorFormat.STATIC:
                # not silently inert: tell the user WHY nothing warmed
                logger.info(
                    "%s: warmup requested but skipped (%s) — no fixed "
                    "invoke signature to warm; async filters warm via "
                    "their own prefill path", self.name,
                    "invoke-async" if self.invoke_async else
                    "invoke-dynamic" if self.invoke_dynamic else
                    "non-static stream format")
            else:
                # the same selection real frames will use (sel was
                # computed above for STATIC caps)
                sel = cfg.info
                if self._in_combi:
                    sel = TensorsInfo(cfg.info[i] for i in self._in_combi)
                if len(sel):
                    self._warmup_invoke(sel)

    def static_transfer(self, in_caps):
        """Model I/O from declared properties only (the framework is
        never opened): input/inputtype are checked against the stream
        with batch-dim tolerance; invoke-dynamic or output/outputtype
        give the out caps, otherwise the output is unknown."""
        incaps = in_caps.get("sink")
        cfg = None
        if incaps is not None and not incaps.any and incaps.structures \
                and incaps.is_fixed():
            try:
                cfg = incaps.to_config()
            except ValueError as exc:
                raise TransferError(f"{self.name}: {exc}", pad="sink")
        rate = (cfg.rate_n, cfg.rate_d) if cfg is not None else (0, 1)
        batch = None
        if self.input and self.inputtype and cfg is not None \
                and cfg.format == TensorFormat.STATIC and len(cfg.info):
            model_in = TensorsInfo.make(self.inputtype, self.input)
            sel = cfg.info
            if self.input_combination:
                idxs = [int(i) for i in self.input_combination.split(",")]
                sel = TensorsInfo(cfg.info[i] for i in idxs)
            if len(sel) and not sel.is_equal(model_in):
                # permissive on batching: SUPPORTS_BATCH is a backend
                # trait we cannot know without opening the framework
                batch = infer_batch_dim(sel, model_in)
                if batch is None:
                    raise TransferError(
                        f"{self.name}: model input {model_in!r} does not "
                        f"match stream caps {sel!r}. Check tensor_"
                        f"converter/tensor_transform output dims, or the "
                        f"input/inputtype properties.", pad="sink")
        if self.invoke_dynamic:
            out_cfg = TensorsConfig(TensorsInfo(), TensorFormat.FLEXIBLE,
                                    *rate)
        elif self.output and self.outputtype:
            out_info = TensorsInfo.make(self.outputtype, self.output)
            if batch is not None:
                out_info = TensorsInfo(
                    TensorInfo(i.name, i.type, (batch,) + tuple(i.shape))
                    for i in out_info)
            out_cfg = TensorsConfig(out_info, TensorFormat.STATIC, *rate)
        else:
            return {"src": None}  # model metadata needs the framework
        return {"src": Caps.from_config(out_cfg)}

    # -- device placement (fusion compiler) --------------------------------
    DEVICE_FUSIBLE = ("sync jax-backend invokes on static caps "
                      "(no invoke-async/dynamic; mesh-sharded members "
                      "fuse when the run shares one mesh spec)")

    _JAX_FRAMEWORKS = ("jax", "jax-tpu", "flax")

    def device_veto(self) -> Optional[str]:
        if self.invoke_async:
            return "invoke-async: output frames are decoupled from inputs"
        if self.invoke_dynamic:
            return "invoke-dynamic: per-frame output shapes (dynamic caps)"
        fw = (self.framework or "").lower()
        if fw in ("auto", ""):
            first = self.model.split(",")[0] if self.model else ""
            if not first.startswith("zoo://"):
                return (f"framework auto-detect on {first!r} cannot be "
                        f"proven to be the jax backend statically")
            return None  # zoo:// always resolves to the jax backend
        if fw not in self._JAX_FRAMEWORKS:
            return f"framework {fw!r} exposes no traceable invoke"
        return None

    def mesh_spec(self) -> str:
        """The declared ``mesh:`` custom option (e.g. ``"2x2x2"``,
        ``"auto"``), "" when unsharded. Static — readable before the
        framework opens; the fusion planner uses it to break runs at
        mesh-spec boundaries (one fused program, one mesh)."""
        for part in str(self.custom or "").split(","):
            part = part.strip()
            if part.startswith("mesh:"):
                return part[len("mesh:"):].strip()
        return ""

    def plan_out_caps(self, incaps: Caps) -> Optional[Caps]:
        """Plan-time refinement of :meth:`static_transfer`: opens the
        framework (the fusion planner runs after validation, before
        start — the one caller allowed to) and answers the same caps
        :meth:`on_sink_caps` would negotiate, without its side
        effects."""
        self._open_fw()
        cfg = incaps.to_config()
        if cfg.format != TensorFormat.STATIC or self._out_info is None:
            return None
        sel = cfg.info
        if self._in_combi:
            sel = TensorsInfo(cfg.info[i] for i in self._in_combi)
        batch = None
        if self._in_info is not None and len(sel) \
                and not sel.is_equal(self._in_info):
            batch = self._infer_batch(sel)
            if batch is None:
                return None
        out_info = self._out_info.copy()
        if batch is not None:
            out_info = TensorsInfo(
                TensorInfo(i.name, i.type, (batch,) + tuple(i.shape))
                for i in out_info)
        return Caps.from_config(TensorsConfig(
            out_info, TensorFormat.STATIC, cfg.rate_n, cfg.rate_d))

    def device_fn(self, ctx=None):
        """The backend's pure apply closure, wrapped with the filter's
        input/output-combination wiring. prefetch-host is ignored for
        MID-segment outputs (activations never leave the device, which
        is the point); the FusedSegment honors it for the segment's
        final outputs instead."""
        if self.device_veto() is not None:
            return None
        # a model that cannot open fails the launch here exactly as it
        # would at start(): the chain path needs the same framework
        self._open_fw()
        get = getattr(self.fw, "traceable_fn", None)
        tr = get() if callable(get) else None
        if tr is None:
            return None
        in_combi, out_combi = self._in_combi, self._out_combi

        def fn(arrays):
            xs = [arrays[i] for i in in_combi] if in_combi else list(arrays)
            outs = tr(*xs)
            if not isinstance(outs, (list, tuple)):
                outs = [outs]
            outs = list(outs)
            if out_combi:
                outs = [arrays[int(t[1:])] if t[0] == "i"
                        else outs[int(t[1:])] for t in out_combi]
            return outs

        return fn

    def _warmup_invoke(self, sel: TensorsInfo) -> None:
        """One zero-filled invoke with the NEGOTIATED stream shapes
        (incl. any batch dim), so the jit cache is hot for the exact
        signature real frames will hit. A failure here is the failure
        every real frame would hit (same shapes, same program), so it
        fails the negotiation instead of becoming N dropped frames."""
        zeros = [np.zeros(tuple(i.shape), i.type.np_dtype) for i in sel]
        self.fw.invoke(zeros)
        if self._watchdog is not None:
            # a long warmup compile must not be answered by an
            # immediate idle-suspend that clears the cache it built
            self._watchdog.feed()
        logger.info("%s: warmup invoke compiled %d input(s)",
                    self.name, len(zeros))

    # -- hot path ---------------------------------------------------------
    def do_chain(self, pad: Pad, buf: Buffer) -> None:
        if self._qos_should_drop(buf):
            # downstream can't keep up: skip the invoke entirely so the
            # accelerator does no wasted work (≙ throttling check,
            # tensor_filter.c:532-584)
            self.stats.inc("qos_dropped")
            return
        if self._breaker is not None and not self._breaker.allow():
            # breaker OPEN: the backend is currently only producing
            # errors — shed without invoking (TF-Serving-style fail
            # fast) and tell upstream/clients when to come back
            self._shed_frame(buf)
            return
        if self._first_chain_ns is None:
            self._first_chain_ns = time.time_ns()
        inputs = [c.raw for c in buf.chunks]
        if self._in_combi:
            inputs = [inputs[i] for i in self._in_combi]
        if self._overlap is not None:
            self._dispatch_windowed(buf, inputs)
            return
        t0 = time.perf_counter_ns()
        c0 = getattr(self.fw, "compile_count", 0)
        try:
            if self.invoke_async:
                # ctx rides along with the invoke so each dispatched
                # output frame inherits ITS prompt's buffer (PTS et al.)
                # even with several invokes in flight; the template is a
                # fallback for backends that don't thread ctx through
                self._async_template = buf
                self.fw.invoke_async(inputs, ctx=buf)
                self._note_recompiles(c0)
                self._record_dispatch(time.perf_counter_ns() - t0)
                self._record_latency(time.perf_counter_ns() - t0)
                return
            outputs = self.fw.invoke(inputs)
        except InvokeDrop:
            # subplugin-signaled drop (≙ invoke result > 0): silent.
            # A deliberate drop is a WORKING backend for the breaker.
            if self._breaker is not None:
                self._breaker.record_success()
            self.stats.inc("frames_dropped")
            return
        except Exception as exc:  # noqa: BLE001
            self._account_invoke_error(exc)
            return
        if self._breaker is not None:
            self._breaker.record_success()
        self._note_recompiles(c0)
        # synchronous path: dispatch and completion are the same event
        dt = time.perf_counter_ns() - t0
        self._record_dispatch(dt)
        self._record_latency(dt)
        if self._watchdog is not None:
            self._watchdog.feed()
        outputs = self._trim_padded_rows(buf, outputs)
        if self.prefetch_host:
            # enqueue on the coalescing fetch service: the frame leaves
            # this element immediately carrying PendingHost handles, and
            # every frame queued while a fetch is in flight shares the
            # next one.
            outputs = submit_fetch(outputs)
        out_chunks = self._combine_outputs(buf, outputs)
        if self._first_buffer_ns is None:
            self._first_buffer_done()
        self.push(buf.with_chunks(out_chunks))

    # -- in-flight window (overlapped execution) ---------------------------
    def _dispatch_windowed(self, buf: Buffer, inputs: List[Any]) -> None:
        """DISPATCHER side of the overlap split: take a window slot
        (blocking here IS the backpressure — it propagates into the
        upstream queue exactly like a slow synchronous invoke), enqueue
        the device program, and hand completion to the completer
        thread. The chain thread never waits on the device."""
        t_disp = self._overlap.window.acquire(
            ctx=_obs_ctx.ctx_of(buf), element=self.name)
        with self._overlap.dispatching(buf):
            t0 = time.perf_counter_ns()
            c0 = getattr(self.fw, "compile_count", 0)
            try:
                handle = self.fw.dispatch(inputs,
                                          donate=bool(self.donate_input))
            except InvokeDrop:
                # release FIRST: the accounting below must not be able
                # to strand the slot (the completer never sees this
                # frame)
                self._overlap.window.release(t_disp)
                if self._breaker is not None:
                    self._breaker.record_success()
                self.stats.inc("frames_dropped")
                return
            except Exception as exc:  # noqa: BLE001
                self._overlap.window.release(t_disp)
                self._account_invoke_error(exc)
                self._settle_failed_rows(buf)
                return
            try:
                self._note_recompiles(c0)
                self._record_dispatch(time.perf_counter_ns() - t0)
                self._overlap.submit(buf, handle, t_disp)
            except BaseException:
                # a dispatch-side failure after acquire: the slot would
                # otherwise leak window depth permanently
                self._overlap.window.release(t_disp)
                raise

    def _complete_frame(self, entry) -> Buffer:
        """COMPLETER side: materialize one frame's results and run the
        per-frame accounting the sync path does inline. Raises on invoke
        failure — the executor routes that to :meth:`_complete_error`."""
        outputs = self.fw.complete(entry.payload)
        if self._breaker is not None:
            self._breaker.record_success()
        self._record_latency(time.perf_counter_ns() - entry.t_dispatch_ns)
        if self._watchdog is not None:
            self._watchdog.feed()
        buf = entry.buf
        outputs = self._trim_padded_rows(buf, outputs)
        if self.prefetch_host:
            outputs = submit_fetch(outputs)
        out = buf.with_chunks(self._combine_outputs(buf, outputs))
        if self._first_buffer_ns is None:
            self._first_buffer_done()
        return out

    def _first_buffer_done(self) -> None:
        """The element's first buffer is complete and about to go
        downstream (chain thread, or the completer under a window): the
        load ends here. Stamped once: ``nns.load.first_buffer`` from
        the buffer's entry into ``do_chain``, recorded after the fact
        as a wait is, under ``nns.load.start``'s span; the backend is
        told, so that a program it builds from now on counts as a
        recompile on the frame path."""
        with self._stats_lock:
            self._first_buffer_ns = time.time_ns() - self._first_chain_ns
        done = getattr(self.fw, "first_buffer_done", None)
        if callable(done):
            done(self.name)
        _obs_spans.record_span(
            "nns.load.first_buffer", "load", self._first_chain_ns,
            self._first_buffer_ns, parent=self._load_spans[-1].sid,
            prof="nns.load.first_buffer", element=self.name)

    def _complete_error(self, entry, exc: BaseException) -> None:
        """A frame that failed at completion: same per-frame accounting
        as a sync invoke failure (invoke_errors / frames_dropped /
        breaker), even though the chain thread returned long ago."""
        self._account_invoke_error(exc)
        self._settle_failed_rows(entry.buf)

    def _settle_failed_rows(self, buf: Buffer) -> None:
        """Serve-batch rows of a failed frame get their on_shed callback
        (wire-level SHED + retry-after) instead of silently timing out
        at the client's deadline. Accounted under frames_dropped — not
        ``shed``, which counts breaker-open rejections."""
        rows = buf.extras.get("serve_rows")
        if not rows:
            return
        for req in rows:
            if req.on_shed is not None:
                try:
                    req.on_shed(req)
                except Exception:  # noqa: BLE001 — one dead client
                    logger.warning("%s: shed callback failed for "
                                   "stream %s", self.name,
                                   req.stream_id, exc_info=True)
        self._record_shed_failed(buf, len(rows))

    @staticmethod
    def _record_shed_failed(buf: Buffer, n: int) -> None:
        """Report rows settled by the filter's failure paths back to the
        scheduler: they left its batcher as ``submitted`` but no demuxed
        result ever returns, so without this terminal the serve
        settlement identity (requests == completed + shed_deadline +
        cancelled + shed_failed + pending) cannot balance."""
        sched = buf.extras.get("serve_sched")
        if sched is not None:
            sched.record_shed_failed(n)

    def _account_invoke_error(self, exc: BaseException) -> None:
        # invoke failure drops THIS frame but keeps the pipeline alive
        # (≙ tensor_filter.c:961-963); the error is surfaced on the
        # bus as a warning with an error counter, not a fatal error.
        # Warnings are rate-limited (1, 2, 4, 8, ... then every 64th)
        # so a permanently broken model can't flood an unread bus, and
        # carry the message string only — holding the exception object
        # would pin the traceback (and the input tensors) in memory.
        n = self.stats.inc("invoke_errors")
        self.stats.inc("frames_dropped")
        if self._breaker is not None:
            self._breaker.record_failure()
        logger.warning("%s: invoke failed (frame dropped, pipeline "
                       "kept): %s", self.name, exc)
        if n & (n - 1) == 0 or n % 64 == 0:
            self.post_message("warning", error=str(exc),
                              invoke_errors=n,
                              remedy="check the model's input "
                                     "dims/dtypes against the "
                                     "negotiated caps, or the "
                                     "subplugin's own logs")

    @staticmethod
    def _trim_padded_rows(buf: Buffer, outputs: List[Any]) -> List[Any]:
        nv = buf.extras.get("batch_valid_rows")
        if nv is None or not buf.chunks:
            return outputs
        # micro-batched upstream (e.g. query serversrc batch=K) padded
        # the stack to a fixed compile signature; drop padded rows of
        # HOST outputs (a free numpy view). Only outputs whose leading
        # dim IS the padded batch axis are touched — anything else
        # (flat vectors, [N,7] detection tables) passes through.
        # Device outputs ship padded: slicing them would be one more
        # eager device op per output per batch.
        pad = buf.chunks[0].shape[0] if buf.chunks[0].shape else None
        return [o[:nv] if isinstance(o, np.ndarray)
                and o.ndim >= 1 and pad is not None
                and o.shape[0] == pad and pad > nv else o
                for o in outputs]

    def transfer_report(self) -> dict:
        """Window occupancy / overlap stats for the pipeline report's
        ``transfer`` block, with the backend's ``prepared_equations``
        (filters/prepare.py: equations run once per load),
        ``prepared_leaves`` / ``prepared_bytes`` (parameters held a
        second time in their compute dtype) and ``kernel_calls`` (the
        program's Pallas kernels by name, with their call sites),
        under ``load`` what :meth:`load_report` gives, and under
        ``state``, for a model that carries one between buffers,
        ``{leaves, bytes, dispatches, drops}``; {} when running
        synchronously with nothing prepared, no kernel and a backend
        that keeps no account of its load."""
        rep = self._overlap.report() if self._overlap is not None else {}
        prepared = getattr(self.fw, "prepared_report", None)
        if callable(prepared):
            held = prepared()
            if rep or held["prepared_equations"] or held["kernel_calls"]:
                rep = {**rep, **held}
        load = self.load_report()
        if load is not None:
            rep = {**rep, "load": load}
        state = self._held_state()
        if state is not None:
            rep = {**rep, "state": state}
        return rep

    def _held_state(self) -> Optional[dict]:
        """The backend's account of a state it carries between buffers
        (``state_report()``), None where it has none."""
        report = getattr(self.fw, "state_report", None)
        return report() if callable(report) else None

    def load_report(self) -> Optional[dict]:
        """Where the seconds between ``start()`` and the first buffer
        went, from the load's own spans (``obs/load.py``):
        ``start_s`` (the ``nns.load.start`` spans: ``start()`` whole
        and an earlier opening of the framework), the backend's
        ``model_s`` and ``place_s`` (and ``model_jit``: what a model file
        compiled for itself inside ``model_s``), ``first_buffer_s`` (the
        first buffer's entry into ``do_chain`` to its completion), ``total_s``
        (the first span's begin to that completion; both None until it
        is there) and ``programs``, one record per program the backend
        built. None for a backend that keeps no such account, and with
        recording off (``NNS_TPU_OBS=0``)."""
        report = getattr(self.fw, "load_report", None)
        block = report() if callable(report) else None
        spans = [s for s in self._load_spans if s.dur_ns]
        if block is None or not spans:
            return None
        first = self._first_buffer_ns
        done = first is not None
        programs = block.pop("programs")
        return {
            "start_s": sum(s.dur_ns for s in spans) / 1e9,
            **block,        # model_s, place_s, model_jit where there is one
            "first_buffer_s": first / 1e9 if done else None,
            "total_s": (self._first_chain_ns + first - spans[0].t0) / 1e9
            if done else None,
            "programs": programs}

    # -- circuit breaker ---------------------------------------------------
    def _shed_frame(self, buf: Buffer) -> None:
        """Answer a frame while the breaker is open: serve-batch rows
        get their on_shed callback (the wire-level SHED + retry-after
        reply), and upstream gets a QosEvent spaced by the retry-after
        hint so sources stop producing doomed frames."""
        self.stats.inc("shed")
        self.stats.inc("dropped")
        _obs_events.emit("shed", source=self.name, element=self,
                         reason="breaker-open", pts=buf.pts)
        retry_after_ms = float(self.breaker_retry_after_ms)
        rows = buf.extras.get("serve_rows")
        if rows:
            for req in rows:
                if req.on_shed is not None:
                    try:
                        req.on_shed(req)
                    except Exception:  # noqa: BLE001 — one dead client
                        logger.warning("%s: shed callback failed for "
                                       "stream %s", self.name,
                                       req.stream_id, exc_info=True)
            self._record_shed_failed(buf, len(rows))
        self.send_upstream_event(QosEvent(
            proportion=2.0, period_ns=int(retry_after_ms * 1e6),
            timestamp=buf.pts))

    def _on_breaker_transition(self, old: str, new: str) -> None:
        from ..fault.breaker import OPEN
        if new == OPEN:
            self.stats.inc("breaker_opened")
        logger.warning("%s: circuit breaker %s -> %s", self.name, old, new)
        _obs_events.emit("breaker", source=self.name, element=self,
                         old=old, new=new)
        self.post_message("warning", breaker=new, breaker_from=old,
                          invoke_errors=self.stats["invoke_errors"],
                          retry_after_ms=float(self.breaker_retry_after_ms))

    # -- QoS throttling ----------------------------------------------------
    def handle_event(self, pad: Pad, event: Event) -> None:
        from ..pipeline.events import FlushEvent, SegmentEvent
        if self._overlap is not None:
            # serialized events (EOS, caps, segment) must not overtake
            # in-flight frames: barrier until the completer has settled
            # and pushed everything dispatched before this event
            self._overlap.flush()
        if isinstance(event, (SegmentEvent, FlushEvent)):
            # new segment / flush = PTS discontinuity: stale throttle state
            # would otherwise qos-drop every post-restart frame forever
            self._throttle_period_ns = 0
            self._next_accept_ts = None
        super().handle_event(pad, event)

    def _qos_should_drop(self, buf: Buffer) -> bool:
        if self._throttle_period_ns <= 0 or buf.pts is None:
            return False
        if self._next_accept_ts is not None and buf.pts < self._next_accept_ts:
            return True
        self._next_accept_ts = buf.pts + self._throttle_period_ns
        return False

    def handle_upstream_event(self, pad: Pad, event: Event) -> None:
        if isinstance(event, QosEvent):
            # keep the larger of the downstream-requested spacing and our
            # own sustainable cadence. Synchronously that cadence is the
            # invoke latency; under a K-frame window K completions are in
            # flight at once, so the sustainable period is latency/K —
            # throttling to full completion latency would forfeit the
            # overlap the window exists to win.
            window = self._overlap.window.limit \
                if self._overlap is not None else 1
            lat_ns = int(self.latency_average_us() * 1e3) // max(1, window)
            self._throttle_period_ns = max(event.period_ns, lat_ns) \
                if event.proportion > 1.0 else 0
            if self._throttle_period_ns == 0:
                self._next_accept_ts = None
            return  # consumed: the filter is the throttling point
        super().handle_upstream_event(pad, event)

    def _combine_outputs(self, inbuf: Buffer, outputs: List[Any]) -> List[Chunk]:
        if not self._out_combi:
            return [Chunk(o) for o in outputs]
        # output-combination: "i0,o1" mixes input passthrough and outputs
        # (≙ out-combination, tensor_filter.c:972-1076)
        chunks = []
        for tok in self._out_combi:
            kind, idx = tok[0], int(tok[1:])
            chunks.append(inbuf.chunks[idx] if kind == "i" else Chunk(outputs[idx]))
        return chunks

    def _dispatch_async(self, outputs: List[Any],
                        ctx: Optional[Buffer] = None) -> None:
        """Called by the backend once per generated output frame
        (≙ gst_tensor_filter_async_output_callback, tensor_filter.c:1099).
        ``ctx`` is the input buffer passed at invoke time — with two
        prompts in flight each token frame is stamped from its OWN
        prompt, not whichever arrived last."""
        template = ctx if ctx is not None \
            else getattr(self, "_async_template", None)
        buf = Buffer([Chunk(o) for o in outputs],
                     pts=template.pts if template else None)
        self.push(buf)

    # -- stats ------------------------------------------------------------
    def _note_recompiles(self, c0: int) -> None:
        """Frame-path compilations: the backend's jit cache missed
        DURING a frame invoke/dispatch (warmup and cache prewarm don't
        route through here, so they never count). A warmed process must
        hold this at zero — `make jit-stability` pins it, and
        /metrics exports it as nns_jit_recompiles_total."""
        d = getattr(self.fw, "compile_count", 0) - c0
        if d > 0:
            self.stats.add(jit_recompiles=d)

    def _record_latency(self, dt_ns: int) -> None:
        """Record one frame's dispatch-to-COMPLETION latency. Sync path:
        chain thread; windowed path: completer thread — every mutation
        sits under _stats_lock, and the bus post happens outside it
        (posting is I/O; never under a leaf lock)."""
        report_us = None
        with self._stats_lock:
            self._invoke_count += 1
            self._total_latency_ns += dt_ns
            self._recent_latency.append(dt_ns)
            if self.latency:
                est_us = (sum(self._recent_latency)
                          / len(self._recent_latency) / 1e3)
                self.latency_us = est_us
                # re-report when the rolling estimate drifts past the 5%
                # headroom or improves by more than 25%
                # (≙ tensor_filter.c:490-527 re-reporting thresholds)
                rep = self._reported_latency_us
                if rep is None or est_us > rep * _LATENCY_REPORT_HEADROOM \
                        or est_us < rep * _LATENCY_IMPROVE_THRESHOLD:
                    self._reported_latency_us = est_us
                    report_us = est_us
        if report_us is not None:
            self.post_message("latency", latency_us=report_us)

    def _record_dispatch(self, dt_ns: int) -> None:
        """Record one frame's dispatch-to-RETURN time (the chain-thread
        cost). Synchronously it equals the completion latency; under a
        window it is near-zero — surfacing both is what makes the
        overlap visible instead of silently misreported."""
        with self._stats_lock:
            self._dispatch_count += 1
            self._total_dispatch_ns += dt_ns
            self._recent_dispatch.append(dt_ns)

    def latency_average_us(self) -> float:
        """Rolling dispatch-to-completion average over the last 10
        frames, µs (≙ latency property, tensor_filter.c:408-448)."""
        with self._stats_lock:
            if not self._recent_latency:
                return 0.0
            return (sum(self._recent_latency)
                    / len(self._recent_latency) / 1e3)

    def dispatch_average_us(self) -> float:
        """Rolling dispatch-to-return average over the last 10 frames,
        µs — the chain-thread cost per frame under the window."""
        with self._stats_lock:
            if not self._recent_dispatch:
                return 0.0
            return (sum(self._recent_dispatch)
                    / len(self._recent_dispatch) / 1e3)

    def throughput_fps(self) -> float:
        """Invokes/sec since start (≙ throughput prop, tensor_filter.c:452)."""
        if self._start_time is None or self._invoke_count == 0:
            return 0.0
        dt = time.monotonic() - self._start_time
        return self._invoke_count / dt if dt > 0 else 0.0

    # -- suspend ----------------------------------------------------------
    def _on_idle(self) -> None:
        if self.fw is not None:
            logger.info("%s: idle %dms, suspending model", self.name, self.suspend)
            self.fw.handle_event(FilterEvent.SUSPEND)

    def reload_model(self, model: Optional[str] = None) -> bool:
        """Hot-swap the model (≙ RELOAD_MODEL / is-updatable path)."""
        if model:
            self.model = model
        data = {"model_files": tuple(self.model.split(","))} if model else None
        return self.fw.handle_event(FilterEvent.RELOAD_MODEL, data)
