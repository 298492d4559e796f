"""FilterFramework: the filter-backend subplugin ABI.

The Python analog of GstTensorFilterFramework **v1**
(ref: gst/nnstreamer/include/nnstreamer_plugin_api_filter.h:399-475 —
open/close/invoke/getFrameworkInfo/getModelInfo/eventHandler), with the
reference's event vocabulary (DESTROY_NOTIFY, RELOAD_MODEL, CUSTOM_PROP,
SET_INPUT_PROP, SET_OUTPUT_PROP, SET_ACCELERATOR, SUSPEND, RESUME) and
async output dispatch for generative models
(ref: nnstreamer_filter_dispatch_output_async, :613).
"""
from __future__ import annotations

import dataclasses
import enum
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from ..tensors.info import TensorsInfo


def parse_custom_properties(s: str) -> Dict[str, str]:
    """``k:v,k:v`` custom option string (the reference's custom-prop
    grammar, e.g. ``custom=mesh:2x1x4,rules:gpt``); a bare key maps to
    ``"true"``."""
    out: Dict[str, str] = {}
    for part in (s or "").split(","):
        part = part.strip()
        if ":" in part:
            k, v = part.split(":", 1)
            out[k.strip()] = v.strip()
        elif part:
            out[part] = "true"
    return out


class InvokeDrop(Exception):
    """Raised by a backend's ``invoke`` to signal "drop this frame, keep
    the pipeline" (≙ invoke result > 0, tensor_filter.c:961-963). Any
    other exception from invoke is counted as an invoke *error*; both
    drop the frame rather than killing the pipeline."""


class HeldStateNotCheckpointable(RuntimeError):
    """Raised by ``tensor_filter.snapshot_state`` when the loaded model
    carries a device-resident state between buffers (the jax backend's
    five-item ``get_model()``): ``checkpoint/`` cannot snapshot it yet,
    and a snapshot without it would not be the stream's."""


class FilterEvent(enum.Enum):
    """(ref: event_ops enum, nnstreamer_plugin_api_filter.h:205-217)"""

    DESTROY_NOTIFY = "destroy_notify"
    RELOAD_MODEL = "reload_model"
    CUSTOM_PROP = "custom_prop"
    SET_INPUT_PROP = "set_input_prop"
    SET_OUTPUT_PROP = "set_output_prop"
    SET_ACCELERATOR = "set_accelerator"
    CHECK_HW_AVAILABILITY = "check_hw_availability"
    SUSPEND = "suspend"
    RESUME = "resume"


class Accelerator(enum.Enum):
    """(ref: accl_hw enum, nnstreamer_plugin_api_filter.h:80-102).
    On this framework DEFAULT means the JAX default device (TPU)."""

    NONE = "none"
    DEFAULT = "default"
    CPU = "cpu"
    TPU = "tpu"
    GPU = "gpu"

    @classmethod
    def parse(cls, s: str) -> List["Accelerator"]:
        """Parse reference-style accelerator strings: "true:tpu.cpu"
        (ref: parse_accl_hw, nnstreamer_plugin_api_filter.h:529-550)."""
        s = (s or "").strip()
        if not s or s.lower() in ("false", "none"):
            return [cls.NONE]
        if ":" in s:
            _, rest = s.split(":", 1)
        elif s.lower() in ("true", "auto"):
            rest = "default"
        else:
            rest = s
        out = []
        for part in rest.replace(",", ".").split("."):
            part = part.strip().lower()
            if not part:
                continue
            try:
                out.append(cls(part))
            except ValueError:
                out.append(cls.DEFAULT)
        return out or [cls.DEFAULT]


@dataclasses.dataclass
class FilterProperties:
    """Per-instance filter configuration handed to the framework
    (ref: GstTensorFilterProperties, nnstreamer_plugin_api_filter.h:112-144)."""

    framework: str = ""
    model_files: Tuple[str, ...] = ()
    input_info: Optional[TensorsInfo] = None
    output_info: Optional[TensorsInfo] = None
    accelerators: Tuple[Accelerator, ...] = (Accelerator.DEFAULT,)
    custom_properties: str = ""
    invoke_dynamic: bool = False   # output shape may vary per invoke
    invoke_async: bool = False     # N outputs per input via dispatcher
    shared_key: Optional[str] = None
    latency_report: bool = False


class FilterFramework:
    """Backend subplugin base class (≙ GstTensorFilterFramework v1).

    Lifecycle: ``open`` loads the model, ``invoke`` runs it, ``close``
    releases. ``invoke`` takes/returns a list of arrays (host ndarrays or
    device jax.Arrays — TPU backends keep everything device-resident).
    """

    NAME = ""
    # framework auto-detect: model-file extensions this backend claims
    # (ref: gst_tensor_filter_detect_framework, tensor_filter_common.c:1174)
    EXTENSIONS: Tuple[str, ...] = ()
    AVAILABLE = True
    # True when invoke() accepts inputs with one extra leading batch dim
    # (the element then negotiates aggregator-stacked streams); backends
    # that lower to a fixed model shape must leave this False
    SUPPORTS_BATCH = False
    # True when the backend can split invoke into a non-blocking
    # dispatch() and a blocking complete() — what the element's K-frame
    # in-flight window (in-flight property) is built on. Backends whose
    # invoke is inherently synchronous leave this False; the element
    # then ignores the window and stays synchronous.
    SUPPORTS_DISPATCH = False

    def open(self, props: FilterProperties) -> None:
        raise NotImplementedError

    def close(self) -> None:
        pass

    def invoke(self, inputs: Sequence[Any]) -> List[Any]:
        raise NotImplementedError

    # overlapped execution -------------------------------------------------
    def dispatch(self, inputs: Sequence[Any], donate: bool = False
                 ) -> Any:
        """Enqueue one frame's device program WITHOUT waiting for the
        results; returns an opaque in-flight handle for
        :meth:`complete`. ``donate`` permits input/output buffer
        aliasing for inputs the backend itself staged (platform
        permitting). The default implementation degrades to a
        synchronous invoke — the handle IS the outputs — so a window of
        K over a non-async backend is merely useless, never wrong."""
        return self.invoke(inputs)

    def complete(self, handle: Any) -> List[Any]:
        """Block until a dispatched frame's outputs are materialized
        enough to hand downstream; raises if the device program failed.
        Called from the element's completer thread — implementations
        must be safe to run concurrently with :meth:`dispatch`."""
        return handle

    def get_model_info(self) -> Tuple[Optional[TensorsInfo], Optional[TensorsInfo]]:
        """(input_info, output_info); either may be None if the backend
        derives it from the negotiated caps (SET_INPUT_PROP path)."""
        return None, None

    def set_input_info(self, info: TensorsInfo) -> Optional[TensorsInfo]:
        """Negotiation push-path: given input info, return output info
        (≙ getModelInfo SET_INPUT_INFO, nnstreamer_plugin_api_filter.h:439)."""
        return None

    def handle_event(self, event: FilterEvent, data: Optional[dict] = None) -> bool:
        """Return True if handled. RELOAD_MODEL/SUSPEND/RESUME arrive here."""
        return False

    # async generative path -----------------------------------------------
    # set by the owner of an async backend (the element installs its
    # invoke-error accounting): a failure AFTER invoke_async returned —
    # generation thread, scheduler loop, admission — has no caller left
    # to raise into, and a stream that silently never yields a token is
    # the one outcome nobody can see
    on_async_error: Optional[Callable[[BaseException], None]] = None

    def _report_async_error(self, exc: BaseException) -> None:
        """Hand one lost stream's failure to :attr:`on_async_error`."""
        if self.on_async_error is not None:
            self.on_async_error(exc)

    def set_async_dispatcher(
            self, dispatch: Callable[..., None]) -> None:
        """Element installs a callback; an async backend calls it once per
        produced output frame (≙ nnstreamer_filter_dispatch_output_async).
        The callback signature is ``dispatch(outputs, ctx=None)`` — the
        backend hands back the opaque ``ctx`` it was given at
        ``invoke_async`` time so the element can attribute each output
        frame to its originating input (the reference passes the
        GstTensorFilter handle + per-invoke data the same way); with
        several invokes in flight, omitting ctx mis-stamps frames."""
        self._dispatch = dispatch

    def invoke_async(self, inputs: Sequence[Any], ctx: Any = None) -> None:
        """1-in/N-out invoke; outputs flow through the dispatcher, each
        carrying ``ctx`` back to the element."""
        raise NotImplementedError
