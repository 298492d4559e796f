"""Pipeline-less single invoke (the ML "single-shot" API).

≙ gst/nnstreamer/tensor_filter/tensor_filter_single.c — the GObject with
klass->invoke/start/stop behind the C ML Single-shot API. Shares the same
backend classes (and therefore the same PJRT client/process) as the
tensor_filter pipeline element, per BASELINE.json's north star.
"""
from __future__ import annotations

from typing import Any, Callable, List, Optional, Sequence

from .filters.base import Accelerator, FilterEvent, FilterProperties
from .filters.registry import detect_framework, find_filter
from .tensors.info import TensorsInfo


class SingleShot:
    """Open a model once, invoke synchronously (or async-callback) without
    building a pipeline."""

    def __init__(self, model: str, framework: str = "auto",
                 input_info: Optional[TensorsInfo] = None,
                 output_info: Optional[TensorsInfo] = None,
                 accelerator: str = "", custom: str = ""):
        models = tuple(model.split(","))
        if framework in ("auto", ""):
            framework = detect_framework(models)
        self.props = FilterProperties(
            framework=framework, model_files=models,
            input_info=input_info, output_info=output_info,
            # empty = framework default (TPU), as on the element
            accelerators=(tuple(Accelerator.parse(accelerator))
                          if accelerator else (Accelerator.DEFAULT,)),
            custom_properties=custom)
        self.fw = find_filter(framework)()
        self._opened = False
        self._async_cb: Optional[Callable[[List[Any]], None]] = None

    def start(self) -> "SingleShot":
        if not self._opened:
            self.fw.open(self.props)
            self._opened = True
        return self

    def stop(self) -> None:
        if self._opened:
            self.fw.close()
            self._opened = False

    def __enter__(self) -> "SingleShot":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    def invoke(self, inputs: Sequence[Any]) -> List[Any]:
        if not self._opened:
            self.start()
        return self.fw.invoke(list(inputs))

    def set_async_callback(self, cb: Callable[[List[Any]], None]) -> None:
        self._async_cb = cb
        # user callbacks take just the outputs; drop the per-invoke ctx
        self.fw.set_async_dispatcher(lambda outputs, ctx=None: cb(outputs))

    def invoke_async(self, inputs: Sequence[Any], ctx: Any = None) -> None:
        if not self._opened:
            self.start()
        self.fw.invoke_async(list(inputs), ctx=ctx)

    def get_model_info(self):
        if not self._opened:
            self.start()
        return self.fw.get_model_info()
