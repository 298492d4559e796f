"""Shared lifecycle for interop (imported-model) filter backends.

tensorflow-lite and onnxruntime differ only in their importer; the
open/compile/invoke/suspend/reload machinery is identical, so it lives
here once. Subclasses set ``NAME``, ``EXTENSIONS``, and ``_load``.
"""
from __future__ import annotations

import threading
from typing import Any, Callable, List, Optional, Sequence, Tuple

import numpy as np

from ..tensors.info import TensorsInfo
from ..utils.log import logger
from ..utils.xla_cache import ensure_compile_cache
from .base import FilterEvent, FilterFramework, FilterProperties
from .jax_backend import _device_for


class ImportedModelFilter(FilterFramework):
    """Backend whose model is imported to one jittable function with
    static input/output_info (interop/tflite.py, interop/onnx.py)."""

    #: importer: path -> object with .fn / .input_info / .output_info
    _load: Callable[[str], Any]

    def __init__(self):
        self._model = None
        self._jit: Any = None
        self._device = None
        self._props: Optional[FilterProperties] = None
        self._lock = threading.Lock()
        self._suspended = False

    # -- lifecycle --------------------------------------------------------
    def open(self, props: FilterProperties) -> None:
        ensure_compile_cache()
        self._props = props
        self._device = _device_for(props.accelerators)
        if not props.model_files:
            raise ValueError(f"{self.NAME} backend needs a model file")
        self._model = type(self)._load(props.model_files[0])
        self._compile()
        logger.info("%s backend imported %s (%d in, %d out) on %s",
                    self.NAME, props.model_files[0],
                    len(self._model.input_info),
                    len(self._model.output_info), self._device)

    def _compile(self) -> None:
        import jax
        self._jit = jax.jit(self._model.fn)

    def close(self) -> None:
        self._model = None
        self._jit = None

    # -- info -------------------------------------------------------------
    def get_model_info(self) -> Tuple[Optional[TensorsInfo],
                                      Optional[TensorsInfo]]:
        if self._model is None:
            return None, None
        return self._model.input_info, self._model.output_info

    # -- invoke -----------------------------------------------------------
    def invoke(self, inputs: Sequence[Any]) -> List[Any]:
        import jax
        with self._lock:
            if self._suspended:
                self._compile()
                self._suspended = False
            infos = self._model.input_info
            xs = []
            for x, info in zip(inputs, infos):
                if not isinstance(x, jax.Array):
                    x = jax.device_put(np.asarray(x), self._device)
                # pipeline buffers omit size-1 batch dims (3:224:224 vs
                # the model's [1,224,224,3]); reshape by element count
                if tuple(x.shape) != tuple(info.shape):
                    x = x.reshape(info.shape)
                xs.append(x)
            out = self._jit(*xs)
        return list(out)

    # -- events -----------------------------------------------------------
    def handle_event(self, event: FilterEvent, data=None) -> bool:
        if event == FilterEvent.RELOAD_MODEL:
            assert self._props is not None
            path = (data or {}).get("model_files",
                                    self._props.model_files)[0]
            fresh = type(self)._load(path)
            with self._lock:
                self._model = fresh
                self._compile()
            return True
        if event == FilterEvent.SUSPEND:
            with self._lock:
                # drop the compiled executable (weights are baked into the
                # XLA program; releasing it releases HBM)
                self._jit = None
                self._suspended = True
            return True
        if event == FilterEvent.RESUME:
            with self._lock:
                if self._suspended:
                    self._compile()
                    self._suspended = False
            return True
        return False
