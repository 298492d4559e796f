"""Tensor frames flowing through the pipeline.

The TPU-native analog of GstBuffer carrying N tensor memories
(ref: gst/nnstreamer/nnstreamer_plugin_api_impl.c —
gst_tensor_buffer_get_nth_memory / append_memory).

Key departure from the reference: a chunk may be **device-resident**
(a ``jax.Array`` living in HBM). Chained device-side elements hand arrays
to each other without materializing to host; only converter/decoder/sink
boundaries call :meth:`Chunk.host`. This is the zero-copy story on TPU —
the reference passes host pointers, we pass HBM references (SURVEY.md §7
hard part (b)). There is no 16-chunk packing limit; chunks are a list.
"""
from __future__ import annotations

import enum
from typing import Any, List, Optional, Sequence

import numpy as np

from .info import TensorInfo, TensorsInfo
from .meta import TensorMetaInfo
from .transfer import PendingHost
from .types import TensorType


def _is_device_array(x) -> bool:
    import jax
    return isinstance(x, jax.Array)


class BufferFlags(enum.IntFlag):
    NONE = 0
    DISCONT = 1     # stream discontinuity
    GAP = 2         # filler frame
    DROPPABLE = 4   # QoS may drop


class Chunk:
    """One tensor memory: a host ndarray, a device jax.Array, or a
    :class:`~..tensors.transfer.PendingHost` (a D2H fetch in flight, started
    by the filter's prefetch-host pool).

    ``meta`` is present on flexible/sparse streams (self-describing header,
    ref: GstTensorMetaInfo); static streams rely on negotiated caps.
    """

    __slots__ = ("_data", "meta")

    def __init__(self, data: Any, meta: Optional[TensorMetaInfo] = None):
        self._data = data
        self.meta = meta

    def _settle(self) -> Any:
        """Resolve an in-flight fetch (blocking) and cache the result."""
        d = self._data
        if isinstance(d, PendingHost):
            d = self._data = d.resolve()
        return d

    # -- residency --------------------------------------------------------
    @property
    def is_device(self) -> bool:
        d = self._data
        if isinstance(d, PendingHost):
            # still device-reachable until the fetch lands: chained
            # device-side elements keep HBM residency without waiting
            return d.dev is not None and not d.done
        return not isinstance(d, (np.ndarray, bytes, bytearray, memoryview))

    @property
    def raw(self) -> Any:
        """The underlying array, wherever it lives. For a chunk whose
        host fetch is in flight this is non-blocking while the device
        array is still reachable (device consumers proceed in HBM);
        otherwise it blocks for the fetched host copy."""
        d = self._data
        if isinstance(d, PendingHost):
            if not d.done and d.dev is not None:
                return d.dev
            d = self._data = d.resolve()
        return d

    def host(self) -> np.ndarray:
        """Materialize to a host ndarray (D2H transfer if device-resident)."""
        d = self._settle()
        if isinstance(d, np.ndarray):
            return d
        if isinstance(d, (bytes, bytearray, memoryview)):
            return np.frombuffer(d, dtype=np.uint8)
        return np.asarray(d)

    def device(self, device=None, sharding=None):
        """Materialize on device (H2D transfer if host-resident)."""
        import jax
        d = self._data
        if isinstance(d, PendingHost):
            # prefer the still-live device array: no wait, no H2D
            d = d.dev if d.dev is not None else self._settle()
        if _is_device_array(d) and device is None and sharding is None:
            return d
        if not _is_device_array(d) and not isinstance(d, np.ndarray):
            d = self.host()
        return jax.device_put(d,
                              sharding if sharding is not None else device)

    # -- shape/dtype ------------------------------------------------------
    @property
    def shape(self):
        d = self._data
        if isinstance(d, (bytes, bytearray, memoryview)):
            return (len(d),)
        return tuple(d.shape)

    @property
    def dtype(self):
        d = self._data
        if isinstance(d, (bytes, bytearray, memoryview)):
            return np.dtype(np.uint8)
        return np.dtype(d.dtype)

    @property
    def nbytes(self) -> int:
        d = self._data
        if isinstance(d, (bytes, bytearray, memoryview)):
            return len(d)
        return int(np.prod(self.shape)) * self.dtype.itemsize

    def to_info(self, name: Optional[str] = None) -> TensorInfo:
        return TensorInfo(name=name, type=TensorType.from_dtype(self.dtype),
                          shape=self.shape)

    def __repr__(self) -> str:
        loc = "dev" if self.is_device else "host"
        return f"Chunk<{loc}:{self.dtype}:{self.shape}>"


class Buffer:
    """One frame: ordered chunks + timing metadata.

    Timing fields are nanoseconds, mirroring GstBuffer pts/dts/duration.
    """

    __slots__ = ("chunks", "pts", "dts", "duration", "flags", "extras")

    def __init__(self, chunks: Sequence[Chunk] = (), pts: Optional[int] = None,
                 dts: Optional[int] = None, duration: Optional[int] = None,
                 flags: BufferFlags = BufferFlags.NONE):
        self.chunks: List[Chunk] = list(chunks)
        self.pts = pts
        self.dts = dts
        self.duration = duration
        self.flags = flags
        self.extras: dict = {}  # side-band metadata (e.g., crop coords, client id)

    @classmethod
    def from_arrays(cls, arrays: Sequence[Any], **kw) -> "Buffer":
        return cls([a if isinstance(a, Chunk) else Chunk(a) for a in arrays], **kw)

    def __len__(self) -> int:
        return len(self.chunks)

    def __getitem__(self, i: int) -> Chunk:
        return self.chunks[i]

    def __iter__(self):
        return iter(self.chunks)

    def append(self, chunk: Chunk) -> None:
        # racecheck: ok(buffers are single-owner: built by one thread, then handed off whole via queue/pad push)
        self.chunks.append(chunk)

    @property
    def nbytes(self) -> int:
        return sum(c.nbytes for c in self.chunks)

    def arrays(self) -> List[Any]:
        return [c.raw for c in self.chunks]

    def host_arrays(self) -> List[np.ndarray]:
        return [c.host() for c in self.chunks]

    def to_infos(self) -> TensorsInfo:
        return TensorsInfo(c.to_info() for c in self.chunks)

    def with_chunks(self, chunks: Sequence[Chunk]) -> "Buffer":
        """New buffer reusing this one's timing metadata."""
        b = Buffer(chunks, self.pts, self.dts, self.duration, self.flags)
        b.extras = dict(self.extras)
        return b

    def copy_meta_from(self, other: "Buffer") -> "Buffer":
        self.pts, self.dts = other.pts, other.dts
        self.duration, self.flags = other.duration, other.flags
        self.extras = dict(other.extras)
        return self

    def __repr__(self) -> str:
        return f"Buffer(pts={self.pts}, chunks={self.chunks!r})"
