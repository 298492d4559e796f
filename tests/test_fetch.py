"""Coalescing D2H fetch service (the download side of tensors/transfer.py).

The service batches frame-at-a-time device->host fetches, each of which
is a host sync with a fixed cost; these tests pin the
semantics (transparent Chunk resolution, shape/dtype without sync,
batching across frames, error delivery) on the CPU backend.
"""
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from nnstreamer_tpu.tensors.buffer import Buffer, Chunk
from nnstreamer_tpu.tensors import transfer as F


@pytest.fixture
def dev_arrays():
    jf = jax.jit(lambda a, s: a * s)
    x = jax.device_put(np.arange(12, dtype=np.float32).reshape(3, 4))
    return [jf(x, 2.0), jf(x, 3.0)]


class TestSubmitFetch:
    def test_wraps_device_arrays(self, dev_arrays):
        outs = F.submit_fetch(dev_arrays)
        assert all(isinstance(o, F.PendingHost) for o in outs)
        # shape/dtype known without resolving (from the aval, no sync)
        assert outs[0].shape == (3, 4)
        assert outs[0].dtype == np.float32
        assert outs[0].ndim == 2

    def test_resolve_values(self, dev_arrays):
        outs = F.submit_fetch(dev_arrays)
        a, b = F.resolve(outs[0]), F.resolve(outs[1])
        np.testing.assert_allclose(a, np.arange(12).reshape(3, 4) * 2.0)
        np.testing.assert_allclose(b, np.arange(12).reshape(3, 4) * 3.0)
        assert isinstance(a, np.ndarray)

    def test_host_arrays_pass_through(self):
        host = np.ones((2, 2), np.float32)
        outs = F.submit_fetch([host])
        assert outs[0] is host

    def test_mixed_host_device(self, dev_arrays):
        host = np.zeros((5,), np.int32)
        outs = F.submit_fetch([dev_arrays[0], host, dev_arrays[1]])
        assert isinstance(outs[0], F.PendingHost)
        assert outs[1] is host
        assert isinstance(outs[2], F.PendingHost)
        np.testing.assert_allclose(
            F.resolve(outs[2]), np.arange(12).reshape(3, 4) * 3.0)

    def test_resolve_identity_on_plain_values(self):
        x = np.ones(3)
        assert F.resolve(x) is x

    def test_many_frames_coalesce(self):
        """Frames submitted while a fetch RPC is in flight share the
        next one; all must land with their own values."""
        jf = jax.jit(lambda s: jnp.full((4,), s))
        pending = [F.submit_fetch([jf(float(i))]) for i in range(64)]
        for i, outs in enumerate(pending):
            np.testing.assert_allclose(F.resolve(outs[0]),
                                       np.full((4,), float(i)))

    def test_fetch_stats_report_achieved_depth(self, monkeypatch):
        """With a slow link (device_get stalled), frames queued behind
        the in-flight RPC must share the NEXT one — frames_per_rpc_avg
        > 1 — and the counters must add up. This is the bench's
        fetch_coalesce proof hook."""
        real_get = jax.device_get
        gate = threading.Event()

        def slow_get(tree):
            gate.wait(5.0)  # hold the first RPC until all frames queue
            return real_get(tree)

        monkeypatch.setattr(jax, "device_get", slow_get)
        F.fetch_stats(reset=True)
        jf = jax.jit(lambda s: jnp.full((4,), s))
        pending = [F.submit_fetch([jf(float(i))]) for i in range(16)]
        gate.set()
        for i, outs in enumerate(pending):
            np.testing.assert_allclose(F.resolve(outs[0]),
                                       np.full((4,), float(i)))
        stats = F.fetch_stats()
        assert stats["frames"] == 16
        assert stats["arrays"] == 16
        # first RPC may carry 1 frame; everything else queued behind it
        # must coalesce: strictly fewer RPCs than frames
        assert stats["rpcs"] < 16
        assert stats["frames_per_rpc_avg"] > 1.0


class TestChunkIntegration:
    def test_chunk_resolves_transparently(self, dev_arrays):
        outs = F.submit_fetch(dev_arrays)
        c = Chunk(outs[0])
        # shape and dtype visible without blocking
        assert c.shape == (3, 4)
        assert c.dtype == np.dtype(np.float32)
        h = c.host()
        assert isinstance(h, np.ndarray)
        np.testing.assert_allclose(h, np.arange(12).reshape(3, 4) * 2.0)
        # resolution is cached: raw now returns the same ndarray
        assert c.raw is h
        assert not c.is_device

    def test_pending_chunk_keeps_device_residency(self, dev_arrays):
        """Until the fetch lands, a pending chunk still behaves as
        device-resident: is_device True, raw/device() return the live
        jax.Array with no blocking, so chained device-side elements pay
        neither a wait nor an H2D re-upload."""
        dev = dev_arrays[0]
        ticket = F._Ticket([dev])  # not submitted: stays pending
        c = Chunk(F.PendingHost(ticket, 0, dev))
        assert c.is_device
        assert c.raw is dev
        assert c.device() is dev
        # fetch lands -> settles to the coalesced host copy
        ticket._deliver([np.asarray(dev)])
        assert not c.is_device
        h = c.host()
        assert isinstance(h, np.ndarray)
        np.testing.assert_allclose(h, np.asarray(dev))

    def test_error_isolated_per_frame(self, dev_arrays):
        """A poisoned array fails only its own frame's ticket; frames
        sharing the coalesced RPC still resolve (per-ticket retry)."""
        class Boom:
            shape, dtype, ndim = (2,), np.float32, 1

            def __array__(self, *a, **k):
                raise RuntimeError("poisoned output")

        good = F.submit_fetch([dev_arrays[0]])
        bad_ticket = F._Ticket([Boom()])
        F._downloader.submit(bad_ticket)
        also_good = F.submit_fetch([dev_arrays[1]])
        np.testing.assert_allclose(
            F.resolve(good[0]), np.arange(12).reshape(3, 4) * 2.0)
        np.testing.assert_allclose(
            F.resolve(also_good[0]), np.arange(12).reshape(3, 4) * 3.0)
        with pytest.raises(BaseException):
            bad_ticket.wait()

    def test_buffer_arrays_resolve(self, dev_arrays):
        import jax
        buf = Buffer.from_arrays(F.submit_fetch(dev_arrays))
        # arrays() never blocks: each entry is either the fetched host
        # copy or the still-live device array, both directly usable
        arrs = buf.arrays()
        assert all(isinstance(a, (np.ndarray, jax.Array)) for a in arrs)
        # host_arrays() is the blocking host boundary
        harrs = buf.host_arrays()
        assert all(isinstance(a, np.ndarray) for a in harrs)
        np.testing.assert_allclose(harrs[0],
                                   np.arange(12).reshape(3, 4) * 2.0)

    def test_concurrent_resolvers(self, dev_arrays):
        """Many threads blocking on the same ticket all wake correctly."""
        outs = F.submit_fetch(dev_arrays)
        results, errs = [], []

        def worker():
            try:
                results.append(F.resolve(outs[0]).sum())
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        ths = [threading.Thread(target=worker) for _ in range(8)]
        for t in ths:
            t.start()
        for t in ths:
            t.join(timeout=30)
        assert not errs
        assert len(results) == 8
