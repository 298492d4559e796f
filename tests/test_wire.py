"""Wire v2: negotiated codecs, downcast, coalescing, and the zero-copy
transport (edge/wire.py + edge/protocol.py).

Covers the unit layer (codec round-trips over every TensorType dtype,
negotiation matrix, DATA_BATCH pack/unpack), the socket layer (vectored
send / recv_into over a real socketpair, payload-length guard), strict
v1 interop (a raw-socket peer that never says "wire" must see plain v1
traffic), and the element layer (query + edge pipelines under
wire-codec=zlib, coalescing flush-by-size and flush-by-age).
"""
import socket
import struct
import threading
import time

import numpy as np
import pytest

from nnstreamer_tpu import Buffer, parse_launch
from nnstreamer_tpu.edge import protocol, wire
from nnstreamer_tpu.edge.protocol import (MsgKind, buffer_to_wire, recv_msg,
                                          send_msg, wire_to_buffer)
from nnstreamer_tpu.tensors.types import TensorType
from nnstreamer_tpu.utils.atomic import Counters


def _free_port():
    s = socket.socket()
    s.bind(("localhost", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _arr(ttype: TensorType, shape=(3, 5)) -> np.ndarray:
    """A deterministic non-trivial array of the given tensor type."""
    rng = np.random.default_rng(int(ttype))
    dt = ttype.np_dtype
    if np.issubdtype(np.dtype(str(dt)) if str(dt) != "bfloat16"
                     else np.float32, np.floating) or "float" in str(dt):
        return rng.standard_normal(shape).astype(np.float32).astype(dt)
    info = np.iinfo(dt)
    return rng.integers(info.min, info.max, shape, dtype=dt,
                        endpoint=False)


CAPS = ('other/tensors,format=static,num_tensors=1,'
        'types=(string)float32,dimensions=(string)4')


# -- codec round-trips --------------------------------------------------------


class TestCodecRoundTrip:
    @pytest.mark.parametrize("ttype", list(TensorType))
    @pytest.mark.parametrize("codec", wire.CODECS)
    def test_all_dtypes(self, ttype, codec):
        arr = _arr(ttype, shape=(16, 33))
        cfg = wire.WireConfig(codec)
        meta, payloads = wire.pack_buffer(
            Buffer.from_arrays([arr], pts=7), cfg)
        # rx mirrors the receiving end of the link (delta keeps its
        # reference state there; the other codecs ignore it)
        out = wire.unpack_buffer(meta, payloads,
                                 cfg=wire.accept(cfg.to_meta()))
        got = out.chunks[0].host()
        assert got.dtype == arr.dtype and got.shape == arr.shape
        np.testing.assert_array_equal(np.asarray(got).view(np.uint8),
                                      np.asarray(arr).view(np.uint8))
        assert got.flags.writeable
        assert out.pts == 7

    @pytest.mark.parametrize("codec", wire.CODECS)
    def test_zero_size_tensor(self, codec):
        arr = np.empty((0, 4), np.float32)
        cfg = wire.WireConfig(codec)
        meta, payloads = wire.pack_buffer(Buffer.from_arrays([arr]), cfg)
        got = wire.unpack_buffer(
            meta, payloads, cfg=wire.accept(cfg.to_meta())).chunks[0].host()
        assert got.shape == (0, 4) and got.dtype == np.float32

    @pytest.mark.parametrize("codec", wire.CODECS)
    def test_non_contiguous_input(self, codec):
        base = np.arange(240, dtype=np.int32).reshape(12, 20)
        arr = base[::2, ::2]  # stride-2 view, not C-contiguous
        assert not arr.flags.c_contiguous
        cfg = wire.WireConfig(codec)
        meta, payloads = wire.pack_buffer(Buffer.from_arrays([arr]), cfg)
        got = wire.unpack_buffer(
            meta, payloads, cfg=wire.accept(cfg.to_meta())).chunks[0].host()
        np.testing.assert_array_equal(got, arr)

    def test_compressible_actually_shrinks(self):
        arr = np.zeros((64, 64), np.float32)  # trivially compressible
        cfg = wire.WireConfig(wire.CODEC_ZLIB)
        stats = Counters()
        meta, payloads = wire.pack_buffer(Buffer.from_arrays([arr]), cfg,
                                          stats=stats)
        assert meta["tensors"][0]["codec"] == wire.CODEC_ZLIB
        assert len(payloads[0]) < arr.nbytes * 0.1
        snap = stats.snapshot()
        assert snap["wire_enc_bytes_out"] < snap["wire_raw_bytes_out"]

    def test_incompressible_ships_raw_after_adaptive_skip(self):
        arr = np.frombuffer(np.random.default_rng(0).bytes(1 << 16),
                            np.uint8).copy()
        cfg = wire.WireConfig(wire.CODEC_ZLIB)
        for _ in range(wire.POOR_LIMIT + 1):
            meta, payloads = wire.pack_buffer(Buffer.from_arrays([arr]), cfg)
            # never kept: random bytes cannot beat KEEP_RATIO
            assert "codec" not in meta["tensors"][0]
        assert cfg._skip > 0  # the link stopped paying for attempts

    def test_v1_meta_is_exact_without_cfg(self):
        buf = Buffer.from_arrays([np.arange(6, dtype=np.float32)], pts=3)
        assert wire.pack_buffer(buf, None)[0] == buffer_to_wire(buf)[0]


# -- precision downcast -------------------------------------------------------


class TestPrecisionDowncast:
    @pytest.mark.parametrize("prec,rtol", [("bf16", 1.0 / 128),
                                           ("fp16", 1e-3)])
    def test_fidelity_bounds(self, prec, rtol):
        arr = np.random.default_rng(1).standard_normal(
            (32, 8)).astype(np.float32)
        cfg = wire.WireConfig(precision=prec)
        meta, payloads = wire.pack_buffer(Buffer.from_arrays([arr]), cfg)
        assert meta["tensors"][0]["wire_dtype"] == wire._PREC_DTYPE[prec]
        assert len(payloads[0]) == arr.nbytes // 2  # halved on the wire
        got = wire.unpack_buffer(meta, payloads).chunks[0].host()
        assert got.dtype == np.float32  # original dtype restored
        np.testing.assert_allclose(got, arr, rtol=rtol, atol=1e-6)

    def test_non_float32_left_alone(self):
        arr = np.arange(12, dtype=np.int32)
        cfg = wire.WireConfig(precision="bf16")
        meta, payloads = wire.pack_buffer(Buffer.from_arrays([arr]), cfg)
        assert "wire_dtype" not in meta["tensors"][0]
        got = wire.unpack_buffer(meta, payloads).chunks[0].host()
        np.testing.assert_array_equal(got, arr)


# -- delta codec (temporal keyframe + sparse diff) ----------------------------


def _motion_frames(n, dtype=np.uint8, shape=(24, 24, 3), patch=6, seed=0):
    """A deterministic ~low-motion stream: a fixed base frame with one
    small patch redrawn per frame — the traffic the delta codec is for."""
    rng = np.random.default_rng(seed)
    if np.issubdtype(np.dtype(dtype), np.floating) or "float" in str(dtype):
        cur = rng.standard_normal(shape).astype(np.float32).astype(dtype)
        draw = lambda s: rng.standard_normal(s).astype(  # noqa: E731
            np.float32).astype(dtype)
    else:
        info = np.iinfo(dtype)
        cur = rng.integers(info.min, info.max, shape, dtype=dtype)
        draw = lambda s: rng.integers(  # noqa: E731
            info.min, info.max, s, dtype=dtype)
    frames = [cur.copy()]
    for _ in range(n - 1):
        cur = cur.copy()
        y = int(rng.integers(0, shape[0] - patch))
        x = int(rng.integers(0, shape[1] - patch))
        cur[y:y + patch, x:x + patch] = draw((patch, patch) + shape[2:])
        frames.append(cur.copy())
    return frames


def _delta_link(delta_k=4, precision="none"):
    """(sender cfg, receiver cfg) for one negotiated delta link, minted
    exactly like edgesink negotiate + edgesrc accept."""
    tx = wire.negotiate(wire.advertise(), codec="delta",
                        precision=precision, delta_k=delta_k)
    assert tx is not None and tx.codec == wire.CODEC_DELTA
    return tx, wire.accept(tx.to_meta())


class TestDeltaCodec:
    """wire-codec=delta unit layer: keyframe/diff stream round trips,
    cadence, promotions, epoch safety, precision composition, batches."""

    @pytest.mark.parametrize("ttype", list(TensorType))
    def test_stream_round_trip_all_dtypes(self, ttype):
        tx, rx = _delta_link(delta_k=4)
        frames = _motion_frames(9, dtype=ttype.np_dtype, seed=int(ttype))
        stats = Counters()
        for f in frames:
            meta, payloads = wire.pack_buffer(Buffer.from_arrays([f]), tx,
                                              stats=stats)
            got = wire.unpack_buffer(meta, payloads, cfg=rx)
            out = got.chunks[0].host()
            assert out.dtype == f.dtype and out.shape == f.shape
            np.testing.assert_array_equal(np.asarray(out).view(np.uint8),
                                          np.asarray(f).view(np.uint8))
            assert out.flags.writeable
        snap = stats.snapshot()
        assert snap["wire_delta_diffs"] > 0  # the codec actually engaged

    def test_keyframe_cadence(self):
        tx, rx = _delta_link(delta_k=4)
        frames = _motion_frames(9)
        stats = Counters()
        keys = []
        for f in frames:
            meta, payloads = wire.pack_buffer(Buffer.from_arrays([f]), tx,
                                              stats=stats)
            keys.append(bool(meta["delta"].get("k")))
            wire.unpack_buffer(meta, payloads, cfg=rx)
        # K D D D K D D D K: a keyframe every delta_k frames, no drift
        assert keys == [True, False, False, False, True,
                        False, False, False, True]
        snap = stats.snapshot()
        assert snap["wire_delta_keyframes"] == 3
        assert snap["wire_delta_diffs"] == 6
        assert snap["wire_delta_promotions"] == 0
        assert snap["wire_delta_bytes_saved"] > 0

    def test_diffs_actually_shrink_the_wire(self):
        """~6% motion on an incompressible base: per-frame zlib finds
        nothing (adaptive skip territory) but the temporal diff sheds
        the static 94%."""
        tx, rx = _delta_link(delta_k=0)  # no scheduled rekey: pure diffs
        frames = _motion_frames(8, shape=(32, 32, 3), patch=8)
        sizes = []
        for f in frames:
            meta, payloads = wire.pack_buffer(Buffer.from_arrays([f]), tx)
            sizes.append(sum(len(bytes(p) if not isinstance(p, np.ndarray)
                                 else p.tobytes()) for p in payloads))
            wire.unpack_buffer(meta, payloads, cfg=rx)
        dense = frames[0].nbytes
        assert sizes[0] >= dense * 0.9       # keyframe ships ~dense
        for s in sizes[1:]:                   # diffs ship ~the patch
            assert s < dense * 0.5

    def test_layout_change_forces_keyframe(self):
        tx, rx = _delta_link(delta_k=32)
        stats = Counters()
        a = np.arange(48, dtype=np.float32).reshape(6, 8)
        b = a.copy()
        b[0, 0] += 1  # one element moved: a genuine diff frame
        for arr in (a, b, a.reshape(8, 6)):  # 3rd frame: new layout
            meta, payloads = wire.pack_buffer(Buffer.from_arrays([arr]), tx,
                                              stats=stats)
            got = wire.unpack_buffer(meta, payloads, cfg=rx)
            np.testing.assert_array_equal(got.chunks[0].host(), arr)
        snap = stats.snapshot()
        assert snap["wire_delta_keyframes"] == 2  # fresh link + layout
        assert snap["wire_delta_promotions"] == 1  # counted as promotion

    def test_unbeatable_diff_promotes_to_keyframe(self):
        """Every pixel changes: the sparse diff costs more than the
        dense frame, so the sender promotes instead of shipping it."""
        tx, rx = _delta_link(delta_k=0)
        rng = np.random.default_rng(3)
        stats = Counters()
        for _ in range(3):  # fully-redrawn noise every frame
            arr = rng.integers(0, 255, (16, 16, 3), np.uint8)
            meta, payloads = wire.pack_buffer(Buffer.from_arrays([arr]), tx,
                                              stats=stats)
            assert meta["delta"].get("k") == 1
            got = wire.unpack_buffer(meta, payloads, cfg=rx)
            np.testing.assert_array_equal(got.chunks[0].host(), arr)
        snap = stats.snapshot()
        assert snap["wire_delta_keyframes"] == 3
        assert snap["wire_delta_promotions"] == 2  # all but the first
        assert snap["wire_delta_diffs"] == 0

    def test_diff_against_missing_reference_raises(self):
        """A diff must never silently patch the wrong baseline: a
        receiver without the sender's reference epoch raises (the link
        layer turns that into a reconnect + fresh keyframe)."""
        tx, _rx = _delta_link(delta_k=0)
        frames = _motion_frames(2)
        key = wire.pack_buffer(Buffer.from_arrays([frames[0]]), tx)
        diff = wire.pack_buffer(Buffer.from_arrays([frames[1]]), tx)
        fresh = wire.accept(tx.to_meta())  # never saw the keyframe
        with pytest.raises(ValueError, match="reference"):
            wire.unpack_buffer(diff[0], diff[1], cfg=fresh)
        # and a receiver holding a DIFFERENT epoch's reference raises too
        other = wire.accept(tx.to_meta())
        rekey = wire.negotiate(wire.advertise(), codec="delta", delta_k=0)
        meta2, p2 = wire.pack_buffer(Buffer.from_arrays([frames[0]]), rekey)
        meta2["delta"]["e"] = 99
        wire.unpack_buffer(meta2, p2, cfg=other)
        with pytest.raises(ValueError, match="epoch"):
            wire.unpack_buffer(diff[0], diff[1], cfg=other)
        del key

    def test_unpack_without_cfg_raises(self):
        tx, _rx = _delta_link()
        meta, payloads = wire.pack_buffer(
            Buffer.from_arrays([np.zeros((4, 4), np.uint8)]), tx)
        with pytest.raises(ValueError, match="negotiate"):
            wire.unpack_buffer(meta, payloads)
        with pytest.raises(ValueError, match="negotiate"):
            wire.unpack_buffer(meta, payloads,
                               cfg=wire.WireConfig(wire.CODEC_ZLIB))

    def test_precision_composes_under_delta(self):
        """bf16 downcast under delta: references live in wire precision
        on both ends, so diffs are exact in the wire domain and the
        delivered stream equals the downcast-upcast of the original."""
        tx, rx = _delta_link(delta_k=4, precision="bf16")
        frames = _motion_frames(6, dtype=np.float32)
        stats = Counters()
        import jax.numpy as jnp
        for f in frames:
            meta, payloads = wire.pack_buffer(Buffer.from_arrays([f]), tx,
                                              stats=stats)
            got = wire.unpack_buffer(meta, payloads, cfg=rx)
            arr = got.chunks[0].host()
            assert arr.dtype == np.float32
            want = np.asarray(jnp.asarray(f).astype(jnp.bfloat16)
                              ).astype(np.float32)
            np.testing.assert_array_equal(arr, want)
        assert stats.snapshot()["wire_delta_diffs"] > 0

    def test_zero_size_and_multi_chunk_stream(self):
        tx, rx = _delta_link(delta_k=3)
        a = np.empty((0, 4), np.float32)
        b = np.arange(12, dtype=np.int16).reshape(3, 4)
        for i in range(5):
            buf = Buffer.from_arrays([a, b + i], pts=i)
            meta, payloads = wire.pack_buffer(buf, tx)
            got = wire.unpack_buffer(meta, payloads, cfg=rx)
            assert got.pts == i
            assert got.chunks[0].host().shape == (0, 4)
            np.testing.assert_array_equal(got.chunks[1].host(), b + i)

    def test_batch_round_trip_with_midbatch_keyframe(self):
        """A coalesced DATA_BATCH spanning a K rollover: frames 0-5
        with delta_k=4 put a keyframe mid-batch; every frame must
        decode byte-exact with per-frame meta restored."""
        tx, rx = _delta_link(delta_k=4)
        frames = _motion_frames(6)
        bufs = [Buffer.from_arrays([f], pts=i * 10)
                for i, f in enumerate(frames)]
        stats = Counters()
        meta, payloads = wire.pack_batch(bufs, tx, stats=stats,
                                         seqs=list(range(1, 7)))
        assert meta["delta"]["ks"] == [1, 0, 0, 0, 1, 0]
        out = wire.unpack_batch(meta, payloads, cfg=rx)
        assert len(out) == 6
        for i, (f, b) in enumerate(zip(frames, out)):
            np.testing.assert_array_equal(b.chunks[0].host(), f)
            assert b.pts == i * 10
            assert b.extras["seq"] == i + 1
        snap = stats.snapshot()
        assert snap["wire_delta_keyframes"] == 2
        assert snap["wire_delta_diffs"] == 4

    def test_batch_then_single_share_reference_state(self):
        """The link reference evolves across message kinds: a DATA
        frame after a DATA_BATCH diffs against the batch's last frame."""
        tx, rx = _delta_link(delta_k=0)
        frames = _motion_frames(4)
        meta, payloads = wire.pack_batch(
            [Buffer.from_arrays([f]) for f in frames[:3]], tx)
        for b, f in zip(wire.unpack_batch(meta, payloads, cfg=rx),
                        frames[:3]):
            np.testing.assert_array_equal(b.chunks[0].host(), f)
        meta, payloads = wire.pack_buffer(Buffer.from_arrays([frames[3]]),
                                          tx)
        assert "k" not in meta["delta"]  # a diff, not a keyframe
        got = wire.unpack_buffer(meta, payloads, cfg=rx)
        np.testing.assert_array_equal(got.chunks[0].host(), frames[3])


class TestDeltaNegotiation:
    """Delta requires per-link receiver state, so it is only chosen by
    the accepting side's own request — and old peers fall back cleanly
    in both directions."""

    def test_peer_wish_never_adopted_without_local_request(self):
        cfg = wire.negotiate(wire.advertise(codec="delta"))
        assert cfg is not None and cfg.codec == wire.CODEC_RAW

    def test_local_request_against_old_peer_falls_back(self):
        old = wire.advertise()
        old["codecs"] = ["raw", "zlib", "shuffle-zlib"]  # pre-delta build
        cfg = wire.negotiate(old, codec="delta")
        assert cfg is not None and cfg.codec == wire.CODEC_RAW

    def test_local_request_against_v1_peer_is_plain(self):
        assert wire.negotiate(None, codec="delta") is None
        assert wire.negotiate({"no": "v"}, codec="delta") is None

    def test_delta_k_rides_the_ack(self):
        tx = wire.negotiate(wire.advertise(), codec="delta", delta_k=7)
        assert tx.to_meta()["delta_k"] == 7
        rx = wire.accept(tx.to_meta())
        assert rx.codec == wire.CODEC_DELTA and rx.delta_k == 7

    def test_non_delta_meta_has_no_delta_k(self):
        assert "delta_k" not in wire.WireConfig(wire.CODEC_ZLIB).to_meta()


class TestDeltaPipelines:
    """Element layer: edgesink wire-codec=delta → edgesrc, byte parity
    with the delta-off control arm."""

    CAPS_BIG = ('other/tensors,format=static,num_tensors=1,'
                'types=(string)float32,dimensions=(string)512')

    def _run(self, extra=""):
        port = _free_port()
        pub = parse_launch(
            f'appsrc name=in caps="{self.CAPS_BIG}" '
            f'! edgesink name=p port={port} topic=t {extra}')
        pub.start()
        time.sleep(0.2)
        sub = parse_launch(
            f'edgesrc name=s dest-port={port} topic=t timeout=15 '
            '! appsink name=out')
        sub.start()
        time.sleep(0.3)
        rng = np.random.default_rng(11)
        frames = []
        cur = rng.standard_normal(512).astype(np.float32)
        for i in range(10):
            cur = cur.copy()
            cur[(i * 13) % 512] = float(i)  # one element moves per frame
            frames.append(cur.copy())
            pub["in"].push_buffer(Buffer.from_arrays([cur], pts=i))
        deadline = time.monotonic() + 15
        while len(sub["out"].buffers) < 10 and time.monotonic() < deadline:
            time.sleep(0.05)
        pub_stats = pub["p"].stats.snapshot()
        sub_stats = sub["s"].stats.snapshot()
        pub["in"].end_stream()
        sub.wait_eos(timeout=15)
        sub.stop()
        pub.stop()
        got = [(b.pts, b.chunks[0].host().copy())
               for b in sub["out"].buffers]
        return frames, got, pub_stats, sub_stats

    def test_delta_link_is_byte_identical_to_control(self):
        frames, got, ps, ss = self._run("wire-codec=delta wire-delta-k=4")
        control_frames, control, _, _ = self._run("")
        assert len(got) == 10 and len(control) == 10
        for i, (f, (pts, arr)) in enumerate(zip(frames, got)):
            assert pts == i
            np.testing.assert_array_equal(arr, f)
        for i, (f, (pts, arr)) in enumerate(zip(control_frames, control)):
            np.testing.assert_array_equal(arr, f)
        # the delta arm really spoke delta
        assert ps["wire_delta_keyframes"] >= 1
        assert ps["wire_delta_diffs"] > 0
        assert ss["wire_delta_diffs_in"] == ps["wire_delta_diffs"]

    def test_delta_link_with_coalescing(self):
        frames, got, ps, ss = self._run(
            "wire-codec=delta wire-delta-k=4 coalesce-frames=4 "
            "coalesce-ms=20")
        assert [pts for pts, _ in got] == list(range(10))
        for f, (_pts, arr) in zip(frames, got):
            np.testing.assert_array_equal(arr, f)
        assert ps["wire_delta_diffs"] > 0


# -- negotiation matrix -------------------------------------------------------


class TestNegotiation:
    def test_v1_peer_means_plain(self):
        assert wire.negotiate(None) is None
        assert wire.negotiate({}) is None  # no version claim
        assert wire.negotiate({"v": 1}) is None
        assert wire.accept(None) is None
        assert wire.accept({"v": 1}) is None

    def test_peer_wish_adopted_when_local_default(self):
        cfg = wire.negotiate(wire.advertise(codec="zlib", precision="fp16"))
        assert cfg.codec == "zlib" and cfg.precision == "fp16"

    def test_local_request_wins_over_peer_wish(self):
        cfg = wire.negotiate(wire.advertise(codec="zlib"),
                             codec="shuffle-zlib")
        assert cfg.codec == "shuffle-zlib"

    def test_unsupported_codec_clamped_to_raw(self):
        peer = {"v": 2, "codec": "lz99", "codecs": ["raw", "lz99"]}
        cfg = wire.negotiate(peer)
        assert cfg is not None and cfg.codec == "raw"
        # and the reverse: we want what the peer can't speak
        peer = {"v": 2, "codec": "raw", "codecs": ["raw"]}
        assert wire.negotiate(peer, codec="zlib").codec == "raw"

    def test_accept_adopts_echoed_choice(self):
        server_cfg = wire.negotiate(wire.advertise(), codec="zlib",
                                    precision="bf16")
        client_cfg = wire.accept(server_cfg.to_meta())
        assert client_cfg.codec == "zlib"
        assert client_cfg.precision == "bf16"


# -- DATA_BATCH pack/unpack ---------------------------------------------------


class TestBatch:
    def test_round_trip_restores_per_frame_meta(self):
        bufs = [Buffer.from_arrays(
            [np.full((4, 4), float(i), np.float32)], pts=i * 100)
            for i in range(5)]
        bufs[2].duration = 40
        cfg = wire.WireConfig(wire.CODEC_ZLIB)
        meta, payloads = wire.pack_batch(bufs, cfg, seqs=[10, 11, 12, 13, 14])
        assert meta["frames"] == 5 and len(meta["tensors"]) == 1
        out = wire.unpack_batch(meta, payloads)
        assert len(out) == 5
        for i, b in enumerate(out):
            assert b.pts == i * 100
            assert b.extras["seq"] == 10 + i
            np.testing.assert_array_equal(
                b.chunks[0].host(), np.full((4, 4), float(i), np.float32))
        assert out[2].duration == 40

    def test_batch_compatible_gates_on_layout(self):
        a = Buffer.from_arrays([np.zeros(4, np.float32)])
        b = Buffer.from_arrays([np.zeros(4, np.float32)])
        c = Buffer.from_arrays([np.zeros(5, np.float32)])
        d = Buffer.from_arrays([np.zeros(4, np.int32)])
        assert wire.batch_compatible(a, b)
        assert not wire.batch_compatible(a, c)
        assert not wire.batch_compatible(a, d)


# -- socket layer: vectored send / recv_into / guards -------------------------


class TestSocketTransport:
    def test_round_trip_preallocates_writable_arrays(self):
        a, b = socket.socketpair()
        try:
            arr = np.arange(1024, dtype=np.float32).reshape(32, 32)
            meta, payloads = buffer_to_wire(Buffer.from_arrays([arr], pts=5))
            tx = Counters()
            rx = Counters()
            sent = send_msg(a, MsgKind.DATA, meta, payloads, stats=tx)
            kind, rmeta, rpay = recv_msg(b, stats=rx)
            assert kind == MsgKind.DATA
            # raw tensors land as shaped writable ndarrays, no copy step
            assert isinstance(rpay[0], np.ndarray)
            assert rpay[0].flags.writeable
            out = wire_to_buffer(rmeta, rpay)
            np.testing.assert_array_equal(out.chunks[0].host(), arr)
            out.chunks[0].host()[0, 0] = -1.0  # writable end to end
            assert tx.snapshot()["wire_bytes_out"] == sent
            assert rx.snapshot()["wire_bytes_in"] == sent
            assert tx.snapshot()["wire_msgs_out"] == 1
        finally:
            a.close()
            b.close()

    def test_zero_size_payload_on_the_wire(self):
        a, b = socket.socketpair()
        try:
            meta, payloads = buffer_to_wire(
                Buffer.from_arrays([np.empty(0, np.uint8)]))
            send_msg(a, MsgKind.DATA, meta, payloads)
            _, rmeta, rpay = recv_msg(b)
            assert wire_to_buffer(rmeta, rpay).chunks[0].host().shape == (0,)
        finally:
            a.close()
            b.close()

    def test_payload_length_guard_rejects_before_allocating(self):
        a, b = socket.socketpair()
        try:
            # hand-frame a message whose payload claims > MAX_PAYLOAD
            mb = b"{}"
            a.sendall(protocol._HDR.pack(protocol.MAGIC, int(MsgKind.DATA),
                                         len(mb)) + mb +
                      struct.pack("<I", 1) +
                      protocol._PLEN.pack(protocol.MAX_PAYLOAD + 1))
            with pytest.raises(ValueError, match="exceeds"):
                recv_msg(b)
        finally:
            a.close()
            b.close()

    def test_meta_length_guard(self):
        a, b = socket.socketpair()
        try:
            a.sendall(protocol._HDR.pack(protocol.MAGIC, int(MsgKind.DATA),
                                         protocol.MAX_META + 1))
            with pytest.raises(ValueError, match="meta length"):
                recv_msg(b)
        finally:
            a.close()
            b.close()

    def test_sendmsg_fallback_path_matches(self, monkeypatch):
        monkeypatch.setattr(protocol, "_HAS_SENDMSG", False)
        a, b = socket.socketpair()
        try:
            arr = np.arange(64, dtype=np.int16)
            meta, payloads = buffer_to_wire(Buffer.from_arrays([arr]))
            send_msg(a, MsgKind.DATA, meta, payloads)
            _, rmeta, rpay = recv_msg(b)
            np.testing.assert_array_equal(
                wire_to_buffer(rmeta, rpay).chunks[0].host(), arr)
        finally:
            a.close()
            b.close()


# -- strict v1 interop --------------------------------------------------------


class TestV1Interop:
    def test_v1_subscriber_gets_plain_frames(self):
        """A raw-socket subscriber that never says "wire" must receive
        per-frame plain-v1 DATA even when the publisher asks for a codec
        AND coalescing — downgrade is per link, not per element."""
        port = _free_port()
        pub = parse_launch(
            f'appsrc name=in caps="{CAPS}" '
            f'! edgesink port={port} topic=t wire-codec=zlib '
            'coalesce-frames=4 coalesce-ms=5')
        pub.start()
        time.sleep(0.2)
        sub = socket.create_connection(("localhost", port), timeout=10)
        try:
            send_msg(sub, MsgKind.SUBSCRIBE, {"topic": "t"})  # no "wire"
            kind, meta, _ = recv_msg(sub)
            assert kind == MsgKind.CAPS_ACK
            assert "wire" not in meta  # no v2 echo for a v1 peer
            for i in range(3):
                pub["in"].push_buffer(Buffer.from_arrays(
                    [np.full(4, float(i), np.float32)]))
            got = []
            sub.settimeout(10)
            while len(got) < 3:
                kind, meta, payloads = recv_msg(sub)
                assert kind == MsgKind.DATA  # never DATA_BATCH
                t = meta["tensors"][0]
                assert "codec" not in t and "wire_dtype" not in t
                got.append(wire_to_buffer(meta, payloads))
            for i, b in enumerate(got):
                np.testing.assert_array_equal(
                    b.chunks[0].host(), np.full(4, float(i), np.float32))
        finally:
            sub.close()
            pub["in"].end_stream()
            pub.stop()

    def test_v1_query_client_round_trips_unchanged(self):
        """A raw-socket v1 client against the upgraded server: CAPS
        without a wire block -> plain v1 both directions."""
        port = _free_port()
        server = parse_launch(
            f'tensor_query_serversrc port={port} id=70 '
            '! tensor_transform mode=arithmetic option=mul:2.0 '
            '! tensor_query_serversink id=70')
        server.start()
        time.sleep(0.2)
        conn = socket.create_connection(("localhost", port), timeout=10)
        try:
            send_msg(conn, MsgKind.CAPS, {"caps": CAPS})
            kind, ack, _ = recv_msg(conn)
            assert kind == MsgKind.CAPS_ACK and "wire" not in ack
            arr = np.full(4, 3.0, np.float32)
            meta, payloads = buffer_to_wire(Buffer.from_arrays([arr]))
            meta["seq"] = 0
            send_msg(conn, MsgKind.DATA, meta, payloads)
            conn.settimeout(10)
            kind, rmeta, rpay = recv_msg(conn)
            assert kind == MsgKind.RESULT
            assert "codec" not in rmeta["tensors"][0]
            np.testing.assert_array_equal(
                wire_to_buffer(rmeta, rpay).chunks[0].host(),
                np.full(4, 6.0, np.float32))
        finally:
            conn.close()
            server.stop()

    def test_client_downgrades_when_ack_has_no_wire_block(self):
        """tensor_query_client asking for a codec against a server that
        never echoes "wire" (a pre-v2 build): the link silently runs
        plain v1 — the request is a wish, not a requirement."""
        port = _free_port()
        done = threading.Event()
        got = {}

        def v1_server():
            lst = socket.socket()
            lst.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            lst.bind(("localhost", port))
            lst.listen(1)
            lst.settimeout(15)
            conn, _ = lst.accept()
            try:
                kind, meta, _ = recv_msg(conn)
                assert kind == MsgKind.CAPS
                send_msg(conn, MsgKind.CAPS_ACK, {})  # v1: no wire echo
                kind, meta, payloads = recv_msg(conn)
                got["meta"] = meta
                # echo the frame back as the RESULT
                meta = dict(meta)
                meta["client_id"] = meta.get("client_id")
                send_msg(conn, MsgKind.RESULT, meta, payloads)
                done.wait(10)
            finally:
                conn.close()
                lst.close()

        t = threading.Thread(target=v1_server, daemon=True)
        t.start()
        client = parse_launch(
            f'appsrc name=in caps="{CAPS}" '
            f'! tensor_query_client port={port} timeout=15 wire-codec=zlib '
            '! appsink name=out')
        client.start()
        # zeros are maximally compressible: if the client ignored the
        # downgrade this payload WOULD have shipped with a codec marker
        client["in"].push_buffer(Buffer.from_arrays(
            [np.zeros(4, np.float32)]))
        deadline = time.monotonic() + 15
        while not client["out"].buffers and time.monotonic() < deadline:
            time.sleep(0.05)
        done.set()
        client["in"].end_stream()
        client.stop()
        t.join(timeout=10)
        assert client["out"].buffers
        assert "codec" not in got["meta"]["tensors"][0]


# -- element layer: pipelines under wire v2 -----------------------------------


class TestPipelinesUnderV2:
    def test_query_round_trip_with_codec(self):
        port = _free_port()
        server = parse_launch(
            f'tensor_query_serversrc port={port} id=71 '
            '! tensor_transform mode=arithmetic option=add:1.0 '
            '! tensor_query_serversink id=71')
        server.start()
        time.sleep(0.2)
        client = parse_launch(
            f'appsrc name=in caps="{CAPS}" '
            f'! tensor_query_client name=qc port={port} timeout=15 '
            'wire-codec=zlib ! appsink name=out')
        client.start()
        # compressible payloads so the codec actually engages
        for i in range(4):
            client["in"].push_buffer(Buffer.from_arrays(
                [np.full(4, float(i), np.float32)]))
        deadline = time.monotonic() + 20
        while len(client["out"].buffers) < 4 and time.monotonic() < deadline:
            time.sleep(0.05)
        client["in"].end_stream()
        stats = client["qc"].stats.snapshot()
        client.stop()
        server.stop()
        out = client["out"].buffers
        assert len(out) == 4
        for i, b in enumerate(out):
            np.testing.assert_array_equal(
                b.chunks[0].host(), np.full(4, 1.0 + float(i), np.float32))
            assert b.chunks[0].host().flags.writeable
        # the link carried traffic and counted it
        assert stats["wire_msgs_out"] >= 4
        assert stats["wire_bytes_out"] > 0
        assert stats["wire_frames_in"] == 4

    def test_edge_pub_sub_with_codec_and_downcast(self):
        port = _free_port()
        pub = parse_launch(
            f'appsrc name=in caps="{CAPS}" '
            f'! edgesink name=p port={port} topic=t wire-codec=zlib '
            'wire-precision=fp16')
        pub.start()
        time.sleep(0.2)
        sub = parse_launch(
            f'edgesrc dest-port={port} topic=t timeout=15 '
            '! appsink name=out')
        sub.start()
        time.sleep(0.3)
        vals = [0.125, 1.5, -2.25]  # fp16-exact so equality holds
        for v in vals:
            pub["in"].push_buffer(Buffer.from_arrays(
                [np.full(4, v, np.float32)]))
        deadline = time.monotonic() + 15
        while len(sub["out"].buffers) < 3 and time.monotonic() < deadline:
            time.sleep(0.05)
        pub["in"].end_stream()
        sub.wait_eos(timeout=15)
        sub.stop()
        pub.stop()
        got = sub["out"].buffers
        assert len(got) == 3
        for v, b in zip(vals, got):
            arr = b.chunks[0].host()
            assert arr.dtype == np.float32  # upcast back on receive
            np.testing.assert_array_equal(arr, np.full(4, v, np.float32))


# -- coalescing ---------------------------------------------------------------


class TestCoalescing:
    def test_flush_by_size_preserves_order(self):
        port = _free_port()
        pub = parse_launch(
            f'appsrc name=in caps="{CAPS}" '
            f'! edgesink name=p port={port} coalesce-frames=4 '
            'coalesce-ms=500')
        pub.start()
        time.sleep(0.2)
        sub = parse_launch(
            f'edgesrc dest-port={port} timeout=15 ! appsink name=out')
        sub.start()
        time.sleep(0.3)
        for i in range(8):  # exactly two full batches
            pub["in"].push_buffer(Buffer.from_arrays(
                [np.full(4, float(i), np.float32)], pts=i * 10))
        deadline = time.monotonic() + 15
        while len(sub["out"].buffers) < 8 and time.monotonic() < deadline:
            time.sleep(0.05)
        pub_stats = pub["p"].stats.snapshot()
        pub["in"].end_stream()
        sub.wait_eos(timeout=15)
        sub.stop()
        pub.stop()
        got = sub["out"].buffers
        assert [float(b.chunks[0].host()[0]) for b in got] == \
            [float(i) for i in range(8)]
        assert [b.pts for b in got] == [i * 10 for i in range(8)]
        # 8 frames crossed in 2 messages: coalescing actually engaged
        assert pub_stats["wire_frames_out"] == 8
        assert pub_stats["wire_msgs_out"] <= 3  # 2 batches (+caps slack)

    def test_flush_by_age(self):
        """A partial batch (2 of 8 frames) must not wait for stragglers:
        the age flusher ships it within ~coalesce-ms."""
        port = _free_port()
        pub = parse_launch(
            f'appsrc name=in caps="{CAPS}" '
            f'! edgesink port={port} coalesce-frames=8 coalesce-ms=40')
        pub.start()
        time.sleep(0.2)
        sub = parse_launch(
            f'edgesrc dest-port={port} timeout=15 ! appsink name=out')
        sub.start()
        time.sleep(0.3)
        t0 = time.monotonic()
        for i in range(2):
            pub["in"].push_buffer(Buffer.from_arrays(
                [np.full(4, float(i), np.float32)]))
        deadline = t0 + 10
        while len(sub["out"].buffers) < 2 and time.monotonic() < deadline:
            time.sleep(0.005)
        elapsed = time.monotonic() - t0
        pub["in"].end_stream()
        sub.wait_eos(timeout=15)
        sub.stop()
        pub.stop()
        assert len(sub["out"].buffers) == 2  # arrived without 6 more frames
        assert elapsed < 5.0  # age flush, not the 10 s give-up deadline

    def test_eos_flushes_pending(self):
        """Frames still coalescing at EOS are delivered, then EOS."""
        port = _free_port()
        pub = parse_launch(
            f'appsrc name=in caps="{CAPS}" '
            f'! edgesink port={port} coalesce-frames=16 coalesce-ms=60000')
        pub.start()
        time.sleep(0.2)
        sub = parse_launch(
            f'edgesrc dest-port={port} timeout=15 ! appsink name=out')
        sub.start()
        time.sleep(0.3)
        for i in range(3):
            pub["in"].push_buffer(Buffer.from_arrays(
                [np.full(4, float(i), np.float32)]))
        pub["in"].end_stream()  # EOS while 3 frames sit in the batch
        sub.wait_eos(timeout=15)
        sub.stop()
        pub.stop()
        assert len(sub["out"].buffers) == 3


# -- session layer: negotiation, ring, receiver, handshake --------------------


from nnstreamer_tpu.edge import session as sess


class TestSessionNegotiation:
    def test_v1_peer_means_no_session(self):
        assert sess.negotiate(None) is None
        assert sess.negotiate({}) is None
        assert sess.negotiate({"v": 0, "sid": "x"}) is None
        assert sess.negotiate({"v": 1}) is None  # no sid
        assert sess.accept(None) is None
        assert sess.accept({}) is None

    def test_round_trip_adopts_cadence_and_budget(self):
        sid = sess.new_session_id()
        adv = sess.advertise(sid, ack_every=4, ack_ms=25.0)
        cfg = sess.negotiate(adv, ring_bytes=1 << 20)
        assert cfg is not None and cfg.sid == sid
        assert cfg.ack_every == 4 and cfg.ack_ms == 25.0
        assert cfg.ring_bytes == 1 << 20
        echoed = sess.accept(cfg.to_meta())
        assert echoed.sid == sid and echoed.ack_every == 4
        assert echoed.ring_bytes == 1 << 20

    def test_session_ids_are_unique(self):
        assert len({sess.new_session_id() for _ in range(64)}) == 64


class TestReplayRing:
    def _frame(self, nbytes=256):
        return np.zeros(nbytes, np.uint8)

    def test_replay_covers_retained_gap_exactly(self):
        ring = sess.ReplayRing(1 << 20)
        for s in range(1, 11):
            ring.append(s, self._frame())
        replay, lost = ring.replay_from(4)
        assert lost == 0
        assert [s for s, _ in replay] == list(range(4, 11))

    def test_release_moves_floor_without_declaring_loss(self):
        ring = sess.ReplayRing(1 << 20)
        for s in range(1, 11):
            ring.append(s, self._frame())
        ring.release(6)
        assert len(ring) == 4
        # released frames were ACKed: a resume from above the floor
        # replays cleanly with zero declared loss
        replay, lost = ring.replay_from(7)
        assert lost == 0 and [s for s, _ in replay] == [7, 8, 9, 10]

    def test_eviction_is_declared_exactly(self):
        ring = sess.ReplayRing(1024)  # room for ~4 x 256B frames
        for s in range(1, 11):
            ring.append(s, self._frame(256))
        assert ring.nbytes <= 1024
        evicted = ring.evicted_through
        assert evicted >= 6  # budget forced evictions
        replay, lost = ring.replay_from(1)
        # the declared loss is EXACTLY the evicted prefix, and the
        # replay hands back every single retained frame after it
        assert lost == evicted
        assert [s for s, _ in replay] == list(range(evicted + 1, 11))

    def test_newest_frame_survives_even_alone_over_budget(self):
        ring = sess.ReplayRing(10)
        ring.append(1, self._frame(256))
        ring.append(2, self._frame(256))
        replay, lost = ring.replay_from(1)
        assert [s for s, _ in replay] == [2] and lost == 1


class TestSessionReceiver:
    def _cfg(self, **kw):
        return sess.SessionConfig(sess.new_session_id(), **kw)

    def test_dedup_by_watermark(self):
        r = sess.SessionReceiver(self._cfg())
        assert r.admit(1) and r.admit(2) and r.admit(3)
        assert not r.admit(2)  # replayed frame we already have
        assert not r.admit(3)
        assert r.dup_drops == 2
        assert r.admit(4)
        assert r.last_delivered == 4

    def test_no_seq_always_passes(self):
        r = sess.SessionReceiver(self._cfg())
        assert r.admit(None) and r.admit(None)
        assert r.last_delivered == 0

    def test_ack_due_by_count(self):
        r = sess.SessionReceiver(self._cfg(ack_every=3, ack_ms=1e9))
        r.admit(1), r.admit(2)
        assert r.ack_due(now=r._ack_t) is None
        r.admit(3)
        assert r.ack_due(now=r._ack_t) == 3
        r.mark_acked(3)
        assert r.ack_due(now=r._ack_t) is None

    def test_ack_due_by_silence(self):
        r = sess.SessionReceiver(self._cfg(ack_every=100, ack_ms=50.0))
        r.admit(1)
        assert r.ack_due(now=r._ack_t + 0.01) is None
        assert r.ack_due(now=r._ack_t + 0.06) == 1

    def test_reset_adopts_new_seq_space(self):
        r = sess.SessionReceiver(self._cfg())
        r.admit(5)
        r.reset(100)
        assert not r.admit(99)   # pre-reset seqs are stale
        assert r.admit(101)


class TestHeartbeat:
    def test_ping_cadence_and_peer_death(self):
        hb = sess.Heartbeat(1.0, miss_limit=2)
        t0 = hb.last_sent
        assert not hb.due(now=t0 + 0.5)
        assert hb.due(now=t0 + 1.1)
        hb.sent(now=t0 + 1.1)
        assert not hb.peer_dead
        hb.sent(now=t0 + 2.2)
        assert hb.peer_dead  # two unanswered pings

    def test_pong_and_any_traffic_prove_liveness(self):
        hb = sess.Heartbeat(1.0, miss_limit=2)
        t0 = hb.last_sent
        hb.sent(now=t0 + 1.0)
        rtt = hb.pong(t0 + 1.0, now=t0 + 1.25)
        assert abs(rtt - 0.25) < 1e-9
        assert hb.outstanding == 0 and hb.pongs == 1
        hb.sent(), hb.heard()  # data counts as a heartbeat
        assert hb.outstanding == 0


# -- session handshake over a raw socket --------------------------------------


def _session_subscribe(port, sid, topic="t", last=0, ack_every=4, v2=False):
    """Raw-socket session subscriber handshake; returns (sock, resume_ack)."""
    sub = socket.create_connection(("localhost", port), timeout=10)
    meta = {"topic": topic, "session": sess.advertise(sid, ack_every)}
    if v2:
        meta["wire"] = wire.advertise()  # batches only flow on v2 links
    send_msg(sub, MsgKind.SUBSCRIBE, meta)
    kind, meta, _ = recv_msg(sub)
    assert kind == MsgKind.CAPS_ACK
    assert meta["session"]["sid"] == sid  # the echo adopts OUR sid
    send_msg(sub, MsgKind.RESUME, {"sid": sid, "last": last})
    kind, rack, _ = recv_msg(sub)
    assert kind == MsgKind.RESUME_ACK
    sub.settimeout(10)
    return sub, rack


class TestSessionHandshake:
    def test_fresh_attach_then_seq_stamped_frames(self):
        port = _free_port()
        pub = parse_launch(f'appsrc name=in caps="{CAPS}" '
                           f'! edgesink name=p port={port} topic=t')
        pub.start()
        time.sleep(0.2)
        sid = sess.new_session_id()
        sub, rack = _session_subscribe(port, sid)
        try:
            assert rack["resumed"] is False and rack["lost"] == 0
            for i in range(3):
                pub["in"].push_buffer(Buffer.from_arrays(
                    [np.full(4, float(i), np.float32)]))
            seqs = []
            while len(seqs) < 3:
                kind, meta, payloads = recv_msg(sub)
                assert kind == MsgKind.DATA
                seqs.append(meta["seq"])
            base = rack["base"]
            assert seqs == [base + 1, base + 2, base + 3]
        finally:
            sub.close()
            pub["in"].end_stream()
            pub.stop()

    def test_v1_subscriber_sees_no_session_echo(self):
        port = _free_port()
        pub = parse_launch(f'appsrc name=in caps="{CAPS}" '
                           f'! edgesink port={port} topic=t session=true')
        pub.start()
        time.sleep(0.2)
        sub = socket.create_connection(("localhost", port), timeout=10)
        try:
            send_msg(sub, MsgKind.SUBSCRIBE, {"topic": "t"})
            kind, meta, _ = recv_msg(sub)
            assert kind == MsgKind.CAPS_ACK
            assert "session" not in meta  # strict v1 on this link
            # the link joins the broadcast set after its ack goes out: a
            # frame pushed in between is not this subscriber's to see
            sink = next(e for e in pub.elements.values()
                        if hasattr(e, "_subs"))
            deadline = time.monotonic() + 10
            while not sink._subs and time.monotonic() < deadline:
                time.sleep(0.005)
            pub["in"].push_buffer(Buffer.from_arrays(
                [np.zeros(4, np.float32)]))
            sub.settimeout(10)
            kind, meta, _ = recv_msg(sub)
            assert kind == MsgKind.DATA and "seq" not in meta
        finally:
            sub.close()
            pub["in"].end_stream()
            pub.stop()

    def test_resume_replays_exactly_the_gap(self):
        port = _free_port()
        pub = parse_launch(f'appsrc name=in caps="{CAPS}" '
                           f'! edgesink name=p port={port} topic=t')
        pub.start()
        time.sleep(0.2)
        sid = sess.new_session_id()
        sub, rack = _session_subscribe(port, sid)
        base = rack["base"]
        for i in range(4):
            pub["in"].push_buffer(Buffer.from_arrays(
                [np.full(4, float(i), np.float32)]))
        got = []
        while len(got) < 4:
            kind, meta, _ = recv_msg(sub)
            assert kind == MsgKind.DATA
            got.append(meta["seq"])
        sub.close()  # the outage
        for i in range(4, 8):  # published while we were gone
            pub["in"].push_buffer(Buffer.from_arrays(
                [np.full(4, float(i), np.float32)]))
        # wait until every outage frame is stamped into the replay ring:
        # resuming earlier would see a shorter gap and live tail frames
        deadline = time.monotonic() + 10.0
        while pub["p"].stats["session_sent"] < 8:
            assert time.monotonic() < deadline, "outage frames never sent"
            time.sleep(0.02)
        sub, rack = _session_subscribe(port, sid, last=base + 4)
        try:
            assert rack["resumed"] is True and rack["lost"] == 0
            replayed = []
            while len(replayed) < 4:
                kind, meta, payloads = recv_msg(sub)
                assert kind == MsgKind.DATA
                replayed.append((meta["seq"],
                                 float(wire.unpack_buffer(
                                     meta, payloads).chunks[0].host()[0])))
            # exactly the gap, in order, carrying the missed values
            assert replayed == [(base + 5 + i, float(4 + i))
                                for i in range(4)]
            assert pub["p"].stats["session_replayed"] == 4
            assert pub["p"].stats["session_resumes"] == 1
        finally:
            sub.close()
            pub["in"].end_stream()
            pub.stop()

    def test_ring_eviction_becomes_declared_loss(self):
        port = _free_port()
        # a ring too small for the outage: 1 KB holds very few frames
        pub = parse_launch(f'appsrc name=in caps="{CAPS}" '
                           f'! edgesink name=p port={port} topic=t '
                           'session-ring-kb=1')
        pub.start()
        time.sleep(0.2)
        sid = sess.new_session_id()
        sub, rack = _session_subscribe(port, sid)
        base = rack["base"]
        sub.close()  # vanish immediately: nothing ever ACKed
        n = 80  # 80 x 16B payloads + overhead >> 1 KB ring
        for i in range(n):
            pub["in"].push_buffer(Buffer.from_arrays(
                [np.full(4, float(i), np.float32)]))
        deadline = time.monotonic() + 10.0
        while pub["p"].stats["session_sent"] < n:
            assert time.monotonic() < deadline, "burst never fully sent"
            time.sleep(0.02)
        sub, rack = _session_subscribe(port, sid, last=base)
        try:
            assert rack["resumed"] is True
            lost = rack["lost"]
            assert lost > 0  # the ring could not cover the gap...
            replayed = []
            while len(replayed) < n - lost:
                kind, meta, _ = recv_msg(sub)
                assert kind == MsgKind.DATA
                replayed.append(meta["seq"])
            # ...and the declared count is EXACT: lost + replayed
            # partitions the gap with no overlap and no hole
            assert replayed == list(range(base + lost + 1, base + n + 1))
            assert pub["p"].stats["session_declared_lost"] == lost
        finally:
            sub.close()
            pub["in"].end_stream()
            pub.stop()


class TestBatchReplayAcrossReconnect:
    def test_partial_batch_never_half_delivered(self):
        """Satellite: DATA_BATCH coalescing x reconnect. A subscriber
        that dies mid-stream under coalescing resumes to EVERY frame
        after its watermark — frames from partially-delivered batches
        are fully replayed (or fully declared lost), never half-lost."""
        port = _free_port()
        pub = parse_launch(f'appsrc name=in caps="{CAPS}" '
                           f'! edgesink name=p port={port} topic=t '
                           'coalesce-frames=4 coalesce-ms=30')
        pub.start()
        time.sleep(0.2)
        sid = sess.new_session_id()
        sub, rack = _session_subscribe(port, sid, v2=True)
        base = rack["base"]
        n = 16
        for i in range(n):
            pub["in"].push_buffer(Buffer.from_arrays(
                [np.full(4, float(i), np.float32)]))
        # read ONE message — with coalescing this is a 4-frame batch —
        # then die with the rest of the stream un-consumed
        kind, meta, payloads = recv_msg(sub)
        assert kind == MsgKind.DATA_BATCH
        first = wire.unpack_batch(meta, payloads)
        watermark = first[-1].extras["seq"]
        assert watermark == base + len(first)
        sub.close()
        time.sleep(0.4)  # let the remaining batches hit the dead sock
        sub, rack = _session_subscribe(port, sid, last=watermark, v2=True)
        try:
            assert rack["resumed"] is True and rack["lost"] == 0
            seqs = []
            while len(seqs) < n - len(first):
                kind, meta, payloads = recv_msg(sub)
                # replay is per-frame DATA; fresh live traffic may
                # arrive as DATA_BATCH — both carry seqs
                if kind == MsgKind.DATA:
                    seqs.append(meta["seq"])
                else:
                    assert kind == MsgKind.DATA_BATCH
                    seqs.extend(b.extras["seq"]
                                for b in wire.unpack_batch(meta, payloads))
            # every frame past the watermark exactly once, in order:
            # no dup from the partially-read batch, no hole after it
            assert seqs == list(range(watermark + 1, base + n + 1))
        finally:
            sub.close()
            pub["in"].end_stream()
            pub.stop()
