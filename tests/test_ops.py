"""Pallas custom-op tests: kernel body exercised via interpret mode on
the CPU mesh, parity against the jnp oracle (the pattern every ops/
kernel must ship with)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from nnstreamer_tpu.ops import fused_normalize, normalize_reference


@pytest.mark.parametrize("shape", [(224, 224, 3), (8,), (3, 5, 7),
                                   (64, 1024)])
def test_kernel_parity_interpret(shape):
    x = np.random.default_rng(0).integers(0, 255, shape, np.uint8,
                                          endpoint=True)
    out = fused_normalize(jnp.asarray(x))
    ref = normalize_reference(jnp.asarray(x), 1 / 127.5, 127.5)
    assert out.dtype == jnp.bfloat16
    assert out.shape == tuple(shape)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=1e-2, atol=1e-2)


def test_custom_scale_offset_and_dtype():
    x = np.array([[0, 255], [128, 64]], np.uint8)
    out = fused_normalize(jnp.asarray(x), scale=2.0, offset=1.0,
                          dtype=jnp.float32)
    np.testing.assert_allclose(
        np.asarray(out), (x.astype(np.float32) - 1.0) * 2.0, rtol=1e-6)


def test_kernel_body_runs_off_tpu(monkeypatch):
    """Off the TPU the kernel runs through the Pallas interpreter — the
    oracle is what it is compared with, never what is returned."""
    from nnstreamer_tpu.ops import normalize
    monkeypatch.setattr(
        normalize, "normalize_reference",
        lambda *a, **k: pytest.fail("reference used as a stand-in"))
    out = fused_normalize(jnp.asarray(np.arange(16, dtype=np.uint8)))
    assert out.shape == (16,) and out.dtype == jnp.bfloat16


class TestSparsePack:
    """ops/sparse.py: device-side sparse pack/unpack vs the numpy oracle."""

    def _arr(self, density=0.1, n=4096, seed=0, dtype=np.float32):
        rng = np.random.default_rng(seed)
        flat = np.zeros(n, dtype)
        k = int(n * density)
        idx = rng.choice(n, size=k, replace=False)
        flat[idx] = rng.standard_normal(k).astype(dtype)
        flat[idx[flat[idx] == 0]] = 1.0  # ensure chosen slots are nonzero
        return flat

    def test_pack_matches_oracle(self):
        from nnstreamer_tpu.ops.sparse import pack, pack_reference
        flat = self._arr(0.1)
        ref_idx, ref_vals = pack_reference(flat)
        idx, vals, nnz = pack(jnp.asarray(flat), 1024)
        nnz = int(nnz)
        assert nnz == len(ref_idx)
        np.testing.assert_array_equal(np.asarray(idx)[:nnz], ref_idx)
        np.testing.assert_array_equal(np.asarray(vals)[:nnz], ref_vals)

    def test_pack_overflow_reports_true_nnz(self):
        from nnstreamer_tpu.ops.sparse import pack
        flat = self._arr(0.5, n=256)
        _, _, nnz = pack(jnp.asarray(flat), 16)  # capacity << nnz
        assert int(nnz) == int((flat != 0).sum())  # not clamped

    def test_unpack_roundtrip(self):
        from nnstreamer_tpu.ops.sparse import pack, unpack
        flat = self._arr(0.07, n=2048, seed=2)
        idx, vals, nnz = pack(jnp.asarray(flat), 256)
        dense = np.asarray(unpack(idx, vals, 2048))
        np.testing.assert_array_equal(dense, flat)

    def test_unpack_empty(self):
        from nnstreamer_tpu.ops.sparse import pack, unpack
        flat = np.zeros(64, np.float32)
        idx, vals, nnz = pack(jnp.asarray(flat), 8)
        assert int(nnz) == 0
        np.testing.assert_array_equal(np.asarray(unpack(idx, vals, 64)),
                                      flat)


class TestSparseElementsDevicePath:
    def test_device_enc_wire_equals_host_wire(self):
        """density<1 device pack produces byte-identical wire output to
        the host encoder, and overflow falls back (never truncates)."""
        import jax
        from nnstreamer_tpu.elements.sparse import (TensorSparseEnc,
                                                    sparse_encode)
        from nnstreamer_tpu.tensors.buffer import Buffer, Chunk

        flat = TestSparsePack()._arr(0.05, n=1024, seed=4).reshape(32, 32)
        host_wire = sparse_encode(flat)
        enc = TensorSparseEnc(density=0.25)
        out = enc.transform(Buffer([Chunk(jax.device_put(flat))]))
        np.testing.assert_array_equal(
            out.chunks[0].host(), np.frombuffer(host_wire, np.uint8))
        # overflow: a denser frame than promised falls back to host path
        dense = np.ones((32, 32), np.float32)
        out2 = enc.transform(Buffer([Chunk(jax.device_put(dense))]))
        np.testing.assert_array_equal(
            out2.chunks[0].host(),
            np.frombuffer(sparse_encode(dense), np.uint8))

    def test_device_dec_roundtrip(self):
        import jax
        from nnstreamer_tpu.elements.sparse import (TensorSparseDec,
                                                    TensorSparseEnc)
        from nnstreamer_tpu.tensors.buffer import Buffer, Chunk
        from nnstreamer_tpu.tensors.caps import Caps

        flat = TestSparsePack()._arr(0.1, n=512, seed=5).reshape(16, 32)
        enc = TensorSparseEnc()
        dec = TensorSparseDec(device=True)
        dec.transform_caps(Caps(
            "other/tensors,format=static,num_tensors=1,"
            "types=(string)float32,dimensions=(string)32:16"))
        wire = enc.transform(Buffer([Chunk(flat)]))
        out = dec.transform(wire)
        assert isinstance(out.chunks[0].raw, jax.Array)
        np.testing.assert_array_equal(out.chunks[0].host(), flat)

    def test_device_dec_varying_nnz_buckets(self):
        """Per-frame nnz varies; the device path pads to pow2 buckets so
        the jitted scatter compiles O(log size) shapes, and every frame
        still decodes exactly."""
        from nnstreamer_tpu.elements.sparse import (TensorSparseDec,
                                                    TensorSparseEnc)
        from nnstreamer_tpu.tensors.buffer import Buffer, Chunk
        from nnstreamer_tpu.tensors.caps import Caps

        enc = TensorSparseEnc()
        dec = TensorSparseDec(device=True)
        dec.transform_caps(Caps(
            "other/tensors,format=static,num_tensors=1,"
            "types=(string)float32,dimensions=(string)64"))
        for seed, density in ((0, 0.02), (1, 0.3), (2, 0.9), (3, 0.0)):
            flat = TestSparsePack()._arr(density, n=64, seed=seed)
            out = dec.transform(enc.transform(Buffer([Chunk(flat)])))
            np.testing.assert_array_equal(out.chunks[0].host(), flat)


class TestSparseDiffMode:
    """elements/sparse.py diff mode (ISSUE 15 satellite): sparse_encode
    against a reference frame encodes the elements that *changed* —
    compared bitwise — and sparse_decode with the same reference patches
    them back. Round trips must be byte-exact for every dtype, including
    non-contiguous views and zero-size tensors."""

    def _dtypes(self):
        from nnstreamer_tpu.tensors.types import TensorType
        return [t.np_dtype for t in TensorType]

    def _pair(self, dtype, shape=(9, 13), seed=0, frac=0.1):
        """(ref, cur) differing in ~frac of the elements."""
        rng = np.random.default_rng(seed)
        if "float" in str(dtype):
            ref = rng.standard_normal(shape).astype(np.float32).astype(dtype)
            cur = ref.copy()
            n = max(1, int(frac * ref.size))
            idx = rng.choice(ref.size, n, replace=False)
            cur.reshape(-1)[idx] = rng.standard_normal(n).astype(
                np.float32).astype(dtype)
        else:
            info = np.iinfo(dtype)
            ref = rng.integers(info.min, info.max, shape, dtype=dtype)
            cur = ref.copy()
            n = max(1, int(frac * ref.size))
            idx = rng.choice(ref.size, n, replace=False)
            cur.reshape(-1)[idx] = rng.integers(info.min, info.max, n,
                                                dtype=dtype)
        return ref, cur

    def test_round_trip_all_dtypes(self):
        from nnstreamer_tpu.elements.sparse import (sparse_decode,
                                                    sparse_encode)
        for i, dtype in enumerate(self._dtypes()):
            ref, cur = self._pair(dtype, seed=i)
            data = sparse_encode(cur, ref=ref)
            out = sparse_decode(data, ref=ref)
            assert out.dtype == cur.dtype and out.shape == cur.shape
            np.testing.assert_array_equal(
                out.view(np.uint8), cur.view(np.uint8),
                err_msg=f"dtype {dtype}")
            # never aliases the reference (callers mutate downstream)
            assert not np.shares_memory(out, ref)

    def test_diff_is_smaller_than_absolute_for_dense_data(self):
        from nnstreamer_tpu.elements.sparse import sparse_encode
        ref, cur = self._pair(np.float32, shape=(64, 64), frac=0.02)
        # dense nonzero data: absolute zero-suppression finds nothing,
        # the temporal diff finds everything static
        assert len(sparse_encode(cur, ref=ref)) < \
            len(sparse_encode(cur)) * 0.2

    def test_non_contiguous_views(self):
        from nnstreamer_tpu.elements.sparse import (sparse_decode,
                                                    sparse_encode)
        base_r = np.arange(240, dtype=np.int32).reshape(12, 20)
        base_c = base_r.copy()
        base_c[4, 6] = -1
        ref, cur = base_r[::2, ::2], base_c[::2, ::2]
        assert not cur.flags.c_contiguous
        out = sparse_decode(sparse_encode(cur, ref=ref), ref=ref)
        np.testing.assert_array_equal(out, cur)
        # non-contiguous on the decode side too
        out2 = sparse_decode(sparse_encode(np.ascontiguousarray(cur),
                                           ref=ref), ref=ref)
        np.testing.assert_array_equal(out2, cur)

    def test_zero_size(self):
        from nnstreamer_tpu.elements.sparse import (sparse_decode,
                                                    sparse_encode)
        ref = np.empty((0, 4), np.float32)
        data = sparse_encode(ref.copy(), ref=ref)
        out = sparse_decode(data, ref=ref)
        assert out.shape == (0, 4) and out.dtype == np.float32

    def test_identical_frames_encode_empty(self):
        from nnstreamer_tpu.elements.sparse import (sparse_decode,
                                                    sparse_encode)
        ref = np.random.default_rng(2).standard_normal(
            (32, 32)).astype(np.float32)
        data = sparse_encode(ref.copy(), ref=ref)
        from nnstreamer_tpu.tensors.meta import HEADER_SIZE
        assert len(data) == HEADER_SIZE  # header only: zero changed
        np.testing.assert_array_equal(sparse_decode(data, ref=ref), ref)

    def test_bitwise_compare_survives_nan_and_signed_zero(self):
        """NaN payloads and -0.0/+0.0 flips are CHANGES bitwise (== would
        miss both) and survive the round trip exactly."""
        from nnstreamer_tpu.elements.sparse import (sparse_decode,
                                                    sparse_encode)
        ref = np.zeros(8, np.float32)
        cur = ref.copy()
        cur[1] = np.nan
        cur[2] = -0.0
        out = sparse_decode(sparse_encode(cur, ref=ref), ref=ref)
        np.testing.assert_array_equal(out.view(np.uint32),
                                      cur.view(np.uint32))

    def test_reference_mismatch_raises(self):
        from nnstreamer_tpu.elements.sparse import (sparse_decode,
                                                    sparse_encode)
        cur = np.zeros((4, 4), np.float32)
        with pytest.raises(ValueError, match="reference mismatch"):
            sparse_encode(cur, ref=np.zeros((4, 5), np.float32))
        with pytest.raises(ValueError, match="reference mismatch"):
            sparse_encode(cur, ref=np.zeros((4, 4), np.float64))
        data = sparse_encode(cur, ref=np.zeros((4, 4), np.float32))
        with pytest.raises(ValueError, match="reference mismatch"):
            sparse_decode(data, ref=np.zeros(7, np.float32))

    def test_absolute_mode_unchanged(self):
        """ref=None keeps the original zero-suppression wire format —
        diff-mode bytes with a zero reference are interchangeable."""
        from nnstreamer_tpu.elements.sparse import (sparse_decode,
                                                    sparse_encode)
        arr = TestSparsePack()._arr(0.1, n=512, seed=9)
        assert sparse_encode(arr) == \
            sparse_encode(arr, ref=np.zeros_like(arr))
        np.testing.assert_array_equal(sparse_decode(sparse_encode(arr)),
                                      arr)


class TestFusedAttention:
    """ops/attention.py: the Pallas fused-attention kernel —
    numerical parity with stock flax attention via the
    interpreter on CPU, plus the mask dispatch contract."""

    def _qkv(self, b=2, s=196, h=4, d=32, dtype=np.float32, seed=0):
        import jax.numpy as jnp
        rng = np.random.default_rng(seed)
        mk = lambda: jnp.asarray(
            rng.standard_normal((b, s, h, d)), dtype)
        return mk(), mk(), mk()

    def test_interpret_matches_flax(self):
        import flax.linen as nn
        import jax.numpy as jnp
        from nnstreamer_tpu.ops.attention import fused_attention
        q, k, v = self._qkv()
        want = nn.dot_product_attention(q, k, v)
        got = fused_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-6)

    def test_unpadded_tile_sizes_match(self):
        """Sequence lengths off the 128-lane tile (the ViT 196 case)
        and head dims below a lane must pad+mask correctly."""
        import flax.linen as nn
        from nnstreamer_tpu.ops.attention import fused_attention
        for s, d in ((196, 64), (128, 128), (7, 8)):
            q, k, v = self._qkv(b=1, s=s, h=2, d=d, seed=s)
            want = nn.dot_product_attention(q, k, v)
            got = fused_attention(q, k, v)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=2e-6, err_msg=f"s={s} d={d}")

    def test_mask_falls_back_to_stock(self):
        """bias/mask are out of the kernel's contract: the wrapper must
        return stock flax results, never silently ignore the mask."""
        import flax.linen as nn
        import jax.numpy as jnp
        from nnstreamer_tpu.ops.attention import fused_attention
        q, k, v = self._qkv(b=1, s=16, h=2, d=8)
        mask = jnp.tril(jnp.ones((1, 2, 16, 16), bool))
        want = nn.dot_product_attention(q, k, v, mask=mask)
        got = fused_attention(q, k, v, mask=mask)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want))

    def test_vit_attn_toggle_same_outputs(self):
        """zoo://vit?attn=pallas and attn=stock share one param tree and
        agree on logits to bf16 rounding (the fused path runs the
        softmax in f32 — slightly BETTER numerics than stock bf16, so
        exact equality is not the contract)."""
        from nnstreamer_tpu.models import zoo
        import jax
        f_stock, p_stock, _, _ = zoo.build(
            "vit", size="64", d_model="64", layers="2", heads="4",
            classes="10", attn="stock")
        f_pl, p_pl, _, _ = zoo.build(
            "vit", size="64", d_model="64", layers="2", heads="4",
            classes="10", attn="pallas")
        assert jax.tree.structure(p_stock) == jax.tree.structure(p_pl)
        frame = np.random.default_rng(1).integers(
            0, 255, (64, 64, 3), np.uint8, endpoint=True)
        a = np.asarray(f_stock(p_stock, frame))
        b = np.asarray(f_pl(p_pl, frame))
        np.testing.assert_allclose(a, b, rtol=0.05, atol=0.05)
