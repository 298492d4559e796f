"""``models/brumby.py`` (gated power-retention layers, a state carried
from one buffer of a document to the next) at a tiny size on the CPU
with seeded weights, against the benchmark's plain reference
(``benchmark/refs/brumby.py``, which imports nothing of the program and
computes the quadratic definition over the whole document, with no
state), through the zoo and the jax filter's held state."""
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from nnstreamer_tpu import Buffer, parse_launch
from nnstreamer_tpu.models import afmoe, brumby, latent, zoo

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
from refs import brumby as ref  # noqa: E402

# the configuration's rehearsal sizes (benchmark/configs/
# brumby_14b_pp4_l10.json) as config.json spells them: 5 query heads a
# key/value head, buffers of 64 tokens are four chunks of 16
HF = dict(model_type="brumby", vocab_size=64, hidden_size=64,
          num_hidden_layers=3, num_attention_heads=10,
          num_key_value_heads=2, head_dim=16, intermediate_size=128,
          rms_norm_eps=1e-6, rope_theta=1000000, attention_bias=False,
          tie_word_embeddings=False, max_position_embeddings=32768,
          sliding_window=None, use_sliding_window=False,
          max_window_layers=40, hidden_act="silu", rope_scaling=None,
          retention_chunk=16, retention_degree=2)
DOC = 256
CAPS = ("other/tensors,format=static,num_tensors=2,"
        "types=(string)\"int32,int32\",dimensions=(string)\"64,1\","
        "framerate=0/1")


def _cfg(dtype=jnp.float32, **over):
    return brumby.BrumbyConfig.from_hf({**HF, **over}, dtype=dtype)


def _document(seed, n=DOC):
    return np.random.default_rng(seed).integers(0, 64, n, np.int32)


def _in_buffers(cfg, params, tokens, buffers):
    """The document sent as ``buffers`` buffers with the state carried:
    ``(last rows [buffers, V], logprobs [T] with 0 at each buffer's
    last position)``."""
    seq = len(tokens) // buffers
    step = jax.jit(lambda p, t, at, s: brumby.forward(p, t, at, s, cfg))
    state = jax.tree.map(jnp.asarray, brumby.zero_state(cfg))
    last, logprobs = [], []
    for k in range(buffers):
        row, lp, state = step(params, tokens[k * seq:(k + 1) * seq],
                              np.int32(k * seq), state)
        last.append(np.asarray(row))
        logprobs.append(np.asarray(lp))
    return np.stack(last), np.concatenate(logprobs)


def _reference(params, tokens, buffer, precision="f32", **without):
    return ref.forward(params, tokens, HF, precision, buffer=buffer,
                       **without)


def _compare(got, want, seq, tol):
    last, logprobs = got
    ref_last, ref_lp = want
    scale = np.abs(ref_last).max()
    assert np.abs(last - ref_last).max() <= tol * scale
    keep = np.arange(len(logprobs)) % seq != seq - 1
    assert (logprobs[~keep] == 0).all()
    assert np.abs(logprobs - ref_lp)[keep].max() <= tol * scale


# float32: the program's products at ``highest`` and its sums in another
# order than the reference's, 3 layers deep: 1e-4 of the largest logit.
# bfloat16: 8 bits of mantissa in every product and in the stream: 4e-2
@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-4),
                                       (jnp.bfloat16, 4e-2)],
                         ids=["float32", "bfloat16"])
def test_program_against_plain_reference(seed, dtype, tol):
    cfg = _cfg(dtype)
    params = brumby.init_params(cfg, jax.random.PRNGKey(seed))
    tokens = _document(seed)
    _compare(_in_buffers(cfg, params, tokens, 4),
             _reference(params, tokens, 64), 64, tol)


@pytest.mark.parametrize("buffers", [1, 2, 4, 8])
def test_a_document_in_buffers_agrees_with_the_document_whole(buffers):
    """Sent as 2, 4 and 8 buffers with the state carried, the document
    reads as it does sent whole (and as the reference's quadratic form
    over all of it): the same sums in float32 in another order, 1e-4 of
    the largest logit."""
    cfg = _cfg()
    params = brumby.init_params(cfg, jax.random.PRNGKey(5))
    tokens = _document(5)
    seq = DOC // buffers
    got = _in_buffers(cfg, params, tokens, buffers)
    _compare(got, _reference(params, tokens, seq), seq, 1e-4)
    whole_last, whole_lp = _in_buffers(cfg, params, tokens, 1)
    np.testing.assert_allclose(got[0][-1], whole_last[0], rtol=0,
                               atol=1e-4 * np.abs(whole_last).max())
    keep = np.arange(DOC) % seq != seq - 1
    np.testing.assert_allclose(got[1][keep], whole_lp[keep], rtol=0,
                               atol=1e-4 * np.abs(whole_last).max())


def test_position0_offsets_and_the_reset_inside_the_program():
    """A buffer at ``position0`` 0 reads the same whatever state it is
    given; the same tokens further into a document read otherwise,
    by the carried state alone: the rotation is relative, so an empty
    state met at position 64 reads as a document's start does."""
    cfg = _cfg()
    params = brumby.init_params(cfg, jax.random.PRNGKey(1))
    step = jax.jit(lambda p, t, at, s: brumby.forward(p, t, at, s, cfg))
    zero = jax.tree.map(jnp.asarray, brumby.zero_state(cfg))
    a, b = _document(1, 64), _document(2, 64)
    first, _, after_a = step(params, a, np.int32(0), zero)
    fresh, _, _ = step(params, b, np.int32(0), zero)
    again, _, _ = step(params, b, np.int32(0), after_a)
    np.testing.assert_array_equal(np.asarray(again), np.asarray(fresh))
    carried, _, _ = step(params, b, np.int32(64), after_a)
    moved, _, _ = step(params, b, np.int32(64), zero)
    scale = np.abs(np.asarray(fresh)).max()
    assert np.abs(np.asarray(carried) - np.asarray(fresh)).max() > 0.01 * scale
    np.testing.assert_allclose(np.asarray(moved), np.asarray(fresh), rtol=0,
                               atol=1e-4 * scale)
    # [1]-shaped position0, as the frame carries it
    same, _, _ = step(params, b, np.array([64], np.int32), after_a)
    np.testing.assert_array_equal(np.asarray(same), np.asarray(carried))


@pytest.mark.parametrize("without", ref.MIXER)
def test_each_mechanism_is_in_the_reference(without):
    """The reference without its gate, normaliser, rotation or head
    norms reads otherwise, so the agreement above holds the program to
    each."""
    cfg = _cfg()
    params = brumby.init_params(cfg, jax.random.PRNGKey(0))
    tokens = _document(0, 128)
    full, _ = _reference(params, tokens, 64)
    less, _ = _reference(params, tokens, 64, **{without: False})
    assert np.abs(less - full).max() > 0.02 * np.abs(full).max()


def test_the_reference_shares_nothing_with_the_program():
    text = open(ref.__file__).read()
    assert "nnstreamer_tpu" not in text.split('"""', 2)[2]
    assert "power_retention(" not in text.split('"""', 2)[2]


def test_the_decoders_share_their_parts():
    assert brumby.qkv_heads is latent.qkv_heads
    assert brumby.rmsnorm is afmoe.rmsnorm and brumby.rope is afmoe.rope
    assert brumby.swiglu is afmoe.swiglu is latent.swiglu


def test_config_reads_the_catalogs_keys():
    """``from_hf`` on the configuration file's keys (every one of the
    catalog's ``config`` among them): the widths as published, what the
    model does not read ignored."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "brumby_14b_pp4_l10.json")) as f:
        published = json.load(f)
    cfg = brumby.BrumbyConfig.from_hf(published)
    assert (cfg.hidden_size, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.head_dim, cfg.intermediate_size,
            cfg.rms_norm_eps, cfg.rope_theta) == (
        5120, 40, 8, 128, 17408, 1e-6, 1000000)
    assert (cfg.num_hidden_layers, cfg.vocab_size, cfg.retention_degree,
            cfg.retention_chunk) == (10, 37984, 2, 512)
    assert published["published"] == {"num_hidden_layers": 40,
                                      "vocab_size": 151936}
    shapes = brumby.state_shapes(cfg)
    assert len(shapes) == 10 and [tuple(x.shape) for x in shapes[0]] == [
        (8, 8320, 128), (8, 128, 128)]
    with pytest.raises(ValueError, match="degree 2"):
        brumby.BrumbyConfig.from_hf({**HF, "retention_degree": 4})
    with pytest.raises(ValueError, match="do not divide"):
        brumby.BrumbyConfig.from_hf({**HF, "num_key_value_heads": 3})


def test_parameter_count_is_the_files():
    cfg = brumby.BrumbyConfig.from_hf(
        json.load(open(os.path.join(ROOT, "benchmark", "configs",
                                    "brumby_14b_pp4_l10.json"))))
    shapes = jax.eval_shape(
        lambda: brumby.init_params(cfg, jax.random.PRNGKey(0)))
    sizes = {jax.tree_util.keystr(p): int(np.prod(x.shape)) for p, x in
             jax.tree_util.tree_leaves_with_path(shapes)}
    gates = sum(n for p, n in sizes.items() if p.endswith("['bg']"))
    assert gates == 80
    assert sum(sizes.values()) - gates == 3_692_490_240


@pytest.mark.parametrize("window", ["", "in-flight=4 prefetch-host=true"],
                         ids=["window1", "window4"])
def test_zoo_entry_through_the_filter_carries_the_state(window):
    """``zoo://brumby`` through a real pipeline: two documents of four
    buffers each on one stream give what the direct calls give, the
    state held by the filter."""
    uri = "zoo://brumby?seq=64&num_hidden_layers=2&seed=3"
    apply_fn, params, in_info, out_info, state = zoo.build(
        "brumby", seq="64", num_hidden_layers="2", seed="3")
    assert [tuple(i.shape) for i in in_info] == [(64,), (1,)]
    assert [tuple(i.shape) for i in out_info] == [(64,), (64,)]
    cfg = brumby.BrumbyConfig(num_hidden_layers=2)
    docs = [_document(7), _document(8)]
    want = [_in_buffers(cfg, params, d, 4) for d in docs]
    p = parse_launch(f"appsrc name=in caps={CAPS} ! tensor_filter name=f "
                     f"framework=jax model={uri} {window} ! appsink name=out")
    p.start()
    for d in docs:
        for k in range(4):
            p["in"].push_buffer(Buffer.from_arrays(
                [d[k * 64:(k + 1) * 64], np.array([k * 64], np.int32)]))
    p["in"].end_stream()
    assert p.wait_eos(timeout=300)
    got = [[np.asarray(c.host()) for c in b.chunks] for b in p["out"].buffers]
    report = p["f"].transfer_report()
    p.stop()
    assert report["kernel_calls"] == {"nns_power_retention": 2}
    # q, k and v re-laid a head at a time, once per load: three a layer
    assert report["prepared_equations"] >= 6
    assert report["state"] == {
        "leaves": 4, "dispatches": 8, "drops": 0,
        "bytes": 2 * 2 * (144 * 16 + 16 * 16) * 4}
    assert len(got) == 8
    for n, (last, logprobs) in enumerate(got):
        d, k = divmod(n, 4)
        assert last.dtype == logprobs.dtype == np.float32
        scale = np.abs(want[d][0]).max()
        # the filter's program and the direct call are two compilations
        # of one trace in bfloat16
        np.testing.assert_allclose(last, want[d][0][k], rtol=0,
                                   atol=0.02 * scale)
        np.testing.assert_allclose(logprobs, want[d][1][k * 64:(k + 1) * 64],
                                   rtol=0, atol=0.02 * scale)
