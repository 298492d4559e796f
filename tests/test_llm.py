"""Generative filter: async token streaming (≙ llamacpp subplugin tests).
"""
import time

import numpy as np
import pytest

from nnstreamer_tpu import Buffer, parse_launch

ZOO = "zoo://gpt?vocab=64&d_model=32&n_heads=4&n_layers=2"
CAPS = ('other/tensors,format=static,num_tensors=1,'
        'types=(string)int32,dimensions=(string)4')


def test_llm_sync_generation():
    from nnstreamer_tpu.filters.registry import find_filter
    from nnstreamer_tpu.filters.base import FilterProperties
    fw = find_filter("llm")()
    fw.open(FilterProperties(model_files=(ZOO,),
                             custom_properties="max_tokens:5"))
    out = fw.invoke([np.array([1, 2, 3], np.int32)])
    assert out[0].shape == (5,)
    assert out[0].dtype == np.int32
    fw.close()


def test_llm_greedy_is_deterministic():
    from nnstreamer_tpu.filters.registry import find_filter
    from nnstreamer_tpu.filters.base import FilterProperties
    outs = []
    for _ in range(2):
        fw = find_filter("llm")()
        fw.open(FilterProperties(model_files=(ZOO,),
                                 custom_properties="max_tokens:6"))
        outs.append(fw.invoke([np.array([5, 9], np.int32)])[0])
        fw.close()
    np.testing.assert_array_equal(outs[0], outs[1])


def test_llm_async_token_stream_pipeline():
    """1 prompt in -> N token buffers out through tensor_filter
    invoke-async (the generative pipeline shape)."""
    pipe = parse_launch(
        f'appsrc name=in caps="{CAPS}" '
        f'! tensor_filter framework=llm model="{ZOO}" invoke-async=true '
        'custom="max_tokens:4" invoke-dynamic=true '
        '! appsink name=out')
    pipe.start()
    pipe["in"].push_buffer(Buffer.from_arrays(
        [np.array([1, 2, 3, 4], np.int32)]))
    deadline = time.monotonic() + 120
    while len(pipe["out"].buffers) < 4 and time.monotonic() < deadline:
        time.sleep(0.05)
    pipe["in"].end_stream()
    pipe.stop()
    out = pipe["out"].buffers
    assert len(out) == 4          # one buffer per generated token
    for b in out:
        assert b.chunks[0].shape == (1,)


def test_async_two_inflight_prompts_keep_their_pts():
    """Two prompts in flight: every token buffer must carry ITS prompt's
    PTS (regression for the single-template race at the element level)
    and the right tokens, with n_parallel decode sharing dispatches."""
    pipe = parse_launch(
        f'appsrc name=in caps="{CAPS}" '
        f'! tensor_filter framework=llm model="{ZOO}" invoke-async=true '
        'custom="max_tokens:4,n_parallel:2,max_len:32" invoke-dynamic=true '
        '! appsink name=out')
    pipe.start()
    p1 = np.array([1, 2, 3, 4], np.int32)
    p2 = np.array([9, 8, 7, 6], np.int32)
    pipe["in"].push_buffer(Buffer.from_arrays([p1], pts=1000))
    pipe["in"].push_buffer(Buffer.from_arrays([p2], pts=2000))
    deadline = time.monotonic() + 120
    while len(pipe["out"].buffers) < 8 and time.monotonic() < deadline:
        time.sleep(0.05)
    pipe["in"].end_stream()
    pipe.stop()
    out = pipe["out"].buffers
    assert len(out) == 8
    by_pts = {1000: [], 2000: []}
    for b in out:
        assert b.pts in by_pts, f"token frame with foreign pts {b.pts}"
        by_pts[b.pts].append(int(b.chunks[0].host()[0]))
    assert len(by_pts[1000]) == 4 and len(by_pts[2000]) == 4
    # tokens must match the single-stream greedy reference per prompt
    from nnstreamer_tpu.filters.base import FilterProperties
    from nnstreamer_tpu.filters.registry import find_filter
    fw = find_filter("llm")()
    fw.open(FilterProperties(model_files=(ZOO,),
                             custom_properties="max_tokens:4,max_len:32"))
    np.testing.assert_array_equal(by_pts[1000], fw.invoke([p1])[0])
    np.testing.assert_array_equal(by_pts[2000], fw.invoke([p2])[0])
    fw.close()


def test_batched_decode_shares_dispatches():
    """n_parallel=2: two concurrent streams decode in shared dispatches
    — decode_dispatches ≈ max_tokens, NOT streams x tokens — and each
    stream's tokens match its single-stream greedy reference."""
    from nnstreamer_tpu.filters.base import FilterProperties
    from nnstreamer_tpu.filters.registry import find_filter
    fw = find_filter("llm")()
    fw.open(FilterProperties(
        model_files=(ZOO,), invoke_async=True,
        custom_properties="max_tokens:6,n_parallel:2,max_len:32"))
    got = {}
    done = {}
    def dispatch(outputs, ctx=None):
        got.setdefault(ctx, []).append(int(outputs[0][0]))
        if len(got[ctx]) == 6:
            done[ctx] = True
    fw.set_async_dispatcher(dispatch)
    p1 = np.array([1, 2, 3], np.int32)
    p2 = np.array([40, 41, 42, 43, 44], np.int32)
    fw.invoke_async([p1], ctx="a")
    fw.invoke_async([p2], ctx="b")
    deadline = time.monotonic() + 120
    while len(done) < 2 and time.monotonic() < deadline:
        time.sleep(0.05)
    n_decode = fw.stats["decode_dispatches"]
    assert len(done) == 2
    fw.close()
    # 2 streams x 6 tokens = 12 per-stream dispatches; shared batched
    # decode needs at most ~6 (+1 slack for admission skew)
    assert n_decode <= 7, n_decode
    ref = find_filter("llm")()
    ref.open(FilterProperties(model_files=(ZOO,),
                              custom_properties="max_tokens:6,max_len:32"))
    np.testing.assert_array_equal(got["a"], ref.invoke([p1])[0])
    np.testing.assert_array_equal(got["b"], ref.invoke([p2])[0])
    ref.close()


def test_batched_max_len_boundary_matches_single():
    """A stream that hits max_len must emit the SAME number of tokens in
    batched mode as in single-stream mode (emit-then-check ordering)."""
    from nnstreamer_tpu.filters.base import FilterProperties
    from nnstreamer_tpu.filters.registry import find_filter
    prompt = np.arange(1, 16, dtype=np.int32)  # 15 tokens, max_len 16
    ref = find_filter("llm")()
    ref.open(FilterProperties(model_files=(ZOO,),
                              custom_properties="max_tokens:8,max_len:16"))
    want = ref.invoke([prompt])[0]
    ref.close()
    fw = find_filter("llm")()
    fw.open(FilterProperties(
        model_files=(ZOO,), invoke_async=True,
        custom_properties="max_tokens:8,max_len:16,n_parallel:2"))
    got = []
    fw.set_async_dispatcher(lambda o, ctx=None: got.append(int(o[0][0])))
    fw.invoke_async([prompt], ctx=None)
    deadline = time.monotonic() + 120
    while len(got) < len(want) and time.monotonic() < deadline:
        time.sleep(0.05)
    time.sleep(0.2)  # would catch any EXTRA token beyond the reference
    fw.close()
    np.testing.assert_array_equal(got, want)


def test_batched_sampling_reproducible_per_stream():
    """temperature>0 with n_parallel: each stream owns its PRNG key, so
    sampled tokens match the n_parallel=1 path for the same seed,
    regardless of co-resident streams."""
    from nnstreamer_tpu.filters.base import FilterProperties
    from nnstreamer_tpu.filters.registry import find_filter
    opts = "max_tokens:5,temperature:0.8,seed:3,max_len:32"
    ref = find_filter("llm")()
    ref.open(FilterProperties(model_files=(ZOO,), custom_properties=opts))
    p1 = np.array([1, 2, 3], np.int32)
    p2 = np.array([7, 8], np.int32)
    want1, want2 = ref.invoke([p1])[0], ref.invoke([p2])[0]
    ref.close()
    fw = find_filter("llm")()
    fw.open(FilterProperties(model_files=(ZOO,), invoke_async=True,
                             custom_properties=opts + ",n_parallel:2"))
    got, done = {}, set()
    def dispatch(outputs, ctx=None):
        got.setdefault(ctx, []).append(int(outputs[0][0]))
        if len(got[ctx]) == 5:
            done.add(ctx)
    fw.set_async_dispatcher(dispatch)
    fw.invoke_async([p1], ctx="a")
    fw.invoke_async([p2], ctx="b")
    deadline = time.monotonic() + 120
    while len(done) < 2 and time.monotonic() < deadline:
        time.sleep(0.05)
    fw.close()
    np.testing.assert_array_equal(got["a"], want1)
    np.testing.assert_array_equal(got["b"], want2)


def test_decode_step_multi_matches_single():
    """decode_step_multi with per-slot positions reproduces two
    independent decode_step loops exactly (same cache layout, same
    logits), including slots at different depths."""
    import jax
    import jax.numpy as jnp
    from nnstreamer_tpu.models import transformer as tfm

    cfg = tfm.GPTConfig(vocab=64, d_model=32, n_heads=4, n_layers=2,
                        d_ff=64, dtype=jnp.float32)
    params = tfm.init_params(cfg, jax.random.PRNGKey(1))
    prompts = [jnp.array([[3, 11, 25]], jnp.int32),
               jnp.array([[40, 7, 19, 22, 5]], jnp.int32)]
    # single-stream references
    refs = []
    for p in prompts:
        logits, cache = tfm.prefill(params, tfm.init_cache(cfg, 1, 16), p, cfg)
        toks = []
        for _ in range(4):
            t = jnp.argmax(logits, -1).astype(jnp.int32)
            toks.append(int(t[0]))
            logits, cache = tfm.decode_step(params, cache, t, cfg)
        refs.append(toks)
    # multi-stream: insert both prefills into a 2-slot cache, decode together
    mcache = tfm.init_cache_multi(cfg, 2, 16)
    logits = jnp.zeros((2, cfg.vocab), jnp.float32)
    for slot, p in enumerate(prompts):
        l1, c1 = tfm.prefill(params, tfm.init_cache(cfg, 1, 16), p, cfg)
        mcache = tfm.cache_insert(mcache, c1, jnp.asarray(slot, jnp.int32))
        logits = logits.at[slot].set(l1[0])
    outs = [[], []]
    active = jnp.ones((2,), bool)
    for _ in range(4):
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        for slot in range(2):
            outs[slot].append(int(tok[slot]))
        logits, mcache = tfm.decode_step_multi(params, mcache, tok, active, cfg)
    assert outs == refs


def test_llamacpp_alias():
    from nnstreamer_tpu.filters.registry import find_filter
    assert find_filter("llamacpp").NAME == "llm"


def test_prefill_single_dispatch_matches_sequential():
    """Batched prefill: tokens identical to the per-token path with a
    prefill dispatch count of exactly 1 (the llamacpp n_batch
    analog)."""
    import jax
    import jax.numpy as jnp
    from nnstreamer_tpu.filters.base import FilterProperties
    from nnstreamer_tpu.filters.registry import find_filter
    from nnstreamer_tpu.models import transformer as tfm

    prompt = np.array([3, 11, 25, 40, 7], np.int32)
    fw = find_filter("llm")()
    fw.open(FilterProperties(model_files=(ZOO,),
                             custom_properties="max_tokens:6"))
    fast = fw.invoke([prompt])[0]
    assert fw.stats["prefill_dispatches"] == 1
    assert fw.stats["decode_dispatches"] == 5  # max_tokens - 1
    cfg = fw._cfg

    # reference: sequential one-token prefill through decode_step
    cache = tfm.init_cache(cfg, batch=1, max_len=len(prompt) + 6)
    step = jax.jit(lambda p, c, t: tfm.decode_step(p, c, t, cfg))
    logits = None
    for t in prompt:
        logits, cache = step(fw._params, cache, jnp.asarray([t], jnp.int32))
    slow = []
    for _ in range(6):
        tok = jnp.argmax(logits, -1)
        slow.append(int(np.asarray(tok)[0]))
        logits, cache = step(fw._params, cache, tok.astype(jnp.int32))
    fw.close()
    np.testing.assert_array_equal(fast, np.asarray(slow, np.int32))


def test_prefill_cache_matches_decode_loop():
    import jax
    import jax.numpy as jnp
    from nnstreamer_tpu.models import transformer as tfm

    cfg = tfm.GPTConfig(vocab=64, d_model=32, n_heads=4, n_layers=2,
                        d_ff=64, dtype=jnp.float32)
    params = tfm.init_params(cfg, jax.random.PRNGKey(1))
    tokens = jnp.array([[3, 11, 25, 40, 7, 19]], jnp.int32)
    fast_logits, fast_cache = tfm.prefill(
        params, tfm.init_cache(cfg, 1, 8), tokens, cfg)
    cache = tfm.init_cache(cfg, 1, 8)
    logits = None
    for i in range(tokens.shape[1]):
        logits, cache = tfm.decode_step(params, cache, tokens[:, i], cfg)
    np.testing.assert_allclose(np.asarray(fast_logits), np.asarray(logits),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(fast_cache["k"]),
                               np.asarray(cache["k"]), rtol=2e-3, atol=2e-3)
    assert int(fast_cache["index"]) == tokens.shape[1]


def test_prefill_length_bucketing_reuses_compilation():
    """Prompts of different lengths within one power-of-two bucket share
    a single compiled prefill (no per-length recompile), and 2-D
    prompts are flattened before the overflow check."""
    from nnstreamer_tpu.filters.base import FilterProperties
    from nnstreamer_tpu.filters.registry import find_filter
    fw = find_filter("llm")()
    fw.open(FilterProperties(model_files=(ZOO,),
                             custom_properties="max_tokens:3,max_len:32"))
    for prompt in (np.array([1, 2, 3, 4, 5], np.int32),
                   np.array([9, 8, 7, 6, 5, 4, 3], np.int32),
                   np.array([[2, 4, 6, 8, 10, 12]], np.int32)):  # 2-D
        out = fw.invoke([prompt])
        assert out[0].shape == (3,)
    # lengths 5, 7, 6 all pad to the 8-bucket: exactly one compilation
    assert fw._prefill._cache_size() == 1
    fw.close()


# -- chunked decode (custom=chunk:K) ----------------------------------------

def _gen_tokens(custom: str, prompt: np.ndarray) -> np.ndarray:
    from nnstreamer_tpu.filters.base import FilterProperties
    from nnstreamer_tpu.filters.registry import find_filter
    fw = find_filter("llm")()
    fw.open(FilterProperties(model_files=(ZOO,), custom_properties=custom))
    out = fw.invoke([prompt])[0]
    stats = dict(fw.stats)
    fw.close()
    return out, stats


def test_chunked_greedy_matches_per_token():
    """chunk:K emits the EXACT token stream of chunk:1 (greedy), with
    K-fold fewer decode dispatches."""
    p = np.array([3, 1, 4], np.int32)
    ref, ref_stats = _gen_tokens("max_tokens:12,max_len:32", p)
    got, got_stats = _gen_tokens("max_tokens:12,max_len:32,chunk:4", p)
    np.testing.assert_array_equal(got, ref)
    assert ref_stats["decode_dispatches"] == 11   # per-token loop
    assert got_stats["decode_dispatches"] == 3    # ceil(12/4) scans


def test_chunked_sampling_matches_per_token():
    """Same seed + temperature: in-graph sampling reproduces the host
    sampling loop's key-split order token-for-token."""
    p = np.array([7, 7], np.int32)
    ref, _ = _gen_tokens("max_tokens:10,max_len:32,temperature:0.8,seed:3", p)
    got, _ = _gen_tokens(
        "max_tokens:10,max_len:32,temperature:0.8,seed:3,chunk:4", p)
    np.testing.assert_array_equal(got, ref)


def test_chunked_max_len_cutoff_matches_per_token():
    """Capacity cutoff (cache full before max_tokens) emits the same
    final-token tail in chunked mode."""
    p = np.array([2, 5, 6], np.int32)
    # max_len 8: prompt 3 -> 5 decodes possible, 6 emits
    ref, _ = _gen_tokens("max_tokens:16,max_len:8", p)
    got, _ = _gen_tokens("max_tokens:16,max_len:8,chunk:4", p)
    np.testing.assert_array_equal(got, ref)
    assert len(ref) == 6


def test_chunked_batched_decode_matches_reference():
    """n_parallel + chunk: two concurrent streams, K tokens per shared
    dispatch, each stream still matching its single-stream reference."""
    from nnstreamer_tpu.filters.base import FilterProperties
    from nnstreamer_tpu.filters.registry import find_filter
    fw = find_filter("llm")()
    fw.open(FilterProperties(
        model_files=(ZOO,), invoke_async=True,
        custom_properties="max_tokens:8,n_parallel:2,max_len:32,chunk:4"))
    got, done = {}, {}

    def dispatch(outputs, ctx=None):
        got.setdefault(ctx, []).append(int(outputs[0][0]))
        if len(got[ctx]) == 8:
            done[ctx] = True

    fw.set_async_dispatcher(dispatch)
    p1 = np.array([1, 2, 3], np.int32)
    p2 = np.array([40, 41, 42, 43, 44], np.int32)
    fw.invoke_async([p1], ctx="a")
    fw.invoke_async([p2], ctx="b")
    deadline = time.monotonic() + 120
    while len(done) < 2 and time.monotonic() < deadline:
        time.sleep(0.05)
    n_decode = fw.stats["decode_dispatches"]
    assert len(done) == 2
    fw.close()
    # 8 tokens at chunk 4 = 2 chunks when co-resident (+2 slack for
    # admission skew: a stream admitted mid-chunk pays its own chunks)
    assert n_decode <= 4, n_decode
    ref, _ = _gen_tokens("max_tokens:8,max_len:32", p1)
    np.testing.assert_array_equal(got["a"], ref)
    ref, _ = _gen_tokens("max_tokens:8,max_len:32", p2)
    np.testing.assert_array_equal(got["b"], ref)


def test_chunked_batched_sampling_reproducible():
    """chunk + n_parallel + temperature: per-stream keys survive chunk
    boundaries; tokens match the single-stream sampling reference."""
    from nnstreamer_tpu.filters.base import FilterProperties
    from nnstreamer_tpu.filters.registry import find_filter
    fw = find_filter("llm")()
    fw.open(FilterProperties(
        model_files=(ZOO,), invoke_async=True,
        custom_properties=("max_tokens:6,n_parallel:2,max_len:32,"
                           "chunk:4,temperature:0.7,seed:5")))
    got, done = {}, {}

    def dispatch(outputs, ctx=None):
        got.setdefault(ctx, []).append(int(outputs[0][0]))
        if len(got[ctx]) == 6:
            done[ctx] = True

    fw.set_async_dispatcher(dispatch)
    p1 = np.array([11, 12], np.int32)
    p2 = np.array([21, 22, 23], np.int32)
    fw.invoke_async([p1], ctx="a")
    fw.invoke_async([p2], ctx="b")
    deadline = time.monotonic() + 120
    while len(done) < 2 and time.monotonic() < deadline:
        time.sleep(0.05)
    assert len(done) == 2
    fw.close()
    ref, _ = _gen_tokens(
        "max_tokens:6,max_len:32,temperature:0.7,seed:5", p1)
    np.testing.assert_array_equal(got["a"], ref)
    ref, _ = _gen_tokens(
        "max_tokens:6,max_len:32,temperature:0.7,seed:5", p2)
    np.testing.assert_array_equal(got["b"], ref)


@pytest.mark.parametrize("extra", ["", ",temperature:0.7,seed:5"])
def test_chunked_batched_max_len_cutoff_matches_single(extra):
    """Capacity cutoff in n_parallel+chunk mode: a stream that fills its
    cache emits the single-stream token count/values (final token emitted
    WITHOUT a decode — no clamped cache write at index max_len), while a
    deeper co-resident stream keeps decoding past that point."""
    from nnstreamer_tpu.filters.base import FilterProperties
    from nnstreamer_tpu.filters.registry import find_filter
    p1 = np.array([2, 5, 6], np.int32)        # fills max_len 8 first
    p2 = np.array([1], np.int32)              # keeps going afterwards
    ref1, _ = _gen_tokens("max_tokens:16,max_len:8" + extra, p1)
    ref2, _ = _gen_tokens("max_tokens:16,max_len:8" + extra, p2)
    assert len(ref1) == 6 and len(ref2) == 8  # capacity vs deeper stream
    fw = find_filter("llm")()
    fw.open(FilterProperties(
        model_files=(ZOO,), invoke_async=True,
        custom_properties=("max_tokens:16,max_len:8,n_parallel:2,chunk:4"
                           + extra)))
    got, done = {}, set()

    def dispatch(outputs, ctx=None):
        got.setdefault(ctx, []).append(int(outputs[0][0]))
        if len(got[ctx]) == (6 if ctx == "a" else 8):
            done.add(ctx)

    fw.set_async_dispatcher(dispatch)
    fw.invoke_async([p1], ctx="a")
    fw.invoke_async([p2], ctx="b")
    deadline = time.monotonic() + 120
    while len(done) < 2 and time.monotonic() < deadline:
        time.sleep(0.05)
    time.sleep(0.2)  # catch any EXTRA tokens beyond the references
    fw.close()
    np.testing.assert_array_equal(got["a"], ref1)
    np.testing.assert_array_equal(got["b"], ref2)


# -- sampling controls (custom=top_k / top_p) -------------------------------

def test_top_k_1_equals_greedy():
    p = np.array([2, 9, 4], np.int32)
    greedy, _ = _gen_tokens("max_tokens:10,max_len:32", p)
    topk1, _ = _gen_tokens(
        "max_tokens:10,max_len:32,temperature:0.9,seed:7,top_k:1", p)
    np.testing.assert_array_equal(topk1, greedy)


def test_tiny_top_p_equals_greedy():
    p = np.array([5, 5, 5], np.int32)
    greedy, _ = _gen_tokens("max_tokens:8,max_len:32", p)
    nucleus, _ = _gen_tokens(
        "max_tokens:8,max_len:32,temperature:1.3,seed:2,top_p:0.0001", p)
    np.testing.assert_array_equal(nucleus, greedy)


def test_chunked_sampling_with_topk_topp_matches_per_token():
    """top_k/top_p ride the shared sample_logits helper: the chunked
    scan emits the same tokens as the per-token host loop."""
    p = np.array([7, 1], np.int32)
    ref, _ = _gen_tokens(
        "max_tokens:10,max_len:32,temperature:0.8,seed:3,top_k:8,top_p:0.9",
        p)
    got, _ = _gen_tokens(
        "max_tokens:10,max_len:32,temperature:0.8,seed:3,top_k:8,"
        "top_p:0.9,chunk:4", p)
    np.testing.assert_array_equal(got, ref)


def test_sample_logits_respects_top_k():
    """Every draw lands inside the top-k set (in-graph masking)."""
    import jax
    import jax.numpy as jnp
    from nnstreamer_tpu.models.transformer import sample_logits

    logits = jnp.asarray(
        np.random.default_rng(0).standard_normal((4, 64)), jnp.float32)
    top4 = np.argsort(np.asarray(logits), axis=-1)[:, -4:]
    for seed in range(5):
        keys = jax.vmap(jax.random.PRNGKey)(
            jnp.arange(4) + seed * 10)
        toks = np.asarray(sample_logits(keys, logits, 1.5, top_k=4))
        for row in range(4):
            assert toks[row] in top4[row], (row, toks[row])


def test_sample_logits_respects_top_p():
    """With a spiked distribution, tiny top_p must always pick the
    spike; with top_p=1.0 sampling stays unrestricted."""
    import jax
    import jax.numpy as jnp
    from nnstreamer_tpu.models.transformer import sample_logits

    logits = jnp.zeros((2, 32), jnp.float32).at[:, 5].set(8.0)
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(2))
    toks = np.asarray(sample_logits(keys, logits, 2.0, top_p=0.5))
    np.testing.assert_array_equal(toks, [5, 5])


def test_top_p_zero_degrades_to_greedy():
    """top_p<=0 must keep the best token (greedy), never an all-masked
    row silently emitting token 0."""
    p = np.array([4, 2], np.int32)
    greedy, _ = _gen_tokens("max_tokens:8,max_len:32", p)
    z, _ = _gen_tokens(
        "max_tokens:8,max_len:32,temperature:1.0,seed:1,top_p:0", p)
    np.testing.assert_array_equal(z, greedy)


def test_nucleus_formed_before_temperature():
    """llamacpp chain order: the top_p candidate set comes from the
    UNSCALED distribution, so cranking temperature cannot widen it."""
    import jax
    import jax.numpy as jnp
    from nnstreamer_tpu.models.transformer import sample_logits

    # two dominant tokens (~50/50), the rest tiny: nucleus at 0.9 keeps
    # exactly {3, 11} regardless of temperature
    logits = jnp.full((1, 32), -10.0).at[0, 3].set(5.0).at[0, 11].set(5.0)
    for seed in range(12):
        keys = jax.random.PRNGKey(seed)[None]
        tok = int(sample_logits(keys, logits, 50.0, top_p=0.9)[0])
        assert tok in (3, 11), tok


def test_llm_loads_trained_weights_from_checkpoint(tmp_path):
    """zoo://gpt?params_dir=... restores orbax weights (the
    tensor_trainer save format): generation differs from random init
    and is reproducible across opens."""
    import jax

    from nnstreamer_tpu.models import transformer as tfm
    from nnstreamer_tpu.trainers.checkpoint import save_params

    cfg = tfm.GPTConfig(vocab=64, d_model=32, n_heads=4, n_layers=2)
    trained = tfm.init_params(cfg, jax.random.PRNGKey(42))  # "trained"
    ckpt = str(tmp_path / "gpt-ckpt")
    save_params(ckpt, trained)

    base = ZOO  # seed 0 random init
    with_ckpt = f"{ZOO}&params_dir={ckpt}"
    p = np.array([7, 3, 1], np.int32)
    out_random, _ = _gen_tokens("max_tokens:8,max_len:32", p)
    fw_tokens = []
    for _ in range(2):
        from nnstreamer_tpu.filters.base import FilterProperties
        from nnstreamer_tpu.filters.registry import find_filter
        fw = find_filter("llm")()
        fw.open(FilterProperties(model_files=(with_ckpt,),
                                 custom_properties="max_tokens:8,max_len:32"))
        fw_tokens.append(fw.invoke([p])[0])
        fw.close()
    np.testing.assert_array_equal(fw_tokens[0], fw_tokens[1])
    assert not np.array_equal(fw_tokens[0], out_random)


def test_async_failure_is_counted_as_an_invoke_error(monkeypatch):
    """A failure AFTER invoke_async returned (here: admission in the
    scheduler thread) has no caller to raise into. It must not be a
    stream that silently never yields a token: the element's
    invoke_errors / frames_dropped see it, like a sync invoke failure."""
    from nnstreamer_tpu.filters.llm import LlmFilter

    def boom(self, prompt, max_len):
        raise RuntimeError("device refused the prefill")

    monkeypatch.setattr(LlmFilter, "_prefill_prompt", boom)
    pipe = parse_launch(
        f'appsrc name=in caps="{CAPS}" '
        f'! tensor_filter name=f framework=llm model="{ZOO}" '
        'invoke-async=true invoke-dynamic=true '
        'custom="max_tokens:4,n_parallel:2,max_len:32" '
        '! appsink name=out')
    pipe.start()
    pipe["in"].push_buffer(Buffer.from_arrays(
        [np.array([1, 2, 3, 4], np.int32)]))
    deadline = time.monotonic() + 60
    while not pipe["f"].stats["invoke_errors"] \
            and time.monotonic() < deadline:
        time.sleep(0.05)
    pipe["in"].end_stream()
    pipe.stop()
    assert pipe["f"].stats["invoke_errors"] == 1
    assert pipe["f"].stats["frames_dropped"] == 1
    assert pipe["out"].buffers == []
