"""``ops/kda.py`` (the chunked gated delta rule) against the token
recurrence of the benchmark's plain reference
(``benchmark/refs/kimi_linear.py::recurrence``, which imports nothing
of the program), float32 on the CPU, seeded."""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from nnstreamer_tpu.ops import kda

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))
from refs import kimi_linear as ref  # noqa: E402

H, DK, DV = 3, 16, 8


def _inputs(seed, s, *, decay, beta, heads=H):
    """Queries and keys as a layer's projections leave them, before
    their l2 norms; ``decay`` is the range of a step's log-decay,
    ``beta`` the range of beta."""
    rng = np.random.default_rng(seed)
    return (rng.standard_normal((heads, s, DK)).astype(np.float32) * 3.0,
            rng.standard_normal((heads, s, DK)).astype(np.float32) * 0.2,
            rng.standard_normal((heads, s, DV)).astype(np.float32),
            -rng.uniform(*decay, (heads, s, DK)).astype(np.float32),
            rng.uniform(*beta, (heads, s)).astype(np.float32))


def _token_by_token(q, k, v, a, beta):
    """The reference's recurrence on unit keys and on queries of length
    ``dk^-0.5`` (the norms the op takes inside)."""
    def unit(x):
        x = np.asarray(x, np.float32)
        return x / np.sqrt(np.sum(x * x, -1, keepdims=True) + 1e-6)

    to = lambda x: jnp.moveaxis(jnp.asarray(x), 0, 1)       # noqa: E731
    return np.moveaxis(np.asarray(ref.recurrence(
        to(unit(q) * DK ** -0.5), to(unit(k)), to(v), to(a), to(beta))),
        1, 0)


RANGES = {                          # a step's log-decay, beta
    "slow": ((0.0, 0.1), (0.0, 1.0)),                   # a long memory
    "minus100_a_chunk": ((1.0, 1.6), (0.0, 1.0)),  # 64 rows sum to -100
    "forgotten_in_a_step": ((0.0, 18.0), (0.0, 1.0)),   # a channel gone
    "beta_near_0": ((0.0, 0.5), (0.0, 0.01)),   # almost nothing written
    "beta_near_1": ((0.0, 0.5), (0.99, 1.0)),   # the key's row replaced
    "plain_delta_rule": ((0.0, 0.0), (1.0, 1.0))}   # no decay, beta 1

# the packings ``kda_chunked`` reads off its shapes (chunk, heads,
# tokens): the state kernel takes 4, 2 or 1 heads a step; a step holds
# 1, 2, 3 or 8 chunks of 64; the inverses of all a head's chunks are
# taken at once (a lane a chunk), every ``turn`` steps where a head has
# more steps than one (64 chunks of 16 in two steps) and more chunks
# than lanes (256 chunks of 16: two turns of four steps)
PACKINGS = [(16, 1, 64), (16, 2, 64), (16, 4, 64), (16, 8, 64),
            (64, 4, 128), (64, 3, 64), (64, 3, 192), (64, 3, 512),
            (32, 3, 128), (16, 2, 1024), (16, 2, 4096)]


# float32 both sides: they differ in the order of their sums; measured
# 1.3e-7 to 7e-7 of the output's largest entry over these cases, 6e-6
# where nothing decays and every token rewrites its key's row (128
# tokens of rounding kept whole): the tolerance stands 5x over that
@pytest.mark.parametrize("chunk,heads,s,ranges,scales", [
    pytest.param(chunk, H, 128, ranges, False, id=f"{ranges}-{chunk}")
    for ranges in RANGES for chunk in (16, 64)] + [
    pytest.param(chunk, heads, s, ranges, False,
                 id=f"{ranges}-{chunk}-{heads}x{s}")
    for chunk, heads, s in PACKINGS for ranges in ("slow", "beta_near_1")
    ] + [pytest.param(16, 4, 64, "slow", True, id="heads_a_thousandfold"),
         pytest.param(64, 4, 128, "slow", True, id="heads_a_thousandfold-64")])
def test_chunked_is_the_token_recurrence(chunk, heads, s, ranges, scales):
    decay, beta = RANGES[ranges]
    q, k, v, a, beta = _inputs(7, s, decay=decay, beta=beta, heads=heads)
    if scales:
        # every head a thousand times the one before it: a head that read
        # its neighbour's state in the packed scratch would be far off
        v = v * (1e3 ** np.arange(heads, dtype=np.float32))[:, None, None]
    want = _token_by_token(q, k, v, a, beta)
    got = np.asarray(kda.kda_chunked(q, k, v, a, beta, chunk=chunk))
    assert got.shape == want.shape == (heads, s, DV)
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() <= 3e-5 * np.abs(want).max()
    if scales:
        for got_h, want_h in zip(got, want):
            assert np.abs(got_h - want_h).max() <= 3e-5 * np.abs(want_h).max()


def _inverse_by_hand(k, a, beta, chunk):
    """``(I + diag(beta) A)^-1`` of every chunk in float64 from the
    module docstring's ``A_ij = sum_d k_i k_j exp(c_i - c_j)`` (``j <
    i``) on unit keys: ``[H, S / chunk, chunk, chunk]``."""
    k, a, beta = (np.asarray(x, np.float64) for x in (k, a, beta))
    heads, s, dk = k.shape
    k = k / np.sqrt(np.sum(k * k, -1, keepdims=True) + 1e-6)
    k, a = (x.reshape(heads, s // chunk, chunk, dk) for x in (k, a))
    c = np.cumsum(a, axis=2)
    pair = np.einsum("hnid,hnjd,hnijd->hnij", k, k,
                     np.exp(np.minimum(c[:, :, :, None] - c[:, :, None], 0)))
    system = np.eye(chunk) + beta.reshape(heads, -1, chunk, 1) * np.tril(
        pair, -1)
    return np.linalg.inv(system)


def _first_kernel_alone(q, k, a, beta, chunk):
    """``T`` as ``nns_kda_chunk_intra`` writes it, ``[H, S / chunk,
    chunk, chunk]``: the first kernel through the interpreter, without
    the second."""
    heads, s = np.shape(beta)
    rows, turn, _, _ = kda._packing(heads, s, chunk)
    t, _ = kda._matrices(q, k, a, beta[..., None], chunk=chunk, rows=rows,
                         turn=turn, interpret=True)
    return np.asarray(t).reshape(heads, s // chunk, chunk, chunk)


@pytest.mark.parametrize("chunk,s", [(16, 128), (64, 128), (16, 1024)])
@pytest.mark.parametrize("ranges", list(RANGES))
def test_the_inverse_is_numpys_in_float64(chunk, s, ranges):
    """The triangular system's inverse alone: float32 substitution, a
    lane a chunk, against ``numpy.linalg.inv`` in float64 of the system
    built by hand. Measured 1e-9 to 3.3e-6 of the largest entry (the
    largest where a channel is forgotten inside a step)."""
    decay, beta = RANGES[ranges]
    q, k, _, a, beta = _inputs(11, s, decay=decay, beta=beta)
    got = _first_kernel_alone(q, k, a, beta, chunk)
    want = _inverse_by_hand(k, a, beta, chunk)
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()


@pytest.mark.parametrize("chunk", [16, 64])
def test_the_inverse_of_all_ones_is_bidiagonal_to_the_bit(chunk):
    """Identical keys of length 1024 (their l2 norm is then exact), no
    decay, beta 1: ``I + M`` is all ones on and under its diagonal, its
    inverse 1 on the diagonal, -1 under it and exactly 0 elsewhere, as
    substitution leaves it and a series in ``M`` could not (its powers
    reach 1e18)."""
    s = 2 * chunk
    k = np.tile(np.eye(DK, dtype=np.float32)[0] * 1024.0, (H, s, 1))
    got = _first_kernel_alone(k, k, np.zeros((H, s, DK), np.float32),
                              np.ones((H, s), np.float32), chunk)
    want = np.eye(chunk, dtype=np.float32) - np.eye(chunk, k=-1,
                                                    dtype=np.float32)
    assert np.array_equal(got, np.broadcast_to(want, got.shape))


def test_a_negated_running_sum_would_overflow_here():
    """The case the chunked form is built around: the running sum over
    a chunk passes -88, so ``exp(-c)`` is infinite in float32 and the
    factored form ``(q exp(c)) (k exp(-c))^T`` gives no number, where
    the differences give the recurrence's."""
    q, k, v, a, beta = _inputs(3, 64, decay=(1.5, 1.6), beta=(0.2, 0.9))
    c = np.cumsum(a, axis=1)
    with np.errstate(all="ignore"):
        assert c.min() < -88 and np.isinf(np.exp(-c.astype(np.float32))).any()
        naive = np.einsum("hid,hjd->hij", q * np.exp(c), k * np.exp(-c))
    assert not np.isfinite(naive).all()
    got = np.asarray(kda.kda_chunked(q, k, v, a, beta, chunk=64))
    assert np.abs(got - _token_by_token(q, k, v, a, beta)).max() <= 5e-6


def test_identical_keys_keep_the_triangular_solve_exact():
    """Every key the same, no decay, beta 1: ``I + diag(beta) A`` is
    all ones below its diagonal, whose inverse is bidiagonal while the
    powers of its nilpotent part reach 1e18: forward substitution gives
    the recurrence's output, a series in those powers could not."""
    rng = np.random.default_rng(0)
    k = np.tile(np.eye(DK, dtype=np.float32)[0], (H, 64, 1))
    q = k * 5.0                 # any length: the op takes the norms
    v = rng.standard_normal((H, 64, DV)).astype(np.float32)
    a, beta = np.zeros((H, 64, DK), np.float32), np.ones((H, 64), np.float32)
    got = np.asarray(kda.kda_chunked(q, k, v, a, beta, chunk=64))
    # the state's one live row is replaced by each token's value
    np.testing.assert_allclose(got, v * DK ** -0.5, atol=1e-5)
    np.testing.assert_allclose(got, _token_by_token(q, k, v, a, beta),
                               atol=1e-5)


def test_the_state_is_carried_from_chunk_to_chunk():
    """With almost no decay the last token still reads the first: the
    output over two chunks differs from the second chunk's alone."""
    q, k, v, a, beta = _inputs(5, 32, decay=(0.0, 0.01), beta=(0.5, 1.0))
    both = np.asarray(kda.kda_chunked(q, k, v, a, beta, chunk=16))
    alone = np.asarray(kda.kda_chunked(q[:, 16:], k[:, 16:], v[:, 16:],
                                       a[:, 16:], beta[:, 16:], chunk=16))
    assert np.abs(both[:, 16:] - alone).max() > 1e-2


def test_bfloat16_operands_stay_near_the_recurrence():
    """The program's dtype: operands rounded to 8 bits, sums, decays,
    the triangular system and the state in float32. Measured 0.004-0.007
    of the output's largest entry."""
    args = _inputs(9, 128, decay=(0.0, 1.6), beta=(0.0, 1.0))
    want = _token_by_token(*args)
    q, k, v, a, beta = args
    got = np.asarray(kda.kda_chunked(
        *(jnp.asarray(x, jnp.bfloat16) for x in (q, k, v)), a, beta))
    assert got.dtype == np.float32
    assert np.abs(got - want).max() <= 0.03 * np.abs(want).max()


def test_shapes_that_cannot_be_chunked_are_refused():
    q, k, v, a, beta = _inputs(1, 48, decay=(0, 1), beta=(0, 1))
    with pytest.raises(ValueError, match="multiple"):
        kda.kda_chunked(q, k, v, a, beta, chunk=64)
    with pytest.raises(ValueError, match="power of two"):
        kda.kda_chunked(q, k, v, a, beta, chunk=48)
