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


def _inputs(seed, s, *, decay, beta):
    """Queries and keys as a layer's projections leave them, before
    their l2 norms; ``decay`` is the range of a step's log-decay,
    ``beta`` the range of beta."""
    rng = np.random.default_rng(seed)
    return (rng.standard_normal((H, s, DK)).astype(np.float32) * 3.0,
            rng.standard_normal((H, s, DK)).astype(np.float32) * 0.2,
            rng.standard_normal((H, s, DV)).astype(np.float32),
            -rng.uniform(*decay, (H, s, DK)).astype(np.float32),
            rng.uniform(*beta, (H, s)).astype(np.float32))


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


# float32 both sides: they differ in the order of their sums; measured
# 1.3e-7 to 7e-7 of the output's largest entry over these cases, 6e-6
# where nothing decays and every token rewrites its key's row (128
# tokens of rounding kept whole): the tolerance stands 5x over that
@pytest.mark.parametrize("chunk", [16, 64])
@pytest.mark.parametrize("decay,beta", [
    ((0.0, 0.1), (0.0, 1.0)),       # a long memory
    ((1.0, 1.6), (0.0, 1.0)),       # -1.6 a step: 64 rows sum to -100
    ((0.0, 18.0), (0.0, 1.0)),      # a channel forgotten inside one step
    ((0.0, 0.5), (0.0, 0.01)),      # beta near 0: almost nothing written
    ((0.0, 0.5), (0.99, 1.0)),      # beta near 1: the key's row replaced
    ((0.0, 0.0), (1.0, 1.0))],      # no decay, beta 1: the plain delta rule
    ids=["slow", "minus100_a_chunk", "forgotten_in_a_step", "beta_near_0",
         "beta_near_1", "plain_delta_rule"])
def test_chunked_is_the_token_recurrence(chunk, decay, beta):
    args = _inputs(7, 128, decay=decay, beta=beta)
    want = _token_by_token(*args)
    got = np.asarray(jax.jit(lambda *x: kda.kda_chunked(*x, chunk=chunk))(
        *args))
    assert got.shape == want.shape == (H, 128, DV)
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() <= 3e-5 * np.abs(want).max()


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
