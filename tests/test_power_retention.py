"""``ops/power_retention.py`` (the chunked gated power retention of
degree 2, a state in and a state out) against the quadratic definition
and against the token-by-token recurrence on the symmetric square, both
written here in numpy float64 and sharing nothing with the op; float32
through the Pallas interpreter on the CPU, seeded."""
import numpy as np
import pytest

import jax.numpy as jnp

from nnstreamer_tpu.ops import power_retention as pr

EPS = 1e-6


def _inputs(seed, t, *, heads=10, kv=2, d=16, gates=(0.0, 0.5)):
    """Queries and keys as a layer's head norms leave them (length about
    ``sqrt(d)``); ``gates``: the range of a step's ``-log_g``."""
    rng = np.random.default_rng(seed)
    return (rng.standard_normal((heads, t, d)).astype(np.float32),
            rng.standard_normal((kv, t, d)).astype(np.float32),
            rng.standard_normal((kv, t, d)).astype(np.float32),
            -rng.uniform(*gates, (kv, t)).astype(np.float32))


def quadratic(q, k, v, g):
    """``o_t = sum_s a_ts v_s / (sum_s a_ts + eps)``, ``a_ts = (q_t .
    k_s / d)^2 exp(G_t - G_s)`` for ``s <= t``: the definition, every
    pair."""
    q, k, v, g = (np.asarray(x, np.float64) for x in (q, k, v, g))
    h, t, d = q.shape
    n = h // k.shape[0]
    big_g = np.cumsum(g, -1)
    o = np.zeros_like(q)
    for i in range(h):
        j = i // n
        a = (q[i] @ k[j].T / d) ** 2 * np.tril(np.exp(np.minimum(
            big_g[j][:, None] - big_g[j][None, :], 0.0)))
        o[i] = a @ v[j] / (a.sum(-1, keepdims=True) + EPS)
    return o


def phi(x):
    """The symmetric square of ``x`` [..., d]: ``x_a^2`` and ``sqrt(2)
    x_a x_b`` (``a < b``), ``d (d + 1) / 2`` entries, so that ``phi(x) .
    phi(y) = (x . y)^2``."""
    d = x.shape[-1]
    a, b = np.triu_indices(d)
    return x[..., a] * x[..., b] * np.where(a == b, 1.0, np.sqrt(2.0))


def token_by_token(q, k, v, g):
    """``S_t = exp(g_t) S_(t-1) + phi(k_t) v_t^T``, ``z_t`` likewise,
    ``o_t = phi(q_t)^T S_t / (phi(q_t)^T z_t + eps)`` on ``q / d``."""
    q, k, v, g = (np.asarray(x, np.float64) for x in (q, k, v, g))
    h, t, d = q.shape
    n = h // k.shape[0]
    o = np.zeros_like(q)
    for j in range(k.shape[0]):
        s = np.zeros((d * (d + 1) // 2, d))
        z = np.zeros(d * (d + 1) // 2)
        for at in range(t):
            s = np.exp(g[j, at]) * s + np.outer(phi(k[j, at]), v[j, at])
            z = np.exp(g[j, at]) * z + phi(k[j, at])
            for i in range(j * n, (j + 1) * n):
                f = phi(q[i, at] / d)
                o[i, at] = f @ s / (f @ z + EPS)
    return o


def _run(q, k, v, g, chunk, state=None):
    o, state = pr.power_retention(*(jnp.asarray(x) for x in (q, k, v, g)),
                                  state, chunk=chunk)
    return np.asarray(o), state


def test_phi_is_the_square_of_the_product():
    rng = np.random.default_rng(0)
    x, y = rng.standard_normal((2, 7, 16))
    np.testing.assert_allclose(np.sum(phi(x) * phi(y), -1),
                               np.sum(x * y, -1) ** 2, rtol=1e-12)
    assert phi(x).shape[-1] == 136 and pr.phi_rows(16) == 144
    assert pr.phi_rows(128) == 8320          # 65 blocks for phi's 8256
    with pytest.raises(ValueError, match="odd"):
        pr.phi_rows(7)


def test_the_two_forms_written_here_agree():
    q, k, v, g = _inputs(1, 24, heads=4, kv=2, d=8)
    np.testing.assert_allclose(token_by_token(q, k, v, g),
                               quadratic(q, k, v, g), rtol=1e-9, atol=1e-12)


# float32 products at ``highest`` and float32 sums of a few hundred
# terms against float64: 2e-5 of the largest entry
TOL = dict(rtol=0, atol=2e-5)


@pytest.mark.parametrize("t,chunk", [
    (64, 16), (64, 64), (96, 32), (48, 128),   # chunks that divide T
    (40, 16), (70, 32), (33, 8)],              # and that do not
    ids=lambda x: str(x))
def test_against_the_quadratic_definition(t, chunk):
    q, k, v, g = _inputs(t, t)
    got, _ = _run(q, k, v, g, chunk)
    want = quadratic(q, k, v, g)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, **TOL)


def test_against_the_token_by_token_recurrence():
    """Five query heads a key/value head read one state."""
    q, k, v, g = _inputs(7, 48, heads=10, kv=2, d=8)
    got, _ = _run(q, k, v, g, 16)
    np.testing.assert_allclose(got, token_by_token(q, k, v, g), **TOL)


@pytest.mark.parametrize("gates", [(0.0, 0.0), (0.0, 0.01), (6.0, 8.0),
                                   (0.0, 8.0)],
                         ids=["no_decay", "slow", "minus100_a_chunk",
                              "mixed"])
def test_gates_down_to_minus_8_a_step(gates):
    """Per-step log-gates down to -8: a chunk of 16 sums past -100 and
    ``exp(-G)`` alone would overflow; the op takes decays as differences
    inside a chunk only."""
    q, k, v, g = _inputs(3, 64, gates=gates)
    got, (s, z) = _run(q, k, v, g, 16)
    assert np.isfinite(got).all()
    assert np.isfinite(np.asarray(s)).all() and np.isfinite(np.asarray(z)).all()
    np.testing.assert_allclose(got, quadratic(q, k, v, g), **TOL)


@pytest.mark.parametrize("parts", [2, 4, 3])
def test_a_sequence_in_parts_with_the_state_carried(parts):
    """The whole sequence against the same sequence in two, four and
    three (ragged) parts, each part given the state the one before it
    returned; and the state after the parts is the state after the
    whole."""
    q, k, v, g = _inputs(11, 96)
    whole, (s_all, z_all) = _run(q, k, v, g, 16)
    cuts = np.linspace(0, 96, parts + 1).astype(int) if parts != 3 \
        else np.array([0, 40, 56, 96])
    state, got = None, []
    for lo, hi in zip(cuts[:-1], cuts[1:]):
        o, state = _run(q[:, lo:hi], k[:, lo:hi], v[:, lo:hi], g[:, lo:hi],
                        16, state)
        got.append(o)
    np.testing.assert_allclose(np.concatenate(got, 1), whole, **TOL)
    np.testing.assert_allclose(np.concatenate(got, 1),
                               quadratic(q, k, v, g), **TOL)
    np.testing.assert_allclose(np.asarray(state[0]), np.asarray(s_all),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(state[1]), np.asarray(z_all),
                               rtol=1e-4, atol=1e-5)


def test_the_state_is_phi_k_v_in_the_rolled_layout():
    """``S``'s block ``r`` holds ``k_a k_(a-r) v``, twice for the pairs
    that appear once; ``Z`` is ``sum k k^T``: one token, no decay."""
    q, k, v, g = _inputs(5, 1, heads=2, kv=1, d=8, gates=(0.0, 0.0))
    _, (s, z) = _run(q, k, v, g, 8)
    s, z = np.asarray(s)[0], np.asarray(z)[0]
    kk, vv = k[0, 0].astype(np.float64), v[0, 0].astype(np.float64)
    np.testing.assert_allclose(z, np.outer(kk, kk), rtol=1e-5, atol=1e-6)
    for r in range(5):
        weight = 2.0 if 0 < r < 4 else 1.0
        want = np.outer(weight * kk * np.roll(kk, r), vv)
        np.testing.assert_allclose(s[r * 8:(r + 1) * 8], want, rtol=1e-5,
                                   atol=1e-6)


def test_bfloat16_operands_float32_state():
    q, k, v, g = _inputs(13, 64)
    o, (s, z) = pr.power_retention(
        *(jnp.asarray(x, jnp.bfloat16) for x in (q, k, v)), jnp.asarray(g),
        chunk=16)
    assert o.dtype == jnp.bfloat16 and s.dtype == z.dtype == jnp.float32
    rounded = [np.asarray(jnp.asarray(x, jnp.bfloat16), np.float32)
               for x in (q, k, v)]
    want = quadratic(*rounded, g)
    # bfloat16 products (8 bits of mantissa) on entries of order 1
    np.testing.assert_allclose(np.asarray(o, np.float32), want, rtol=0,
                               atol=0.03)


def test_heads_that_do_not_divide_are_refused():
    q, k, v, g = _inputs(0, 16, heads=5, kv=2)
    with pytest.raises(ValueError, match="do not divide"):
        _run(q, k, v, g, 16)
