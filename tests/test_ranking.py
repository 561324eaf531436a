"""Sort-order helpers (``core/ranking.py``): inverse permutations and the
stable-prefix test, against NumPy's stable argsort and the scatter form
they replace."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.ranking import in_stable_prefix, inv_rank

# Few distinct values, so ties are common; both zeros and both infinities.
VALUES = (-np.inf, -2.5, -1.0, -0.0, 0.0, 0.5, 1.0, 3.0, np.inf)


def _scatter_inv(order):
    M = order.shape[0]
    return jnp.zeros(M, jnp.int32).at[order].set(jnp.arange(M, dtype=jnp.int32))


def _keys(rng, shape, dtype):
    return rng.choice(np.asarray(VALUES), size=shape).astype(dtype)


def _positions(key):
    """Each element's 0-based position in NumPy's stable argsort of ``key``."""
    return np.argsort(np.argsort(key, kind="stable"), kind="stable")


@pytest.mark.parametrize("M", [1, 2, 7, 1000])
def test_inv_rank_is_the_inverse_permutation(M):
    rng = np.random.default_rng(M)
    key = rng.standard_normal(M)
    order = jnp.argsort(jnp.asarray(key))
    got = inv_rank(order)
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(got, _positions(key))
    np.testing.assert_array_equal(got, _scatter_inv(order))
    perm = jnp.asarray(rng.permutation(M))
    np.testing.assert_array_equal(inv_rank(perm), np.argsort(np.asarray(perm)))


def test_inv_rank_under_vmap():
    rng = np.random.default_rng(3)
    perms = np.stack([rng.permutation(1000) for _ in range(32)]).astype(np.int32)
    got = jax.jit(jax.vmap(inv_rank))(jnp.asarray(perms))
    np.testing.assert_array_equal(got, np.argsort(perms, axis=1))
    np.testing.assert_array_equal(got, jax.vmap(_scatter_inv)(jnp.asarray(perms)))


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
@pytest.mark.parametrize("k_of_M", [lambda M: 0, lambda M: 1, lambda M: M // 2,
                                    lambda M: M - 1, lambda M: M, lambda M: M + 3],
                         ids=["0", "1", "M/2", "M-1", "M", "M+3"])
def test_stable_prefix_matches_inverse_permutation(dtype, k_of_M):
    rng = np.random.default_rng(11)
    for M in (1, 2, 7, 64):
        k = k_of_M(M)
        for _ in range(20):
            key = jnp.asarray(_keys(rng, M, dtype))
            order = jnp.argsort(key)
            got = in_stable_prefix(key, order, k)
            np.testing.assert_array_equal(got, inv_rank(order) < k)
            np.testing.assert_array_equal(got, _positions(np.asarray(key)) < k)


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_stable_prefix_with_a_dynamic_k_per_lane(dtype):
    rng = np.random.default_rng(5)
    lanes, M = 32, 1000
    keys = _keys(rng, (lanes, M), dtype)
    keys[:, ::3] = rng.standard_normal((lanes, (M + 2) // 3))  # distinct keys too
    ks = rng.integers(0, M + 4, lanes).astype(np.int32)
    ks[:6] = (0, 1, M // 2, M - 1, M, M + 3)

    def lane(key, k):
        return in_stable_prefix(key, jnp.argsort(key), k)

    got = jax.jit(jax.vmap(lane))(jnp.asarray(keys), jnp.asarray(ks))
    want = np.stack([_positions(kr) < k for kr, k in zip(keys, ks)])
    np.testing.assert_array_equal(got, want)


def test_vmapped_stable_prefix_lowers_to_no_scatter():
    def lane(key, k):
        return in_stable_prefix(key, jnp.argsort(key), k)

    text = jax.jit(jax.vmap(lane)).lower(
        jnp.zeros((32, 1000), jnp.float32), jnp.zeros(32, jnp.int32)
    ).compile().as_text()
    assert "scatter(" not in text
