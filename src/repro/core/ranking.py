"""Shared sort-order helpers: ranks and prefix tests from an argsort.

Three modules used to carry their own copy of the same inverse
permutation (``engine._inv_rank``, ``policies.size_ranks_desc``'s ranks and
``policies.weighted_hesrpt``'s inline inverse).  They live here now — a
leaf module importable by both ``core.policies`` and ``core.engine``
(policies cannot import engine: engine imports policies) and by
``kernels.alloc``, whose fused allocation path must produce bit-identical
ranks to the unfused one.

No helper scatters.  A batched scatter of M updates runs one update at a
time on a TPU v5e (~4.6 ns each at M = 1000, 11x the sort it followed), so an
inverse permutation is a second argsort, and a quantizer that only asks
"is this job among the first k" uses :func:`in_stable_prefix`, which needs
no inverse at all.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def inv_rank(order: jax.Array) -> jax.Array:
    """Position of each element in its own argsort (the inverse permutation).

    ``inv_rank(jnp.argsort(key))[i]`` is the 0-based position job ``i``
    takes when sorted by ``key``.  It is the argsort of the permutation:
    its keys are unique, so stability does not matter, and the int32
    result equals the scatter ``zeros.at[order].set(arange)``.
    """
    return jnp.argsort(order).astype(jnp.int32)


def stable_order(key: jax.Array, tie: jax.Array | None = None) -> jax.Array:
    """Stable argsort of ``key``: equal keys in ``tie`` order, then by index.

    ``tie`` is None on a finite tape, whose index already is the arrival
    order, and then this is ``jnp.argsort(key)`` itself.  In a slot pool
    the index is a slot's position, so the pool passes each slot's job id:
    equal keys then keep the tape's order, as :func:`jnp.argsort` keeps it
    on the tape.  One sort either way, with ``tie`` as a second key.
    """
    if tie is None:
        return jnp.argsort(key)
    idx = jnp.arange(key.shape[0], dtype=jnp.int32)
    return jax.lax.sort((key, tie, idx), num_keys=2, is_stable=True)[2]


def in_stable_prefix(key: jax.Array, order: jax.Array, k,
                     tie: jax.Array | None = None) -> jax.Array:
    """``inv_rank(order) < k`` for an order ``stable_order(key, tie)``.

    Element ``i`` is among the first ``k`` iff ``k > 0`` and ``(key[i],
    tie[i], i) <= (key[j], tie[j], j)`` lexicographically (no ``tie`` term
    when ``tie`` is None), where ``j = order[k - 1]`` (clipped into range,
    so ``k >= M`` takes every element).  One gather and an O(M) compare, no
    inverse permutation.  Exact for keys without NaN: ``<`` and ``==``
    agree with the sort's comparator on ±0.0 and ±inf.
    """
    M = key.shape[0]
    j = order[jnp.clip(k - 1, 0, M - 1)]
    kj = key[j]
    idx = jnp.arange(M, dtype=order.dtype)
    if tie is None:
        return (k > 0) & ((key < kj) | ((key == kj) & (idx <= j)))
    tj = tie[j]
    after = (tie < tj) | ((tie == tj) & (idx <= j))
    return (k > 0) & ((key < kj) | ((key == kj) & after))


def size_order_desc(x: jax.Array) -> jax.Array:
    """Argsort of the active jobs by remaining size, descending.

    Active (``x > 0``) jobs come first, largest first; inactive jobs sort
    last.  Ties break by index (stable argsort).  This is THE sorted order
    of the per-event hot path: ``ranks_from_order`` turns it into the
    1-based descending-size ranks every rank-space policy consumes, and the
    fused allocation kernel (``kernels.alloc``) reuses it for the
    oversubscription cut instead of re-sorting.
    """
    return jnp.argsort(jnp.where(x > 0, -x, jnp.inf))


def ranks_from_order(order: jax.Array, active: jax.Array) -> jax.Array:
    """1-based ranks from a :func:`size_order_desc` order (0 = inactive).

    The largest active job gets rank 1, the smallest rank ``m``; every
    rank is ``inv_rank + 1`` masked to the active set.
    """
    return jnp.where(active, inv_rank(order) + 1, 0)
