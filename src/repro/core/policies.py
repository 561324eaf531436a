"""Server-allocation policies from the paper (and its competitors).

Every policy maps the *remaining* job sizes ``x`` (shape ``[M]``, entries
``<= 0`` mean "job already departed") and the speedup exponent ``p`` to an
allocation vector ``theta`` (shape ``[M]``, ``theta_i in [0, 1]``,
``sum(theta) <= 1``).  ``theta_i`` is the *fraction* of the ``N``-server
system granted to job ``i``; the job then progresses at rate
``s(theta_i * N) = (theta_i * N) ** p``.

All functions are pure, vectorized and ``jax.jit``-able; they are the
building block used by both the fluid simulator (``core/simulator.py``) and
the cluster scheduler (``sched/cluster.py``).

Paper: Berg, Vesilo, Harchol-Balter, "heSRPT: Optimal Parallel Scheduling of
Jobs With Known Sizes", 2019.
"""

from __future__ import annotations

import functools
from collections.abc import Callable

import jax
import jax.numpy as jnp

from repro.core.ranking import inv_rank, ranks_from_order, size_order_desc

Policy = Callable[..., jax.Array]  # (x, p, ...) -> theta


def _active(x: jax.Array) -> jax.Array:
    return x > 0


def size_ranks_desc(x: jax.Array) -> jax.Array:
    """Rank of each *active* job when sorted by remaining size, descending.

    The largest active job gets rank 1, the smallest active job gets rank
    ``m`` (the number of active jobs).  Inactive jobs get rank 0.  Ties are
    broken by index (stable argsort), which is WLOG optimal by symmetry.
    """
    # Inactive jobs sort last (key = -inf after negation -> +inf); the
    # order -> rank conversion is the shared inverse permutation.
    return ranks_from_order(size_order_desc(x), _active(x))


# Rank-space policy forms.  Theorem 6 proves the optimal allocation is
# size-invariant: it depends on the remaining sizes only through their
# descending-size *ranks* and the active count ``m``.  These helpers take
# the ranks directly (rank 0 == inactive), which is what lets the online
# simulator's fast path (core/arrivals.py) carry ranks incrementally
# through its scan instead of re-sorting at every event.
def hesrpt_theta_from_ranks(
    ranks: jax.Array, m: jax.Array, p: jax.Array, *, dtype=None
) -> jax.Array:
    """Theorem 7 in rank space: theta_i = (r/m)^(1/(1-p)) - ((r-1)/m)^(1/(1-p))."""
    dtype = dtype or jnp.result_type(float)
    active = ranks > 0
    rf = ranks.astype(dtype)
    c = 1.0 / (1.0 - p)
    m_safe = jnp.maximum(m, 1).astype(dtype)
    hi = (rf / m_safe) ** c
    lo = ((rf - 1.0) / m_safe) ** c
    return jnp.where(active, hi - lo, 0.0)


def equi_theta_from_ranks(
    ranks: jax.Array, m: jax.Array, p: jax.Array | None = None, *, dtype=None
) -> jax.Array:
    dtype = dtype or jnp.result_type(float)
    active = ranks > 0
    m_safe = jnp.maximum(m, 1).astype(dtype)
    return jnp.where(active, 1.0 / m_safe, jnp.zeros((), dtype))


def srpt_theta_from_ranks(
    ranks: jax.Array, m: jax.Array, p: jax.Array | None = None, *, dtype=None
) -> jax.Array:
    """The whole system to the smallest active job — rank m by definition."""
    dtype = dtype or jnp.result_type(float)
    return jnp.where((ranks == m) & (m > 0), jnp.ones((), dtype),
                     jnp.zeros((), dtype))


def hesrpt(x: jax.Array, p: jax.Array) -> jax.Array:
    """heSRPT (Theorem 7): the optimal allocation for total flow time.

    With ``m`` jobs remaining, ranked ``i = 1..m`` from largest to smallest
    remaining size::

        theta_i = (i/m)^(1/(1-p)) - ((i-1)/m)^(1/(1-p))

    Allocations are increasing in rank: the *smallest* job gets the largest
    share, but every active job gets a non-zero share (high efficiency).
    Size-invariant (Thm 6): depends only on the size *ordering* and ``m``.
    """
    active = _active(x)
    m = jnp.sum(active)
    ranks = size_ranks_desc(x)
    return hesrpt_theta_from_ranks(ranks, m, p, dtype=x.dtype)


def helrpt(x: jax.Array, p: jax.Array) -> jax.Array:
    """heLRPT (Theorem 2): the optimal allocation for makespan.

    ``gamma_i = x_i^(1/p) / sum_j x_j^(1/p)`` over active jobs.  All jobs
    complete simultaneously at ``||X||_{1/p}`` (Thm 1/2).  The allocation is
    stable under recomputation from remaining sizes, because remaining sizes
    stay proportional to the originals (x_i(t) = x_i (1 - t/T*)).
    """
    active = _active(x)
    xs = jnp.where(active, x, 1.0)
    # Normalize by the max for overflow safety before the 1/p power.
    xmax = jnp.max(jnp.where(active, x, 0.0))
    xmax = jnp.maximum(xmax, jnp.finfo(x.dtype).tiny)
    w = jnp.where(active, (xs / xmax) ** (1.0 / p), 0.0)
    total = jnp.maximum(jnp.sum(w), jnp.finfo(x.dtype).tiny)
    return w / total


def srpt(x: jax.Array, p: jax.Array | None = None) -> jax.Array:
    """SRPT: the whole system to the single job with the shortest remaining
    size.  Optimal iff p == 1 (embarrassingly parallel)."""
    active = _active(x)
    key = jnp.where(active, x, jnp.inf)
    shortest = jnp.argmin(key)
    theta = jnp.zeros_like(x).at[shortest].set(1.0)
    return jnp.where(jnp.any(active), theta, jnp.zeros_like(x))


def equi(x: jax.Array, p: jax.Array | None = None) -> jax.Array:
    """EQUI: equal split between active jobs.  Optimal for unknown
    exponentially-distributed sizes [5]; a lower-efficiency-loss baseline
    here."""
    active = _active(x)
    m = jnp.sum(active)
    m_safe = jnp.maximum(m, 1).astype(x.dtype)
    return jnp.where(active, 1.0 / m_safe, 0.0)


def hell(x: jax.Array, p: jax.Array, n_servers: jax.Array) -> jax.Array:
    """HELL [21]: greedy efficiency-to-remaining-time heuristic.

    [21] iteratively picks the job maximizing ``(s(k)/k) / (x_i / s(k)) =
    s(k)^2 / (k x_i) = k^(2p-1) / x_i`` and grants it the maximizing ``k``.

    With a continuously divisible system this degenerates into two closed
    forms (documented deviation from the loosely-specified original, see
    DESIGN.md §9):

    * ``p >= 1/2``: the ratio is non-decreasing in ``k`` -> the first pick
      takes *all* servers for the smallest job -> SRPT.
    * ``p < 1/2``: the ratio is decreasing in ``k`` -> greedy water-filling;
      the fixed point equalizes ``k_i^(2p-1) / x_i`` across jobs, giving
      ``k_i \\propto x_i^{-1/(1-2p)}`` (strong bias towards short jobs).
    """
    del n_servers  # continuous limit; the fixed point is N-independent
    active = _active(x)
    p = jnp.asarray(p, dtype=x.dtype)

    def waterfill(_):
        xs = jnp.where(active, x, 1.0)
        xmin = jnp.min(jnp.where(active, x, jnp.inf))
        # Guarded: this branch is only *selected* for p < 1/2, but lax.cond
        # traces it for any p, so keep the denominator non-zero.
        expo = -1.0 / jnp.maximum(1.0 - 2.0 * p, 1e-12)
        w = jnp.where(active, (xs / xmin) ** expo, 0.0)
        total = jnp.maximum(jnp.sum(w), jnp.finfo(x.dtype).tiny)
        return w / total

    def srpt_like(_):
        return srpt(x)

    return jax.lax.cond(p < 0.5, waterfill, srpt_like, operand=None)


def knee(
    x: jax.Array,
    p: jax.Array,
    n_servers: jax.Array,
    alpha: jax.Array,
) -> jax.Array:
    """KNEE [21]: allocate each job its "knee" number of servers.

    A job's knee is where the marginal run-time reduction of one more server
    drops below ``alpha``.  In the continuous relaxation::

        d/dk [x k^-p] = -p x k^-(p+1)   =>   knee_i = (p x_i / alpha)^(1/(1+p))

    Jobs are served in increasing-knee order (== increasing size).  If the
    knees oversubscribe the system, the prefix of shortest jobs get their
    knees and the boundary job gets the remainder.  If the knees
    undersubscribe, [21] repeats the process; the limit of repeated rounds is
    a proportional-to-knee split of all ``N`` servers (see DESIGN.md §9).

    ``alpha`` has no principled setting; the benchmark brute-forces it and
    reports the best, mirroring the paper's optimistic treatment of KNEE.
    """
    active = _active(x)
    xs = jnp.where(active, x, 0.0)
    kn = jnp.where(active, (p * xs / alpha) ** (1.0 / (1.0 + p)), 0.0)
    total_knee = jnp.sum(kn)

    def undersub(_):
        tot = jnp.maximum(total_knee, jnp.finfo(x.dtype).tiny)
        return kn / tot  # proportional split of the full system

    def oversub(_):
        # Serve in increasing-knee order until N runs out.
        key = jnp.where(active, kn, jnp.inf)
        order = jnp.argsort(key)
        kn_sorted = kn[order]
        csum = jnp.cumsum(kn_sorted)
        prev = csum - kn_sorted
        grant_sorted = jnp.clip(n_servers - prev, 0.0, kn_sorted)
        grant = jnp.zeros_like(kn).at[order].set(grant_sorted)
        return jnp.where(active, grant / n_servers, 0.0)

    return jax.lax.cond(total_knee <= n_servers, undersub, oversub, None)


# ------------------------------------------------- multi-class (per-job p)
# These policies accept a per-job exponent vector ``p`` (shape [M]) so job
# classes with different speedup curves (Berg et al. 2024) share one system.
# They are also the building blocks of ``core/multiclass.py``, which owns
# the class-id bookkeeping, the static class-blind reduction, and the
# engine/cluster dispatch.
def hesrpt_per_class(x: jax.Array, p: jax.Array) -> jax.Array:
    """Class-aware heSRPT: per-job Thm-7 brackets with each job's own ``p``.

    Jobs are ranked globally by remaining size (descending, as in heSRPT);
    job ``i`` with rank ``r`` and exponent ``p_i`` takes the bracket::

        (r/m)^(1/(1-p_i)) - ((r-1)/m)^(1/(1-p_i))

    i.e. the share Thm 7 would grant it in a homogeneous system of its own
    class — jobs with a *flatter* speedup curve (small ``p_i``) claim
    relatively less of the pool at the same rank, which is the class-aware
    fluid intuition of Berg et al. 2024.  Brackets are renormalized to sum
    to 1 (with uniform ``p`` the brackets telescope to 1 already, so this
    reduces to heSRPT up to a last-ulp renormalization; ``core/multiclass``
    dispatches the uniform case to :func:`hesrpt` statically so the
    reduction is bit-for-bit).
    """
    active = _active(x)
    m = jnp.sum(active)
    ranks = size_ranks_desc(x)
    rf = ranks.astype(x.dtype)
    c = 1.0 / (1.0 - p)  # per-job exponent
    m_safe = jnp.maximum(m, 1).astype(x.dtype)
    th = jnp.where(active, (rf / m_safe) ** c - ((rf - 1.0) / m_safe) ** c, 0.0)
    total = jnp.maximum(jnp.sum(th), jnp.finfo(x.dtype).tiny)
    return th / total


def weighted_hesrpt(x: jax.Array, p: jax.Array, w: jax.Array) -> jax.Array:
    """Weighted heSRPT: Thm-7 brackets over cumulative *weight* fractions.

    Generalizes heSRPT toward weighted flow time ``sum_i w_i T_i``: replace
    the count fraction ``r/m`` by the cumulative weight fraction ``W_r/W``
    of the jobs ranked largest..smallest by remaining size (Berg et al.
    2020 derive this bracket structure for mean slowdown, where
    ``w_i = 1/x_i(0)``)::

        theta_(r) = (W_r/W)^(1/(1-p_r)) - (W_{r-1}/W)^(1/(1-p_r))

    Heavier-weight jobs take a larger jump of the concave bracket curve, so
    they finish sooner; uniform weights reduce to :func:`hesrpt` (the
    cumulative count fraction is exactly ``r/m``) and per-job ``p`` is
    supported the same way as :func:`hesrpt_per_class`.  The brackets are
    renormalized so the allocation always sums to 1.
    """
    active = _active(x)
    order = size_order_desc(x)  # active desc by size, then inactive
    w_act = jnp.where(active, w, 0.0)
    csum_sorted = jnp.cumsum(w_act[order])
    W_hi = csum_sorted[inv_rank(order)]  # cum. weight of jobs at least this large
    W_lo = W_hi - w_act
    W_tot = jnp.maximum(csum_sorted[-1], jnp.finfo(x.dtype).tiny)
    c = 1.0 / (1.0 - p)
    th = jnp.where(active, (W_hi / W_tot) ** c - (W_lo / W_tot) ** c, 0.0)
    total = jnp.maximum(jnp.sum(th), jnp.finfo(x.dtype).tiny)
    return th / total


def waterfill(
    x: jax.Array,
    p: jax.Array,
    n_servers: jax.Array,
    w: jax.Array | None = None,
    *,
    n_iter: int = 64,
) -> jax.Array:
    """Class-weighted water-filling (the Berg et al. 2024 fluid allocation).

    Chooses ``theta`` maximizing the aggregate weighted service rate::

        max  sum_i  w_i / x_i * s(theta_i N)      s.t.  sum theta_i = 1

    over the active jobs (``w_i`` an optional per-job class weight, default
    1; the ``1/x_i`` factor biases toward short remaining work, the myopic
    flow-time/slowdown greedy).  The objective is strictly concave in
    ``theta`` for ``p_i in (0,1)``, so the KKT stationarity condition

        w_i/x_i * p_i * N^{p_i} * theta_i^{p_i - 1} = lambda

    has the closed-form water level ``theta_i(lambda) =
    (g_i/lambda)^{1/(1-p_i)}`` with ``g_i = w_i/x_i * p_i * N^{p_i}``; every
    active job sits in the interior (the marginal rate blows up at 0), so a
    monotone bisection on ``log lambda`` solves ``sum theta = 1`` to float
    precision in ``n_iter`` fixed steps — jit/vmap-safe inside the engine's
    scan.  The result is renormalized for exact conservation.
    """
    active = _active(x)
    dtype = x.dtype
    p = jnp.broadcast_to(jnp.asarray(p, dtype), x.shape)
    xs = jnp.where(active, x, 1.0)
    wv = jnp.ones_like(x) if w is None else jnp.asarray(w, dtype)
    wv = jnp.where(active, jnp.maximum(wv, jnp.finfo(dtype).tiny), 1.0)
    n = jnp.asarray(n_servers, dtype)
    # log g_i, computed in log space for heavy-tailed x
    log_g = jnp.log(wv) - jnp.log(xs) + jnp.log(p) + p * jnp.log(n)
    m = jnp.maximum(jnp.sum(active), 1).astype(dtype)
    one_minus_p = 1.0 - p
    # Bracket: at lam_lo = max_i g_i some theta_i = 1 (sum >= 1); at
    # lam_hi = max_i g_i * m^{1-p_i} every theta_i <= 1/m (sum <= 1).
    neg_inf = jnp.asarray(-jnp.inf, dtype)
    lo = jnp.max(jnp.where(active, log_g, neg_inf))
    hi = jnp.max(jnp.where(active, log_g + one_minus_p * jnp.log(m), neg_inf))

    def theta_of(log_lam):
        t = jnp.exp((log_g - log_lam) / one_minus_p)
        return jnp.where(active, t, 0.0)

    def bisect(_, lohi):
        lo, hi = lohi
        mid = 0.5 * (lo + hi)
        too_big = jnp.sum(theta_of(mid)) > 1.0
        return jnp.where(too_big, mid, lo), jnp.where(too_big, hi, mid)

    lo, hi = jax.lax.fori_loop(0, n_iter, bisect, (lo, hi))
    th = theta_of(0.5 * (lo + hi))
    total = jnp.maximum(jnp.sum(th), jnp.finfo(dtype).tiny)
    return jnp.where(jnp.any(active), th / total, jnp.zeros_like(x))


# Rank-space registry: policies whose allocation is a pure function of the
# descending-size ranks (Thm 6 size-invariance).  For all three, the rate is
# non-increasing in remaining size, so between decision epochs the size
# order is preserved and the smallest active job departs first — the two
# invariants the online simulator's sort-free fast path relies on
# (core/arrivals.py::simulate_online_ranked).
RANK_POLICIES = {
    "hesrpt": hesrpt_theta_from_ranks,
    "equi": equi_theta_from_ranks,
    "srpt": srpt_theta_from_ranks,
}


def make_rank_policy(name: str):
    """Rank-space form ``(ranks, m, p) -> theta`` or None if unavailable."""
    return RANK_POLICIES.get(name.lower())


# Registry used by the simulator / benchmarks. HELL and KNEE close over the
# discrete system parameters they need.
def make_policy(name: str, *, n_servers: float = 1.0, alpha: float = 1.0) -> Policy:
    name = name.lower()
    if name == "hesrpt":
        return hesrpt
    if name == "helrpt":
        return helrpt
    if name == "srpt":
        # Returned unwrapped so identity checks (the engine's superstep
        # attachment) see the registry function, same as heSRPT.
        return srpt
    if name == "equi":
        return equi
    if name == "hell":
        return functools.partial(hell, n_servers=jnp.asarray(n_servers))
    if name == "waterfill":
        return functools.partial(waterfill, n_servers=jnp.asarray(n_servers))
    if name == "knee":
        return functools.partial(
            knee, n_servers=jnp.asarray(n_servers), alpha=jnp.asarray(alpha)
        )
    raise ValueError(f"unknown policy {name!r}")


POLICY_NAMES = ("hesrpt", "helrpt", "srpt", "equi", "hell", "knee", "waterfill")
