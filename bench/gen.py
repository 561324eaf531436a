"""Traffic generation: every job the benchmark plays or checks, from a seed.

The sweep engine draws its own jobs from the keys a grid is given.  This
module draws the same jobs again, key for key, as the engine's samplers
are documented to (``scenario`` ``poisson``: Exp(rate) gaps summed, from
the first half of a split key, and Pareto(alpha) sizes with minimum 1
from the second; ``batch``: Pareto sizes from the key itself, all arriving
at 0), so that the reference is fed what the timed lanes simulated.  The
live cell's tapes come from here alone.  Traffic may state a load instead
of a rate (:func:`rate_at_load`).
"""

from __future__ import annotations

import hashlib

import numpy as np


def seed32(seed: int) -> int:
    """A 31-bit seed from any whole number: ``PRNGKey`` keeps only the low
    32 bits when 64-bit types are off, so large seeds would collide."""
    digest = hashlib.sha256(str(int(seed)).encode()).digest()
    return int.from_bytes(digest[:4], "little") & 0x7FFFFFFF


def lane_keys(seed: int, n_seeds: int):
    """The ``n_seeds`` keys a grid call with spec seed ``seed`` gives its lanes."""
    import jax

    return jax.random.split(jax.random.PRNGKey(seed), n_seeds)


def _one(scenario: str, n_jobs: int, size_alpha: float):
    import jax
    import jax.numpy as jnp

    def draw(key, rate):
        if scenario == "batch":
            x0 = jax.random.pareto(key, size_alpha, (n_jobs,))
            return jnp.zeros(n_jobs, x0.dtype), x0
        if scenario == "poisson":
            k1, k2 = jax.random.split(key)
            arr = jnp.cumsum(jax.random.exponential(k1, (n_jobs,)) / rate)
            return arr, jax.random.pareto(k2, size_alpha, (n_jobs,))
        raise ValueError(f"no generator for scenario {scenario!r}")

    return draw


def draw_lanes(scenario: str, seed: int, n_seeds: int, rates, n_jobs: int,
               size_alpha: float):
    """Arrivals and sizes of every lane, ``[n_rates, n_seeds, n_jobs]``,
    float64 copies of what the device drew (in the device's precision)."""
    import jax
    import jax.numpy as jnp

    keys = lane_keys(seed, n_seeds)
    draw = jax.jit(jax.vmap(_one(scenario, n_jobs, size_alpha)))
    arr, x0 = [], []
    for rate in rates:
        a, x = draw(keys, jnp.full(n_seeds, rate, jnp.result_type(float)))
        arr.append(np.asarray(a, np.float64))
        x0.append(np.asarray(x, np.float64))
    return np.stack(arr), np.stack(x0)


def rate_at_load(load: float, n_servers: float, size_alpha: float) -> float:
    """Arrival rate at which jobs bring ``load`` times the work the cluster
    serves with one server per job: ``load * n_servers / E[X]``, with
    ``E[X] = alpha / (alpha - 1)`` for Pareto(alpha) sizes with minimum 1.
    One server per job is the most work a server does (``s(k) / k`` falls
    with ``k``), so the queue is stable at every load below 1: as the
    backlog grows, shares shrink towards one server each."""
    return float(load) * float(n_servers) * (size_alpha - 1.0) / size_alpha


def rates(mix: dict, cfg: dict) -> list[float]:
    """The arrival rates a traffic mix states: ``rates`` as given, or
    ``loads`` turned into rates for the configuration's cluster and sizes."""
    if "rates" in mix:
        return [float(r) for r in mix["rates"]]
    return [rate_at_load(load, cfg["n_servers"], float(cfg["size_alpha"]))
            for load in mix["loads"]]


def live_tape(seed: int, tape: int, *, rate: float, n_jobs: int, size_alpha: float):
    """Arrival times and sizes of tape ``tape`` of a run with ``seed``:
    ``n_jobs`` Exp(rate) gaps and Pareto(alpha) sizes with minimum 1, each
    drawn stratified (the i-th smallest in the i-th of ``n_jobs`` strata of
    equal probability, at a point and in an order drawn from the seed).
    Every job comes from the seed, and every tape holds nearly the same
    work, so a window's work varies little from seed to seed."""
    rng = np.random.default_rng([seed32(seed), int(tape)])

    def strata():
        return rng.permutation((np.arange(n_jobs) + rng.random(n_jobs)) / n_jobs)

    gaps = -np.log1p(-strata()) / rate
    x0 = (1.0 - strata()) ** (-1.0 / size_alpha)
    return np.cumsum(gaps), x0
