"""Scenario registry: where the engine's jobs and arrival epochs come from.

A *scenario* is a pure sampler ``(key, n_jobs, rate) -> Scenario`` drawing
the workload the allocation engine (``core/engine.py``) is run against:

- ``batch`` — every job present at t=0, Pareto sizes (the paper's setting).
- ``poisson`` — Poisson(rate) arrivals, Pareto sizes: the classic M/G
  heavy-traffic stream used by ``load_sweep`` (bit-identical draws to the
  historical ``core/arrivals.py`` sweep).
- ``deterministic`` — evenly spaced arrivals at interval 1/rate.
- ``bursty`` — a 2-state MAP (Markov-modulated) on-off stream: interarrival
  gaps are Exp(rate_on) or Exp(rate_off) according to a persistent hidden
  state, producing the correlated bursts heavy-traffic studies care about.
- ``multiclass_poisson`` / ``multiclass_bursty`` / ``drift_multiclass`` —
  K-class mixtures with per-class speedup exponent, size distribution and
  arrival share (``drift_multiclass`` additionally drifts every class's
  true exponent mid-stream via per-job ``PDrift`` rows); the samplers live
  in ``core/multiclass.py`` and register here lazily.
- ``drift_poisson`` / ``drift_bursty`` — the estimation regime: the TRUE
  speedup exponent changes mid-run (``p0`` → ``p1`` at ``drift_frac`` of
  the stream's nominal span, e.g. the workload turning
  communication-bound), carried as an ``engine.PDrift`` on the scenario.
  An oracle scheduler re-reads the current truth, a stale one keeps
  ``p0``; only an online estimator (``core/estimation.py``) can *track*
  it — the three arms ``benchmarks/estimation.py`` compares.

Every sampler accepts ``sigma_size``/``sigma_p`` estimation noise (scalars
or per-class sequences): the returned ``size_factors`` (lognormal, median
1) and ``p_hat`` perturb what the *policy* sees while the true dynamics
keep ``x0`` and ``p`` — see ``engine.continuous_rule``.  ``trace_scenario``
wraps externally supplied arrival/size vectors so trace-driven replay is
the base case.

The registry is deliberately small and flat: benchmarks address scenarios
by name (``make_scenario("bursty", p=0.5, sigma_size=0.3)``), and adding a
scenario is adding one sampler function and one registry line.
"""

from __future__ import annotations

from collections.abc import Callable
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.engine import PDrift


class Scenario(NamedTuple):
    """One drawn workload, in input (unsorted) job order.

    ``size_factors``/``p_hat`` are ``None`` when the scenario carries no
    estimation noise — the policy then sees the true sizes and exponent.
    ``class_ids``/``p_job`` are ``None`` for single-class scenarios; the
    multi-class samplers (``core/multiclass.py``) fill them so every job
    carries its class id and its class's true speedup exponent.
    ``p_drift`` (``engine.PDrift``) makes the true exponent
    piecewise-constant in time — it then supersedes the scalar ``p`` the
    simulation wrappers are called with.
    """

    x0: jax.Array  # [M] true job sizes
    arrival_times: jax.Array  # [M] arrival epochs (zeros for batch)
    size_factors: jax.Array | None = None  # [M] policy sees x * factors
    p_hat: jax.Array | None = None  # scalar or [M]; policy sees p_hat
    class_ids: jax.Array | None = None  # [M] int32 job class ids
    p_job: jax.Array | None = None  # [M] per-job true speedup exponent
    p_drift: PDrift | None = None  # piecewise-constant true exponent


# A sampler draws a Scenario; ``rate`` is the sweep knob (arrivals per unit
# time; ignored by batch/trace scenarios).
ScenarioSampler = Callable[[jax.Array, int, float], Scenario]


# ------------------------------------------------------- arrival primitives
def poisson_arrivals(key: jax.Array, n_jobs: int, rate) -> jax.Array:
    """Arrival epochs of a Poisson(rate) stream: cumsum of Exp(rate) gaps."""
    gaps = jax.random.exponential(key, (n_jobs,)) / rate
    return jnp.cumsum(gaps)


def deterministic_arrivals(n_jobs: int, rate) -> jax.Array:
    """Evenly spaced arrivals at interval 1/rate (first arrival at 1/rate)."""
    return jnp.arange(1, n_jobs + 1) / rate


def bursty_arrivals(
    key: jax.Array,
    n_jobs: int,
    rate_on,
    rate_off,
    *,
    p_stay: float = 0.95,
) -> jax.Array:
    """2-state MAP on-off stream: gap ~ Exp(rate of the current state).

    The hidden state persists with probability ``p_stay`` per arrival and
    flips otherwise, so bursts have geometric length 1/(1-p_stay).  The
    state path is the parity of a cumulative flip count — no scan needed.
    """
    k_flip, k_init, k_gap = jax.random.split(key, 3)
    flips = jax.random.uniform(k_flip, (n_jobs,)) > p_stay
    s0 = jax.random.bernoulli(k_init)
    state = (s0.astype(jnp.int32) + jnp.cumsum(flips.astype(jnp.int32))) % 2
    rate = jnp.where(state == 1, rate_on, rate_off)
    gaps = jax.random.exponential(k_gap, (n_jobs,)) / rate
    return jnp.cumsum(gaps)


def pareto_sizes(key: jax.Array, n_jobs: int, alpha: float = 1.5) -> jax.Array:
    """Pareto(alpha) job sizes with minimum 1 — the benchmarks' heavy tail.

    Matches ``numpy.random.Generator.pareto(alpha) + 1`` in distribution
    (classical Pareto with x_m = 1).
    """
    return jax.random.pareto(key, alpha, (n_jobs,))


# -------------------------------------------------------------- the registry
def _any_pos(sigma) -> bool:
    """True when a scalar or per-class sequence sigma carries any noise."""
    if isinstance(sigma, (tuple, list)):
        return any(s > 0 for s in sigma)
    return sigma > 0


def _with_noise(
    scn: Scenario, key: jax.Array, p, sigma_size, sigma_p
) -> Scenario:
    """Attach estimation noise drawn from fold_in streams of ``key`` (the
    base draw consumed ``key`` itself, so noiseless runs stay bit-identical
    to the historical samplers).

    ``sigma_size``/``sigma_p`` may be scalars or per-class sequences (one
    entry per class id, requires ``scn.class_ids``).  For multi-class
    scenarios the ``p_hat`` perturbation is per-job, centered on each job's
    true class exponent ``scn.p_job``.
    """
    size_factors, p_hat = scn.size_factors, scn.p_hat
    n = scn.x0.shape[0]

    def per_job(sigma):
        if isinstance(sigma, (tuple, list)):
            if scn.class_ids is None:
                raise ValueError("per-class sigma needs a multi-class scenario")
            return jnp.asarray(sigma, scn.x0.dtype)[scn.class_ids]
        return sigma

    if _any_pos(sigma_size):
        kf = jax.random.fold_in(key, 1)
        size_factors = jnp.exp(per_job(sigma_size) * jax.random.normal(kf, (n,)))
    if _any_pos(sigma_p):
        kp = jax.random.fold_in(key, 2)
        center = scn.p_job if scn.p_job is not None else p
        per_job_hat = scn.p_job is not None or isinstance(sigma_p, (tuple, list))
        shape = (n,) if per_job_hat else ()
        p_hat = jnp.clip(
            center + per_job(sigma_p) * jax.random.normal(kp, shape), 0.05, 0.95
        )
    return scn._replace(size_factors=size_factors, p_hat=p_hat)


def _batch(key, n_jobs, rate, *, size_alpha):
    del rate
    x0 = pareto_sizes(key, n_jobs, size_alpha)
    return Scenario(x0=x0, arrival_times=jnp.zeros(n_jobs, x0.dtype))


def _poisson(key, n_jobs, rate, *, size_alpha):
    # Key discipline matches the historical load_sweep draw exactly, so the
    # default sweep is bit-identical to pre-registry results.
    k1, k2 = jax.random.split(key)
    arr = poisson_arrivals(k1, n_jobs, rate)
    x0 = pareto_sizes(k2, n_jobs, size_alpha)
    return Scenario(x0=x0, arrival_times=arr)


def _deterministic(key, n_jobs, rate, *, size_alpha):
    arr = deterministic_arrivals(n_jobs, rate)
    x0 = pareto_sizes(key, n_jobs, size_alpha)
    return Scenario(x0=x0, arrival_times=jnp.asarray(arr, x0.dtype))


def _bursty(key, n_jobs, rate, *, size_alpha, burst=4.0, p_stay=0.95):
    # rate_on/off bracket the nominal rate by ``burst``; the states are
    # visited 50/50 in steady state, so the raw mean gap would be
    # (1/burst + burst)/(2*rate) — scale both rates by that factor so the
    # long-run intensity equals the nominal ``rate`` and bursty rows are
    # load-comparable to the poisson scenario's.
    k1, k2 = jax.random.split(key)
    norm = 0.5 * (burst + 1.0 / burst)
    arr = bursty_arrivals(k1, n_jobs, rate * burst * norm,
                          rate / burst * norm, p_stay=p_stay)
    x0 = pareto_sizes(k2, n_jobs, size_alpha)
    return Scenario(x0=x0, arrival_times=arr)


def _with_drift(scn: Scenario, n_jobs, rate, *, p0, p1, drift_frac):
    """Attach a single regime change ``p0 -> p1`` at ``drift_frac`` of the
    stream's nominal span ``n_jobs / rate`` (the mean time to draw all
    arrivals), so the drift lands mid-stream at every load of a sweep."""
    dtype = scn.x0.dtype
    t_d = jnp.asarray(drift_frac * n_jobs / rate, dtype)
    drift = PDrift(
        times=t_d[None], values=jnp.asarray([p0, p1], dtype)
    )
    return scn._replace(p_drift=drift)


def _drift_poisson(
    key, n_jobs, rate, *, size_alpha, p0=0.8, p1=0.3, drift_frac=0.5
):
    scn = _poisson(key, n_jobs, rate, size_alpha=size_alpha)
    return _with_drift(scn, n_jobs, rate, p0=p0, p1=p1, drift_frac=drift_frac)


def _drift_bursty(
    key, n_jobs, rate, *, size_alpha, p0=0.8, p1=0.3, drift_frac=0.5,
    burst=4.0, p_stay=0.95,
):
    scn = _bursty(key, n_jobs, rate, size_alpha=size_alpha, burst=burst,
                  p_stay=p_stay)
    return _with_drift(scn, n_jobs, rate, p0=p0, p1=p1, drift_frac=drift_frac)


SCENARIOS: dict[str, Callable[..., Scenario]] = {
    "batch": _batch,
    "poisson": _poisson,
    "deterministic": _deterministic,
    "bursty": _bursty,
    "drift_poisson": _drift_poisson,
    "drift_bursty": _drift_bursty,
}


def make_scenario(
    name: str,
    *,
    size_alpha: float = 1.5,
    sigma_size: float = 0.0,
    sigma_p: float = 0.0,
    p: float = 0.5,
    **cfg,
) -> ScenarioSampler:
    """Build a sampler ``(key, n_jobs, rate) -> Scenario`` from the registry.

    ``sigma_size`` is the lognormal sd of the multiplicative size-estimation
    error; ``sigma_p`` the sd of the additive error on the speedup exponent
    the policy assumes (clipped to (0.05, 0.95)) — each a scalar, or a
    per-class sequence for multi-class scenarios.  ``p`` is only used as the
    center of the ``p_hat`` perturbation (multi-class scenarios center on
    each job's true class exponent instead).  Extra ``cfg`` kwargs go to the
    scenario function (e.g. ``burst``/``p_stay`` for ``bursty``, ``classes``
    for the multi-class samplers).
    """
    fn = SCENARIOS.get(name.lower())
    if fn is None:
        # The multi-class samplers register themselves on import; resolve
        # them lazily so `make_scenario("multiclass_poisson", ...)` works
        # without the caller importing core.multiclass first.
        from repro.core import multiclass  # noqa: F401  (registers samplers)

        fn = SCENARIOS.get(name.lower())
    if fn is None:
        raise ValueError(f"unknown scenario {name!r}; known: {sorted(SCENARIOS)}")

    def sample(key, n_jobs, rate):
        scn = fn(key, n_jobs, rate, size_alpha=size_alpha, **cfg)
        if _any_pos(sigma_size) or _any_pos(sigma_p):
            scn = _with_noise(scn, key, p, sigma_size, sigma_p)
        return scn

    return sample


def stream_tape(scn: Scenario) -> tuple[jax.Array, jax.Array]:
    """Reduce a :class:`Scenario` to the plain ``(sizes, arrivals)`` tape
    the bounded-slot engine (``engine.run_stream``) consumes.

    The streaming regime plays plain tapes, so scenario features that
    attach per-job vectors to the tape — estimation noise
    (``size_factors``/``p_hat``), per-job class exponents (``p_job``) and
    drift schedules (``p_drift``) — raise here rather than silently
    dropping their physics.  Those regimes take the finite-tape sweep
    path, whose whole-chip tapes carry noise and exponents in their slots
    (``engine.run_stream``); the drift clock stays with ``engine.run``.
    """
    for field, why in (
        ("size_factors", "estimation noise is per-job tape state"),
        ("p_hat", "estimation noise is per-job tape state"),
        ("p_job", "per-job class exponents take the finite-tape sweep"),
        ("p_drift", "the drift clock belongs to the finite-tape engine"),
    ):
        if getattr(scn, field) is not None:
            raise ValueError(
                f"scenario with {field} cannot stream: {why} "
                "(use the finite-tape engine.run path)"
            )
    return scn.x0, scn.arrival_times


def trace_scenario(arrival_times, sizes) -> ScenarioSampler:
    """Replay externally supplied arrivals/sizes (key and rate are ignored)."""
    x0 = jnp.asarray(sizes)
    arr = jnp.asarray(arrival_times)

    def sample(key, n_jobs, rate):
        del key, rate
        if n_jobs != x0.shape[0]:
            raise ValueError(f"trace has {x0.shape[0]} jobs, asked for {n_jobs}")
        return Scenario(x0=x0, arrival_times=arr)

    return sample


__all__ = [
    "SCENARIOS",
    "Scenario",
    "ScenarioSampler",
    "bursty_arrivals",
    "deterministic_arrivals",
    "make_scenario",
    "pareto_sizes",
    "poisson_arrivals",
    "stream_tape",
    "trace_scenario",
]
