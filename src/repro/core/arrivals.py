"""Online (arrival-stream) simulation — thin wrappers over ``core/engine.py``.

The paper proves heSRPT optimal when every job is present at t=0 and leaves
the arrival-stream case as a heuristic (§4.3): re-run the policy on the
active set at every arrival and departure.  The follow-up heavy-traffic work
(Berg et al. 2020 on mean slowdown, Berg et al. 2024 on multiple job
classes) studies exactly this online regime, which is why it is the
foundation for every heavy-traffic scenario in this repo.

The event-driven ``lax.scan`` itself lives in ``core/engine.py`` (one
engine for batch, online, and quantized-chips trajectories); this module
keeps the historical public API —

- :func:`simulate_online` — generic sort-per-event path for any policy,
- :func:`simulate_online_ranked` — sort-free incremental-rank fast path for
  rank-space policies (heSRPT/EQUI/SRPT),
- :func:`simulate_online_superstep` — the closed-form arrival-superstep
  path (``core/superstep.py``): one scan step per arrival instead of per
  event, zero for batches,
- :func:`simulate_online_quantized` — whole-chips allocation (the
  ``ClusterScheduler`` integer regime) in the same scan,
- :func:`load_sweep` / :func:`load_sweep_raw` — seeds × loads sweeps for
  any registered scenario (Poisson, bursty MAP, estimation noise, ...; see
  ``core/scenarios.py``), thin specs over the sweep subsystem
  (``core/sweeps.py``: chunked/sharded executors, ``SweepResult``
  artifacts),

— and converts engine trajectories into per-job flow times and slowdowns
(:class:`OnlineSimResult`).  Arrival processes and size distributions come
from the scenario registry; ``poisson_arrivals`` & co are re-exported here
for compatibility.
"""

from __future__ import annotations

from collections.abc import Sequence
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import engine
from repro.core.flowtime import speedup
from repro.core.policies import Policy
from repro.core.scenarios import (  # noqa: F401  (re-exported public API)
    Scenario,
    deterministic_arrivals,
    make_scenario,
    pareto_sizes,
    poisson_arrivals,
)


class OnlineSimResult(NamedTuple):
    completion_times: jax.Array  # [M] absolute departure time of each job
    flow_times: jax.Array  # [M] completion - arrival, per job
    slowdowns: jax.Array  # [M] flow / (x0 / s(N)): 1.0 == ran alone at full N
    total_flowtime: jax.Array  # scalar
    mean_flowtime: jax.Array  # scalar
    mean_slowdown: jax.Array  # scalar
    makespan: jax.Array  # scalar, last departure time


@jax.named_scope("engine.reduce")
def _finalize(x0, arrival_times, times, p, n_servers) -> OnlineSimResult:
    """Per-job flow times / slowdowns from completion times (input order)."""
    flows = times - arrival_times
    alone = x0 / speedup(jnp.asarray(n_servers, x0.dtype), p)
    slow = flows / alone
    return OnlineSimResult(
        completion_times=times,
        flow_times=flows,
        slowdowns=slow,
        total_flowtime=jnp.sum(flows),
        mean_flowtime=jnp.mean(flows),
        mean_slowdown=jnp.mean(slow),
        makespan=jnp.max(times),
    )


def simulate_online(
    x0: jax.Array,
    arrival_times: jax.Array,
    p: jax.Array,
    n_servers: jax.Array,
    policy: Policy,
    *,
    rel_tol: float = 1e-9,
    horizon: int | None = None,
) -> OnlineSimResult:
    """Run ``policy`` online over an arrival stream to completion.

    ``x0[i]`` is the size of job ``i`` and ``arrival_times[i]`` its arrival
    epoch (any order; ties allowed — simultaneous arrivals are admitted in
    one event step).  The policy sees only jobs that have arrived and not
    yet finished; it is re-evaluated at every arrival and departure, which
    is the paper's §4.3 heuristic made exact for the fluid model.

    ``horizon`` bounds the scan length; the default ``2M`` (M arrivals + M
    departures, each step processing at least one event) is always enough.
    Jobs that never depart within the horizon report ``inf`` times.
    """
    x0 = jnp.asarray(x0)
    dtype = jnp.result_type(x0.dtype, jnp.float32)
    x0 = x0.astype(dtype)
    arrival_times = jnp.asarray(arrival_times).astype(dtype)
    res = engine.run(
        x0,
        arrival_times,
        p,
        engine.continuous_rule(policy, n_servers, dtype=dtype),
        horizon=horizon,
        rel_tol=rel_tol,
    )
    return _finalize(x0, arrival_times, res.completion_times, p, n_servers)


def simulate_online_ranked(
    x0: jax.Array,
    arrival_times: jax.Array,
    p: jax.Array,
    n_servers: jax.Array,
    rank_policy,
    *,
    horizon: int | None = None,
) -> OnlineSimResult:
    """Sort-free fast path of ``simulate_online`` for rank-space policies.

    See ``engine.run_ranked`` for the invariants this exploits (carried
    descending-size ranks instead of a per-event sort — ~20x the generic
    path at M=1000) and for tie-handling semantics.
    """
    x0 = jnp.asarray(x0)
    dtype = jnp.result_type(x0.dtype, jnp.float32)
    x0 = x0.astype(dtype)
    arrival_times = jnp.asarray(arrival_times).astype(dtype)
    times = engine.run_ranked(
        x0, arrival_times, p, n_servers, rank_policy, horizon=horizon
    )
    return _finalize(x0, arrival_times, times, p, n_servers)


def simulate_online_superstep(
    x0: jax.Array,
    arrival_times: jax.Array,
    p: jax.Array,
    n_servers: jax.Array,
    policy: str = "hesrpt",
    *,
    weights: jax.Array | None = None,
    pre_arrived: bool = False,
    horizon: int | None = None,
    p_drift=None,
) -> OnlineSimResult:
    """Closed-form superstep fast path of ``simulate_online``.

    One scan step per arrival (plus one per drift boundary) instead of one
    per event, and zero steps for ``pre_arrived`` batches — every departure
    inside an inter-arrival gap is computed analytically from the Thm-3/8
    bracket geometry.  ``policy`` is a name from
    ``core.superstep.SUPERSTEP_POLICIES`` (heSRPT/EQUI/SRPT and the
    cumulative-weight ``weighted_hesrpt``, which reads per-job
    ``weights``).  See ``core/superstep.py`` for the supported-config
    decision table; everything else raises at trace time and takes
    :func:`simulate_online` / :func:`simulate_scenario`.
    """
    from repro.core.superstep import run_superstep

    x0 = jnp.asarray(x0)
    dtype = jnp.result_type(x0.dtype, jnp.float32)
    x0 = x0.astype(dtype)
    arrival_times = jnp.asarray(arrival_times).astype(dtype)
    res = run_superstep(
        x0, arrival_times, p, n_servers, policy, weights=weights,
        pre_arrived=pre_arrived, horizon=horizon, p_drift=p_drift,
    )
    return _finalize(x0, arrival_times, res.completion_times, p, n_servers)


def simulate_online_quantized(
    x0: jax.Array,
    arrival_times: jax.Array,
    p: jax.Array,
    n_chips: int,
    policy: Policy,
    *,
    min_chips: int = 1,
    rel_tol: float = 1e-9,
    horizon: int | None = None,
    record: bool = False,
    fused: bool = False,
):
    """Online simulation with whole-chip allocations (integer regime).

    Each event re-runs ``policy`` and rounds ``theta * n_chips`` to integer
    chips by largest-remainder apportionment with a ``min_chips`` floor —
    bit-for-bit the ``ClusterScheduler`` decision epoch, but inside the
    engine's scan so thousands of seeds × loads sweep in one device call
    (see ``benchmarks/quantized.py``).  With ``record=True`` returns
    ``(OnlineSimResult, EngineResult)`` where the engine trace carries the
    per-event chips/time/sizes trajectory (arrival-sorted job order).
    ``fused=True`` takes the ``kernels/alloc.py`` fused allocate (heSRPT
    only; chip-exact vs the unfused rule).
    """
    x0 = jnp.asarray(x0)
    dtype = jnp.result_type(x0.dtype, jnp.float32)
    x0 = x0.astype(dtype)
    arrival_times = jnp.asarray(arrival_times).astype(dtype)
    res = engine.run(
        x0,
        arrival_times,
        p,
        engine.quantized_rule(policy, n_chips, min_chips=min_chips, dtype=dtype),
        horizon=horizon,
        rel_tol=rel_tol,
        record=record,
        fused=fused,
    )
    out = _finalize(x0, arrival_times, res.completion_times, p, n_chips)
    return (out, res) if record else out


def simulate_scenario(
    scn: Scenario,
    p,
    n_servers,
    policy: Policy,
    *,
    n_chips: int | None = None,
    min_chips: int = 1,
    rel_tol: float = 1e-9,
    horizon: int | None = None,
    fused: bool = False,
    telemetry=None,
) -> OnlineSimResult:
    """Run one drawn :class:`Scenario` through the engine.

    Estimation noise (``scn.size_factors``/``scn.p_hat``) reaches only the
    allocation rule; the dynamics use the true sizes and exponent.  Pass
    ``n_chips`` for the quantized (whole-chips) regime, else the
    continuously-divisible system with ``n_servers`` is simulated.

    Multi-class scenarios (``scn.p_job`` set) run with each job's true
    class exponent in the *physics* while the policy keeps seeing the
    scalar ``p`` (or ``scn.p_hat``) — i.e. this wrapper is the class-BLIND
    baseline; class-aware policies live in ``core/multiclass.py``.

    Drift scenarios (``scn.p_drift`` set) make the true exponent
    piecewise-constant in time; the policy then sees the *current* true
    regime (the oracle arm) unless ``scn.p_hat`` pins what it believes
    (the stale arm).  The arm that has to *earn* its estimate —
    allocating with an online p-hat fit from observed throughput — is
    ``estimation.simulate_scenario_estimated``.

    ``fused=True`` runs the engine on the ``kernels/alloc.py`` fused
    allocate (heSRPT only — other policies raise): fewer sorts per event
    on CPU, the Pallas kernel on TPU, chip-exact either way.

    ``telemetry`` takes a probe (``core/telemetry.py``); the return value
    is then ``(OnlineSimResult, TelemetryResult)``.  The trajectory is
    bit-for-bit the probe-free run either way.
    """
    x0 = jnp.asarray(scn.x0)
    dtype = jnp.result_type(x0.dtype, jnp.float32)
    x0 = x0.astype(dtype)
    arrival_times = jnp.asarray(scn.arrival_times).astype(dtype)
    order = jnp.argsort(arrival_times)
    factors = scn.size_factors
    if factors is not None:
        # The engine scans jobs in arrival order; permute to match.
        factors = jnp.asarray(factors, dtype)[order]
    p_phys = p
    p_hat = scn.p_hat
    if scn.p_job is not None:
        p_phys = jnp.asarray(scn.p_job, dtype)
        if p_hat is None:
            p_hat = p  # the class-blind policy still assumes the scalar p
    if p_hat is not None and jnp.ndim(p_hat) >= 1:
        # A per-job p_hat vector (multi-class sigma_p noise) cannot be fed
        # to the single-class policies — their rank brackets only telescope
        # to sum(theta)=1 for ONE exponent.  The class-blind scheduler this
        # wrapper models holds a single estimate anyway: the mean of its
        # per-job noisy estimates.  (Class-aware per-job p_hat handling
        # lives in core/multiclass.py, whose policies renormalize.)
        p_hat = jnp.mean(jnp.asarray(p_hat, dtype))
    if n_chips is not None:
        rule = engine.quantized_rule(
            policy, n_chips, min_chips=min_chips, dtype=dtype,
            size_factors=factors, p_hat=p_hat,
        )
        n_alone = n_chips
    else:
        rule = engine.continuous_rule(
            policy, n_servers, dtype=dtype,
            size_factors=factors, p_hat=p_hat,
        )
        n_alone = n_servers
    res = engine.run(
        x0, arrival_times, p_phys, rule, horizon=horizon, rel_tol=rel_tol,
        p_drift=scn.p_drift, fused=fused, telemetry=telemetry,
    )
    out = _finalize(x0, arrival_times, res.completion_times, p_phys, n_alone)
    return (out, res.telemetry) if telemetry is not None else out


def simulate_stream(
    scn: Scenario,
    p,
    n_servers,
    policy: Policy,
    *,
    n_slots: int,
    window=None,
    n_chips: int | None = None,
    min_chips: int = 1,
    rel_tol: float = 1e-9,
    horizon: int | None = None,
    record_times: bool = False,
    fused: bool = False,
    telemetry=None,
) -> engine.StreamResult:
    """Run one drawn :class:`Scenario` through the bounded-slot engine.

    The streaming counterpart of :func:`simulate_scenario`: the same
    regime split (``n_chips`` = whole chips, else the continuous system
    on ``n_servers``), but the event scan carries ``[n_slots]`` recycled
    slots instead of ``[n_jobs]`` state, and the read-out is the
    stationary-window :class:`~repro.core.engine.StreamResult` (windowed
    mean flow/slowdown, occupancy, blocked-admission counters) rather
    than per-job arrays.  Scenarios carrying per-job tape state —
    estimation noise, classes, drift — raise in
    :func:`~repro.core.scenarios.stream_tape`; they stay on the
    finite-tape path.
    """
    from repro.core.scenarios import stream_tape

    x0, arrival_times = stream_tape(scn)
    dtype = jnp.result_type(jnp.asarray(x0).dtype, jnp.float32)
    if n_chips is not None:
        rule = engine.quantized_rule(
            policy, n_chips, min_chips=min_chips, dtype=dtype
        )
        n_alone = n_chips
    else:
        rule = engine.continuous_rule(policy, n_servers, dtype=dtype)
        n_alone = n_servers
    return engine.run_stream(
        x0, arrival_times, p, rule, n_slots=n_slots, window=window,
        n_alone=n_alone, horizon=horizon, rel_tol=rel_tol,
        record_times=record_times, fused=fused, telemetry=telemetry,
    )


# --------------------------------------------------------------- load sweeps
def load_sweep(
    policies: Sequence[str],
    rates: Sequence[float],
    *,
    n_jobs: int = 1000,
    n_seeds: int = 100,
    p: float = 0.5,
    n_servers: float = 256.0,
    size_alpha: float = 1.5,
    seed: int = 0,
    metric: str = "mean_flowtime",
    scenario: str = "poisson",
    scenario_kw: dict | None = None,
    n_chips: int | None = None,
    min_chips: int = 1,
    chunk_seeds: int | None = None,
    max_jobs_in_flight: int | None = None,
    shard: bool = False,
) -> dict:
    """Sweep arrival rates × seeds × policies in one device call per policy.

    Seeds are shared across rates and policies (paired comparison), so
    "heSRPT beats EQUI at every load" is tested on identical sample paths.
    ``scenario`` selects the workload generator from the registry
    (``core/scenarios.py``); ``n_chips`` switches to the quantized
    whole-chips engine.  Returns ``{rate: {policy: mean-over-seeds of
    `metric`}}``.  The execution-scale knobs (seed chunking, device
    sharding) pass through to ``core/sweeps.py``.
    """
    per_seed = load_sweep_raw(
        policies, rates, n_jobs=n_jobs, n_seeds=n_seeds, p=p,
        n_servers=n_servers, size_alpha=size_alpha, seed=seed, metric=metric,
        scenario=scenario, scenario_kw=scenario_kw, n_chips=n_chips,
        min_chips=min_chips, chunk_seeds=chunk_seeds,
        max_jobs_in_flight=max_jobs_in_flight, shard=shard,
    )
    out = {}
    for ri, rate in enumerate(rates):
        out[float(rate)] = {
            name: float(jnp.mean(per_seed[name][ri])) for name in policies
        }
    return out


def load_sweep_raw(
    policies: Sequence[str],
    rates: Sequence[float],
    *,
    n_jobs: int = 1000,
    n_seeds: int = 100,
    p: float = 0.5,
    n_servers: float = 256.0,
    size_alpha: float = 1.5,
    seed: int = 0,
    metric: str = "mean_flowtime",
    scenario: str = "poisson",
    scenario_kw: dict | None = None,
    n_chips: int | None = None,
    min_chips: int = 1,
    chunk_seeds: int | None = None,
    max_jobs_in_flight: int | None = None,
    shard: bool = False,
) -> dict:
    """Like ``load_sweep`` but returns the full ``[n_rates, n_seeds]`` array
    of per-seed metrics for each policy (for CIs, paired tests, plotting).

    Since the sweep-subsystem refactor this is a thin spec over
    ``core/sweeps.py`` (golden-pinned bit-for-bit against the historical
    jit+vmap path), which is also where the scale knobs live:
    ``chunk_seeds``/``max_jobs_in_flight`` bound memory via seed-chunked
    ``lax.map`` execution, ``shard=True`` splits seeds across devices.
    """
    from repro.core.sweeps import Sweep, run_sweep

    spec = Sweep.create(
        policies, rates, scenario=scenario, scenario_kw=scenario_kw,
        n_jobs=n_jobs, n_seeds=n_seeds, seed=seed, p=p, n_servers=n_servers,
        size_alpha=size_alpha, n_chips=n_chips, min_chips=min_chips,
        metrics=(metric,),
    )
    res = run_sweep(spec, chunk_seeds=chunk_seeds,
                    max_jobs_in_flight=max_jobs_in_flight, shard=shard)
    return {name: res.stats[name][metric] for name in spec.policies}
