"""One scan-based allocation engine behind every simulator in the repo.

Theorem 3 of the paper proves the optimal allocation is constant between
decision epochs, so *every* fluid trajectory this repo simulates — batch
(all jobs at t=0), online arrival streams, and the integer-chips cluster
regime — is the same loop: query an allocation rule at an event, advance
every job linearly, repeat.  This module is that loop, written once as a
single ``jax.lax.scan`` and parameterized along two axes:

- **Allocation rule** (:class:`StatefulRule`): a triple ``(init, observe,
  allocate)`` whose state threads through the event scan's carry.
  ``allocate`` maps ``(state, x_active, p)`` to ``(alloc, rate)`` per job;
  ``observe`` folds the epoch's realized :class:`Observation` (allocation,
  throughput, epoch length) back into the state — which is what lets
  *online estimation* (``core/estimation.py`` fits the speedup exponent
  p̂ from observed throughput) run jit-safe inside the scan instead of on
  a per-event Python loop.  A plain callable ``(x_active, p) -> (alloc,
  rate)`` is accepted everywhere and wrapped by :func:`as_stateful` into
  the trivial stateless instance (empty state, identity ``observe``) —
  with trivial state the scan is bit-for-bit the pre-stateful engine.
  The speedup exponent may be a scalar (the paper) or a per-job vector
  (multi-class workloads, ``core/multiclass.py``); quantized rules can
  additionally snap chip counts to power-of-two ICI slices
  (:func:`snap_to_slices_jax`).

  * :func:`continuous_rule` — the paper's continuously-divisible system:
    ``theta`` from any ``core/policies.py`` policy, rate ``s(theta_i N)``.
    Optional size-estimation noise (the scheduler acts on perturbed sizes
    ``x * size_factors`` and a perturbed exponent ``p_hat`` while the true
    dynamics use ``x`` and ``p``).
  * :func:`quantized_rule` — whole chips: ``theta`` is rounded to integer
    chip counts by :func:`quantize_allocation_jax`, the vectorized-jnp port
    of ``sched/quantize.py``'s largest-remainder apportionment with a
    min-chips floor (the NumPy version remains the oracle it is
    property-tested against).  Rate is ``s(chips_i) = chips_i ** p``.
  * :func:`run_ranked` — the sort-free rank-space fast path for policies in
    ``core.policies.RANK_POLICIES`` (heSRPT/EQUI/SRPT); it carries the
    descending-size ranks through the scan instead of re-sorting per event.

- **Scenario** (``core/scenarios.py``): where the jobs and arrival epochs
  come from — batch, trace/Poisson, bursty MAP on-off streams, size
  estimation noise — exposed through a small registry usable from the
  benchmarks.

``core/simulator.py`` (batch) and ``core/arrivals.py`` (online) are thin
wrappers over :func:`run`; ``sched/cluster.py`` delegates its fluid advance
and quantization here so integer-allocation sweeps run jit+vmap at
``load_sweep`` scale instead of one Python event at a time.

Everything is jit-able and vmap-able over seeds/loads/configs.
"""

from __future__ import annotations

from collections.abc import Callable
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.flowtime import speedup
from repro.core.policies import Policy, equi, hesrpt, knee, srpt
from repro.core.ranking import inv_rank

# (x_active, p) -> (alloc, rate); ``alloc`` is theta for continuous rules
# and integer chips for quantized rules, ``rate`` the per-job service rate.
# ``p`` may be a scalar (single class) or a per-job vector (multi-class, in
# the engine's arrival-sorted order — see :func:`run`).
AllocRule = Callable[[jax.Array, jax.Array], tuple[jax.Array, jax.Array]]


class Observation(NamedTuple):
    """What an allocation rule gets to see after each epoch.

    The fluid model's observable is exactly what a production scheduler
    measures between decision epochs: which allocation each job held
    (``alloc`` — theta for continuous rules, integer chips for quantized
    ones), the realized throughput (``rate`` = work done / wall time, the
    fluid service rate), and for how long (``dt``).  ``active`` marks the
    jobs that were present and unfinished during the epoch; rules must
    ignore inactive rows.
    """

    alloc: jax.Array  # [M] allocation held during the epoch
    rate: jax.Array  # [M] realized service rate (work per unit time)
    dt: jax.Array  # scalar epoch length (0 on no-op steps)
    active: jax.Array  # [M] bool, job arrived & unfinished this epoch


class ProbeEvent(NamedTuple):
    """What a telemetry probe (``core/telemetry.py``) sees at each event.

    A strict superset of :class:`Observation`: probes additionally read the
    epoch-start clock, the remaining sizes, the true exponent in effect
    (post-drift), and the allocation rule's carry state — which is how the
    p̂-error probe reaches an :class:`~repro.core.estimation.EstState`
    without the rule knowing it is being watched.  All per-job arrays are
    in the engine's arrival-sorted order.
    """

    t: jax.Array  # scalar epoch-start time
    dt: jax.Array  # scalar epoch length (0 on no-op steps)
    alloc: jax.Array  # [M] allocation held during the epoch
    rate: jax.Array  # [M] realized service rate
    active: jax.Array  # [M] bool, job arrived & unfinished this epoch
    x: jax.Array  # [M] remaining sizes at epoch start
    p: Any  # scalar or [M] true exponent in effect this epoch
    rule_state: Any  # the allocation rule's carry state at epoch start


class StatefulRule(NamedTuple):
    """An allocation rule with scan-carried state: ``(init, observe,
    allocate)``.

    ``init()`` builds the state pytree; ``allocate(state, x_active, p)``
    returns ``(alloc, rate)`` for the epoch; ``observe(state, obs)`` folds
    the epoch's :class:`Observation` back into the state.  The stateless
    rules (:func:`continuous_rule`, :func:`quantized_rule`) are the trivial
    instances via :func:`as_stateful`; ``core/estimation.py`` builds the
    estimating instances (online p̂ from observed throughput).
    """

    init: Callable[[], Any]
    observe: Callable[[Any, Observation], Any]
    allocate: Callable[[Any, jax.Array, jax.Array], tuple[jax.Array, jax.Array]]


def as_stateful(rule: AllocRule | StatefulRule) -> StatefulRule:
    """Wrap a plain ``(x_active, p) -> (alloc, rate)`` callable as the
    trivial :class:`StatefulRule` (empty state, identity ``observe``) —
    the wrapped scan runs the exact same ops, so stateless trajectories
    are bit-for-bit unchanged.  Already-stateful rules pass through."""
    if isinstance(rule, StatefulRule):
        return rule
    return StatefulRule(
        init=lambda: (),
        observe=lambda state, obs: state,
        allocate=lambda state, x_act, p: rule(x_act, p),
    )


class PDrift(NamedTuple):
    """Piecewise-constant true speedup exponent: regime changes mid-run.

    ``times`` are the ``D`` regime-change epochs (ascending); ``values``
    holds the ``D + 1`` regimes — scalars (shape ``[D+1]``) or per-job
    rows (shape ``[D+1, M]``, input job order; :func:`run` permutes the
    columns into arrival-sorted order).  Between ``times[r-1]`` and
    ``times[r]`` the *physics* (and the ``p`` an allocation rule is shown)
    use ``values[r]`` — e.g. a job set turning communication-bound has its
    effective ``p`` drop.  A stale scheduler keeps allocating with the old
    exponent; an online estimator (``core/estimation.py``) re-fits it from
    observed throughput.  ``core/scenarios.py``'s drift scenarios draw
    these.
    """

    times: jax.Array  # [D] regime-change epochs, ascending
    values: jax.Array  # [D+1] or [D+1, M] exponent per regime

# Power-of-two ICI-friendly slice sizes shared with ``sched.quantize``'s
# ``snap_to_slices`` NumPy oracle (single source of truth lives here so the
# engine's scan and the per-event cluster path can never disagree).
DEFAULT_SLICES = (1, 2, 4, 8, 16, 32, 64, 128, 256)


class EngineTrace(NamedTuple):
    """Per-event trajectory (in arrival-sorted job order, see ``order``)."""

    alloc: jax.Array  # [E, M] allocation chosen at each event (theta / chips)
    times: jax.Array  # [E] event start times
    sizes: jax.Array  # [E, M] remaining sizes at each event start


class EngineResult(NamedTuple):
    completion_times: jax.Array  # [M] absolute departure times, input order
    x_final: jax.Array  # [M] remaining sizes at horizon, arrival-sorted order
    order: jax.Array  # [M] arrival-sorted permutation used internally
    trace: EngineTrace | None = None  # populated when ``record=True``
    telemetry: Any = None  # probe read-out when ``run(telemetry=)`` is set


# ----------------------------------------------------------- allocation rules
def finish_alloc(
    theta: jax.Array,
    p,
    *,
    n_alloc,
    n_chips: int | None,
    min_chips: int = 1,
    snap_slices: bool = False,
    slices: tuple[int, ...] = DEFAULT_SLICES,
    dtype,
):
    """The ONE ``theta -> (alloc, rate)`` tail every allocation rule shares.

    Continuous regime (``n_chips`` is None): the allocation is ``theta``
    itself and the rate is ``s(theta * n_alloc)``.  Whole-chips regime:
    largest-remainder rounding (:func:`quantize_allocation_jax`) with a
    ``min_chips`` floor, optionally snapped to power-of-two ICI slices
    (:func:`snap_to_slices_jax`), rate ``s(chips)``.  Centralized so the
    stateless rules here, :func:`knee_rule`, the class-aware rules
    (``core/multiclass.py``) and the estimating rules
    (``core/estimation.py``) cannot desynchronize on quantization order or
    the chip unit.
    """
    theta = theta.astype(dtype)
    if n_chips is None:
        return theta, speedup(theta * n_alloc, p)
    chips = quantize_allocation_jax(theta, n_chips, min_chips=min_chips)
    if snap_slices:
        chips = snap_to_slices_jax(chips, n_chips, slices=slices)
    return chips, speedup(chips.astype(dtype), p)


def continuous_rule(
    policy: Policy,
    n_servers,
    *,
    dtype,
    size_factors: jax.Array | None = None,
    p_hat=None,
) -> AllocRule:
    """The paper's continuously-divisible allocation: ``rate = s(theta N)``.

    ``size_factors``/``p_hat`` inject estimation error: the *policy* sees
    ``x * size_factors`` and ``p_hat`` while the *dynamics* keep the true
    ``x`` and ``p`` — the scheduler mis-ranks jobs, the physics don't lie.
    NOTE: ``size_factors`` must be in arrival-sorted job order (the order
    the engine's scan runs in).

    For the heSRPT policy the returned rule carries a ``fused_variant``
    attribute — the ``kernels/alloc.py`` fused path :func:`run` swaps in
    under ``fused=True`` (bit-for-bit on CPU, on-chip on TPU).  For the
    noise-free rank family (heSRPT/EQUI/SRPT) it also carries a
    ``superstep_spec`` — the closed-form arrival-superstep path
    (``core/superstep.py``) :func:`run` dispatches to under
    ``superstep=True``.
    """

    def rule(x_act, p):
        x_seen = x_act if size_factors is None else x_act * size_factors
        p_seen = p if p_hat is None else p_hat
        return finish_alloc(
            policy(x_seen, p_seen), p, n_alloc=n_servers, n_chips=None,
            dtype=dtype,
        )

    if size_factors is None and p_hat is None:
        # Estimation noise desynchronizes the policy's ranking from the
        # physics, which breaks the closed form's departure-order premise.
        for fn, sname in ((hesrpt, "hesrpt"), (equi, "equi"), (srpt, "srpt")):
            if policy is fn:
                setattr(rule, "superstep_spec", (sname, n_servers))  # noqa: B010
                break
    if policy is hesrpt:
        from repro.kernels.alloc import hesrpt_theta_fused

        def fused(x_act, p):
            x_seen = x_act if size_factors is None else x_act * size_factors
            p_seen = p if p_hat is None else p_hat
            theta = hesrpt_theta_fused(x_seen, p_seen).astype(dtype)
            return theta, speedup(theta * n_servers, p)

        setattr(rule, "fused_variant", fused)  # noqa: B010
    return rule


def quantized_rule(
    policy: Policy,
    n_chips: int,
    *,
    min_chips: int = 1,
    dtype,
    size_factors: jax.Array | None = None,
    p_hat=None,
    snap_slices: bool = False,
    slices: tuple[int, ...] = DEFAULT_SLICES,
) -> AllocRule:
    """Whole-chips allocation: largest-remainder rounding of ``theta * N``.

    This is ``sched/cluster.py``'s decision epoch — policy then quantize —
    as a pure scan step, so the integer-allocation regime can be swept
    jit+vmap instead of one Python event at a time.  ``snap_slices=True``
    additionally restricts every job to ICI-friendly power-of-two slice
    sizes (:func:`snap_to_slices_jax`, exact vs the NumPy
    ``sched.quantize.snap_to_slices`` oracle), making the slice-snapped
    regime sweepable too.

    For the heSRPT policy the returned rule carries a ``fused_variant``
    attribute: the ``kernels/alloc.py`` fused rank -> theta -> chips pass
    (2 sorts per event instead of 3 on CPU, 0 on TPU), chip-exact vs this
    rule, selected by :func:`run`'s ``fused=True``.
    """

    def rule(x_act, p):
        x_seen = x_act if size_factors is None else x_act * size_factors
        p_seen = p if p_hat is None else p_hat
        return finish_alloc(
            policy(x_seen, p_seen), p, n_alloc=n_chips, n_chips=n_chips,
            min_chips=min_chips, snap_slices=snap_slices, slices=slices,
            dtype=dtype,
        )

    if policy is hesrpt:
        from repro.kernels.alloc import hesrpt_alloc_fused

        def fused(x_act, p):
            x_seen = x_act if size_factors is None else x_act * size_factors
            p_seen = p if p_hat is None else p_hat
            _theta, chips = hesrpt_alloc_fused(
                x_seen, p_seen, n_chips, min_chips=min_chips
            )
            if snap_slices:
                chips = snap_to_slices_jax(chips, n_chips, slices=slices)
            return chips, speedup(chips.astype(dtype), p)

        setattr(rule, "fused_variant", fused)  # noqa: B010
    return rule


def knee_rule(
    n_servers,
    *,
    n_chips: int | None = None,
    min_chips: int = 1,
    snap_slices: bool = False,
    dtype,
) -> StatefulRule:
    """KNEE with its per-epoch ``alpha`` refit, as an engine rule.

    The per-event ``ClusterScheduler`` loop re-derives KNEE's knob at every
    decision epoch — ``alpha = median(remaining work of active jobs) * p /
    N`` — which made KNEE the last policy stuck on the Python-only path:
    ``make_policy("knee")`` closes over a *static* alpha.  The refit is a
    pure function of the epoch's active set, so inside the scan it is simply
    recomputed by ``allocate`` each step; the returned
    :class:`StatefulRule` therefore carries the trivial (empty) state — the
    statefulness lives in the per-epoch recomputation, not the carry.  The
    masked median matches ``np.median`` over the active subset exactly
    (average of the two middle order statistics), so the per-event Python
    loop remains the bit-for-bit cross-check oracle.

    Continuous when ``n_chips`` is None, else whole chips (largest-remainder
    + min-chips floor, optionally slice-snapped) — the same regime split as
    :func:`continuous_rule` / :func:`quantized_rule`.
    """
    n_alloc = float(n_chips) if n_chips is not None else float(n_servers)

    def rule(x_act, p):
        active = x_act > 0
        m = jnp.maximum(jnp.sum(active, dtype=jnp.int32), 1)
        v = jnp.sort(jnp.where(active, x_act, jnp.inf))
        med = 0.5 * (v[(m - 1) // 2] + v[m // 2])
        alpha = med * p / n_alloc
        theta = knee(x_act, p, jnp.asarray(n_alloc, dtype), alpha)
        return finish_alloc(
            theta, p, n_alloc=n_alloc, n_chips=n_chips, min_chips=min_chips,
            snap_slices=snap_slices, dtype=dtype,
        )

    return as_stateful(rule)


def _resolve_fused(rule, fused: bool):
    """Swap in the rule's kernel-fused allocate when ``fused=True``."""
    if not fused:
        return rule
    fused_rule = getattr(rule, "fused_variant", None)
    if fused_rule is None:
        raise ValueError(
            "fused=True needs a rule with a fused_variant — built by "
            "continuous_rule/quantized_rule over the heSRPT policy"
        )
    return fused_rule


def _resolve_superstep(rule, *, fused, record, telemetry, p, p_drift):
    """Trace-time gate for ``run(superstep=True)``.

    Returns the rule's ``(policy_name, n_servers)`` superstep spec, or
    raises ``ValueError`` for every configuration whose physics the
    closed form cannot represent — those take the generic per-event scan
    (just drop ``superstep=True``; see ``core/superstep.py`` for the
    decision table).
    """
    fallback = " — this configuration takes the generic per-event scan"
    spec = getattr(rule, "superstep_spec", None)
    if spec is None:
        raise ValueError(
            "superstep=True needs a rule with a superstep_spec — built by "
            "continuous_rule over heSRPT/EQUI/SRPT without estimation "
            "noise (quantized and stateful/estimating rules have none)"
            + fallback
        )
    if fused:
        raise ValueError(
            "superstep=True already replaces the scan; fused= fuses the "
            "quantized per-event allocate" + fallback
        )
    if record:
        raise ValueError(
            "record=True needs the per-event trajectory" + fallback
        )
    if telemetry is not None:
        raise ValueError(
            "telemetry probes ride the per-event scan" + fallback
        )
    if jnp.ndim(p) >= 1:
        raise ValueError(
            "superstep=True needs a scalar p (per-job exponents break the "
            "rank-order departure invariant)" + fallback
        )
    if p_drift is not None and jnp.asarray(p_drift.values).ndim != 1:
        raise ValueError(
            "superstep=True supports scalar drift regimes only" + fallback
        )
    return spec


# ------------------------------------------------------------ the event scan
def run(
    x0: jax.Array,
    arrival_times: jax.Array,
    p,
    rule: AllocRule | StatefulRule,
    *,
    pre_arrived: bool = False,
    horizon: int | None = None,
    rel_tol: float = 1e-9,
    t0=0.0,
    record: bool = False,
    p_drift: PDrift | None = None,
    fused: bool = False,
    superstep: bool = False,
    telemetry: Any = None,
) -> EngineResult:
    """Run the event-driven fluid trajectory to completion in one scan.

    Each step advances to the next event (``min`` of next departure and next
    arrival), re-querying ``rule`` on the active set — the paper's Thm 3
    epoch structure, with arrivals as the §4.3 heuristic.  An M-job stream
    has at most ``2M`` events (``M`` with ``pre_arrived=True``, at least one
    job departing per step for work-conserving rules), which bounds the scan
    length; steps after the last event are no-ops.

    ``rule`` is a :class:`StatefulRule` or a plain ``(x_active, p) ->
    (alloc, rate)`` callable (wrapped via :func:`as_stateful`; bit-for-bit
    the stateless scan).  A stateful rule's state rides in the scan carry:
    each step calls ``allocate`` on the epoch-start state and ``observe``
    on the realized epoch, so estimators update once per event — the same
    observation schedule a per-event scheduler loop would produce.

    ``pre_arrived=True`` marks every job as already present (the batch
    case): ``arrival_times`` then only defines the job order and flow-time
    zero points.  Jobs that never depart within the horizon report ``inf``.
    ``record=True`` additionally returns the full per-event trajectory
    (allocations, event times, remaining sizes) in arrival-sorted order.

    ``p`` may be a scalar (the paper's single job class) or a per-job
    vector in *input* order (the multi-class case: each job carries its
    class's speedup exponent).  A vector ``p`` is permuted into the
    engine's arrival-sorted order alongside the sizes before it reaches
    ``rule`` — rule closures over per-job vectors (weights, noise factors)
    must be pre-sorted the same way by the caller.

    ``p_drift`` makes the *true* exponent piecewise-constant in time
    (:class:`PDrift`; it then supersedes ``p``): regime boundaries become
    events of their own — ``dt`` is clamped so no epoch straddles one, the
    next epoch re-queries the rule under the new exponent — which costs at
    most one extra scan step per boundary (the default horizon accounts
    for them).

    ``fused=True`` swaps in the rule's ``fused_variant`` — the
    ``kernels/alloc.py`` single-pass allocate attached by
    :func:`continuous_rule` / :func:`quantized_rule` for the heSRPT policy
    (chip-exact; see that module for the collapse) — and raises
    ``ValueError`` for rules without one.

    ``superstep=True`` dispatches to the closed-form arrival-superstep
    path (``core/superstep.py``): zero scan steps for ``pre_arrived``
    batches, one step per arrival/drift boundary online — for the rules
    that carry a ``superstep_spec`` (:func:`continuous_rule` over
    heSRPT/EQUI/SRPT, noise-free).  Everything else — quantized chips,
    stateful/estimating rules, per-job ``p``, per-job drift rows,
    ``record``, ``telemetry``, ``fused`` — raises at trace time and takes
    this generic per-event scan instead.  ``rel_tol`` is ignored there
    (the analytic trajectory has no float residue to clamp).

    ``telemetry`` takes a probe (``core/telemetry.py``: ``(init, step,
    finalize)``) whose state rides in the scan carry; each step sees the
    epoch's :class:`ProbeEvent` and the finalized read-out is returned on
    ``EngineResult.telemetry``.  The branch is resolved at trace time:
    with ``telemetry=None`` the compiled program is *exactly* the probe-
    free scan — trajectories stay bit-for-bit identical (tested against
    the golden pins).
    """
    if superstep:
        pol_name, n_srv = _resolve_superstep(
            rule, fused=fused, record=record, telemetry=telemetry, p=p,
            p_drift=p_drift,
        )
        from repro.core.superstep import run_superstep

        return run_superstep(
            x0, arrival_times, p, n_srv, pol_name,
            pre_arrived=pre_arrived, horizon=horizon, t0=t0,
            p_drift=p_drift,
        )
    rule = _resolve_fused(rule, fused)
    x0 = jnp.asarray(x0)
    M = x0.shape[0]
    n_drift = 0 if p_drift is None else p_drift.times.shape[0]
    E = ((M if pre_arrived else 2 * M) + n_drift) if horizon is None else horizon
    dtype = jnp.result_type(x0.dtype, jnp.float32)
    x0 = x0.astype(dtype)
    arrival_times = jnp.asarray(arrival_times).astype(dtype)
    tol = rel_tol * jnp.max(x0)

    # Event logic walks arrivals in time order; un-sort at the end.
    order = jnp.argsort(arrival_times)
    arr = arrival_times[order]
    xs = x0[order]
    if jnp.ndim(p) >= 1:  # per-job exponents travel with their jobs
        p = jnp.asarray(p)[order]
    if p_drift is not None:
        drift_t = jnp.asarray(p_drift.times).astype(dtype)
        drift_v = jnp.asarray(p_drift.values).astype(dtype)
        if drift_v.ndim == 2:  # per-job regime rows travel with their jobs
            drift_v = drift_v[:, order]
    idx = jnp.arange(M)
    i0 = jnp.asarray(M if pre_arrived else 0, jnp.int32)
    srule = as_stateful(rule)

    def body(carry, _):
        with jax.named_scope("engine.advance"):
            if telemetry is None:
                x, t, i, times, st = carry
            else:
                x, t, i, times, st, tel = carry
            active = (idx < i) & (x > 0)
            x_act = jnp.where(active, x, 0.0)
            if p_drift is None:
                p_now = p
                dt_drift = jnp.inf
                t_next_drift = jnp.inf
            else:
                r = jnp.searchsorted(drift_t, t, side="right")
                p_now = drift_v[r]
                n_d = drift_t.shape[0]
                t_next_drift = jnp.where(
                    r < n_d, drift_t[jnp.minimum(r, n_d - 1)], jnp.inf
                )
                dt_drift = jnp.maximum(t_next_drift - t, 0.0)
        with jax.named_scope("engine.allocate"):
            alloc, rate = srule.allocate(st, x_act, p_now)
        with jax.named_scope("engine.advance"):
            tt = jnp.where(active & (rate > 0), x / rate, jnp.inf)
            dt_dep = jnp.min(tt)  # inf when nothing is active
            t_next_arr = jnp.where(i < M, arr[jnp.minimum(i, M - 1)], jnp.inf)
            dt_arr = jnp.maximum(t_next_arr - t, 0.0)
            dt = jnp.minimum(jnp.minimum(dt_dep, dt_arr), dt_drift)
            any_event = jnp.isfinite(dt)
            dt = jnp.where(any_event, dt, 0.0)
            # Landing on an arrival pins t to the exact arrival time so the
            # searchsorted admission below cannot miss it to float rounding
            # (same for a drift boundary: the next epoch's regime lookup uses
            # side="right", so t == boundary already reads the new exponent).
            admit = any_event & (dt_arr <= jnp.minimum(dt_dep, dt_drift))
            take_dep = any_event & (dt_dep <= jnp.minimum(dt_arr, dt_drift))
            take_drift = any_event & ~admit & ~take_dep
            t_new = jnp.where(
                admit, t_next_arr, jnp.where(take_drift, t_next_drift, t + dt)
            )
            x_new = jnp.where(active, x - dt * rate, x)
            # The argmin job departs BY CONSTRUCTION when the departure is the
            # next event; float residue (~eps*x) must not be allowed to keep it.
            departing = (idx == jnp.argmin(tt)) & active & take_dep
            x_new = jnp.where(departing | (active & (x_new <= tol)), 0.0, x_new)
            newly_done = active & (x_new == 0.0)
            times = jnp.where(newly_done, t_new, times)
            i_new = jnp.searchsorted(arr, t_new, side="right").astype(i.dtype)
            i_new = jnp.maximum(i, i_new)  # monotone even on no-op steps
            st_new = srule.observe(
                st, Observation(alloc=alloc, rate=rate, dt=dt, active=active)
            )
            out = (alloc, t, x) if record else None
            if telemetry is None:
                return (x_new, t_new, i_new, times, st_new), out
            tel_new, tel_out = telemetry.step(
                tel,
                ProbeEvent(
                    t=t, dt=dt, alloc=alloc, rate=rate, active=active, x=x,
                    p=p_now, rule_state=st,
                ),
            )
            return (x_new, t_new, i_new, times, st_new, tel_new), (out, tel_out)

    init = (xs, jnp.asarray(t0, dtype), i0, jnp.zeros(M, dtype), srule.init())
    if telemetry is not None:
        init = (*init, telemetry.init())
    carry_fin, ys = jax.lax.scan(body, init, None, length=E)
    x_fin, _, _, times = carry_fin[:4]
    tel_result = None
    if telemetry is not None:
        ys, tel_ys = ys
        tel_result = telemetry.finalize(carry_fin[5], tel_ys)
    # Safety: any job that never departed (pathological rule) -> inf.
    times = jnp.where(x_fin > 0, jnp.inf, times)
    times_in = jnp.zeros(M, dtype).at[order].set(times)  # back to input order
    trace = EngineTrace(alloc=ys[0], times=ys[1], sizes=ys[2]) if record else None
    return EngineResult(
        completion_times=times_in, x_final=x_fin, order=order, trace=trace,
        telemetry=tel_result,
    )


def run_ranked(
    x0: jax.Array,
    arrival_times: jax.Array,
    p,
    n_servers,
    rank_policy,
    *,
    horizon: int | None = None,
) -> jax.Array:
    """Sort-free fast path of :func:`run` for rank-space policies.

    ``rank_policy(ranks, m, p) -> theta`` must be a pure function of the
    descending-size ranks (Thm 6 size-invariance), with rates non-increasing
    in remaining size — true for heSRPT, EQUI and SRPT (see
    ``core.policies.RANK_POLICIES``).  Those two properties give two
    invariants this scan exploits:

    - the size order of active jobs never changes between events, so the
      rank vector can be *carried* and updated in O(M) per event (an arrival
      inserts one rank, a departure removes the highest) instead of
      re-sorted — XLA's per-step sort is what makes the generic path ~20x
      slower at M=1000;
    - the next departure is always the current-smallest active job (rank m),
      so no argmin over per-job finish times is needed.

    Admissions are one job per step, so the default ``2M`` horizon (M
    arrivals + M departures) is exact.  Agreement with the generic path is
    property-tested in tests/test_arrivals.py.

    Tie handling: jobs with *exactly* equal remaining sizes get distinct
    adjacent ranks (ties break by arrival order, as in
    ``size_ranks_desc``).  For SRPT this serves tied jobs in the opposite
    order to the generic path's ``argmin`` — per-job times permute within
    the tied group, while totals/means are exchange-invariant.  Ties are
    measure-zero for continuous size distributions.

    Returns the per-job completion times in input order (``inf`` if never
    departed).

    ``p`` must be a *scalar*: with per-job exponents (multi-class) the
    service rate is no longer monotone in remaining size, so neither
    carried invariant survives — multi-class streams take the generic
    :func:`run` path (or are statically dispatched back here when every
    class shares one exponent, see ``core/multiclass.py``).
    """
    x0 = jnp.asarray(x0)
    M = x0.shape[0]
    E = 2 * M if horizon is None else horizon
    dtype = jnp.result_type(x0.dtype, jnp.float32)
    x0 = x0.astype(dtype)
    arrival_times = jnp.asarray(arrival_times).astype(dtype)

    order = jnp.argsort(arrival_times)  # one sort total, not one per event
    arr = arrival_times[order]
    xs = x0[order]
    idx = jnp.arange(M)

    def body(carry, _):
        x, t, i, ranks, m, times = carry
        with jax.named_scope("engine.allocate"):
            theta = rank_policy(ranks, m, p, dtype=dtype)
            rate = speedup(theta * n_servers, p)
        with jax.named_scope("engine.advance"):
            # Next departure: the smallest active job, i.e. rank m, found by
            # argmax since ranks are unique with maximum m (0 when inactive).
            small = jnp.argmax(ranks)
            has_active = m > 0
            x_s = x[small]
            r_s = rate[small]
            dt_dep = jnp.where(has_active & (r_s > 0), x_s / r_s, jnp.inf)
            t_next_arr = jnp.where(i < M, arr[jnp.minimum(i, M - 1)], jnp.inf)
            dt_arr = jnp.maximum(t_next_arr - t, 0.0)
            dt = jnp.minimum(dt_dep, dt_arr)
            any_event = jnp.isfinite(dt)
            dt = jnp.where(any_event, dt, 0.0)
            admit = any_event & (dt_arr <= dt_dep)
            take_dep = any_event & (dt_dep <= dt_arr)
            t_new = jnp.where(admit, t_next_arr, t + dt)
            active = ranks > 0
            x_new = jnp.where(active, jnp.maximum(x - dt * rate, 0.0), x)
            # Departure: drop rank m; every other active rank stays valid.
            departing = (idx == small) & active & take_dep
            x_new = jnp.where(departing, 0.0, x_new)
            times = jnp.where(departing, t_new, times)
            ranks = jnp.where(departing, 0, ranks)
            m = m - jnp.where(take_dep & has_active, 1, 0)
            # Arrival: insert job i at its rank among the (post-departure)
            # active set; ties break by index, matching size_ranks_desc.
            i_c = jnp.minimum(i, M - 1)
            x_a = xs[i_c]
            still = ranks > 0
            ahead = still & ((x_new > x_a) | ((x_new == x_a) & (idx < i_c)))
            r_a = 1 + jnp.sum(ahead, dtype=jnp.int32)
            bumped = jnp.where(still & (ranks >= r_a), ranks + 1, ranks)
            inserted = bumped.at[i_c].set(r_a)
            ranks = jnp.where(admit, inserted, ranks)
            m = m + jnp.where(admit, 1, 0)
            i = i + jnp.where(admit, 1, 0)
            return (x_new, t_new, i, ranks, m, times), None

    init = (
        xs,
        jnp.zeros((), dtype),
        jnp.zeros((), jnp.int32),
        jnp.zeros(M, jnp.int32),
        jnp.zeros((), jnp.int32),
        jnp.zeros(M, dtype),
    )
    (x_fin, _, _, ranks_fin, _, times), _ = jax.lax.scan(
        body, init, None, length=E
    )
    times = jnp.where((x_fin > 0) | (ranks_fin > 0), jnp.inf, times)
    return jnp.zeros(M, dtype).at[order].set(times)


# ----------------------------------------------------- bounded-slot streaming
class StreamSource(NamedTuple):
    """Pull-based arrival stream for the bounded-slot engine.

    ``init()`` builds the carried stream state; ``peek(state)`` reads the
    next arrival's ``(time, size)`` without consuming it (``time = inf``
    once exhausted); ``advance(state)`` consumes it.  The peek/advance
    split is what lets :func:`run_stream` defer an arrival for any number
    of events while the slot pool is full and still admit it later — the
    recorded arrival time stays the stream's true one, so blocked wait
    counts toward flow time.
    """

    init: Callable[[], Any]
    peek: Callable[[Any], tuple[jax.Array, jax.Array]]
    advance: Callable[[Any], Any]


def tape_source(x0_sorted: jax.Array, arrivals_sorted: jax.Array) -> StreamSource:
    """A finite, arrival-sorted ``(sizes, times)`` tape as a StreamSource.

    State is the next tape index; :func:`run_stream`'s admission counter
    then equals the tape position, which is what lets it scatter
    completion times back to jobs (``record_times=True``).
    """
    x0_sorted = jnp.asarray(x0_sorted)
    arrivals_sorted = jnp.asarray(arrivals_sorted)
    T = x0_sorted.shape[0]

    def init():
        return jnp.zeros((), jnp.int32)

    def peek(i):
        j = jnp.minimum(i, T - 1)
        t_next = jnp.where(i < T, arrivals_sorted[j], jnp.inf)
        return t_next, x0_sorted[j]

    def advance(i):
        return i + 1

    return StreamSource(init=init, peek=peek, advance=advance)


def poisson_source(key: jax.Array, rate, *, size_alpha: float = 1.5, dtype) -> StreamSource:
    """A truly unbounded Poisson/Pareto arrival stream in O(1) state.

    State is ``(key, t_next, x_next)`` — one PRNG key plus the peeked
    arrival — so no tape is ever materialized: through
    :func:`run_stream_source` the whole simulation is O(n_slots) memory
    for any event budget.  Gaps are Exp(``rate``), sizes Pareto
    (``size_alpha``, minimum 1) — the same laws the ``poisson`` scenario
    samples, equal in distribution but not sample-path equal (the tape
    sampler draws one batch from two keys; this stream splits a fresh key
    per arrival).
    """

    def draw(k):
        k_next, k_gap, k_size = jax.random.split(k, 3)
        gap = jax.random.exponential(k_gap, dtype=dtype) / rate
        size = jax.random.pareto(k_size, size_alpha, dtype=dtype)
        return k_next, gap, size

    def init():
        k_next, gap, size = draw(key)
        return (k_next, jnp.asarray(gap, dtype), jnp.asarray(size, dtype))

    def peek(state):
        _, t_next, x_next = state
        return t_next, x_next

    def advance(state):
        k, t_next, _ = state
        k_next, gap, size = draw(k)
        return (k_next, t_next + gap, size)

    return StreamSource(init=init, peek=peek, advance=advance)


class StreamResult(NamedTuple):
    """Read-out of a bounded-slot streaming run.

    The ``w``-prefixed docs below mean the stationary window ``[lo, hi)``
    (``window=None`` = the whole stream): flow/slowdown aggregates count
    jobs that *arrived* inside the window and completed within the event
    budget, so near a window's trailing edge long jobs are right-censored
    exactly as a finite-horizon measurement would censor them — pick
    windows (and budgets) that let the tail drain when that matters.
    Slowdown compares against running alone on ``n_alone`` servers:
    ``flow / (size / s(n_alone))``.
    """

    mean_flow: jax.Array  # windowed mean flow time
    mean_slowdown: jax.Array  # windowed mean slowdown
    n_window: jax.Array  # completions counted into the window
    n_arrived_window: jax.Array  # admissions whose arrival fell in the window
    flow_sum: jax.Array  # windowed flow-time sum
    slow_sum: jax.Array  # windowed slowdown sum
    n_admitted: jax.Array  # arrivals admitted to a slot
    n_completed: jax.Array  # total departures
    blocked_steps: jax.Array  # events where a full pool deferred an arrival
    occupancy_max: jax.Array  # peak in-flight jobs (epoch-start census)
    t_final: jax.Array  # clock at the end of the scan
    x_final: jax.Array  # [n_slots] remaining sizes (0 = free slot)
    completion_times: jax.Array | None  # [n_jobs] input order (record_times)
    telemetry: Any  # TelemetryResult when a probe was attached


def _window_bounds(window, dtype):
    if window is None:
        return jnp.asarray(-jnp.inf, dtype), jnp.asarray(jnp.inf, dtype)
    lo, hi = window
    return jnp.asarray(lo, dtype), jnp.asarray(hi, dtype)


def _finalize_stream(acc, t_fin, x_fin, comp, tel, dtype) -> StreamResult:
    n_w = jnp.maximum(acc["w_count"], 1).astype(dtype)
    return StreamResult(
        mean_flow=acc["w_flow"] / n_w,
        mean_slowdown=acc["w_slow"] / n_w,
        n_window=acc["w_count"],
        n_arrived_window=acc["w_arrived"],
        flow_sum=acc["w_flow"],
        slow_sum=acc["w_slow"],
        n_admitted=acc["n_admitted"],
        n_completed=acc["n_completed"],
        blocked_steps=acc["blocked"],
        occupancy_max=acc["occ_max"],
        t_final=t_fin,
        x_final=x_fin,
        completion_times=comp,
        telemetry=tel,
    )


def _stream_scan(
    source: StreamSource, p, srule: StatefulRule, *, n_slots: int,
    n_events: int, w_lo, w_hi, alone_rate, tol, t0, dtype, n_times: int,
    telemetry,
):
    """The bounded-slot event scan shared by the tape and source runners.

    Carries only ``[n_slots]`` per-job state (remaining size, original
    size, arrival time, job id) plus O(1) scalars, so memory and per-event
    cost are flat in the number of jobs ever streamed.  Slot lifecycle:
    a slot is *free* iff its remaining size is 0; an admitted arrival
    claims the free slot with the smallest cyclic offset after a rotating
    ring pointer and a completion simply zeroes its slot.  With
    ``n_slots >= n_jobs`` the pointer never wraps, slot ``i`` is the
    ``i``-th arrival, and every per-step quantity equals :func:`run`'s —
    the bit-for-bit reduction the tests pin.  When the pool is full the
    next arrival is *deferred* (the arrival leg of the event race drops
    out) and admitted — at its true arrival time, so the wait counts
    toward flow — on a later event once a departure frees a slot.
    """
    S = int(n_slots)
    idx = jnp.arange(S)
    zi = jnp.zeros((), jnp.int32)
    acc0 = {
        "n_admitted": zi, "n_completed": zi, "w_count": zi,
        "w_arrived": zi, "blocked": zi, "occ_max": zi,
        "w_flow": jnp.zeros((), dtype), "w_slow": jnp.zeros((), dtype),
    }

    def body(carry, _):
        if telemetry is None:
            slots, t, ptr, src, st, acc, times = carry
        else:
            slots, t, ptr, src, st, acc, times, tel = carry
        x, sx0, sarr, sid = slots
        active = x > 0  # free slots hold exactly 0, like completed jobs
        x_act = jnp.where(active, x, 0.0)
        alloc, rate = srule.allocate(st, x_act, p)
        tt = jnp.where(active & (rate > 0), x / rate, jnp.inf)
        dt_dep = jnp.min(tt)
        t_next, x_next = source.peek(src)
        dt_arr = jnp.maximum(t_next - t, 0.0)
        free = ~active
        has_free = jnp.any(free)
        # A full pool defers the arrival: it drops out of the event race
        # until a departure frees a slot.
        eff_dt_arr = jnp.where(has_free, dt_arr, jnp.inf)
        dt = jnp.minimum(dt_dep, eff_dt_arr)
        any_event = jnp.isfinite(dt)
        dt = jnp.where(any_event, dt, 0.0)
        admit = any_event & has_free & (dt_arr <= dt_dep)
        take_dep = any_event & (dt_dep <= eff_dt_arr)
        blocked_now = jnp.isfinite(dt_dep) & ~has_free & (dt_arr < dt_dep)
        # On-time admissions pin t to the exact arrival time (as in `run`);
        # a deferred arrival is admitted at the later clock t.
        t_new = jnp.where(admit, jnp.maximum(t_next, t), t + dt)
        x_new = jnp.where(active, x - dt * rate, x)
        departing = (idx == jnp.argmin(tt)) & active & take_dep
        x_new = jnp.where(departing | (active & (x_new <= tol)), 0.0, x_new)
        newly_done = active & (x_new == 0.0)
        # Windowed flow/slowdown, vectorized: the tol clamp can finish
        # several stragglers in one step.  sx0 init 1.0 keeps idle slots'
        # (masked-out) slowdown read free of 0/0.
        flow = t_new - sarr
        slow = flow * alone_rate / sx0
        done_w = newly_done & (sarr >= w_lo) & (sarr < w_hi)
        if times is not None:
            tix = jnp.where(newly_done, sid, n_times)
            times = times.at[tix].set(t_new, mode="drop")
        # Claim: the free slot at the smallest cyclic offset after the
        # ring pointer (epoch-start free mask — the departing slot is
        # claimable from the *next* event, matching the admit gate above).
        offs = (idx - ptr) % S
        cand = jnp.argmin(jnp.where(free, offs, S)).astype(jnp.int32)
        claimed = admit & (idx == cand)
        arr_id = acc["n_admitted"]
        x_new = jnp.where(claimed, x_next, x_new)
        acc_new = {
            "n_admitted": arr_id + admit,
            "n_completed": acc["n_completed"]
            + jnp.sum(newly_done, dtype=jnp.int32),
            "w_count": acc["w_count"] + jnp.sum(done_w, dtype=jnp.int32),
            "w_arrived": acc["w_arrived"]
            + (admit & (t_next >= w_lo) & (t_next < w_hi)),
            "blocked": acc["blocked"] + blocked_now,
            "occ_max": jnp.maximum(
                acc["occ_max"], jnp.sum(active, dtype=jnp.int32)
            ),
            "w_flow": acc["w_flow"] + jnp.sum(jnp.where(done_w, flow, 0.0)),
            "w_slow": acc["w_slow"] + jnp.sum(jnp.where(done_w, slow, 0.0)),
        }
        slots_new = (
            x_new,
            jnp.where(claimed, x_next, sx0),
            jnp.where(claimed, t_next, sarr),
            jnp.where(claimed, arr_id, sid),
        )
        ptr_new = jnp.where(admit, (cand + 1) % S, ptr)
        src_adv = source.advance(src)
        src_new = jax.tree.map(
            lambda a, b: jnp.where(admit, a, b), src_adv, src
        )
        st_new = srule.observe(
            st, Observation(alloc=alloc, rate=rate, dt=dt, active=active)
        )
        if telemetry is None:
            carry = (slots_new, t_new, ptr_new, src_new, st_new, acc_new, times)
            return carry, None
        tel_new, tel_out = telemetry.step(
            tel,
            ProbeEvent(
                t=t, dt=dt, alloc=alloc, rate=rate, active=active, x=x,
                p=p, rule_state=st,
            ),
        )
        carry = (
            slots_new, t_new, ptr_new, src_new, st_new, acc_new, times, tel_new
        )
        return carry, tel_out

    slots0 = (
        jnp.zeros(S, dtype),  # remaining size: free slots hold 0
        jnp.ones(S, dtype),  # original size (1.0: see slowdown note above)
        jnp.zeros(S, dtype),  # arrival time
        jnp.full(S, n_times, jnp.int32),  # job id (sentinel = never used)
    )
    times0 = jnp.full(n_times, jnp.inf, dtype) if n_times else None
    init = (slots0, jnp.asarray(t0, dtype), zi, source.init(), srule.init(),
            acc0, times0)
    if telemetry is not None:
        init = (*init, telemetry.init())
    carry_fin, tel_ys = jax.lax.scan(body, init, None, length=n_events)
    tel_result = None
    if telemetry is not None:
        tel_result = telemetry.finalize(carry_fin[7], tel_ys)
    slots_fin, t_fin = carry_fin[0], carry_fin[1]
    return slots_fin[0], t_fin, carry_fin[5], carry_fin[6], tel_result


def run_stream(
    x0: jax.Array,
    arrival_times: jax.Array,
    p,
    rule: AllocRule | StatefulRule,
    *,
    n_slots: int,
    window: tuple[Any, Any] | None = None,
    n_alone=1.0,
    horizon: int | None = None,
    rel_tol: float = 1e-9,
    t0=0.0,
    record_times: bool = False,
    fused: bool = False,
    telemetry: Any = None,
) -> StreamResult:
    """:func:`run` over a fixed pool of ``n_slots`` recycled job slots.

    Same event loop, same rules (stateful, fused, telemetry all compose),
    but the scan carries ``[n_slots]`` state instead of ``[n_jobs]``: the
    tape can be arbitrarily long while memory stays O(n_slots) and each
    event pays O(n_slots log n_slots) in the rule's sort instead of
    O(n_jobs log n_jobs).  At any stable load the in-flight population is
    O(load), not O(horizon), so ``n_slots`` is a small constant — see
    :func:`_stream_scan` for the slot lifecycle and the full-pool
    (deferred-admission) semantics, and :func:`run_stream_source` for the
    tape-free unbounded variant.

    Reduction: with ``n_slots >= n_jobs`` the trajectory is value-
    identical to :func:`run` on the same tape (tested bit-for-bit), with
    two measure-zero caveats — exactly tied arrival times are admitted
    one per event here (extra zero-length epochs; `run` batch-admits
    them), and a departure epoch whose float rounding overshoots the next
    arrival time admits that arrival one epoch later.

    ``window=(lo, hi)`` selects the stationary measurement window (see
    :class:`StreamResult`); ``record_times=True`` additionally scatters
    per-job completion times (input order) through an ``[n_jobs]`` carry
    — parity/debug tooling, not the O(n_slots) production path.  ``p``
    must be a scalar: per-job exponents would have to ride in the slots
    (future work), and ``p_drift``'s global regime clock belongs to the
    finite-tape engine.
    """
    if jnp.ndim(p) != 0:
        raise ValueError(
            "run_stream needs a scalar p — per-job exponents do not ride "
            "in slots yet; multi-class streams take the finite-tape run()"
        )
    rule = _resolve_fused(rule, fused)
    x0 = jnp.asarray(x0)
    T = x0.shape[0]
    E = 2 * T if horizon is None else horizon
    dtype = jnp.result_type(x0.dtype, jnp.float32)
    x0 = x0.astype(dtype)
    arrival_times = jnp.asarray(arrival_times).astype(dtype)
    tol = rel_tol * jnp.max(x0)
    order = jnp.argsort(arrival_times)
    source = tape_source(x0[order], arrival_times[order])
    w_lo, w_hi = _window_bounds(window, dtype)
    x_fin, t_fin, acc, times, tel = _stream_scan(
        source, p, as_stateful(rule), n_slots=n_slots, n_events=E,
        w_lo=w_lo, w_hi=w_hi, alone_rate=speedup(jnp.asarray(n_alone, dtype), p),
        tol=tol, t0=jnp.asarray(t0, dtype), dtype=dtype,
        n_times=T if record_times else 0, telemetry=telemetry,
    )
    comp = None
    if record_times:
        comp = jnp.zeros(T, dtype).at[order].set(times)
    return _finalize_stream(acc, t_fin, x_fin, comp, tel, dtype)


def run_stream_source(
    source: StreamSource,
    p,
    rule: AllocRule | StatefulRule,
    *,
    n_slots: int,
    n_events: int,
    window: tuple[Any, Any] | None = None,
    n_alone=1.0,
    x_scale=1.0,
    rel_tol: float = 1e-9,
    t0=0.0,
    dtype=jnp.float64,
    fused: bool = False,
    telemetry: Any = None,
) -> StreamResult:
    """:func:`run_stream` for an unbounded :class:`StreamSource`.

    Runs exactly ``n_events`` scan steps against a generator source (e.g.
    :func:`poisson_source`), so nothing anywhere is sized by a job count:
    the millions-of-users regime in O(n_slots) memory.  The completion
    tolerance is absolute — ``rel_tol * x_scale``, with ``x_scale`` the
    caller's typical-size scale (there is no tape to take a max over).
    Per-job completion times are not recorded (no finite job set to
    scatter into); windowed aggregates and telemetry are the read-out.
    """
    if jnp.ndim(p) != 0:
        raise ValueError(
            "run_stream_source needs a scalar p — per-job exponents do "
            "not ride in slots yet"
        )
    rule = _resolve_fused(rule, fused)
    w_lo, w_hi = _window_bounds(window, dtype)
    x_fin, t_fin, acc, _, tel = _stream_scan(
        source, p, as_stateful(rule), n_slots=n_slots, n_events=n_events,
        w_lo=w_lo, w_hi=w_hi,
        alone_rate=speedup(jnp.asarray(n_alone, dtype), p),
        tol=jnp.asarray(rel_tol * x_scale, dtype),
        t0=jnp.asarray(t0, dtype), dtype=dtype, n_times=0,
        telemetry=telemetry,
    )
    return _finalize_stream(acc, t_fin, x_fin, None, tel, dtype)


def run_stream_ranked(
    x0: jax.Array,
    arrival_times: jax.Array,
    p,
    n_servers,
    rank_policy,
    *,
    n_slots: int,
    window: tuple[Any, Any] | None = None,
    n_alone=1.0,
    horizon: int | None = None,
    t0=0.0,
    record_times: bool = False,
) -> StreamResult:
    """:func:`run_ranked` over a fixed pool of recycled job slots.

    The rank-space fast path and the bounded-slot refactor compose: ranks
    live on slots (0 = free, which is also how :func:`run_ranked` marks
    inactive jobs), a departure drops rank ``m``, an arrival inserts one
    rank and claims a slot from the ring pointer.  Per-event cost is
    O(n_slots) with no sort at all.  Admission, deferral and windowed
    accounting follow :func:`run_stream` exactly (same reduction to
    :func:`run_ranked` when ``n_slots >= n_jobs``, same blocked-arrival
    semantics when smaller), so the two streaming paths agree the same
    way the two finite-tape paths do.
    """
    if jnp.ndim(p) != 0:
        raise ValueError("run_stream_ranked needs a scalar p (see run_ranked)")
    x0 = jnp.asarray(x0)
    T = x0.shape[0]
    S = int(n_slots)
    E = 2 * T if horizon is None else horizon
    dtype = jnp.result_type(x0.dtype, jnp.float32)
    x0 = x0.astype(dtype)
    arrival_times = jnp.asarray(arrival_times).astype(dtype)
    order = jnp.argsort(arrival_times)
    arr = arrival_times[order]
    xs = x0[order]
    idx = jnp.arange(S)
    w_lo, w_hi = _window_bounds(window, dtype)
    alone_rate = speedup(jnp.asarray(n_alone, dtype), p)
    n_times = T if record_times else 0
    zi = jnp.zeros((), jnp.int32)

    def body(carry, _):
        slots, ranks, m, t, i, ptr, acc, times = carry
        x, sx0, sarr, sid = slots
        theta = rank_policy(ranks, m, p, dtype=dtype)
        rate = speedup(theta * n_servers, p)
        small = jnp.argmax(ranks)
        has_active = m > 0
        x_s = x[small]
        r_s = rate[small]
        dt_dep = jnp.where(has_active & (r_s > 0), x_s / r_s, jnp.inf)
        t_next = jnp.where(i < T, arr[jnp.minimum(i, T - 1)], jnp.inf)
        dt_arr = jnp.maximum(t_next - t, 0.0)
        has_free = m < S
        eff_dt_arr = jnp.where(has_free, dt_arr, jnp.inf)
        dt = jnp.minimum(dt_dep, eff_dt_arr)
        any_event = jnp.isfinite(dt)
        dt = jnp.where(any_event, dt, 0.0)
        admit = any_event & has_free & (dt_arr <= dt_dep)
        take_dep = any_event & (dt_dep <= eff_dt_arr)
        blocked_now = jnp.isfinite(dt_dep) & ~has_free & (dt_arr < dt_dep)
        t_new = jnp.where(admit, jnp.maximum(t_next, t), t + dt)
        active = ranks > 0
        x_new = jnp.where(active, jnp.maximum(x - dt * rate, 0.0), x)
        departing = (idx == small) & active & take_dep
        dep_real = take_dep & has_active
        x_new = jnp.where(departing, 0.0, x_new)
        # Windowed accounting on the single departer (rank m).
        arr_s = sarr[small]
        flow = t_new - arr_s
        slow = flow * alone_rate / sx0[small]
        cw = dep_real & (arr_s >= w_lo) & (arr_s < w_hi)
        if times is not None:
            tj = jnp.where(dep_real, sid[small], n_times)
            times = times.at[tj].set(t_new, mode="drop")
        ranks = jnp.where(departing, 0, ranks)
        m_mid = m - jnp.where(dep_real, 1, 0)
        # Arrival: claim a slot from the ring pointer (epoch-start free
        # mask, as in _stream_scan) and insert its rank among the post-
        # departure active set.  Every active job arrived earlier, so the
        # arriving job loses exact-size ties — the same predicate as
        # run_ranked's ``idx < i_c`` (see its tie-handling note).
        free = ~active
        offs = (idx - ptr) % S
        cand = jnp.argmin(jnp.where(free, offs, S)).astype(jnp.int32)
        x_a = xs[jnp.minimum(i, T - 1)]
        still = ranks > 0
        ahead = still & (x_new >= x_a)
        r_a = 1 + jnp.sum(ahead, dtype=jnp.int32)
        bumped = jnp.where(still & (ranks >= r_a), ranks + 1, ranks)
        inserted = bumped.at[cand].set(r_a)
        ranks = jnp.where(admit, inserted, ranks)
        claimed = admit & (idx == cand)
        slots_new = (
            jnp.where(claimed, x_a, x_new),
            jnp.where(claimed, x_a, sx0),
            jnp.where(claimed, t_next, sarr),
            jnp.where(claimed, i, sid),
        )
        acc_new = {
            "n_admitted": acc["n_admitted"] + admit,
            "n_completed": acc["n_completed"] + dep_real,
            "w_count": acc["w_count"] + cw,
            "w_arrived": acc["w_arrived"]
            + (admit & (t_next >= w_lo) & (t_next < w_hi)),
            "blocked": acc["blocked"] + blocked_now,
            "occ_max": jnp.maximum(acc["occ_max"], m),
            "w_flow": acc["w_flow"] + jnp.where(cw, flow, 0.0),
            "w_slow": acc["w_slow"] + jnp.where(cw, slow, 0.0),
        }
        m_new = m_mid + jnp.where(admit, 1, 0)
        i_new = i + jnp.where(admit, 1, 0)
        ptr_new = jnp.where(admit, (cand + 1) % S, ptr)
        return (slots_new, ranks, m_new, t_new, i_new, ptr_new, acc_new,
                times), None

    slots0 = (
        jnp.zeros(S, dtype),
        jnp.ones(S, dtype),
        jnp.zeros(S, dtype),
        jnp.full(S, n_times, jnp.int32),
    )
    acc0 = {
        "n_admitted": zi, "n_completed": zi, "w_count": zi,
        "w_arrived": zi, "blocked": zi, "occ_max": zi,
        "w_flow": jnp.zeros((), dtype), "w_slow": jnp.zeros((), dtype),
    }
    times0 = jnp.full(n_times, jnp.inf, dtype) if record_times else None
    init = (slots0, jnp.zeros(S, jnp.int32), zi, jnp.asarray(t0, dtype), zi,
            zi, acc0, times0)
    (slots_fin, _, _, t_fin, _, _, acc_fin, times_fin), _ = jax.lax.scan(
        body, init, None, length=E
    )
    comp = None
    if record_times:
        comp = jnp.zeros(T, dtype).at[order].set(times_fin)
    return _finalize_stream(acc_fin, t_fin, slots_fin[0], comp, None, dtype)


# -------------------------------------------------- JAX-native quantization
def quantize_allocation_jax(
    theta: jax.Array, n_chips: int, *, min_chips: int = 1
) -> jax.Array:
    """Vectorized-jnp port of ``sched.quantize.quantize_allocation``.

    Largest-remainder rounding of ``theta * n_chips`` (``theta`` sums to
    ~1 over the active jobs, ``theta <= 0`` means inactive) with a
    ``min_chips`` floor, matching the NumPy oracle *exactly* — including
    its greedy trim order and stable tie-breaking — but with every
    data-dependent loop replaced by sorts and a static-length binary
    search, so it jit/vmaps inside the engine's scan:

    - **Oversubscription** (more active jobs than ``n_chips // min_chips``
      can hold): keep the largest-theta jobs, queue the rest at 0 chips,
      renormalize.  The oracle recurses once; a single unrolled pass
      suffices because the restriction can't oversubscribe again.
    - **Min-chips overflow trim**: the oracle greedily decrements the job
      maximizing ``base - raw``.  Candidate ``j``'s successive priorities
      are ``-(frac_j + k)``, which fall in disjoint unit bands per trim
      round ``k`` — so the greedy is exactly "full rounds + one partial
      round in ascending-frac order".  The number of full rounds is found
      by binary search on ``T(r) = sum_j min(cap_j, r)`` (monotone in
      ``r``), ``ceil(log2(n_chips))`` iterations, each O(M).
    - **Leftover distribution**: +1 chip to the largest fractional parts
      (stable on ties), active jobs only.

    The trim and the leftover passes are *mutually exclusive* (a trim ends
    with ``sum(base) == n_chips`` exactly, so the remainder is 0; no trim
    means ``K == 0`` and nothing was removed), so one argsort on a
    conditionally-selected key serves both — two sorts per call, not the
    three the first port paid.  Tie-breaking is unchanged: each branch
    sorts the exact key (and stable order) it sorted before.

    ``n_chips``/``min_chips`` are static Python ints.  Returns int32 chips.
    """
    theta = jnp.asarray(theta)
    M = theta.shape[0]
    if n_chips <= 0 or min_chips <= 0 or M == 0:
        return jnp.zeros(M, jnp.int32)
    cap = n_chips // min_chips  # most jobs the floor allows us to serve

    active0 = theta > 0
    n_active = jnp.sum(active0, dtype=jnp.int32)
    # Oversubscribed: serve the largest-theta jobs (stable on ties), queue
    # the rest with 0, renormalize — the oracle's single recursion, unrolled.
    desc = inv_rank(jnp.argsort(jnp.where(active0, -theta, jnp.inf)))
    servable = active0 & (desc < cap)
    over = n_active * min_chips > n_chips
    sub = jnp.where(servable, theta, 0.0)
    tot = jnp.sum(sub)
    theta_eff = jnp.where(over, jnp.where(tot > 0, sub / tot, 0.0), theta)
    active = theta_eff > 0

    raw = theta_eff * n_chips
    fl = jnp.floor(raw)
    frac = raw - fl
    base = jnp.where(active, jnp.maximum(fl, min_chips), 0.0).astype(jnp.int32)

    # Min-chips floor oversubscribed the pool: trim K chips from the
    # largest holdings, exactly as the oracle's greedy (see docstring).
    K = jnp.maximum(jnp.sum(base) - n_chips, 0)
    capj = jnp.maximum(base - min_chips, 0) * (base > min_chips)

    def bisect(_, lohi):
        lo, hi = lohi
        mid = (lo + hi) // 2
        ge = jnp.sum(jnp.minimum(capj, mid)) >= K
        return jnp.where(ge, lo, mid + 1), jnp.where(ge, mid, hi)

    n_bits = (n_chips + 1).bit_length()
    lo, _hi = jax.lax.fori_loop(
        0, n_bits, bisect, (jnp.int32(0), jnp.int32(n_chips))
    )
    r_star = lo  # smallest r with T(r) >= K (0 when K == 0)
    full = jnp.minimum(capj, jnp.maximum(r_star - 1, 0))
    extra_needed = K - jnp.sum(full)
    elig = capj >= jnp.maximum(r_star, 1)
    # One sort serves the partial trim round (ascending frac among eligible
    # jobs, taken when K > 0) AND the leftover distribution (descending
    # frac among active jobs, only reachable when K == 0) — the branches
    # are mutually exclusive, see the docstring.
    trim = K > 0
    key = jnp.where(
        trim, jnp.where(elig, frac, jnp.inf), jnp.where(active, -frac, jnp.inf)
    )
    pos = inv_rank(jnp.argsort(key))
    extra = (elig & (pos < extra_needed)).astype(jnp.int32)
    base = base - full - extra

    # Leftover chips (only when no trim happened): largest fracs first.
    remainder = n_chips - jnp.sum(base)
    base = base + (active & (pos < remainder)).astype(jnp.int32)
    return base


def snap_to_slices_jax(
    chips: jax.Array, n_chips: int, *, slices: tuple[int, ...] = DEFAULT_SLICES
) -> jax.Array:
    """Vectorized-jnp port of ``sched.quantize.snap_to_slices``.

    Snap each job's chip count DOWN to the largest slice size ``<= count``
    (0 if below the smallest slice), then hand leftover chips back greedily:
    at each round, among jobs whose next slice step still fits the leftover
    pool and whose *lost* allocation (original chips - snapped) is
    non-negative, upgrade the job with the largest lost allocation (ties
    break toward the higher index, matching the oracle's ``>=`` scan).  The
    leftover pool strictly shrinks every round, so the ``while_loop`` is
    bounded by ``n_chips`` iterations.

    ``n_chips``/``slices`` are static; returns int32 chips.  Exact
    agreement with the NumPy oracle is property-tested in
    tests/test_quantize.py.
    """
    sl = jnp.asarray(sorted(slices), jnp.int32)
    S = sl.shape[0]
    chips0 = jnp.asarray(chips).astype(jnp.int32)
    M = chips0.shape[0]
    if M == 0:
        return chips0
    idx = jnp.arange(M, dtype=jnp.int32)

    # Snap down: largest slice <= count (0 when count < slices[0]).
    down = jnp.searchsorted(sl, chips0, side="right") - 1
    snapped0 = jnp.where(down >= 0, sl[jnp.maximum(down, 0)], 0)
    left0 = jnp.int32(n_chips) - jnp.sum(snapped0)

    def candidate(snapped, left):
        nxt_i = jnp.searchsorted(sl, snapped, side="right")
        nxt = sl[jnp.minimum(nxt_i, S - 1)]
        step = nxt - snapped
        lost = chips0 - snapped
        elig = (
            (nxt_i < S)
            & (step <= left)
            & (lost >= 0)
            & ~((snapped == 0) & (chips0 == 0))
        )
        # Max lost, ties to the highest index — the oracle's `>=` scan.
        key = jnp.where(elig, lost * M + idx, -1)
        j = jnp.argmax(key)
        return j, nxt[j], step[j], key[j] >= 0

    # The chosen candidate rides in the carry so each round computes it
    # once (the next candidate is derived at the end of body, not re-done
    # in cond) — this runs inside every quantized scan step.
    def cond(state):
        _, left, _, _, _, any_elig = state
        return any_elig & (left > 0)

    def body(state):
        snapped, left, j, nxt_j, step_j, _ = state
        snapped = snapped.at[j].set(nxt_j)
        left = left - step_j
        return (snapped, left, *candidate(snapped, left))

    init = (snapped0, left0, *candidate(snapped0, left0))
    snapped, *_ = jax.lax.while_loop(cond, body, init)
    return snapped


__all__ = [
    "AllocRule",
    "DEFAULT_SLICES",
    "EngineResult",
    "EngineTrace",
    "Observation",
    "PDrift",
    "ProbeEvent",
    "StatefulRule",
    "StreamResult",
    "StreamSource",
    "as_stateful",
    "continuous_rule",
    "finish_alloc",
    "knee_rule",
    "poisson_source",
    "quantize_allocation_jax",
    "quantized_rule",
    "run",
    "run_ranked",
    "run_stream",
    "run_stream_ranked",
    "run_stream_source",
    "snap_to_slices_jax",
    "tape_source",
]
