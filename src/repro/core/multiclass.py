"""Multi-class workload subsystem: per-class speedup, sizes, arrivals.

The paper proves heSRPT optimal for ONE job class — a single speedup
exponent ``p`` shared by every job.  The follow-up line of work shows the
production-relevant regime is heterogeneous: "Asymptotically Optimal
Scheduling of Multiple Parallelizable Job Classes" (Berg, Moseley, Wang,
Harchol-Balter 2024) derives class-aware fluid allocations when classes
differ in speedup and size distribution, and "heSRPT: Parallel Scheduling
to Minimize Mean Slowdown" (Berg, Vesilo, Harchol-Balter 2020) changes the
objective itself.  This module is the repo's home for that regime:

- :class:`ClassSpec` — one job class: speedup exponent ``p``, arrival-rate
  share ``mix``, Pareto size distribution (``size_alpha``/``size_scale``),
  policy ``weight``, and burstiness.
- Multi-class scenario samplers (``multiclass_poisson`` — superposed
  per-class Poisson streams via i.i.d. class marks; ``multiclass_bursty``
  — per-class 2-state MAP on-off streams, merged), registered into the
  ``core/scenarios.py`` registry so ``make_scenario("multiclass_poisson",
  classes=...)`` works everywhere a scenario name does, including the
  per-class ``sigma_size``/``sigma_p`` estimation-noise knobs.
- :func:`class_theta` — the ONE pure allocation function shared by the
  engine's scan rule and the per-event ``ClusterScheduler`` oracle, so
  cross-checks are exact (identical jnp ops, identical bits):
  ``hesrpt_pc`` (per-class heSRPT brackets), ``waterfill`` (the
  class-weighted water-filling fluid allocation), ``hesrpt_sd``
  (slowdown-weighted heSRPT), ``hesrpt_blind`` (class-blind heSRPT that
  assumes the active-average exponent — the baseline the class-aware
  policies are measured against).
- :func:`simulate_multiclass` — runs a multi-class scenario through the
  unified engine (``core/engine.py``) with per-job ``p`` vectors,
  continuous or whole-chips (optionally slice-snapped) allocation.  When
  every class shares one exponent it statically dispatches back to the
  single-class engine path, so the K-classes-with-equal-``p`` case
  reproduces the single-class engine **bit-for-bit**.
- :func:`multiclass_sweep` — seeds x loads x policies in one jit+vmap
  device call per policy, reporting overall and per-class mean flow time
  and mean slowdown (the Berg 2020 objective).

The per-event NumPy oracle lives in ``sched/cluster.py``
(``ClusterScheduler(class_aware=True)``); ``benchmarks/multiclass.py``
cross-checks the engine against it event-for-event.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import engine
from repro.core.analysis import per_class_mean
from repro.core.arrivals import (
    OnlineSimResult,
    _finalize,
    run_pooled,
    simulate_online,
    simulate_online_quantized,
)
from repro.core.policies import (
    hesrpt,
    hesrpt_per_class,
    make_policy,
    waterfill,
    weighted_hesrpt,
)
from repro.core.scenarios import (
    SCENARIOS,
    Scenario,
    bursty_arrivals,
    poisson_arrivals,
)

#: Class-aware policy names accepted by :func:`class_theta` and friends.
MULTICLASS_POLICY_NAMES = ("hesrpt_pc", "waterfill", "hesrpt_sd", "hesrpt_blind")


class ClassSpec(NamedTuple):
    """One job class: static Python floats, hashable for jit caches."""

    p: float = 0.5  # speedup exponent of the class
    mix: float = 1.0  # arrival-rate share (normalized over classes)
    size_alpha: float = 1.5  # Pareto tail of the class's size distribution
    size_scale: float = 1.0  # multiplicative size scale (the Pareto x_m)
    weight: float = 1.0  # class weight for weighted policies
    burst: float = 4.0  # MAP on/off rate ratio (multiclass_bursty only)
    min_chips: int | None = None  # smallest slice a job fits on (whole chips)
    max_chips: int | None = None  # widest slice a job can use (whole chips)


def as_specs(classes) -> tuple[ClassSpec, ...]:
    """Coerce a sequence of ClassSpec / tuples / dicts into ClassSpec."""
    out = []
    for c in classes:
        if isinstance(c, ClassSpec):
            out.append(c)
        elif isinstance(c, dict):
            out.append(ClassSpec(**c))
        else:
            out.append(ClassSpec(*c))
    if not out:
        raise ValueError("need at least one job class")
    return tuple(out)


def has_limits(classes) -> bool:
    """Whether any class sets a per-job width limit (``min_chips`` or
    ``max_chips``)."""
    return classes is not None and any(
        c.min_chips is not None or c.max_chips is not None for c in as_specs(classes)
    )


def job_limits(classes, class_ids, *, n_chips: int, min_chips: int = 1):
    """Per-job ``(lo, hi)`` int32 chip limits from each job's class: the
    class's ``min_chips``/``max_chips``, else the global ``min_chips`` and
    ``n_chips``."""
    specs = as_specs(classes)
    lo = [min_chips if c.min_chips is None else c.min_chips for c in specs]
    hi = [n_chips if c.max_chips is None else c.max_chips for c in specs]
    ids = jnp.asarray(class_ids, jnp.int32)
    return jnp.asarray(lo, jnp.int32)[ids], jnp.asarray(hi, jnp.int32)[ids]


def uniform_p(classes) -> float | None:
    """The shared exponent when every class has the same ``p``, else None."""
    ps = {float(c.p) for c in as_specs(classes)}
    return ps.pop() if len(ps) == 1 else None


# ----------------------------------------------------- multi-class sampling
def _class_fields(specs, field, dtype=None):
    return jnp.asarray([getattr(c, field) for c in specs], dtype)


def _pareto_mixture_sizes(key, cls, specs):
    """Per-job Pareto sizes: x = scale_k * U^(-1/alpha_k) for job class k
    (inverse-CDF so per-job tail exponents vectorize in one draw)."""
    alphas = _class_fields(specs, "size_alpha")[cls]
    scales = _class_fields(specs, "size_scale")[cls]
    u = jax.random.uniform(
        key, cls.shape, minval=jnp.finfo(jnp.result_type(float)).tiny, maxval=1.0
    )
    return scales * u ** (-1.0 / alphas)


def _multiclass_poisson(key, n_jobs, rate, *, classes, size_alpha=None, **_):
    """Superposed per-class Poisson streams: a Poisson(rate) stream with
    i.i.d. class marks drawn from the mix (exact superposition identity).
    ``size_alpha`` from ``make_scenario`` is ignored — classes carry their
    own size distributions."""
    del size_alpha
    specs = as_specs(classes)
    mixes = _class_fields(specs, "mix")
    k_cls, k_arr, k_size = jax.random.split(key, 3)
    cls = jax.random.choice(
        k_cls, len(specs), (n_jobs,), p=mixes / jnp.sum(mixes)
    ).astype(jnp.int32)
    arr = poisson_arrivals(k_arr, n_jobs, rate)
    x0 = _pareto_mixture_sizes(k_size, cls, specs)
    return Scenario(
        x0=x0,
        arrival_times=arr,
        class_ids=cls,
        p_job=_class_fields(specs, "p", x0.dtype)[cls],
    )


def _class_counts(specs, n_jobs: int) -> list[int]:
    """Largest-remainder split of ``n_jobs`` across the class mix (static)."""
    total = sum(c.mix for c in specs)
    raw = [n_jobs * c.mix / total for c in specs]
    counts = [int(r) for r in raw]
    fracs = sorted(
        range(len(specs)), key=lambda k: (raw[k] - counts[k], -k), reverse=True
    )
    for k in fracs[: n_jobs - sum(counts)]:
        counts[k] += 1
    return counts


def _multiclass_bursty(
    key, n_jobs, rate, *, classes, p_stay=0.95, size_alpha=None, **_
):
    """Per-class bursty MAP on-off streams, superposed.

    Each class k runs its own 2-state MAP stream at long-run intensity
    ``rate * mix_k`` with its own ``burst`` ratio (see
    ``scenarios.bursty_arrivals`` for the normalization); the engine's
    arrival sort merges the streams.  Job counts split by largest
    remainder of the mix, so the drawn class census is deterministic.
    """
    del size_alpha
    specs = as_specs(classes)
    total_mix = sum(c.mix for c in specs)
    counts = _class_counts(specs, n_jobs)
    # Per-class streams live under fold_in(key, 3): ``_with_noise`` reserves
    # fold_in(key, 1)/fold_in(key, 2) on the SAME base key for the
    # estimation-noise draws, so deriving class streams directly from
    # ``key`` would correlate the noise with the workload.
    base = jax.random.fold_in(key, 3)
    arrs, sizes, ids, ps = [], [], [], []
    for k, (spec, n_k) in enumerate(zip(specs, counts, strict=True)):
        if n_k == 0:
            continue
        rate_k = rate * spec.mix / total_mix
        norm = 0.5 * (spec.burst + 1.0 / spec.burst)
        k_arr = jax.random.fold_in(base, 2 * k)
        k_size = jax.random.fold_in(base, 2 * k + 1)
        arrs.append(
            bursty_arrivals(
                k_arr,
                n_k,
                rate_k * spec.burst * norm,
                rate_k / spec.burst * norm,
                p_stay=p_stay,
            )
        )
        cls_k = jnp.full((n_k,), k, jnp.int32)
        sizes.append(_pareto_mixture_sizes(k_size, cls_k, specs))
        ids.append(cls_k)
        ps.append(jnp.full((n_k,), spec.p, sizes[-1].dtype))
    return Scenario(
        x0=jnp.concatenate(sizes),
        arrival_times=jnp.concatenate(arrs),
        class_ids=jnp.concatenate(ids),
        p_job=jnp.concatenate(ps),
    )


def _drift_multiclass(
    key, n_jobs, rate, *, classes, p1, drift_frac=0.5, size_alpha=None, **_
):
    """Per-class time-varying drift: the ROADMAP "Next" regime.

    A ``multiclass_poisson`` draw whose TRUE exponents change mid-stream:
    class ``k`` drifts from its ``ClassSpec.p`` to ``p1[k]`` at
    ``drift_frac`` of the stream's nominal span ``n_jobs / rate`` (the same
    placement rule as the single-class drift scenarios, so the drift lands
    mid-stream at every load of a sweep).  The scenario's ``PDrift`` uses
    the per-job rows form (``values`` shape ``[2, M]``) — each job's
    physics follow its OWN class's regime schedule, e.g. only the
    communication-bound class degrades.  ``scn.p_job`` keeps the PRE-drift
    exponents (what a stale scheduler believes); the engine's physics
    follow ``p_drift`` wherever it is set.
    """
    del size_alpha
    specs = as_specs(classes)
    if len(p1) != len(specs):
        raise ValueError(
            f"p1 needs one post-drift exponent per class "
            f"({len(p1)} != {len(specs)})"
        )
    scn = _multiclass_poisson(key, n_jobs, rate, classes=specs)
    dtype = scn.x0.dtype
    p1_job = jnp.asarray(p1, dtype)[scn.class_ids]
    t_d = jnp.asarray(drift_frac * n_jobs / rate, dtype)
    drift = engine.PDrift(
        times=t_d[None],
        values=jnp.stack([jnp.asarray(scn.p_job, dtype), p1_job]),
    )
    return scn._replace(p_drift=drift)


SCENARIOS.setdefault("multiclass_poisson", _multiclass_poisson)
SCENARIOS.setdefault("multiclass_bursty", _multiclass_bursty)
SCENARIOS.setdefault("drift_multiclass", _drift_multiclass)


# ------------------------------------------------- class-aware allocation
def class_theta(
    name: str,
    x: jax.Array,
    p: jax.Array,
    *,
    n_servers,
    w: jax.Array | None = None,
) -> jax.Array:
    """The shared pure allocation ``(x, p_vec[, w]) -> theta``.

    One function used verbatim by the engine's scan rule AND the per-event
    ``ClusterScheduler`` oracle, so the two paths run identical jnp ops and
    the cross-checks can demand exact agreement.  ``w`` is the per-job
    weight vector :func:`policy_weights` builds (ignored by unweighted
    policies); ``hesrpt_blind`` re-derives the active-average exponent at
    every call — exactly the class-blind scheduler's view.
    """
    name = name.lower()
    if name == "hesrpt_pc":
        return hesrpt_per_class(x, p)
    if name == "waterfill":
        return waterfill(x, p, n_servers, w)
    if name == "hesrpt_sd":
        if w is None:
            raise ValueError("hesrpt_sd needs per-job weights (1/x0)")
        return weighted_hesrpt(x, p, w)
    if name == "hesrpt_blind":
        active = x > 0
        m = jnp.maximum(jnp.sum(active), 1).astype(x.dtype)
        p_blind = jnp.sum(jnp.where(active, p, 0.0)) / m
        return hesrpt(x, p_blind)
    raise ValueError(
        f"unknown multi-class policy {name!r}; known: {MULTICLASS_POLICY_NAMES}"
    )


def policy_weights(
    name: str,
    *,
    x0: jax.Array | None = None,
    class_w: jax.Array | None = None,
) -> jax.Array | None:
    """Per-job weight vector ``name`` expects, or None.

    ``hesrpt_sd`` weights each job by ``class_weight / x0`` (original size:
    the mean-slowdown objective weights flow time by 1/size); ``waterfill``
    takes the bare class weights.  Other policies are unweighted.
    """
    name = name.lower()
    if name == "hesrpt_sd":
        if x0 is None:
            raise ValueError("hesrpt_sd weights need the original sizes x0")
        return (1.0 if class_w is None else class_w) / x0
    if name == "waterfill":
        return class_w
    return None


def class_rule(
    name: str,
    *,
    n_servers: float | None = None,
    n_chips: int | None = None,
    min_chips: int = 1,
    snap_slices: bool = False,
    dtype,
    w: jax.Array | None = None,
    size_factors: jax.Array | None = None,
    p_hat: jax.Array | None = None,
    lo: jax.Array | None = None,
    hi: jax.Array | None = None,
) -> engine.AllocRule:
    """Build the engine :data:`~repro.core.engine.AllocRule` for a
    class-aware policy: continuous when ``n_chips`` is None, else whole
    chips (largest-remainder + min-chips floor, optionally slice-snapped;
    per-job width limits ``lo``/``hi`` take ``engine.finish_alloc``'s
    capped rounding).

    All captured per-job vectors (``w``, ``size_factors``, vector
    ``p_hat``, ``lo``, ``hi``) must be in the engine's arrival-sorted
    order — the same contract as ``engine.continuous_rule``.  The rule can
    also be played through a slot pool (``engine.run_stream``): those
    vectors then ride in the slots, and ties break by job id.
    """
    n_alloc = float(n_chips) if n_chips is not None else float(n_servers)

    def make(job, tie):
        factors, p_hat_, w_ = job["size_factors"], job["p_hat"], job["w"]
        lo_, hi_ = job["lo"], job["hi"]

        def rule(x_act, p):
            x_seen = x_act if factors is None else x_act * factors
            p_seen = p if p_hat_ is None else p_hat_
            theta = class_theta(name, x_seen, p_seen, n_servers=n_alloc, w=w_)
            return engine.finish_alloc(
                theta, p, n_alloc=n_alloc, n_chips=n_chips, min_chips=min_chips,
                snap_slices=snap_slices, dtype=dtype, lo=lo_, hi=hi_, tie=tie,
            )

        return rule

    return engine.slotted_rule(make, {"size_factors": size_factors, "p_hat": p_hat,
                                   "w": w, "lo": lo, "hi": hi})


# ----------------------------------------------------- engine entry points
def simulate_multiclass(
    scn: Scenario,
    *,
    classes=None,
    policy: str = "hesrpt_pc",
    n_servers: float = 256.0,
    n_chips: int | None = None,
    min_chips: int = 1,
    snap_slices: bool = False,
    rel_tol: float = 1e-9,
    horizon: int | None = None,
    estimator_kw: dict | None = None,
) -> OnlineSimResult:
    """Run a multi-class scenario through the unified engine.

    Per-job exponents come from ``scn.p_job`` (drawn by the multi-class
    samplers); physics use them always, while what the *policy* sees flows
    through the usual estimation-noise channel (``scn.size_factors`` /
    ``scn.p_hat``).  ``n_chips`` switches to whole-chips allocation,
    ``snap_slices`` additionally restricts jobs to power-of-two slices, and
    classes with ``min_chips``/``max_chips`` hold each of their jobs within
    those widths (:func:`job_limits`; whole chips only, truth-driven
    policies only).

    ``estimator_kw`` switches the policy's exponents from the drawn truth
    to *online estimates*: the engine runs the stateful
    ``estimation.estimating_class_rule`` — per-class p̂_k recursively fit
    from observed throughput inside the scan, priors and forgetting from
    the dict (``prior_p`` per class, ``prior_weight``, ``discount``) —
    while the physics keep ``scn.p_job``.  This is the class-aware
    estimation regime (``ClusterScheduler(class_aware=True,
    use_estimator=True)`` is its per-event oracle).

    **Class-blind reduction (static):** when ``classes`` is given and every
    class shares one exponent, ``hesrpt_pc``/``hesrpt_blind`` degenerate to
    plain heSRPT — this dispatches to the *single-class* engine wrappers at
    trace time, so K equal-``p`` classes reproduce the single-class engine
    bit-for-bit (property-tested in tests/test_multiclass.py).
    """
    return _multiclass(
        scn, classes=classes, policy=policy, n_servers=n_servers,
        n_chips=n_chips, min_chips=min_chips, snap_slices=snap_slices,
        rel_tol=rel_tol, horizon=horizon, estimator_kw=estimator_kw,
    )


def _multiclass(scn, *, classes, policy, n_servers, n_chips, min_chips,
                snap_slices, rel_tol=1e-9, horizon=None, estimator_kw=None,
                n_slots: int | None = None):
    """:func:`simulate_multiclass`, or with ``n_slots`` the sweep's slot
    pool: a whole-chip tape with no drift or estimator is then played
    through ``arrivals.run_pooled``, its exponents, width limits, weights,
    noise and class ids in the slots, and read as a ``PoolSimResult``."""
    specs = as_specs(classes) if classes is not None else None
    x0 = jnp.asarray(scn.x0)
    dtype = jnp.result_type(x0.dtype, jnp.float32)
    x0 = x0.astype(dtype)
    arr = jnp.asarray(scn.arrival_times).astype(dtype)

    p_shared = uniform_p(specs) if specs is not None else None
    limited = has_limits(specs)
    if limited and (n_chips is None or scn.class_ids is None
                    or estimator_kw is not None):
        raise ValueError(
            "per-class width limits need n_chips, a multi-class scenario and "
            "no estimator"
        )
    noiseless = scn.size_factors is None and scn.p_hat is None
    pooled = (n_slots is not None and n_chips is not None
              and scn.p_drift is None and estimator_kw is None)
    n_classes = len(specs) if specs is not None and scn.class_ids is not None else 0
    if (
        p_shared is not None
        and noiseless
        and not limited
        and scn.p_drift is None  # drift physics need the generic engine run
        and estimator_kw is None
        and policy.lower() in ("hesrpt", "hesrpt_pc", "hesrpt_blind")
        and not (n_chips is not None and snap_slices)
    ):
        pol = make_policy(
            "hesrpt", n_servers=float(n_chips if n_chips is not None else n_servers)
        )
        if pooled:
            return run_pooled(
                x0, arr, p_shared,
                engine.quantized_rule(pol, n_chips, min_chips=min_chips, dtype=dtype),
                n_slots=n_slots, n_alone=n_chips, rel_tol=rel_tol,
                horizon=horizon, class_ids=scn.class_ids, n_classes=n_classes,
            )
        if n_chips is None:
            return simulate_online(
                x0, arr, p_shared, n_servers, pol, rel_tol=rel_tol, horizon=horizon
            )
        return simulate_online_quantized(
            x0, arr, p_shared, n_chips, pol,
            min_chips=min_chips, rel_tol=rel_tol, horizon=horizon,
        )

    p_job = scn.p_job
    if p_job is None:
        if p_shared is None:
            raise ValueError(
                "scenario has no p_job; draw it with a multi-class sampler "
                "or pass uniform classes"
            )
        p_job = jnp.full(x0.shape, p_shared, dtype)
    p_job = jnp.asarray(p_job).astype(dtype)

    order = jnp.argsort(arr)  # engine scans in arrival order; pre-sort
    factors = scn.size_factors
    if factors is not None:
        factors = jnp.asarray(factors, dtype)[order]
    p_hat = scn.p_hat
    if p_hat is not None and jnp.ndim(p_hat) >= 1:
        p_hat = jnp.asarray(p_hat, dtype)[order]
    class_w = None
    if specs is not None and scn.class_ids is not None:
        class_w = _class_fields(specs, "weight", dtype)[scn.class_ids]
    x0_seen = x0 if scn.size_factors is None else x0 * jnp.asarray(
        scn.size_factors, dtype
    )
    w = policy_weights(policy, x0=x0_seen, class_w=class_w)
    if w is not None:
        w = jnp.asarray(w, dtype)[order]

    if estimator_kw is not None:
        from repro.core import estimation as est

        if scn.class_ids is None:
            raise ValueError("estimator_kw needs a multi-class scenario")
        kw = dict(estimator_kw)
        kw.setdefault("prior_p", jnp.mean(p_job))
        rule = est.estimating_class_rule(
            policy,
            class_ids=jnp.asarray(scn.class_ids, jnp.int32)[order],
            n_classes=len(specs) if specs is not None else
            int(jnp.max(scn.class_ids)) + 1,
            dtype=dtype,
            n_servers=float(n_servers),
            n_chips=n_chips,
            min_chips=min_chips,
            snap_slices=snap_slices,
            w=w,
            **kw,
        )
    else:
        lo = hi = None
        if limited:
            lo, hi = job_limits(specs, scn.class_ids, n_chips=n_chips,
                                min_chips=min_chips)
            lo, hi = lo[order], hi[order]
        rule = class_rule(
            policy,
            n_servers=float(n_servers),
            n_chips=n_chips,
            min_chips=min_chips,
            snap_slices=snap_slices,
            dtype=dtype,
            w=w,
            size_factors=factors,
            p_hat=p_hat,
            lo=lo,
            hi=hi,
        )
    n_alone = n_chips if n_chips is not None else n_servers
    if pooled:
        return run_pooled(
            x0, arr, p_job, rule, n_slots=n_slots, n_alone=n_alone,
            rel_tol=rel_tol, horizon=horizon, class_ids=scn.class_ids,
            n_classes=n_classes,
        )
    res = engine.run(
        x0, arr, p_job, rule, horizon=horizon, rel_tol=rel_tol,
        p_drift=scn.p_drift,
    )
    return _finalize(x0, arr, res.completion_times, p_job, n_alone)


def per_class_metrics(
    res: OnlineSimResult, class_ids: jax.Array, n_classes: int
) -> dict[str, jax.Array]:
    """Per-class mean flow time / slowdown arrays (shape ``[K]``)."""
    return {
        "mean_flowtime": per_class_mean(res.flow_times, class_ids, n_classes),
        "mean_slowdown": per_class_mean(res.slowdowns, class_ids, n_classes),
    }


def multiclass_sweep(
    policies,
    rates,
    *,
    classes,
    n_jobs: int = 1000,
    n_seeds: int = 10,
    n_servers: float = 256.0,
    seed: int = 0,
    scenario: str = "multiclass_poisson",
    scenario_kw: dict | None = None,
    n_chips: int | None = None,
    min_chips: int = 1,
    snap_slices: bool = False,
    chunk_seeds: int | None = None,
    max_jobs_in_flight: int | None = None,
    shard: bool = False,
) -> dict:
    """Sweep seeds x loads x class-aware policies: ONE compiled device call
    per policy (the quantized-benchmark shape, now with per-job ``p``).

    Seeds are shared across rates and policies (paired sample paths).
    Returns ``{policy: {"mean_flowtime": [R,S], "mean_slowdown": [R,S],
    "class_flowtime": [R,S,K], "class_slowdown": [R,S,K]}}``.

    Since the sweep-subsystem refactor this is a thin spec over
    ``core/sweeps.py`` (golden-pinned bit-for-bit against the historical
    jit+vmap path); ``chunk_seeds``/``max_jobs_in_flight``/``shard`` are
    that engine's memory/device scale knobs.
    """
    from repro.core.sweeps import Sweep, run_sweep

    spec = Sweep.create(
        policies, rates, scenario=scenario, scenario_kw=scenario_kw,
        n_jobs=n_jobs, n_seeds=n_seeds, seed=seed, n_servers=n_servers,
        n_chips=n_chips, min_chips=min_chips, snap_slices=snap_slices,
        classes=as_specs(classes),
        metrics=("mean_flowtime", "mean_slowdown", "class_flowtime",
                 "class_slowdown"),
    )
    res = run_sweep(spec, chunk_seeds=chunk_seeds,
                    max_jobs_in_flight=max_jobs_in_flight, shard=shard)
    return {name: dict(res.stats[name]) for name in spec.policies}


__all__ = [
    "MULTICLASS_POLICY_NAMES",
    "ClassSpec",
    "as_specs",
    "class_rule",
    "class_theta",
    "has_limits",
    "job_limits",
    "multiclass_sweep",
    "per_class_metrics",
    "policy_weights",
    "simulate_multiclass",
    "uniform_p",
]
