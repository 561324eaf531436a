"""One sweep subsystem: the declarative experiment engine behind every
simulator.

Every heavy-traffic experiment in this repo is the same shape — draw a
scenario per ``(seed, rate)`` cell, run it through the allocation engine
(``core/engine.py``), reduce to a few metrics, repeat for a handful of
policies.  Historically each experiment re-implemented its own jit+vmap
scaffolding (``load_sweep``/``load_sweep_raw``, ``multiclass_sweep``, and
three divergent benchmark ``sweep()`` copies); none of them chunked memory,
sharded across devices, or emitted machine-readable results.  This module
is the single replacement path:

- :class:`Sweep` — a hashable, declarative spec of the whole grid
  (policies x rates x seeds, scenario + kwargs, single-class / multi-class
  / estimation-arm regimes).  Specs are pure data: two equal specs share
  one compiled executor.
- :func:`run_sweep` — one compiled executor per policy, with three scale
  layers the hand-rolled versions lacked:

  1. **Chunked execution** — ``lax.map`` over seed-chunks of the inner
     ``vmap`` so the number of simultaneously simulated jobs never exceeds
     a ``max_jobs_in_flight`` memory budget; a 2,000-jobs x 200-seeds x
     5-loads grid (2M simulated jobs per policy) runs on CPU without OOM.
     Chunked results are bit-for-bit the unchunked ``vmap`` (tested).
  2. **Device sharding** — opt-in ``jax.shard_map`` over the seed or the
     rate axis, so multi-device hosts split the grid across devices;
     sharded == single-device bit for bit (tested under
     ``XLA_FLAGS=--xla_force_host_platform_device_count``).
  3. **Structured artifacts** — every run returns a :class:`SweepResult`
     (spec, per-seed stats, wall/compile time, backend, chunking) that
     serializes to JSON; every ``run_sweep`` call also appends a compact
     record to the module :data:`RUN_LOG`, which ``benchmarks/run.py``
     flushes to ``BENCH_sweeps.json`` so the perf trajectory accumulates
     across commits.

``load_sweep``/``load_sweep_raw`` (``core/arrivals.py``),
``multiclass_sweep`` (``core/multiclass.py``) and the benchmark ``sweep()``
functions are thin spec-plus-formatting wrappers over this module; golden
pins in ``tests/test_sweeps.py`` hold the refactor to bit-for-bit f64
agreement with the pre-refactor outputs.
"""

from __future__ import annotations

import functools
import itertools
import json
import os
import subprocess
import time
from datetime import datetime, timezone
from typing import Any, NamedTuple

import numpy as np

__all__ = [
    "RUN_LOG",
    "SCHEMA_VERSION",
    "STREAM_KEYS",
    "STREAM_METRICS",
    "Sweep",
    "SweepResult",
    "bench_records",
    "provenance",
    "run_sweep",
    "write_bench_json",
]

#: Version of the ``BENCH_sweeps.json`` record layout.  Bump when a field
#: changes meaning; ``tools/bench_diff.py`` parses rows from any version
#: tolerantly (missing fields are never a failure).
#: v2: provenance stamps + telemetry columns (this layer); v1: unstamped.
SCHEMA_VERSION = 2

#: Metrics computed per class (shape ``[n_rates, n_seeds, K]``); everything
#: else must be a scalar field of ``OnlineSimResult`` (``[n_rates, n_seeds]``).
CLASS_METRICS = {
    "class_flowtime": "flow_times",
    "class_slowdown": "slowdowns",
}

#: Streaming-regime metrics (``Sweep.create(stream=...)``): per-cell scalar
#: read-outs of ``engine.StreamResult`` — stationary-window aggregates from
#: the bounded-slot scan, not per-job reductions (there is no per-job array
#: to reduce; that is the point of the regime).
STREAM_METRICS = {
    "stream_flow": "mean_flow",
    "stream_slowdown": "mean_slowdown",
    "stream_completed": "n_window",
    "stream_arrived": "n_arrived_window",
    "stream_blocked": "blocked_steps",
    "stream_occupancy": "occupancy_max",
}

#: ``Sweep.create(stream=...)`` config keys: the slot-pool size and the
#: stationary window as fractions of the tape's nominal span ``n_jobs/rate``
#: (arrivals inside ``[warmup_frac, end_frac] * span`` are measured, so the
#: warm-up ramp and the drain tail are both discarded).
STREAM_KEYS = ("n_slots", "warmup_frac", "end_frac")

#: Estimation-regime arms (see ``benchmarks/estimation.py``): how the policy
#: learns the speedup exponent on a p-drift scenario.
ARMS = ("oracle", "stale", "estimator")


@functools.lru_cache(maxsize=1)
def _build_info() -> dict:
    """The per-process half of the provenance stamp (git SHA + library
    versions are fixed for the process lifetime; the timestamp is not)."""
    import jax

    try:
        import jaxlib

        jaxlib_version = jaxlib.__version__
    except Exception:  # pragma: no cover - jaxlib always ships with jax
        jaxlib_version = None
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=10, check=True,
        ).stdout.strip() or None
    except Exception:
        sha = None  # not a checkout (installed wheel, stripped CI tarball)
    return {
        "git_sha": sha,
        "jax_version": jax.__version__,
        "jaxlib_version": jaxlib_version,
    }


def provenance() -> dict:
    """Provenance stamp for one benchmark record: schema version, git SHA
    (``None`` outside a checkout), jax/jaxlib versions, and the UTC
    creation timestamp — enough to answer "which code produced this row,
    on which stack, when" from the artifact alone."""
    return {
        "schema_version": SCHEMA_VERSION,
        **_build_info(),
        "created_utc": datetime.now(timezone.utc).isoformat(
            timespec="seconds"
        ),
    }


def _hashable(v):
    """Coerce JSON-ish values (lists, dicts, ClassSpec rows) to hashables."""
    if isinstance(v, dict):
        return tuple(sorted((k, _hashable(x)) for k, x in v.items()))
    if isinstance(v, (list, tuple)):
        return tuple(_hashable(x) for x in v)
    return v


class Sweep(NamedTuple):
    """Declarative sweep spec: pure hashable data, no arrays, no closures.

    Use :meth:`Sweep.create` (it normalizes sequences/dicts into the
    hashable tuples jit caching needs).  The spec pins *what* is simulated;
    execution strategy (chunking, sharding) is a :func:`run_sweep` argument
    so the same spec produces identical numbers under any strategy.
    """

    policies: tuple[str, ...]
    rates: tuple[float, ...]
    scenario: str = "poisson"
    scenario_kw: tuple = ()
    n_jobs: int = 1000
    n_seeds: int = 100
    seed: int = 0
    p: float = 0.5
    n_servers: float = 256.0
    size_alpha: float = 1.5
    n_chips: int | None = None
    min_chips: int = 1
    snap_slices: bool = False
    classes: tuple | None = None  # tuple[ClassSpec, ...] for multi-class
    metrics: tuple[str, ...] = ("mean_flowtime",)
    arm: str | None = None  # estimation regime: oracle | stale | estimator
    arm_kw: tuple = ()  # e.g. (("discount", 0.9), ("prior_weight", 1.0))
    fused: bool = False  # kernels/alloc.py fused allocate (quantized heSRPT)
    telemetry: tuple[str, ...] = ()  # in-scan probe metrics -> tel_* columns
    stream: tuple = ()  # bounded-slot regime: (("n_slots", S), ...) kv pairs
    superstep: bool = False  # core/superstep.py closed-form arrival scan

    @classmethod
    def create(
        cls,
        policies,
        rates,
        *,
        scenario: str = "poisson",
        scenario_kw: dict | tuple | None = None,
        n_jobs: int = 1000,
        n_seeds: int = 100,
        seed: int = 0,
        p: float = 0.5,
        n_servers: float = 256.0,
        size_alpha: float = 1.5,
        n_chips: int | None = None,
        min_chips: int = 1,
        snap_slices: bool = False,
        classes=None,
        metrics=None,
        arm: str | None = None,
        arm_kw: dict | tuple | None = None,
        fused: bool = False,
        telemetry=(),
        stream: dict | tuple | None = None,
        superstep: bool = False,
    ) -> "Sweep":
        from repro.core.arrivals import OnlineSimResult
        from repro.core.multiclass import as_specs
        from repro.core.scenarios import _any_pos
        from repro.core.telemetry import DEFAULT_METRICS, METRICS

        if classes is not None:
            classes = as_specs(classes)
        stream = _hashable(stream or {})
        if stream:
            skw = dict(stream)
            unknown_keys = tuple(k for k in skw if k not in STREAM_KEYS)
            if unknown_keys:
                raise ValueError(
                    f"unknown stream key(s) {unknown_keys}; known: {STREAM_KEYS}"
                )
            if "n_slots" not in skw or int(skw["n_slots"]) < 1:
                raise ValueError("stream needs n_slots >= 1 (the slot pool)")
            warm = float(skw.get("warmup_frac", 0.1))
            end = float(skw.get("end_frac", 0.9))
            if not 0.0 <= warm < end:
                raise ValueError(
                    "stream window needs 0 <= warmup_frac < end_frac "
                    f"(got {warm} / {end})"
                )
            if classes is not None or arm is not None:
                raise ValueError(
                    "streaming sweeps are single-class and arm-free — "
                    "per-job class/estimator state does not ride in slots"
                )
            skw_scn = dict(_hashable(scenario_kw or {}))
            if scenario.startswith(("drift_", "multiclass_")) or _any_pos(
                skw_scn.get("sigma_size", 0.0)
            ) or _any_pos(skw_scn.get("sigma_p", 0.0)):
                raise ValueError(
                    "streaming sweeps need a plain tape scenario (no drift, "
                    "classes or estimation noise — see scenarios.stream_tape)"
                )
        if metrics is None:
            if stream:
                metrics = ("stream_flow", "stream_slowdown")
            elif classes is not None:
                metrics = ("mean_flowtime", "mean_slowdown", "class_flowtime",
                           "class_slowdown")
            else:
                metrics = ("mean_flowtime",)
        metrics = tuple(metrics)
        for m in metrics:
            if stream:
                if m not in STREAM_METRICS:
                    raise ValueError(
                        f"metric {m!r} is not a streaming metric; streaming "
                        f"sweeps read {tuple(STREAM_METRICS)}"
                    )
            elif m in STREAM_METRICS:
                raise ValueError(f"metric {m!r} needs a streaming sweep (stream=)")
            elif m in CLASS_METRICS:
                if classes is None:
                    raise ValueError(f"metric {m!r} needs a multi-class sweep")
            elif m not in OnlineSimResult._fields:
                raise ValueError(f"unknown metric {m!r}")
        if arm is not None and arm not in ARMS:
            raise ValueError(f"unknown arm {arm!r}; known: {ARMS}")
        if arm is not None and classes is not None:
            raise ValueError("estimation arms are single-class sweeps")
        if arm is not None and n_chips is not None:
            # The arm cells run the continuous simulators; accepting n_chips
            # would record a "quantized" spec whose physics were continuous.
            raise ValueError("estimation arms are continuous-only (no n_chips)")
        if arm is not None and "p0" not in dict(_hashable(scenario_kw or {})):
            # Without an explicit p0 the stale arm would pin its belief to
            # the generic default ``p`` while the drift sampler uses its
            # OWN p0 default — a silently wrong three-arm comparison.
            raise ValueError(
                "estimation arms need scenario_kw['p0'] (the pre-drift "
                "exponent the stale/estimator arms anchor their belief to)"
            )
        if snap_slices and classes is None:
            raise ValueError("snap_slices is only wired for multi-class sweeps")
        if fused:
            # The fused allocate exists for the quantized heSRPT hot path;
            # continuous heSRPT already dispatches to the (faster) carried-
            # rank scan, and no other policy has a fused variant.
            if classes is not None or arm is not None:
                raise ValueError("fused sweeps are single-class, arm-free")
            if n_chips is None:
                raise ValueError(
                    "fused=True needs n_chips (the quantized regime; "
                    "continuous heSRPT already runs the ranked fast path)"
                )
            bad = tuple(p for p in policies if p != "hesrpt")
            if bad:
                raise ValueError(f"fused sweeps support only heSRPT, got {bad}")
        if superstep:
            # The closed-form superstep path (core/superstep.py) is exact
            # only for the continuous, noise-free, scalar-p rank family;
            # every other regime keeps its per-event scan.
            if classes is not None or arm is not None:
                raise ValueError("superstep sweeps are single-class, arm-free")
            if n_chips is not None:
                raise ValueError(
                    "superstep=True is the continuous closed-form path "
                    "(quantized chips need the per-event scan)"
                )
            if fused or telemetry or stream:
                raise ValueError(
                    "superstep sweeps take no fused/telemetry/stream "
                    "options (all three ride the per-event scan)"
                )
            bad = tuple(q for q in policies if q not in ("hesrpt", "equi",
                                                         "srpt"))
            if bad:
                raise ValueError(
                    f"superstep sweeps support heSRPT/EQUI/SRPT, got {bad}"
                )
            skw_ss = dict(_hashable(scenario_kw or {}))
            if _any_pos(skw_ss.get("sigma_size", 0.0)) or _any_pos(
                skw_ss.get("sigma_p", 0.0)
            ):
                raise ValueError(
                    "superstep sweeps need noise-free scenarios "
                    "(estimation noise takes the generic scan)"
                )
            if scenario.startswith("multiclass_"):
                raise ValueError(
                    "superstep sweeps are single-class (per-job exponents "
                    "take the generic scan)"
                )
        if telemetry is True:
            telemetry = DEFAULT_METRICS
        telemetry = tuple(telemetry or ())
        if telemetry:
            unknown = tuple(m for m in telemetry if m not in METRICS)
            if unknown:
                raise ValueError(
                    f"unknown telemetry metric(s) {unknown}; known: {METRICS}"
                )
            if classes is not None:
                # The multi-class cells run simulate_multiclass, which owns
                # its own engine invocation; telemetry is not threaded
                # through it yet (ROADMAP: windowed per-class aggregates
                # belong to the streaming-engine refactor).
                raise ValueError(
                    "telemetry columns are single-class only for now"
                )
            if "p_hat_err" in telemetry and arm != "estimator":
                raise ValueError(
                    "telemetry metric 'p_hat_err' needs arm='estimator' "
                    "(only an estimating rule carries a p-hat to be wrong)"
                )
        return cls(
            policies=tuple(policies),
            rates=tuple(float(r) for r in rates),
            scenario=scenario,
            scenario_kw=_hashable(scenario_kw or {}),
            n_jobs=int(n_jobs),
            n_seeds=int(n_seeds),
            seed=int(seed),
            p=float(p),
            n_servers=float(n_servers),
            size_alpha=float(size_alpha),
            n_chips=None if n_chips is None else int(n_chips),
            min_chips=int(min_chips),
            snap_slices=bool(snap_slices),
            classes=classes,
            metrics=metrics,
            arm=arm,
            arm_kw=_hashable(arm_kw or {}),
            fused=bool(fused),
            telemetry=telemetry,
            stream=stream,
            superstep=bool(superstep),
        )

    def jobs_per_seed(self) -> int:
        """Simulated jobs one seed contributes across the rate axis."""
        return len(self.rates) * self.n_jobs

    def total_jobs(self) -> int:
        """Simulated jobs in the whole grid, per policy."""
        return self.n_seeds * self.jobs_per_seed()


# --------------------------------------------------------- per-cell functions
def _cell_fn(spec: Sweep, name: str):
    """Build ``one(key, rate) -> tuple_of_metrics`` for one policy.

    These closures are verbatim ports of the per-experiment bodies this
    module replaced (the jit+vmap closures that lived in
    ``core/arrivals.py``, ``core/multiclass.py`` and
    ``benchmarks/estimation.py`` before the refactor) — same sampler
    construction, same fast-path dispatch — which is what lets the
    golden-pin tests demand bit-for-bit f64 agreement with the
    pre-refactor sweeps.
    """
    import jax
    import jax.numpy as jnp

    from repro.core.analysis import per_class_mean
    from repro.core.scenarios import make_scenario

    kw = dict(spec.scenario_kw)

    tel_probe = None
    if spec.telemetry and not spec.stream:
        # O(1) streaming aggregates in the scan carry — the per-cell
        # scalar columns (tel_*_mean / tel_*_max) cost no per-event
        # memory, so telemetry rides along at any sweep scale.
        from repro.core.telemetry import make_probe, p_hat_error_metric

        reader = None
        if spec.arm == "estimator":
            akw_t = dict(spec.arm_kw)
            reader = p_hat_error_metric(
                kw["p0"], prior_weight=akw_t.get("prior_weight", 1.0)
            )
        tel_probe = make_probe(
            spec.telemetry,
            mode="stream",
            alloc_unit=float(spec.n_chips) if spec.n_chips else 1.0,
            n_jobs=spec.n_jobs,
            p_hat_reader=reader,
            dtype=jnp.result_type(float),
        )

    def tel_values(tel):
        from repro.core.telemetry import scalar_values

        return scalar_values(tel, spec.telemetry)

    @jax.named_scope("engine.reduce")
    def metrics_of(res, scn):
        out = []
        for m in spec.metrics:
            if m in CLASS_METRICS:
                out.append(
                    per_class_mean(
                        getattr(res, CLASS_METRICS[m]),
                        scn.class_ids,
                        len(spec.classes),
                    )
                )
            else:
                out.append(getattr(res, m))
        return tuple(out)

    if spec.stream:
        # Bounded-slot regime: same sampler, but the cell runs the O(n_slots)
        # streaming engine and reads stationary-window aggregates instead of
        # whole-tape means.  The window is a fixed fraction of the expected
        # tape span so every (rate, seed) cell discards the same share of
        # warm-up and tail truncation.
        from repro.core import engine
        from repro.core.arrivals import simulate_stream
        from repro.core.policies import make_policy, make_rank_policy
        from repro.core.scenarios import stream_tape

        sampler = make_scenario(
            spec.scenario, size_alpha=spec.size_alpha, p=spec.p, **kw
        )
        skw = dict(spec.stream)
        n_slots = int(skw["n_slots"])
        warm = float(skw.get("warmup_frac", 0.1))
        end = float(skw.get("end_frac", 0.9))
        dtype = jnp.result_type(float)
        # Carried-rank fast path under the same conditions as the finite-tape
        # branch below (telemetry probes need the generic scan's ProbeEvent).
        rank_pol = (
            make_rank_policy(name)
            if spec.n_chips is None and not spec.telemetry and not spec.fused
            else None
        )
        pol = make_policy(
            name,
            n_servers=(
                spec.n_chips if spec.n_chips is not None else spec.n_servers
            ),
        )

        def one(key, rate):
            with jax.named_scope("engine.sample"):
                scn = sampler(key, spec.n_jobs, rate)
            span = spec.n_jobs / rate  # expected arrival span at this rate
            window = (warm * span, end * span)
            probe = None
            if spec.telemetry:
                from repro.core.telemetry import make_probe

                probe = make_probe(
                    spec.telemetry,
                    mode="stream",
                    alloc_unit=float(spec.n_chips) if spec.n_chips else 1.0,
                    n_jobs=n_slots,
                    window=window,
                    dtype=dtype,
                )
            if rank_pol is not None:
                x0, arr = stream_tape(scn)
                res = engine.run_stream_ranked(
                    x0, arr, spec.p, spec.n_servers, rank_pol,
                    n_slots=n_slots, window=window, n_alone=spec.n_servers,
                )
            else:
                res = simulate_stream(
                    scn, spec.p, spec.n_servers, pol, n_slots=n_slots,
                    window=window, n_chips=spec.n_chips,
                    min_chips=spec.min_chips, fused=spec.fused,
                    telemetry=probe,
                )
            out = tuple(
                jnp.asarray(getattr(res, STREAM_METRICS[m]), dtype)
                for m in spec.metrics
            )
            if probe is not None:
                return out + tel_values(res.telemetry)
            return out

        return one

    if spec.classes is not None:
        from repro.core.multiclass import simulate_multiclass

        sampler = make_scenario(
            spec.scenario, size_alpha=spec.size_alpha, p=spec.p,
            classes=spec.classes, **kw,
        )

        def one(key, rate):
            with jax.named_scope("engine.sample"):
                scn = sampler(key, spec.n_jobs, rate)
            res = simulate_multiclass(
                scn,
                classes=spec.classes,
                policy=name,
                n_servers=spec.n_servers,
                n_chips=spec.n_chips,
                min_chips=spec.min_chips,
                snap_slices=spec.snap_slices,
            )
            return metrics_of(res, scn)

        return one

    sampler = make_scenario(
        spec.scenario, size_alpha=spec.size_alpha, p=spec.p, **kw
    )

    if spec.arm is not None:
        from repro.core.arrivals import simulate_scenario
        from repro.core.estimation import simulate_scenario_estimated
        from repro.core.policies import make_policy

        akw = dict(spec.arm_kw)
        p0 = kw["p0"]  # presence enforced by Sweep.create
        pol = make_policy(name, n_servers=spec.n_servers)

        def one(key, rate):
            with jax.named_scope("engine.sample"):
                scn = sampler(key, spec.n_jobs, rate)
            if spec.arm == "oracle":
                # simulate_scenario shows the rule the CURRENT true regime.
                res = simulate_scenario(
                    scn, p0, spec.n_servers, pol, telemetry=tel_probe
                )
            elif spec.arm == "stale":
                # a pinned p_hat: the scheduler never notices the drift.
                res = simulate_scenario(
                    scn._replace(p_hat=jnp.asarray(p0)), p0, spec.n_servers,
                    pol, telemetry=tel_probe,
                )
            else:  # estimator: allocate with the online blended p-hat
                res = simulate_scenario_estimated(
                    scn, p0, spec.n_servers, pol, prior_p=p0,
                    prior_weight=akw.get("prior_weight", 1.0),
                    discount=akw.get("discount", 1.0), telemetry=tel_probe,
                )
            if tel_probe is not None:
                res, tel = res
                return metrics_of(res, scn) + tel_values(tel)
            return metrics_of(res, scn)

        return one

    from repro.core.arrivals import (
        simulate_online_ranked,
        simulate_online_superstep,
        simulate_scenario,
    )
    from repro.core.policies import make_policy, make_rank_policy
    from repro.core.scenarios import _any_pos

    noisy = _any_pos(kw.get("sigma_size", 0.0)) or _any_pos(
        kw.get("sigma_p", 0.0)
    )
    # Sort-free ranked scan where the policy allows it (heSRPT, EQUI,
    # SRPT — ~20x faster at M=1000); generic sort-per-event otherwise.
    # Estimation noise and chip quantization both break the carried-rank
    # invariants; per-job exponents (``p_job``) and p-drift boundaries
    # (``p_drift``) are static per sampler, so the branch is resolved at
    # trace time.  Telemetry probes hook the generic scan's ProbeEvent,
    # so a telemetry sweep takes that path too.  ``spec.superstep``
    # upgrades further, to the closed-form arrival-superstep scan
    # (core/superstep.py — one step per arrival, departures analytic);
    # Sweep.create has already pinned its supported envelope, including
    # scalar-regime drift.
    rank_pol = (
        make_rank_policy(name)
        if spec.n_chips is None and not noisy and not spec.telemetry
        and not spec.superstep
        else None
    )
    pol = make_policy(
        name,
        n_servers=(
            spec.n_chips if spec.n_chips is not None else spec.n_servers
        ),
    )

    def one(key, rate):
        with jax.named_scope("engine.sample"):
            scn = sampler(key, spec.n_jobs, rate)
        if spec.superstep:
            res = simulate_online_superstep(
                scn.x0, scn.arrival_times, spec.p, spec.n_servers, name,
                p_drift=scn.p_drift,
            )
        elif rank_pol is not None and scn.p_job is None and scn.p_drift is None:
            res = simulate_online_ranked(
                scn.x0, scn.arrival_times, spec.p, spec.n_servers, rank_pol
            )
        else:
            res = simulate_scenario(
                scn, spec.p, spec.n_servers, pol, n_chips=spec.n_chips,
                min_chips=spec.min_chips, fused=spec.fused,
                telemetry=tel_probe,
            )
            if tel_probe is not None:
                res, tel = res
                return metrics_of(res, scn) + tel_values(tel)
        return metrics_of(res, scn)

    return one


# ------------------------------------------------------------- the executors
def _metric_ndim(spec: Sweep, metric: str) -> int:
    """Trailing rank of one cell's value for ``metric`` (0 or 1)."""
    return 1 if metric in CLASS_METRICS else 0


def _out_names(spec: Sweep) -> tuple[str, ...]:
    """Every stat column one cell emits: the simulator metrics followed by
    the telemetry scalar columns (``tel_<metric>_mean`` / ``_max``)."""
    from repro.core.telemetry import scalar_columns

    return spec.metrics + scalar_columns(spec.telemetry)


def _build_fn(
    spec: Sweep, name: str, chunk: int | None, shard: bool,
    shard_axis: str = "seeds",
):
    """The pure ``(keys, rates) -> tuple_of_metric_arrays`` a policy runs.

    ``keys`` (or ``rates``, under ``shard_axis="rates"``) may be padded to
    the shard grid; each metric comes back ``[n_rates, len(keys)(, K)]``.
    """
    import jax
    import jax.numpy as jnp

    one = _cell_fn(spec, name)

    def inner(keys, rates):
        # One vmap over the flat (rate, seed) lanes, rate-major, not a
        # vmap over rates of a vmap over seeds.  Every per-cell reduction
        # is then a [lanes, jobs] -> [lanes] reduce whatever slice of the
        # grid a device holds; the TPU compiler lowered the nested
        # [rates, seeds, jobs] form differently for a one-rate shard, and
        # its means came out a few ulp off the whole grid's.  The price is
        # compile time on the TPU, which grows with the count of lanes that
        # carry their own key: R x S lanes compile like R*S seeds.
        R, S = rates.shape[0], keys.shape[0]
        lane_keys = jnp.broadcast_to(keys, (R, *keys.shape))
        lane_keys = lane_keys.reshape(R * S, *keys.shape[1:])
        out = jax.vmap(one)(lane_keys, jnp.repeat(rates, S))
        return tuple(a.reshape(R, S, *a.shape[1:]) for a in out)

    def over_seeds(keys, rates):
        # Rate-axis shards see a slice of the rate grid, so the rate count
        # comes from the argument, not the spec.
        R = rates.shape[0]
        s_local = keys.shape[0]
        if chunk is None or chunk >= s_local:
            return inner(keys, rates)
        n_chunks = -(-s_local // chunk)
        pad = n_chunks * chunk - s_local
        kp = jnp.concatenate([keys, keys[:1].repeat(pad, axis=0)]) if pad else keys
        kc = kp.reshape(n_chunks, chunk, *keys.shape[1:])
        # lax.map: one chunk of seeds resident at a time — the memory
        # budget — while each chunk still runs the full vmap'd grid.
        outs = jax.lax.map(lambda k: inner(k, rates), kc)
        return tuple(
            jnp.moveaxis(a, 0, 1).reshape(R, n_chunks * chunk, *a.shape[3:])[
                :, :s_local
            ]
            for a in outs
        )

    if not shard:
        return over_seeds

    from jax.sharding import Mesh, PartitionSpec as P

    devices = np.asarray(jax.devices())
    mesh = Mesh(devices, (shard_axis,))
    if shard_axis == "rates":
        # Wide load grids: split the rate axis, replicate seeds.  Metric
        # arrays are [n_rates, n_seeds(, K)], so the sharded axis leads.
        in_specs = (P(), P("rates"))
        out_specs = tuple(
            P("rates", None, *(None,) * _metric_ndim(spec, m))
            for m in _out_names(spec)
        )
    else:
        in_specs = (P("seeds"), P())
        out_specs = tuple(
            P(None, "seeds", *(None,) * _metric_ndim(spec, m))
            for m in _out_names(spec)
        )

    def sharded(keys, rates):
        # Each device runs its slice of the grid alone: no collective runs
        # inside the scan, so there is no cross-device value for the
        # varying-manual-axes check to track.  With the check on, the
        # scan's carry (device-invariant initial state) and its output
        # (varying) get different types and tracing fails.
        return jax.shard_map(
            over_seeds,
            mesh=mesh,
            in_specs=in_specs,
            out_specs=out_specs,
            check_vma=False,
        )(keys, rates)

    return sharded


# Compiled-executor cache: one AOT-compiled callable per (spec-sans-policies,
# policy, padded seed count, chunk, shard) — repeat run_sweep calls (and the
# benchmarks' warmup-before-timing idiom) reuse it instead of recompiling.
# Bounded like the lru_cache(64) it replaced: oldest entry evicted first
# (dict preserves insertion order), so long-lived processes sweeping many
# distinct configs plateau instead of accumulating executables forever.
_EXECUTORS: dict[tuple, Any] = {}
_EXECUTORS_MAX = 64


def _executor(spec: Sweep, name: str, keys, rates, chunk: int | None,
              shard: bool, shard_axis: str = "seeds"):
    """Return ``(compiled, compile_seconds)`` for one policy column."""
    import jax

    cache_key = (
        spec._replace(policies=()), name, int(keys.shape[0]),
        int(rates.shape[0]), chunk, shard, shard_axis,
        str(keys.dtype), str(rates.dtype),
    )
    hit = _EXECUTORS.get(cache_key)
    if hit is not None:
        # LRU refresh: re-insert so hot executors survive the eviction
        # sweep below (dict preserves insertion order).
        _EXECUTORS[cache_key] = _EXECUTORS.pop(cache_key)
        return hit, 0.0
    f = _build_fn(spec, name, chunk, shard, shard_axis)
    t0 = time.perf_counter()
    compiled = jax.jit(f).lower(keys, rates).compile()
    compile_s = time.perf_counter() - t0
    while len(_EXECUTORS) >= _EXECUTORS_MAX:
        _EXECUTORS.pop(next(iter(_EXECUTORS)))
    _EXECUTORS[cache_key] = compiled
    return compiled, compile_s


def resolve_chunk(spec: Sweep, chunk_seeds: int | None,
                  max_jobs_in_flight: int | None) -> int | None:
    """Seed-chunk size from an explicit count or a jobs-in-flight budget.

    The inner vmap materializes ``chunk * n_rates * n_jobs`` jobs at once;
    ``max_jobs_in_flight`` caps that product (floor: one seed per chunk).
    """
    if chunk_seeds is not None and max_jobs_in_flight is not None:
        raise ValueError("pass chunk_seeds or max_jobs_in_flight, not both")
    if max_jobs_in_flight is not None:
        return max(1, int(max_jobs_in_flight) // spec.jobs_per_seed())
    return None if chunk_seeds is None else max(1, int(chunk_seeds))


class SweepResult(NamedTuple):
    """A completed sweep: the spec, per-seed stats, and how it ran.

    ``stats[policy][metric]`` is a numpy array ``[n_rates, n_seeds]`` (or
    ``[n_rates, n_seeds, K]`` for per-class metrics).  ``compile_s`` is 0.0
    when every executor was already cached.  Serializes with
    :meth:`to_json` / :meth:`from_json` (exact float round-trip) and
    compacts to a ``BENCH_sweeps.json`` record with :meth:`record`.

    ``spec`` is normally a :class:`Sweep`; benchmarks whose grid is not a
    (policies x rates x seeds) sweep — e.g. ``benchmarks/sched_scale.py``
    times decision epochs over job counts M — report through the same
    container with a plain params dict carrying a ``"kind"`` tag (their
    ``stats`` rows are then indexed by that grid instead of rates).
    """

    spec: "Sweep | dict"
    stats: dict[str, dict[str, np.ndarray]]
    wall_s: float
    compile_s: float
    backend: str
    device_count: int
    chunk_seeds: int | None
    sharded: bool

    # ------------------------------------------------------------ read-outs
    def per_seed(self, policy: str, metric: str | None = None) -> np.ndarray:
        metric = metric or self.spec.metrics[0]
        return self.stats[policy][metric]

    def cell_means(self, metric: str | None = None) -> dict:
        """``{rate: {policy: mean-over-seeds}}`` — the ``load_sweep`` shape."""
        metric = metric or self.spec.metrics[0]
        out: dict[float, dict[str, float]] = {}
        for ri, rate in enumerate(self.spec.rates):
            out[float(rate)] = {
                name: float(np.mean(self.stats[name][metric][ri]))
                for name in self.spec.policies
            }
        return out

    # -------------------------------------------------------- serialization
    def _spec_jsonable(self) -> dict:
        if isinstance(self.spec, dict):
            return dict(self.spec)
        d = self.spec._asdict()
        d["scenario_kw"] = [list(kv) for kv in self.spec.scenario_kw]
        d["arm_kw"] = [list(kv) for kv in self.spec.arm_kw]
        d["stream"] = [list(kv) for kv in self.spec.stream]
        if self.spec.classes is not None:
            d["classes"] = [list(c) for c in self.spec.classes]
        d["policies"] = list(self.spec.policies)
        d["rates"] = list(self.spec.rates)
        d["metrics"] = list(self.spec.metrics)
        return d

    def record(self) -> dict:
        """Compact JSON-able record (per-cell mean/std, not per-seed rows) —
        the unit ``BENCH_sweeps.json`` accumulates."""
        from repro.core.analysis import seed_axis_stats

        cells = {
            name: {metric: seed_axis_stats(a) for metric, a in by_m.items()}
            for name, by_m in self.stats.items()
        }
        is_sweep = isinstance(self.spec, Sweep)
        return {
            "kind": "sweep" if is_sweep else self.spec.get("kind", "bench"),
            "provenance": provenance(),
            "spec": self._spec_jsonable(),
            "cells": cells,
            "n_seeds": self.spec.n_seeds if is_sweep else None,
            "total_jobs": (
                self.spec.total_jobs() * len(self.spec.policies)
                if is_sweep else None
            ),
            "wall_s": self.wall_s,
            "compile_s": self.compile_s,
            "backend": self.backend,
            "device_count": self.device_count,
            "chunk_seeds": self.chunk_seeds,
            "sharded": self.sharded,
        }

    def to_json(self) -> str:
        """Full serialization including the per-seed arrays (exact float
        round-trip: ``json`` emits ``repr`` floats)."""
        return json.dumps(
            {
                "spec": self._spec_jsonable(),
                "stats": {
                    name: {m: a.tolist() for m, a in by_m.items()}
                    for name, by_m in self.stats.items()
                },
                "wall_s": self.wall_s,
                "compile_s": self.compile_s,
                "backend": self.backend,
                "device_count": self.device_count,
                "chunk_seeds": self.chunk_seeds,
                "sharded": self.sharded,
            }
        )

    @classmethod
    def from_json(cls, text: str) -> "SweepResult":
        d = json.loads(text)
        s = d["spec"]
        if "policies" not in s:  # dict-spec result (e.g. sched_scale)
            return cls(
                spec=s,
                stats={
                    name: {
                        m: np.asarray(v, dtype=np.float64)
                        for m, v in by_m.items()
                    }
                    for name, by_m in d["stats"].items()
                },
                wall_s=d["wall_s"], compile_s=d["compile_s"],
                backend=d["backend"], device_count=d["device_count"],
                chunk_seeds=d["chunk_seeds"], sharded=d["sharded"],
            )
        spec = Sweep.create(
            s["policies"], s["rates"], scenario=s["scenario"],
            scenario_kw=dict((k, _hashable(v)) for k, v in s["scenario_kw"]),
            n_jobs=s["n_jobs"], n_seeds=s["n_seeds"], seed=s["seed"],
            p=s["p"], n_servers=s["n_servers"], size_alpha=s["size_alpha"],
            n_chips=s["n_chips"], min_chips=s["min_chips"],
            snap_slices=s["snap_slices"], classes=s["classes"],
            metrics=s["metrics"], arm=s["arm"],
            arm_kw=dict((k, _hashable(v)) for k, v in s["arm_kw"]),
            fused=s.get("fused", False),
            telemetry=s.get("telemetry", ()),
            stream=dict((k, _hashable(v)) for k, v in s.get("stream", [])),
            superstep=s.get("superstep", False),
        )
        stats = {
            name: {m: np.asarray(v, dtype=np.float64) for m, v in by_m.items()}
            for name, by_m in d["stats"].items()
        }
        return cls(
            spec=spec, stats=stats, wall_s=d["wall_s"],
            compile_s=d["compile_s"], backend=d["backend"],
            device_count=d["device_count"], chunk_seeds=d["chunk_seeds"],
            sharded=d["sharded"],
        )


#: Every ``run_sweep`` (and ``benchmarks/sched_scale.py``) appends its
#: compact record here; ``benchmarks/run.py`` flushes it to
#: ``BENCH_sweeps.json``.  Process-scoped by design (a benchmark run is
#: one fresh process) and bounded: long-lived sessions hammering
#: ``load_sweep`` keep only the most recent records.
RUN_LOG: list[dict] = []
RUN_LOG_MAX = 512

_CALLS = itertools.count()  # request ids of run_sweep calls in this process


def bench_records() -> list[dict]:
    return list(RUN_LOG)


def write_bench_json(path: str = "BENCH_sweeps.json") -> str:
    """Flush the run log to ``path`` (the perf-trajectory artifact)."""
    with open(path, "w") as f:
        json.dump(
            {"schema_version": SCHEMA_VERSION, "records": RUN_LOG}, f, indent=1
        )
    return path


def run_sweep(
    spec: Sweep,
    *,
    chunk_seeds: int | None = None,
    max_jobs_in_flight: int | None = None,
    shard: bool = False,
    shard_axis: str = "seeds",
    log: bool = True,
) -> SweepResult:
    """Execute a :class:`Sweep`: one compiled device call per policy.

    Seeds are shared across rates and policies (paired sample paths), so
    "policy A beats policy B at every load" is tested on identical draws.

    ``chunk_seeds`` / ``max_jobs_in_flight`` bound memory by running the
    seed axis in ``lax.map`` chunks (identical results); ``shard=True``
    additionally splits one grid axis across ``jax.devices()`` with
    ``shard_map`` (identical results; pass it on multi-device hosts).
    ``shard_axis`` picks that axis: ``"seeds"`` (default) or ``"rates"``
    for very wide load grids with few seeds (the accelerator-lane shape,
    ``benchmarks/backend_lane.py``).  ``log=False`` keeps the run out of
    :data:`RUN_LOG` (used by tests).
    """
    import jax
    import jax.numpy as jnp

    from repro import tracing

    if shard_axis not in ("seeds", "rates"):
        raise ValueError(f"shard_axis must be 'seeds' or 'rates', not {shard_axis!r}")
    call = next(_CALLS)
    with tracing.span("sweep.prepare", request=call):
        chunk = resolve_chunk(spec, chunk_seeds, max_jobs_in_flight)
        keys = jax.random.split(jax.random.PRNGKey(spec.seed), spec.n_seeds)
        rates = jnp.asarray(spec.rates, dtype=jnp.result_type(float))

        n_dev = jax.device_count() if shard else 1
        S = spec.n_seeds
        R = len(spec.rates)
        if shard and shard_axis == "rates":
            # Pad the rate grid to the device count; padded rows are sliced
            # off below.  Seeds stay whole per device.
            r_pad = -(-R // n_dev) * n_dev
            if r_pad > R:
                rates = jnp.concatenate([rates, rates[:1].repeat(r_pad - R)])
            if chunk is not None and chunk >= S:
                chunk = None
        else:
            s_pad = -(-S // n_dev) * n_dev  # shard grid; chunk pads inside it
            if s_pad > S:
                keys = jnp.concatenate([keys, keys[:1].repeat(s_pad - S, axis=0)])
            if chunk is not None and chunk >= s_pad // n_dev:
                chunk = None  # one chunk == the plain vmap; share its executor

    stats: dict[str, dict[str, np.ndarray]] = {}
    compile_s = 0.0
    wall_s = 0.0
    for step, name in enumerate(spec.policies):
        with tracing.span("sweep.executor", request=call):
            f, c_s = _executor(spec, name, keys, rates, chunk, shard, shard_axis)
        compile_s += c_s
        t0 = time.perf_counter()
        # StepTraceAnnotation is a no-op unless a jax.profiler trace is
        # active (``benchmarks/run.py --profile-dir``); under one, each
        # policy's executor shows up as its own named step in the
        # Perfetto/TensorBoard timeline.
        with jax.profiler.StepTraceAnnotation(
            "run_sweep", step_num=step, policy=name, scenario=spec.scenario
        ):
            with tracing.span("sweep.dispatch", request=call) as traced:
                out = f(keys, rates)
            if traced:
                # Split the wait from the copies only when it is recorded.
                # The copies are queued first, as np.asarray alone queues
                # them: a copy asked for after the wait would cost one more
                # round trip between host and device per call.
                for a in out:
                    a.copy_to_host_async()
                with tracing.span("sweep.wait", request=call):
                    jax.block_until_ready(out)
            with tracing.span("sweep.fetch", request=call):
                out = tuple(np.asarray(a) for a in out)
        wall_s += time.perf_counter() - t0
        stats[name] = {
            m: a[:R, :S] for m, a in zip(_out_names(spec), out, strict=True)
        }
    result = SweepResult(
        spec=spec,
        stats=stats,
        wall_s=wall_s,
        compile_s=compile_s,
        backend=jax.default_backend(),
        device_count=jax.device_count(),
        chunk_seeds=chunk,
        sharded=shard,
    )
    if log:
        RUN_LOG.append(result.record())
        del RUN_LOG[:-RUN_LOG_MAX]
    return result
