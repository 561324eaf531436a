"""In-scan telemetry probes: per-event derived metrics inside the jit.

The paper's whole argument is a *trajectory* claim — heSRPT trades
momentary system efficiency for size-order completions at every instant
(Thm 3's epoch structure) — yet until this module the repo could only
observe end-of-run scalars, or dump the raw ``record=True`` trace and
post-process it on the host (O(events × jobs) memory, a non-starter for
2M-job sweeps).  A *probe* composes with the engine's event scan
(``core/engine.py``: ``run(telemetry=)``) and computes derived metrics at
every epoch, **inside** the compiled scan:

- ``efficiency`` — the paper's system efficiency ``Σ θ_i^p``
  (:func:`~repro.core.analysis.system_efficiency`), total service rate
  relative to embarrassingly-parallel capacity;
- ``utilization`` — allocated fraction of the system, ``Σ θ_i``;
- ``queue`` — active-job count (arrived, unfinished);
- ``entropy`` — allocation entropy ``-Σ s_i ln s_i`` of the allocation
  shares (0 = one job holds everything, ``ln m`` = EQUI split);
- ``p_hat_err`` — absolute error of the online speedup-exponent estimate
  under an estimating rule (``core/estimation.py``), read from the rule's
  scan-carried :class:`~repro.core.estimation.EstState` without the rule
  knowing it is being watched.

Two accumulation modes, one probe contract (:class:`Probe` — ``init`` /
``step`` / ``finalize``, mirroring the engine's ``StatefulRule`` shape):

- ``mode="series"`` — the full per-event time series ``[E]`` per metric
  (plus epoch starts and lengths).  Memory is O(events × metrics): right
  for ``record=True``-sized runs, and what the Perfetto exporter
  (``core/trace_export.py``) turns into counter tracks.
- ``mode="stream"`` — O(1) streaming aggregates carried through the scan:
  time-weighted means (``Σ m·dt / Σ dt``), maxima over positive-length
  epochs, and fixed-bin time-weighted histograms via scatter-add
  (``hist.at[bin].add(dt)``).  Memory is independent of the event count,
  so 2M-job sweeps get telemetry columns (``core/sweeps.py``:
  ``Sweep.create(telemetry=)``) for the cost of a few carried scalars.

Both modes share the same metric functions, so the streaming aggregates
are checkable against the series (tests do exactly that).  Probes never
touch the trajectory: ``run(telemetry=None)`` compiles to the identical
probe-free program, and with a probe attached the dynamics ops are
unchanged — golden pins hold either way.
"""

from __future__ import annotations

import math
from collections.abc import Callable
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.analysis import system_efficiency
from repro.core.engine import ProbeEvent

#: Metrics every probe knows how to derive from a :class:`ProbeEvent`.
#: ``p_hat_err`` additionally needs the estimating-rule reader
#: (:func:`p_hat_error_metric`) wired in via ``make_probe(p_hat_reader=)``.
METRICS = ("efficiency", "utilization", "queue", "entropy", "p_hat_err")

#: The default metric set (``p_hat_err`` is opt-in — it only means
#: something under an estimating rule).
DEFAULT_METRICS = ("efficiency", "utilization", "queue", "entropy")


class Probe(NamedTuple):
    """The engine-facing probe contract: ``(init, step, finalize)``.

    ``init()`` builds the carried accumulator pytree; ``step(state, ev)``
    folds one :class:`~repro.core.engine.ProbeEvent` and returns
    ``(new_state, per_event_out)`` (``()`` in stream mode); ``finalize
    (final_state, stacked_outs)`` shapes the post-scan read-out — still
    inside the jit, pure pytree work.  Build instances with
    :func:`make_probe`.
    """

    init: Callable[[], Any]
    step: Callable[[Any, ProbeEvent], tuple[Any, Any]]
    finalize: Callable[[Any, Any], Any]


class TelemetryResult(NamedTuple):
    """What a probe hands back on ``EngineResult.telemetry``.

    Exactly one of ``series`` / ``aggregates`` is populated (by mode).
    ``series`` maps ``"t"`` / ``"dt"`` and each metric name to ``[E]``
    arrays (event order, no-op tail epochs carry ``dt == 0``).
    ``aggregates`` maps ``"time"`` (total simulated span) and, per metric
    ``m``, ``"{m}_mean"`` (time-weighted), ``"{m}_max"`` and ``"{m}_hist"``
    (``[bins]`` time-weighted occupancy).  ``hist_edges`` carries the
    static ``[bins+1]`` bin edges per metric in stream mode.
    """

    series: dict[str, jax.Array] | None
    aggregates: dict[str, jax.Array] | None
    hist_edges: dict[str, jax.Array] | None


def _true_p_scalar(ev: ProbeEvent) -> jax.Array:
    """The scalar truth an estimator is judged against: the active-job mean
    of the per-job exponent (a no-op for the paper's scalar ``p``)."""
    p = jnp.asarray(ev.p)
    if p.ndim == 0:
        return p
    n = jnp.maximum(jnp.sum(ev.active), 1)
    return jnp.sum(jnp.where(ev.active, p, 0.0)) / n


def p_hat_error_metric(prior_p, *, prior_weight=1.0) -> Callable:
    """Reader for the ``p_hat_err`` metric under ``estimating_rule``.

    Recomputes the blended p̂ the rule allocates with (same read-out the
    rule itself uses: work-weighted over active jobs, same prior blend)
    from the rule state the engine exposes on the probe event, and returns
    ``|p̂ - p_true|`` with the *current* true exponent — under drift the
    error is against the regime in effect, which is what "did the
    estimator track the change" means.
    """
    from repro.core.estimation import blended_p_hat

    def err(ev: ProbeEvent) -> jax.Array:
        x_act = jnp.where(ev.active, ev.x, 0.0)
        p_hat = blended_p_hat(
            ev.rule_state, x_act, prior_p, prior_weight=prior_weight
        )
        return jnp.abs(p_hat - _true_p_scalar(ev))

    return err


def _metric_fns(
    metrics, alloc_unit: float, p_hat_reader: Callable | None
) -> dict[str, Callable]:
    """Bind the metric functions; ``alloc_unit`` converts the event's
    allocation to theta shares (1.0 for continuous rules — alloc *is*
    theta — and ``n_chips`` for quantized rules)."""

    def theta_of(ev):
        return ev.alloc.astype(ev.x.dtype) / alloc_unit

    def efficiency(ev):
        return system_efficiency(theta_of(ev), ev.p)

    def utilization(ev):
        return jnp.sum(theta_of(ev))

    def queue(ev):
        return jnp.sum(ev.active).astype(ev.x.dtype)

    def entropy(ev):
        th = theta_of(ev)
        tot = jnp.sum(th)
        s = th / jnp.maximum(tot, jnp.finfo(th.dtype).tiny)
        return -jnp.sum(jnp.where(s > 0, s * jnp.log(jnp.where(s > 0, s, 1.0)), 0.0))

    fns: dict[str, Callable] = {
        "efficiency": efficiency,
        "utilization": utilization,
        "queue": queue,
        "entropy": entropy,
    }
    out = {}
    for name in metrics:
        if name == "p_hat_err":
            if p_hat_reader is None:
                raise ValueError(
                    "metric 'p_hat_err' needs p_hat_reader= (built with "
                    "p_hat_error_metric; only meaningful under an "
                    "estimating rule)"
                )
            out[name] = p_hat_reader
        elif name in fns:
            out[name] = fns[name]
        else:
            raise ValueError(f"unknown telemetry metric {name!r}; known: {METRICS}")
    return out


def default_hist_ranges(n_jobs: int) -> dict[str, tuple[float, float]]:
    """Static histogram supports per metric, sized to the job count.

    ``efficiency``'s upper bound ``m^{1-p}`` is taken at the paper's
    reference ``p = 0.5`` (``sqrt(m)``); runs with much smaller ``p``
    should pass their own range — out-of-range values clip into the edge
    bins, they are never dropped.
    """
    m = max(int(n_jobs), 1)
    return {
        "efficiency": (0.0, float(m) ** 0.5),
        "utilization": (0.0, 1.0),
        "queue": (0.0, float(m)),
        # math.log, not jnp.log: this must stay a Python float so probes can
        # be built inside a jitted cell (a staged constant is not float()-able)
        "entropy": (0.0, math.log(max(m, 2))),
        "p_hat_err": (0.0, 1.0),
    }


def make_probe(
    metrics=DEFAULT_METRICS,
    *,
    mode: str = "stream",
    alloc_unit: float = 1.0,
    n_jobs: int | None = None,
    hist_bins: int = 32,
    hist_ranges: dict[str, tuple[float, float]] | None = None,
    p_hat_reader: Callable | None = None,
    window: tuple[Any, Any] | None = None,
    dtype=jnp.float64,
) -> Probe:
    """Build a :class:`Probe` for ``engine.run(telemetry=)``.

    ``metrics`` is an ordered subset of :data:`METRICS`; ``alloc_unit``
    is 1.0 for continuous rules and ``n_chips`` for quantized rules (the
    divisor that turns the event's allocation back into theta shares).
    ``mode="series"`` emits ``[E]`` per-event arrays; ``mode="stream"``
    carries O(1) aggregates (``n_jobs`` then sizes the default histogram
    supports; override any of them with ``hist_ranges``).  ``dtype`` is
    the accumulator dtype — match the engine's (f64 under the benchmark
    x64 flag) so time weights don't lose precision against it.

    ``window=(lo, hi)`` (stream mode; values may be traced scalars)
    restricts every time weight to the stationary window: each epoch
    contributes ``|[t, t+dt) ∩ [lo, hi)|`` instead of ``dt``, so means,
    maxima and histogram mass describe the windowed span only — the
    warm-up (and drain) transients of a streaming run are discarded
    without a second pass.  An epoch *straddling* an edge contributes
    exactly its overlap.  ``window=None`` is byte-identical to the
    pre-window probe (the branch resolves at trace time).
    """
    metrics = tuple(metrics)
    if mode not in ("series", "stream"):
        raise ValueError(f"mode must be 'series' or 'stream', not {mode!r}")
    if window is not None and mode != "stream":
        raise ValueError(
            "window= is stream-mode only (a series is windowed host-side)"
        )
    fns = _metric_fns(metrics, float(alloc_unit), p_hat_reader)

    if mode == "series":

        def init_series():
            return ()

        def step_series(state, ev: ProbeEvent):
            vals = tuple(fns[m](ev).astype(dtype) for m in metrics)
            return state, (ev.t.astype(dtype), ev.dt.astype(dtype), *vals)

        def finalize_series(state, outs):
            series = {"t": outs[0], "dt": outs[1]}
            for i, m in enumerate(metrics):
                series[m] = outs[2 + i]
            return TelemetryResult(
                series=series, aggregates=None, hist_edges=None
            )

        return Probe(init=init_series, step=step_series, finalize=finalize_series)

    if n_jobs is None:
        raise ValueError("mode='stream' needs n_jobs (default hist supports)")
    ranges = dict(default_hist_ranges(n_jobs))
    ranges.update(hist_ranges or {})
    B = int(hist_bins)

    def init_stream():
        state: dict[str, Any] = {"t_sum": jnp.zeros((), dtype)}
        for m in metrics:
            state[m] = {
                "wsum": jnp.zeros((), dtype),
                "max": jnp.full((), -jnp.inf, dtype),
                "hist": jnp.zeros(B, dtype),
            }
        return state

    if window is not None:
        w_lo, w_hi = window
        w_lo = jnp.asarray(w_lo, dtype)
        w_hi = jnp.asarray(w_hi, dtype)

    def step_stream(state, ev: ProbeEvent):
        dt = ev.dt.astype(dtype)
        if window is not None:  # overlap of [t, t+dt) with the window
            t_ev = ev.t.astype(dtype)
            dt = jnp.clip(
                jnp.minimum(t_ev + dt, w_hi) - jnp.maximum(t_ev, w_lo),
                0.0, None,
            )
        live = dt > 0  # no-op tail epochs and zero-length arrival batches
        new: dict[str, Any] = {"t_sum": state["t_sum"] + dt}
        for m in metrics:
            v = fns[m](ev).astype(dtype)
            lo, hi = ranges[m]
            span = max(hi - lo, 1e-12)
            vb = jnp.where(live, v, lo)  # keep the index finite on no-ops
            b = jnp.clip(((vb - lo) / span * B).astype(jnp.int32), 0, B - 1)
            s = state[m]
            new[m] = {
                "wsum": s["wsum"] + v * dt,
                "max": jnp.maximum(s["max"], jnp.where(live, v, -jnp.inf)),
                "hist": s["hist"].at[b].add(dt),
            }
        return new, ()

    def finalize_stream(state, outs):
        t = state["t_sum"]
        agg: dict[str, jax.Array] = {"time": t}
        edges: dict[str, jax.Array] = {}
        for m in metrics:
            s = state[m]
            agg[f"{m}_mean"] = s["wsum"] / jnp.maximum(
                t, jnp.finfo(dtype).tiny
            )
            mx = s["max"]
            agg[f"{m}_max"] = jnp.where(jnp.isfinite(mx), mx, 0.0)
            agg[f"{m}_hist"] = s["hist"]
            lo, hi = ranges[m]
            edges[m] = jnp.linspace(lo, hi, B + 1, dtype=dtype)
        return TelemetryResult(series=None, aggregates=agg, hist_edges=edges)

    return Probe(init=init_stream, step=step_stream, finalize=finalize_stream)


def scalar_columns(metrics) -> tuple[str, ...]:
    """The per-cell column names a stream probe contributes to a sweep:
    time-weighted mean and max per metric (histograms stay out of the
    sweep artifact — they are per-run read-outs, not per-cell scalars)."""
    names: list[str] = []
    for m in tuple(metrics):
        names.append(f"tel_{m}_mean")
        names.append(f"tel_{m}_max")
    return tuple(names)


def scalar_values(tel: TelemetryResult, metrics) -> tuple[jax.Array, ...]:
    """The values matching :func:`scalar_columns`, from a stream result."""
    if tel.aggregates is None:
        raise ValueError("scalar_values needs a stream-mode TelemetryResult")
    out: list[jax.Array] = []
    for m in tuple(metrics):
        out.append(tel.aggregates[f"{m}_mean"])
        out.append(tel.aggregates[f"{m}_max"])
    return tuple(out)


__all__ = [
    "DEFAULT_METRICS",
    "METRICS",
    "Probe",
    "TelemetryResult",
    "default_hist_ranges",
    "make_probe",
    "p_hat_error_metric",
    "scalar_columns",
    "scalar_values",
]
