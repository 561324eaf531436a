"""The bounded-slot streaming engine: reduction, recycling, windows.

Three layers of proof, mirroring the engine's own contract:

- **Reduction (golden pin)**: with ``n_slots >= n_jobs`` the slot pool
  never recycles, so ``run_stream`` / ``run_stream_ranked`` must
  reproduce ``run`` / ``run_ranked`` *bit-for-bit* on the same tape —
  continuous, quantized, fused and stateful rules alike.  Any drift
  means the refactor changed the physics, not just the memory layout.
- **Recycling**: with ``n_slots`` far below the job count the engine
  defers admissions instead of dropping them; completion order, blocked
  accounting and the windowed aggregates must match the per-event Python
  ``ClusterScheduler`` oracle on the same tape.
- **Slot invariance**: telemetry's time-weighted aggregates and the
  windowed flow/slowdown sums are functions of the *active set*, never
  of which slot a job happens to sit in — so any two pools wide enough
  to avoid blocking must agree exactly (hypothesis property + seeded
  regression twin).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine, make_policy, make_rank_policy, make_scenario
from repro.core.scenarios import stream_tape
from repro.core.telemetry import make_probe, scalar_values

N_JOBS = 40


def _tape(seed=0, n_jobs=N_JOBS, rate=2.0, p=0.5):
    scn = make_scenario("poisson", p=p)(jax.random.key(seed), n_jobs, rate)
    return scn.x0, scn.arrival_times


def _rule(kind, dtype, n_chips=16):
    pol = make_policy("hesrpt")
    if kind == "continuous":
        return engine.continuous_rule(pol, 1.0, dtype=dtype), False
    if kind == "quantized":
        return engine.quantized_rule(pol, n_chips, dtype=dtype), False
    if kind == "fused":
        return engine.quantized_rule(pol, n_chips, dtype=dtype), True
    if kind == "knee":
        knee = make_policy("knee", n_servers=1.0)
        return engine.continuous_rule(knee, 1.0, dtype=dtype), False
    raise AssertionError(kind)


# ------------------------------------------------------- reduction golden pin
@pytest.mark.parametrize("kind", ["continuous", "quantized", "fused", "knee"])
@pytest.mark.parametrize("seed", [0, 3])
def test_run_stream_reduces_to_run_bitforbit(kind, seed):
    x0, arr = _tape(seed)
    rule, fused = _rule(kind, x0.dtype)
    ref = engine.run(x0, arr, 0.5, rule, fused=fused)
    res = engine.run_stream(
        x0, arr, 0.5, rule, n_slots=N_JOBS, record_times=True, fused=fused,
    )
    np.testing.assert_array_equal(
        np.asarray(res.completion_times), np.asarray(ref.completion_times)
    )
    assert int(res.n_admitted) == N_JOBS
    assert int(res.n_completed) == N_JOBS
    assert int(res.blocked_steps) == 0
    assert not np.any(np.asarray(res.x_final))


@pytest.mark.parametrize("name", ["hesrpt", "srpt", "equi"])
def test_run_stream_ranked_reduces_to_run_ranked_bitforbit(name):
    x0, arr = _tape(seed=1)
    ref = engine.run_ranked(x0, arr, 0.5, 1.0, make_rank_policy(name))
    res = engine.run_stream_ranked(
        x0, arr, 0.5, 1.0, make_rank_policy(name), n_slots=N_JOBS,
        record_times=True,
    )
    np.testing.assert_array_equal(np.asarray(res.completion_times),
                                  np.asarray(ref))


def test_ranked_and_generic_streams_agree_under_recycling():
    x0, arr = _tape(seed=2, n_jobs=80)
    rule, _ = _rule("continuous", x0.dtype)
    span = float(arr[-1])
    window = (0.1 * span, 0.9 * span)
    a = engine.run_stream(x0, arr, 0.5, rule, n_slots=12, window=window)
    b = engine.run_stream_ranked(
        x0, arr, 0.5, 1.0, make_rank_policy("hesrpt"), n_slots=12,
        window=window,
    )
    np.testing.assert_allclose(float(a.mean_flow), float(b.mean_flow),
                               rtol=1e-9)
    assert int(a.n_window) == int(b.n_window)
    assert int(a.blocked_steps) == int(b.blocked_steps)
    assert int(a.occupancy_max) == int(b.occupancy_max)


# ------------------------------------------------------ recycling vs oracle
def test_recycled_stream_matches_python_oracle_window():
    from benchmarks.arrivals import run_stream_reference, stream_trace

    arr_np, x_np = stream_trace(100, rate=2.0, seed=5)
    span = float(arr_np[-1])
    window = (0.1 * span, 0.9 * span)
    in_w = (arr_np >= window[0]) & (arr_np < window[1])
    dtype = jnp.result_type(float)
    pol = make_policy("hesrpt", n_servers=64)
    for quantize in (False, True):
        rule = (
            engine.quantized_rule(pol, 64, dtype=dtype) if quantize
            else engine.continuous_rule(pol, 64, dtype=dtype)
        )
        res = engine.run_stream(
            jnp.asarray(x_np, dtype), jnp.asarray(arr_np, dtype), 0.5, rule,
            n_slots=16, window=window, n_alone=64,
        )
        flows = run_stream_reference("hesrpt", arr_np, x_np, p=0.5,
                                     n_chips=64, quantize=quantize)
        assert int(res.n_window) == int(in_w.sum())
        np.testing.assert_allclose(
            float(res.mean_flow), float(np.mean(flows[in_w])), rtol=1e-9,
        )


def test_blocked_arrival_defers_not_drops():
    # One slot, two unit jobs: the second arrives at t=0.1 into a full
    # pool, waits for the slot, and its flow time counts the wait.
    x0 = jnp.asarray([1.0, 1.0])
    arr = jnp.asarray([0.0, 0.1])
    rule, _ = _rule("continuous", x0.dtype)
    res = engine.run_stream(x0, arr, 0.5, rule, n_slots=1, horizon=8,
                            record_times=True)
    np.testing.assert_allclose(np.asarray(res.completion_times), [1.0, 2.0],
                               rtol=1e-12)
    assert int(res.n_admitted) == 2 and int(res.n_completed) == 2
    assert int(res.blocked_steps) >= 1
    assert int(res.occupancy_max) == 1
    # windowed flow counts from TRUE arrival: job 2 waited 0.9 in the queue
    assert float(res.flow_sum) == pytest.approx(1.0 + 1.9, rel=1e-12)


def test_poisson_source_runs_unbounded():
    dtype = jnp.result_type(float)
    rule, _ = _rule("continuous", dtype)
    src = engine.poisson_source(jax.random.key(0), 1.5, dtype=dtype)
    res = engine.run_stream_source(src, 0.5, rule, n_slots=8, n_events=400)
    assert int(res.n_completed) > 50
    assert int(res.occupancy_max) <= 8
    assert int(res.n_admitted) >= int(res.n_completed)
    assert float(res.t_final) > 0


# ------------------------------------------------- slot-placement invariance
def _invariance_pair(x0, arr, window, wide, narrow):
    """Run the same tape through two non-blocking pool widths with a
    telemetry probe; aggregates must not see the slot layout."""
    rule, _ = _rule("continuous", x0.dtype)
    out = []
    for n_slots in (wide, narrow):
        probe = make_probe(("utilization", "queue"), mode="stream",
                           n_jobs=n_slots, window=window, dtype=x0.dtype)
        res = engine.run_stream(x0, arr, 0.5, rule, n_slots=n_slots,
                                window=window, telemetry=probe)
        assert int(res.blocked_steps) == 0, "pool too narrow for the pin"
        out.append(res)
    return out


def _assert_invariant(a, b):
    np.testing.assert_allclose(float(a.mean_flow), float(b.mean_flow),
                               rtol=1e-12)
    np.testing.assert_allclose(float(a.mean_slowdown), float(b.mean_slowdown),
                               rtol=1e-12)
    assert int(a.n_window) == int(b.n_window)
    assert int(a.n_arrived_window) == int(b.n_arrived_window)
    for m in ("utilization", "queue"):
        np.testing.assert_allclose(
            float(a.telemetry.aggregates[f"{m}_mean"]),
            float(b.telemetry.aggregates[f"{m}_mean"]), rtol=1e-12,
        )
        np.testing.assert_allclose(
            float(a.telemetry.aggregates[f"{m}_max"]),
            float(b.telemetry.aggregates[f"{m}_max"]), rtol=1e-12,
        )
        # histograms are time-weighted masses over the same trajectory;
        # the queue support is sized by n_jobs=n_slots, so compare the
        # slot-size-independent utilization one bin-for-bin
        if m == "utilization":
            np.testing.assert_allclose(
                np.asarray(a.telemetry.aggregates[f"{m}_hist"]),
                np.asarray(b.telemetry.aggregates[f"{m}_hist"]), atol=1e-12,
            )


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_telemetry_invariant_to_slot_placement_seeded(seed):
    x0, arr = _tape(seed=seed, n_jobs=30, rate=1.0)
    span = float(arr[-1])
    window = (0.1 * span, 0.9 * span)
    probe_res = engine.run_stream(
        x0, arr, 0.5, _rule("continuous", x0.dtype)[0], n_slots=30,
    )
    narrow = max(int(probe_res.occupancy_max), 2)
    a, b = _invariance_pair(x0, arr, window, 30, narrow)
    _assert_invariant(a, b)


def test_telemetry_invariant_to_slot_placement_property():
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    @hyp.given(seed=st.integers(0, 2**16), rate=st.floats(0.5, 3.0))
    @hyp.settings(max_examples=15, deadline=None)
    def check(seed, rate):
        x0, arr = _tape(seed=seed, n_jobs=16, rate=rate)
        span = float(arr[-1])
        window = (0.2 * span, 0.8 * span)
        first = engine.run_stream(
            x0, arr, 0.5, _rule("continuous", x0.dtype)[0], n_slots=16,
        )
        narrow = max(int(first.occupancy_max), 2)
        a, b = _invariance_pair(x0, arr, window, 16, narrow)
        _assert_invariant(a, b)

    check()


def test_windowed_probe_counts_only_window_time():
    # One job, size 4, rate 1: active over [0, 4); the window [1, 3)
    # must contribute exactly 2.0 of time mass regardless of the tail.
    x0 = jnp.asarray([4.0])
    arr = jnp.asarray([0.0])
    rule, _ = _rule("continuous", x0.dtype)
    probe = make_probe(("utilization",), mode="stream", n_jobs=1,
                       window=(1.0, 3.0), dtype=x0.dtype)
    res = engine.run_stream(x0, arr, 0.5, rule, n_slots=1, telemetry=probe)
    assert float(res.telemetry.aggregates["time"]) == pytest.approx(2.0)
    un = make_probe(("utilization",), mode="stream", n_jobs=1, dtype=x0.dtype)
    res2 = engine.run_stream(x0, arr, 0.5, rule, n_slots=1, telemetry=un)
    assert float(res2.telemetry.aggregates["time"]) == pytest.approx(4.0)


def test_stream_telemetry_is_neutral():
    x0, arr = _tape(seed=4)
    rule, _ = _rule("continuous", x0.dtype)
    plain = engine.run_stream(x0, arr, 0.5, rule, n_slots=10)
    probe = make_probe(("utilization",), mode="stream", n_jobs=10,
                       dtype=x0.dtype)
    with_tel = engine.run_stream(x0, arr, 0.5, rule, n_slots=10,
                                 telemetry=probe)
    np.testing.assert_array_equal(np.asarray(plain.x_final),
                                  np.asarray(with_tel.x_final))
    assert float(plain.mean_flow) == float(with_tel.mean_flow)
    vals = scalar_values(with_tel.telemetry, ("utilization",))
    assert all(np.isfinite(float(v)) for v in vals)


# ----------------------------------------------------- sweep-layer threading
def test_streaming_sweep_end_to_end_and_roundtrip():
    from repro.core.sweeps import (
        STREAM_METRICS, Sweep, SweepResult, run_sweep,
    )

    spec = Sweep.create(
        ["hesrpt", "helrpt"], [1.0, 4.0], n_jobs=60, n_seeds=2,
        stream={"n_slots": 12},
        metrics=tuple(STREAM_METRICS),
    )
    res = run_sweep(spec, log=False)
    for name in spec.policies:
        for m in spec.metrics:
            assert res.stats[name][m].shape == (2, 2)
        assert np.all(res.stats[name]["stream_flow"] > 0)
        assert np.all(res.stats[name]["stream_occupancy"] <= 12)
    back = SweepResult.from_json(res.to_json())
    assert back.spec == spec
    rec = res.record()
    assert dict(rec["spec"]["stream"])["n_slots"] == 12


def test_simulate_stream_quantized_plumbing():
    from repro.core.arrivals import simulate_stream

    scn = make_scenario("poisson", p=0.5)(jax.random.key(0), 50, 2.0)
    res = simulate_stream(scn, 0.5, 1.0, make_policy("hesrpt", n_servers=32),
                          n_slots=10, n_chips=32)
    assert int(res.n_completed) > 0
    assert int(res.occupancy_max) <= 10


# ------------------------------------------------------------- validation
def test_stream_rejects_per_job_p():
    # A tape's per-job exponents ride in the slots (run_stream); a source
    # brings them with its arrivals, and the carried-rank scan needs one p.
    x0, arr = _tape(seed=0, n_jobs=8)
    rule, _ = _rule("continuous", x0.dtype)
    p_job = jnp.full(8, 0.5)
    src = engine.poisson_source(jax.random.key(0), 1.5, dtype=x0.dtype)
    with pytest.raises(ValueError, match="scalar p"):
        engine.run_stream_source(src, p_job, rule, n_slots=8, n_events=16)
    with pytest.raises(ValueError, match="scalar p"):
        engine.run_stream_ranked(x0, arr, p_job, 1.0,
                                 make_rank_policy("hesrpt"), n_slots=8)


def test_stream_tape_rejects_non_slot_state():
    scn = make_scenario("poisson", p=0.5)(jax.random.key(0), 8, 1.0)
    x0, arr = stream_tape(scn)
    assert x0.shape == (8,) and arr.shape == (8,)
    noisy = scn._replace(size_factors=jnp.ones(8))
    with pytest.raises(ValueError, match="estimation noise"):
        stream_tape(noisy)
    classed = scn._replace(p_job=jnp.full(8, 0.5))
    with pytest.raises(ValueError, match="per-job class"):
        stream_tape(classed)


def test_window_is_stream_mode_only():
    with pytest.raises(ValueError, match="stream-mode only"):
        make_probe(("utilization",), mode="series", window=(0.0, 1.0))


# ------------------------------------------- finite tapes through a slot pool
def _pool_case(kind):
    """A seeded tape, a whole-chip rule over it and the pool width the
    sweep would pick (``sweeps._pool_slots``), at a load whose most jobs
    in flight the pool holds."""
    from repro.core import multiclass as mc

    dtype = jnp.result_type(float)
    if kind == "capped_snap":
        classes = (mc.ClassSpec(p=0.3, mix=0.8, min_chips=1, max_chips=8),
                   mc.ClassSpec(p=0.6, mix=0.17, size_scale=8.0, min_chips=8,
                                max_chips=64),
                   mc.ClassSpec(p=0.9, mix=0.03, size_scale=64.0, min_chips=64,
                                max_chips=256))
        scn = make_scenario("multiclass_poisson", classes=classes)(
            jax.random.key(11), 400, 9.99)
        order = jnp.argsort(scn.arrival_times)
        lo, hi = mc.job_limits(classes, scn.class_ids, n_chips=256)
        rule = mc.class_rule("hesrpt_pc", n_servers=256.0, n_chips=256,
                             snap_slices=True, dtype=dtype, lo=lo[order],
                             hi=hi[order])
        return scn, scn.p_job, rule, 256, scn.class_ids, len(classes)
    scn = make_scenario("poisson", p=0.5, sigma_size=0.3 if kind == "noisy" else 0.0)(
        jax.random.key(7), 120, 2.0)
    factors = None
    if kind == "noisy":
        factors = scn.size_factors[jnp.argsort(scn.arrival_times)]
    rule = engine.quantized_rule(make_policy("hesrpt"), 16, min_chips=2, dtype=dtype,
                                 size_factors=factors)
    return scn, 0.5, rule, 16, None, 0


@pytest.mark.parametrize("kind", ["quantized_min_chips", "capped_snap", "noisy"])
def test_pooled_tape_equals_the_full_tape(kind):
    from repro.core.analysis import per_class_mean
    from repro.core.arrivals import _finalize, run_pooled

    scn, p, rule, n_slots, cls, n_classes = _pool_case(kind)
    x0, arr = scn.x0, scn.arrival_times
    assert n_slots < x0.shape[0]
    ref = engine.run(x0, arr, p, rule)
    res = engine.run_stream(x0, arr, p, rule, n_slots=n_slots, n_alone=n_slots,
                            record_times=True, class_ids=cls, n_classes=n_classes)
    assert int(res.blocked_steps) == 0 and 1 < int(res.occupancy_max) <= n_slots
    # The trajectory: every job leaves at the same time, bit for bit.
    np.testing.assert_array_equal(np.asarray(res.completion_times),
                                  np.asarray(ref.completion_times))
    # The pooled read-out sums the same per-job values as they finish; only
    # the order of the additions differs from the full tape's reduction.
    full = _finalize(x0, arr, ref.completion_times, p, n_slots)
    pooled = run_pooled(x0, arr, p, rule, n_slots=n_slots, n_alone=n_slots,
                        rel_tol=1e-9, horizon=None, class_ids=cls,
                        n_classes=n_classes)
    assert not bool(pooled.overflow)
    for m in ("mean_flowtime", "mean_slowdown", "total_flowtime", "makespan"):
        np.testing.assert_allclose(float(getattr(pooled, m)), float(getattr(full, m)),
                                   rtol=1e-13, err_msg=m)
    if cls is not None:
        for m, per_job in (("class_flowtime", full.flow_times),
                           ("class_slowdown", full.slowdowns)):
            np.testing.assert_allclose(np.asarray(getattr(pooled, m)),
                                       np.asarray(per_class_mean(per_job, cls, n_classes)),
                                       rtol=1e-13, err_msg=m)


def test_overflowing_pool_is_played_again_on_the_full_tape(monkeypatch):
    from repro import tracing
    from repro.core import sweeps

    spec = sweeps.Sweep.create(("hesrpt", "equi"), (2.0, 6.0), n_jobs=60, n_seeds=2,
                               p=0.5, n_servers=16.0, n_chips=16, min_chips=2,
                               metrics=("mean_flowtime", "mean_slowdown"))
    assert sweeps._pool_slots(spec) == 16
    monkeypatch.setattr(sweeps, "_EXECUTORS", {})
    monkeypatch.setattr(sweeps, "_FALLBACKS", {})
    monkeypatch.setattr(sweeps, "_pool_slots", lambda s: None)
    full = sweeps.run_sweep(spec, log=False)
    # A pool of 3 slots overflows at these loads: each policy's call is
    # played again on the full-tape executor, compiled only then.
    monkeypatch.setattr(sweeps, "_pool_slots", lambda s: 3)
    before = tracing.counts().get("engine.pool_overflows", 0)
    sweeps._EXECUTORS.clear()
    again = sweeps.run_sweep(spec, log=False)
    assert tracing.counts()["engine.pool_overflows"] - before == 2
    assert len(sweeps._FALLBACKS) == 2
    for name in spec.policies:
        for m in spec.metrics:
            np.testing.assert_array_equal(again.stats[name][m], full.stats[name][m])
    # A pool that holds compiles no fallback and counts nothing.
    monkeypatch.setattr(sweeps, "_pool_slots", lambda s: 16)
    monkeypatch.setattr(sweeps, "_FALLBACKS", {})
    before = tracing.counts().get("engine.pool_overflows", 0)
    light = sweeps.Sweep.create(("hesrpt",), (1.0,), n_jobs=60, n_seeds=2, p=0.5,
                                n_servers=16.0, n_chips=16, min_chips=2)
    sweeps.run_sweep(light, log=False)
    assert tracing.counts().get("engine.pool_overflows", 0) == before
    assert sweeps._FALLBACKS == {}


@pytest.mark.parametrize("kw", [
    dict(n_chips=None),  # continuous rules (the carried-rank scan)
    dict(fused=True),
    dict(telemetry=("utilization",)),
    dict(scenario="batch"),  # every job in flight at once
    dict(scenario="drift_poisson", scenario_kw={"p0": 0.8}),
    dict(n_jobs=16),  # no narrower than the tape
    dict(metrics=("flow_times",)),  # a per-job metric
])
def test_bypassed_sweeps_keep_the_full_tape(kw):
    from repro.core import sweeps

    base = dict(n_jobs=600, n_seeds=2, p=0.5, n_servers=256.0, n_chips=256)
    assert sweeps._pool_slots(sweeps.Sweep.create(("hesrpt",), (76.8,), **base)) == 256
    spec = sweeps.Sweep.create(("hesrpt",), (76.8,), **{**base, **kw})
    assert sweeps._pool_slots(spec) is None


@pytest.mark.parametrize("kw", [dict(fused=True), dict(telemetry="probe"),
                                dict(n_chips=None), dict(drift=True)])
def test_bypassed_simulations_keep_the_full_tape(kw):
    from repro.core.arrivals import OnlineSimResult, PoolSimResult, _scenario

    scn = make_scenario("poisson", p=0.5)(jax.random.key(0), 40, 2.0)
    args = dict(n_chips=16, min_chips=1, rel_tol=1e-9, horizon=None, fused=False,
                telemetry=None)
    pol = make_policy("hesrpt")
    assert isinstance(_scenario(scn, 0.5, 16.0, pol, n_slots=8, **args), PoolSimResult)
    if kw.pop("drift", False):
        scn = scn._replace(p_drift=engine.PDrift(times=jnp.asarray([5.0]),
                                                 values=jnp.asarray([0.5, 0.3])))
    probe = kw.get("telemetry") == "probe"
    if probe:
        kw["telemetry"] = make_probe(("utilization",), mode="stream", n_jobs=40,
                                     dtype=scn.x0.dtype)
    res = _scenario(scn, 0.5, 16.0, pol, n_slots=8, **{**args, **kw})
    assert isinstance(res[0] if probe else res, OnlineSimResult)
