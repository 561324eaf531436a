"""Closed-form superstep path (core/superstep.py) vs the generic engine.

The superstep scan advances one *arrival* (or drift boundary) per step and
resolves every departure in between analytically, so its completion times
must agree with the generic per-event scan wherever the closed form is
valid: continuous allocation, uniform scalar ``p`` per regime, the rank
family (heSRPT / EQUI / SRPT).  The contract under test:

- every registered single-class scenario x policy agrees <= 1e-10;
- the batch closed form is *exact* against Theorem 3 / Theorem 8 (and the
  weighted Thm-8 analogue) in float64;
- tie semantics match the generic scan (heSRPT/EQUI exactly; SRPT up to a
  permutation within the tied group, so sorted times agree);
- every unsupported configuration raises at trace time with a message
  pointing back at the generic scan.

Hypothesis twins live in tests/test_superstep_properties.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine as eng
from repro.core.arrivals import simulate_online_superstep, simulate_scenario
from repro.core.flowtime import (
    hesrpt_completion_times,
    hesrpt_total_flowtime,
    weighted_total_flowtime,
)
from repro.core.policies import make_policy, weighted_hesrpt
from repro.core.scenarios import make_scenario
from repro.core.simulator import simulate
from repro.core.superstep import (
    SUPERSTEP_POLICIES,
    batch_result_closed_form,
    run_superstep,
)
from repro.core.sweeps import Sweep, run_sweep


SCENARIO_NAMES = (
    "batch", "poisson", "deterministic", "bursty",
    "drift_poisson", "drift_bursty",
)
POLICIES = ("hesrpt", "equi", "srpt")


def _generic(x0, arr, p, n, pol, **kw):
    rule = eng.continuous_rule(
        make_policy(pol), n_servers=n, dtype=jnp.float64
    )
    return eng.run(x0, arr, p, rule, **kw)


def _assert_times_match(pol, got, want, tol=1e-10):
    got, want = np.asarray(got), np.asarray(want)
    if pol == "srpt":
        # SRPT breaks remaining-size ties arbitrarily (generic argmin vs
        # superstep rank order); totals are exchange-invariant within the
        # tied group, so compare the sorted spectra.
        got, want = np.sort(got), np.sort(want)
    np.testing.assert_allclose(got, want, rtol=0, atol=tol)


@pytest.mark.parametrize("pol", POLICIES)
@pytest.mark.parametrize("scenario", SCENARIO_NAMES)
def test_matches_generic_on_registry(scenario, pol):
    """Superstep == generic scan on every registered continuous scenario."""
    sampler = make_scenario(scenario)
    for seed in (0, 1):
        scn = sampler(jax.random.PRNGKey(seed), 40, 1.2)
        gen = _generic(
            scn.x0, scn.arrival_times, 0.5, 8, pol, p_drift=scn.p_drift
        )
        ss = run_superstep(
            scn.x0, scn.arrival_times, 0.5, 8, pol, p_drift=scn.p_drift
        )
        _assert_times_match(pol, ss.completion_times, gen.completion_times)


@pytest.mark.parametrize("p", [0.25, 0.5, 0.9])
def test_batch_closed_form_thm3_exact(p):
    """Batch completion times == Theorem 3, same floats (both closed form)."""
    x = jnp.sort(
        jax.random.uniform(
            jax.random.PRNGKey(2), (64,), dtype=jnp.float64,
            minval=0.05, maxval=5.0,
        )
    )[::-1]
    bc = batch_result_closed_form(x, p, "hesrpt", n_servers=16)
    thm3 = hesrpt_completion_times(x, p, 16)
    np.testing.assert_array_equal(
        np.asarray(bc.completion_times), np.asarray(thm3)
    )
    # Theorem 8: the sum is the optimal total flow time.
    np.testing.assert_allclose(
        float(jnp.sum(bc.completion_times)),
        float(hesrpt_total_flowtime(x, p, 16)),
        rtol=1e-13,
    )


def test_batch_closed_form_weighted_thm8():
    """Weighted batch times reproduce the weighted Thm-8 total and the
    event-driven simulator, for Berg-style slowdown weights (w = 1/x —
    the non-increasing-in-size envelope where the closed form is valid)."""
    x = jnp.sort(
        jax.random.uniform(
            jax.random.PRNGKey(3), (40,), dtype=jnp.float64,
            minval=0.1, maxval=3.0,
        )
    )[::-1]
    w = 1.0 / x
    bc = batch_result_closed_form(
        x, 0.5, "weighted_hesrpt", n_servers=8, weights=w
    )
    np.testing.assert_allclose(
        float(jnp.sum(w * bc.completion_times)),
        float(weighted_total_flowtime(x, w, 0.5, 8)),
        rtol=1e-13,
    )
    res = simulate(x, 0.5, 8, lambda xs, ps: weighted_hesrpt(xs, ps, w))
    np.testing.assert_allclose(
        np.asarray(bc.completion_times),
        np.asarray(res.completion_times),
        rtol=0, atol=1e-10,
    )


def test_batch_trajectory_sizes_at():
    """x_i(t): exact at t=0, zero past the makespan, non-increasing, and
    self-consistent — restarting the batch from a snapshot at time t
    reproduces the original completion times shifted by t."""
    x = jnp.sort(
        jax.random.uniform(
            jax.random.PRNGKey(4), (20,), dtype=jnp.float64,
            minval=0.2, maxval=4.0,
        )
    )[::-1]
    p, n = 0.5, 8.0
    bc = batch_result_closed_form(x, p, "hesrpt", n_servers=n)
    t_mid = 0.4 * float(jnp.max(bc.completion_times))
    ev = jnp.array([0.0, t_mid, 2.0 * float(jnp.max(bc.completion_times))])
    bct = batch_result_closed_form(x, p, "hesrpt", n_servers=n, eval_times=ev)
    np.testing.assert_array_equal(np.asarray(bct.sizes_at[0]), np.asarray(x))
    assert float(jnp.max(bct.sizes_at[2])) == 0.0
    assert bool(jnp.all(bct.sizes_at[1] <= bct.sizes_at[0] + 1e-12))
    # Memorylessness of the allocation: survivors at t_mid, restarted as a
    # fresh batch, finish at (T_i - t_mid).
    x_mid = bct.sizes_at[1]
    bc2 = batch_result_closed_form(x_mid, p, "hesrpt", n_servers=n)
    alive = np.asarray(x_mid) > 0
    np.testing.assert_allclose(
        np.asarray(bc2.completion_times)[alive],
        np.asarray(bc.completion_times)[alive] - t_mid,
        rtol=0, atol=1e-10,
    )


def test_batch_t0_offset_and_zero_sizes():
    """t0 shifts all finite times; zero-size jobs stay at 0.0 (the generic
    engine never activates them)."""
    x = jnp.array([3.0, 2.0, 0.0, 1.0, 0.0], dtype=jnp.float64)
    bc = batch_result_closed_form(x, 0.5, "hesrpt", n_servers=4, t0=7.0)
    t = np.asarray(bc.completion_times)
    assert t[2] == 0.0 and t[4] == 0.0
    assert np.all(t[[0, 1, 3]] > 7.0)
    bc0 = batch_result_closed_form(x, 0.5, "hesrpt", n_servers=4)
    np.testing.assert_allclose(
        t[[0, 1, 3]], np.asarray(bc0.completion_times)[[0, 1, 3]] + 7.0,
        rtol=0, atol=1e-12,
    )


@pytest.mark.parametrize("pol", POLICIES)
def test_exact_size_ties(pol):
    """Exact remaining-size ties: heSRPT/EQUI agree job-for-job with the
    generic scan; SRPT agrees up to permutation within the tied group."""
    x = jnp.array(
        [2.0, 2.0, 2.0, 1.0, 1.0, 3.0, 0.5, 0.5], dtype=jnp.float64
    )
    arr = jnp.array(
        [0.0, 0.0, 0.3, 0.3, 0.7, 0.7, 1.1, 1.1], dtype=jnp.float64
    )
    gen = _generic(x, arr, 0.5, 4, pol)
    ss = run_superstep(x, arr, 0.5, 4, pol)
    _assert_times_match(pol, ss.completion_times, gen.completion_times)


@pytest.mark.parametrize("pol", POLICIES)
def test_simultaneous_arrival_and_departure(pol):
    """An arrival landing exactly on another job's departure instant: both
    scans fire the departure at the arrival time."""
    from repro.core.flowtime import speedup

    n, p = 4.0, 0.5
    # Lone job of size 1 departs at exactly 1/s(N); schedule the second
    # arrival there.
    t_dep = float(1.0 / speedup(jnp.asarray(n), p))
    x = jnp.array([1.0, 2.0], dtype=jnp.float64)
    arr = jnp.array([0.0, t_dep], dtype=jnp.float64)
    gen = _generic(x, arr, p, n, pol)
    ss = run_superstep(x, arr, p, n, pol)
    _assert_times_match(pol, ss.completion_times, gen.completion_times)
    np.testing.assert_allclose(
        float(ss.completion_times[0]), t_dep, rtol=0, atol=1e-12
    )


@pytest.mark.parametrize("pol", POLICIES)
def test_pre_arrived_scanless_path(pol):
    """pre_arrived=True without drift takes the zero-scan batch closed form
    and still matches the generic engine."""
    x = jax.random.uniform(
        jax.random.PRNGKey(5), (30,), dtype=jnp.float64,
        minval=0.1, maxval=2.0,
    )
    arr = jnp.zeros_like(x)
    gen = _generic(x, arr, 0.5, 8, pol, pre_arrived=True)
    ss = run_superstep(x, arr, 0.5, 8, pol, pre_arrived=True)
    _assert_times_match(pol, ss.completion_times, gen.completion_times)


def test_engine_run_superstep_dispatch():
    """engine.run(superstep=True) routes to run_superstep and agrees with
    the same call on the generic path."""
    x = jax.random.uniform(
        jax.random.PRNGKey(6), (25,), dtype=jnp.float64,
        minval=0.1, maxval=2.0,
    )
    arr = jnp.sort(
        jax.random.uniform(jax.random.PRNGKey(7), (25,), dtype=jnp.float64)
        * 4.0
    )
    rule = eng.continuous_rule(
        make_policy("hesrpt"), n_servers=8, dtype=jnp.float64
    )
    gen = eng.run(x, arr, 0.5, rule)
    ss = eng.run(x, arr, 0.5, rule, superstep=True)
    np.testing.assert_allclose(
        np.asarray(ss.completion_times),
        np.asarray(gen.completion_times),
        rtol=0, atol=1e-10,
    )


def test_simulate_online_superstep_metrics():
    """The arrivals-layer wrapper reproduces simulate_scenario's metrics."""
    sampler = make_scenario("poisson")
    scn = sampler(jax.random.PRNGKey(8), 40, 1.0)
    base = simulate_scenario(scn, 0.5, 8, make_policy("hesrpt"))
    ss = simulate_online_superstep(
        scn.x0, scn.arrival_times, 0.5, 8, "hesrpt"
    )
    np.testing.assert_allclose(
        float(ss.mean_flowtime), float(base.mean_flowtime), rtol=1e-10
    )


def test_sweep_superstep_equivalence_and_roundtrip():
    """Sweep.create(superstep=True) matches the plain sweep cell-for-cell
    and survives the JSON round-trip."""
    kw = dict(
        scenario="poisson", policies=("hesrpt", "srpt"), rates=(0.8,),
        n_jobs=30, n_seeds=2, p=0.5, n_servers=8,
    )
    plain = run_sweep(Sweep.create(**kw))
    ss = run_sweep(Sweep.create(**kw, superstep=True))
    for pol in kw["policies"]:
        for m, v in plain.stats[pol].items():
            np.testing.assert_allclose(
                np.asarray(ss.stats[pol][m]), np.asarray(v), rtol=1e-9
            )
    rt = type(ss).from_json(ss.to_json())
    assert rt.spec.superstep is True
    assert type(plain).from_json(plain.to_json()).spec.superstep is False


# ---------------------------------------------------------------------------
# Trace-time rejection: every documented fallback raises before compiling.
# ---------------------------------------------------------------------------

def _x_arr(m=6):
    x = jnp.linspace(1.0, 2.0, m, dtype=jnp.float64)
    return x, jnp.zeros_like(x)


def test_raises_quantized_rule():
    x, arr = _x_arr()
    rule = eng.quantized_rule(
        make_policy("hesrpt", n_servers=4), n_chips=4, dtype=jnp.float64
    )
    with pytest.raises(ValueError, match="generic per-event scan"):
        eng.run(x, arr, 0.5, rule, superstep=True)


def test_raises_record_and_telemetry():
    x, arr = _x_arr()
    rule = eng.continuous_rule(
        make_policy("hesrpt"), n_servers=4, dtype=jnp.float64
    )
    with pytest.raises(ValueError, match="generic per-event scan"):
        eng.run(x, arr, 0.5, rule, superstep=True, record=True)


def test_raises_per_job_p():
    x, arr = _x_arr()
    rule = eng.continuous_rule(
        make_policy("hesrpt"), n_servers=4, dtype=jnp.float64
    )
    with pytest.raises(ValueError, match="scalar p"):
        eng.run(x, arr, jnp.full(x.shape, 0.5), rule, superstep=True)


def test_raises_estimating_rule():
    x, arr = _x_arr()
    rule = eng.continuous_rule(
        make_policy("hesrpt"), n_servers=4, dtype=jnp.float64,
        p_hat=jnp.asarray(0.4),
    )
    with pytest.raises(ValueError, match="generic per-event scan"):
        eng.run(x, arr, 0.5, rule, superstep=True)


def test_raises_unknown_policy_and_missing_weights():
    x, arr = _x_arr()
    with pytest.raises(ValueError, match="superstep path supports"):
        run_superstep(x, arr, 0.5, 4, "knee")
    with pytest.raises(ValueError, match="weights"):
        run_superstep(x, arr, 0.5, 4, "weighted_hesrpt")
    assert set(SUPERSTEP_POLICIES) == {
        "hesrpt", "equi", "srpt", "weighted_hesrpt"
    }


def test_sweep_create_rejects_unsupported():
    kw = dict(
        scenario="poisson", policies=("hesrpt",), rates=(0.8,),
        n_jobs=10, n_seeds=1, p=0.5, n_servers=8,
    )
    with pytest.raises(ValueError, match="continuous closed-form"):
        Sweep.create(**kw, superstep=True, n_chips=8)
    with pytest.raises(ValueError, match="heSRPT/EQUI/SRPT"):
        Sweep.create(**dict(kw, policies=("knee",)), superstep=True)
    with pytest.raises(ValueError, match="noise-free"):
        Sweep.create(
            **kw, superstep=True, scenario_kw={"sigma_size": 0.1}
        )
    with pytest.raises(ValueError, match="single-class"):
        Sweep.create(
            **dict(kw, scenario="multiclass_poisson"), superstep=True
        )


# ---------------------------------------------------------------------------
# Seeded fuzz (non-hypothesis twin of test_superstep_properties.py).
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", range(4))
def test_fuzz_random_instances(seed):
    """Random sizes/arrivals (with deliberate duplicates) across all three
    policies and two exponents."""
    key = jax.random.PRNGKey(100 + seed)
    kx, ka, kd = jax.random.split(key, 3)
    m = 24
    x = jax.random.uniform(kx, (m,), dtype=jnp.float64, minval=0.05,
                           maxval=4.0)
    # Force duplicate sizes and coincident arrivals half the time.
    x = x.at[1].set(x[0]).at[5].set(x[4])
    arr = jnp.sort(
        jnp.round(
            jax.random.uniform(ka, (m,), dtype=jnp.float64) * 6.0, 1
        )
    )
    for pol in POLICIES:
        for p in (0.3, 0.7):
            gen = _generic(x, arr, p, 8, pol)
            ss = run_superstep(x, arr, p, 8, pol)
            _assert_times_match(pol, ss.completion_times,
                                gen.completion_times)
