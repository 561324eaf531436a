"""The unified allocation engine: wrapper equivalences, quantized
trajectories vs the ClusterScheduler oracle, scenario registry.

The batch/online wrappers were refactored onto ``core/engine.py`` with a
bit-for-bit guarantee (verified against the pre-refactor implementations
when the refactor landed); these tests keep that contract enforceable:

- batch ``simulate`` and online ``simulate_online`` at t=0 are the *same*
  scan and must agree exactly (not approximately);
- a golden f64 trajectory pins the online wrapper against silent drift
  (tolerance 1e-13: elementwise ops are deterministic, but libm pow may
  differ in the last ulp across platforms);
- the quantized engine in float32 stays within one chip of the float64
  ``ClusterScheduler`` loop (the float64 event-for-event contract is
  tests/test_contract_live.py).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    engine,
    make_policy,
    make_scenario,
    simulate,
    simulate_online,
    simulate_online_quantized,
    simulate_scenario,
    trace_scenario,
)
from repro.sched import ClusterScheduler, Job

POLICIES = ("hesrpt", "equi", "srpt")


# ------------------------------------------------------ wrapper equivalences
@pytest.mark.parametrize("name", POLICIES + ("helrpt",))
def test_batch_wrapper_is_online_wrapper_at_t0_exactly(name):
    """One engine: the batch scan is the online scan with every job
    pre-arrived, so at t=0 the two wrappers must agree bit-for-bit."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.pareto(1.5, 20) + 1.0)
    pol = make_policy(name, n_servers=256.0)
    batch = simulate(x, 0.5, 256.0, pol)
    online = simulate_online(x, jnp.zeros(20), 0.5, 256.0, pol)
    np.testing.assert_array_equal(np.asarray(batch.completion_times),
                                  np.asarray(online.completion_times))
    np.testing.assert_array_equal(np.asarray(batch.makespan),
                                  np.asarray(online.makespan))


def test_online_wrapper_golden_trajectory_f64():
    """Regression pin: completion times of a fixed 10-job heSRPT stream,
    recorded from the pre-refactor ``simulate_online`` (f64)."""
    x = jnp.asarray([1.488817, 1.081145, 1.182775, 1.227906, 1.063113,
                     4.795832, 17.443706, 1.10859, 1.393492, 1.734739])
    arr = jnp.asarray([0.355747, 0.501643, 1.153774, 1.341068, 1.644977,
                       1.968636, 2.445131, 2.503631, 2.705213, 2.81598])
    golden = np.array([
        0.5480690341836435, 0.6599991420918218, 1.301620875, 1.49455625,
        1.7778661249999999, 2.6006604927609605, 4.982769206018355,
        2.695985347983885, 2.9209760889648533, 3.1013667034775536,
    ])
    res = simulate_online(x, arr, 0.5, 64.0,
                          make_policy("hesrpt", n_servers=64.0))
    np.testing.assert_allclose(np.asarray(res.completion_times), golden,
                               rtol=1e-13)


def test_engine_trace_matches_simresult_fields():
    """The batch wrapper repackages the engine trace unchanged."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.pareto(1.5, 8) + 1.0)
    pol = make_policy("hesrpt", n_servers=64.0)
    res = simulate(x, 0.5, 64.0, pol)
    eng = engine.run(
        x, jnp.zeros(8), 0.5,
        engine.continuous_rule(pol, 64.0, dtype=x.dtype),
        pre_arrived=True, horizon=8, record=True,
    )
    np.testing.assert_array_equal(np.asarray(res.theta_trace),
                                  np.asarray(eng.trace.alloc))
    np.testing.assert_array_equal(np.asarray(res.epoch_times),
                                  np.asarray(eng.trace.times))
    np.testing.assert_array_equal(np.asarray(res.sizes_trace),
                                  np.asarray(eng.trace.sizes))


def test_seeded_fuzz_quantizer_matches_oracle():
    """Seeded-fuzz twin of tests/test_quantize.py's hypothesis property
    (which is skipped when hypothesis is absent): exact jnp == NumPy-oracle
    agreement, including oversubscription and min-chips trims."""
    from repro.sched.quantize import quantize_allocation

    rng = np.random.default_rng(42)
    # Small static (m, n_chips, min_chips) grids keep eager-mode lax
    # compilation cached; the hypothesis twin sweeps the full ranges in CI.
    for _ in range(120):
        m = int(rng.choice([1, 2, 3, 5, 9, 14]))
        n_chips = int(rng.choice([1, 7, 16, 64, 250]))
        min_chips = int(rng.choice([1, 2, 4]))
        w = rng.pareto(1.2, m) + 0.01
        w[rng.random(m) < 0.3] = 0.0
        s = w.sum()
        theta = w / s if s > 0 else w
        ref = quantize_allocation(theta, n_chips, min_chips=min_chips)
        got = np.asarray(engine.quantize_allocation_jax(
            jnp.asarray(theta), n_chips, min_chips=min_chips))
        np.testing.assert_array_equal(got.astype(np.int64), ref,
                                      err_msg=f"{theta} {n_chips} {min_chips}")


# ------------------------------------------- quantized engine vs the cluster
# Event-for-event equality with ClusterScheduler, batch and online, is the
# path contract of tests/test_contract_live.py.
def test_cluster_engine_runs_in_float32_without_x64():
    """Without x64 the scheduler still runs the engine (float32), and says
    so.  Against the float64 per-event loop fed the same float32 sizes,
    chips differ only where float32 breaks a largest-remainder tie the
    other way (one chip per job, on a few percent of events) and flows
    agree to float32 accuracy.

    How many events tie depends on the cluster: at p = 0.5 the brackets
    are ``(2r - 1) / m**2``, so for some active counts ``m`` the rounded
    shares of many jobs tie exactly (40 jobs on 64 chips tie at most
    events).  This batch, 200 jobs on 256 chips, is of the kind
    ``chip_smoke.py`` checks."""
    from benchmarks.quantized import compare_events

    sizes = (np.random.default_rng(3).pareto(1.5, 200) + 1.0).astype(
        np.float32)

    def scheduler():
        s = ClusterScheduler(256, policy="hesrpt")
        for i, x in enumerate(sizes):
            s.add_job(Job(f"j{i}", size=float(x), p=0.5))
        return s

    a, b = scheduler(), scheduler()
    with jax.enable_x64(False):
        ra = a.run_fluid_to_completion()
    rb = b.run_fluid_to_completion(use_engine=False)
    assert (ra["path"], rb["path"]) == ("engine", "events")

    def allocs(s):
        return [(e["t"], e["chips"]) for e in s.events
                if e["event"] == "allocate"]

    differing, max_diff, t_rel = compare_events(allocs(a), allocs(b))
    assert max_diff <= 1 and differing <= 0.05 * len(allocs(b)), (
        differing, max_diff)
    assert t_rel < 1e-2
    np.testing.assert_allclose(ra["mean_flow_time"], rb["mean_flow_time"],
                               rtol=1e-5)


def test_compare_events_pairs_by_live_jobs():
    """Events pair in order by their set of live jobs; unpaired events on
    either side count as differing."""
    from benchmarks.quantized import compare_events

    ref = [(0.0, {"a": 2, "b": 2}), (1.0, {"a": 4}), (3.0, {"c": 4})]
    assert compare_events(ref, ref) == (0, 0, 0.0)
    off = [(0.0, {"a": 3, "b": 1}), (1.1, {"a": 4}), (3.0, {"c": 4})]
    differing, max_diff, t_rel = compare_events(off, ref)
    assert (differing, max_diff) == (1, 1)
    assert t_rel == pytest.approx(0.1)
    # One side splits a departure into two events: one extra, unpaired.
    split = ref[:2] + [(2.0, {"a": 1, "c": 3})] + ref[2:]
    assert compare_events(split, ref)[:2] == (1, 0)


def test_quantized_oversubscription_queues_and_completes():
    """More jobs than chips: the engine must queue (0 chips) yet finish."""
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.pareto(1.5, 12) + 1.0)
    res, eng = simulate_online_quantized(
        x, jnp.zeros(12), 0.5, 4, make_policy("hesrpt", n_servers=4.0),
        record=True)
    assert np.all(np.isfinite(np.asarray(res.completion_times)))
    chips = np.asarray(eng.trace.alloc)
    assert chips.max() <= 4
    assert np.all(chips.sum(axis=1) <= 4)
    # at least one event had a queued active job
    sizes = np.asarray(eng.trace.sizes)
    assert np.any((sizes > 0) & (chips == 0))


def test_quantized_sweep_jit_vmap_single_call():
    """The acceptance-criterion shape: seeds x loads in ONE jitted vmap of
    the quantized engine (scaled down for test runtime)."""
    from repro.core import load_sweep_raw

    raw = load_sweep_raw(("hesrpt",), (0.5, 2.0, 8.0), n_jobs=40, n_seeds=6,
                         p=0.5, n_servers=16.0, n_chips=16)
    assert raw["hesrpt"].shape == (3, 6)
    assert np.all(np.isfinite(np.asarray(raw["hesrpt"])))


# ----------------------------------------------------------------- scenarios
def test_scenario_registry_names_and_errors():
    with pytest.raises(ValueError, match="unknown scenario"):
        make_scenario("nope")
    key = jax.random.PRNGKey(0)
    for name in ("batch", "poisson", "deterministic", "bursty"):
        scn = make_scenario(name)(key, 16, 2.0)
        assert scn.x0.shape == (16,)
        assert scn.arrival_times.shape == (16,)
        assert scn.size_factors is None and scn.p_hat is None
    assert np.all(np.asarray(make_scenario("batch")(key, 16, 2.0)
                             .arrival_times) == 0)


def test_poisson_scenario_matches_legacy_draw_exactly():
    """The registry's poisson sampler must reproduce the historical
    load_sweep key discipline bit-for-bit (paired-seed continuity)."""
    from repro.core import pareto_sizes, poisson_arrivals

    key = jax.random.PRNGKey(7)
    scn = make_scenario("poisson", size_alpha=1.5)(key, 32, 3.0)
    k1, k2 = jax.random.split(key)
    np.testing.assert_array_equal(np.asarray(scn.arrival_times),
                                  np.asarray(poisson_arrivals(k1, 32, 3.0)))
    np.testing.assert_array_equal(np.asarray(scn.x0),
                                  np.asarray(pareto_sizes(k2, 32, 1.5)))


def test_noise_reaches_policy_not_physics():
    """sigma_size perturbs only what the policy sees: with a *rank-preserving*
    noise draw the trajectory would be identical; generically it degrades
    heSRPT toward mis-ranked allocations but never changes total work."""
    key = jax.random.PRNGKey(3)
    sampler = make_scenario("poisson", sigma_size=1.0)
    scn = sampler(key, 24, 2.0)
    assert scn.size_factors is not None
    clean = scn._replace(size_factors=None, p_hat=None)
    pol = make_policy("hesrpt", n_servers=64.0)
    res_noisy = simulate_scenario(scn, 0.5, 64.0, pol)
    res_clean = simulate_scenario(clean, 0.5, 64.0, pol)
    assert np.all(np.isfinite(np.asarray(res_noisy.completion_times)))
    # same jobs, same physics: identical work, different (worse) schedule
    assert float(res_noisy.mean_flowtime) >= float(res_clean.mean_flowtime)


def test_p_hat_noise_clips_and_runs():
    key = jax.random.PRNGKey(9)
    scn = make_scenario("poisson", sigma_p=10.0, p=0.5)(key, 12, 1.0)
    assert 0.05 <= float(scn.p_hat) <= 0.95
    res = simulate_scenario(scn, 0.5, 32.0, make_policy("hesrpt"))
    assert np.all(np.isfinite(np.asarray(res.completion_times)))


def test_trace_scenario_replay():
    arr = jnp.asarray([0.0, 1.0, 2.0])
    x = jnp.asarray([3.0, 2.0, 1.0])
    scn = trace_scenario(arr, x)(jax.random.PRNGKey(0), 3, 99.0)
    res = simulate_scenario(scn, 0.5, 8.0, make_policy("hesrpt"))
    ref = simulate_online(x, arr, 0.5, 8.0, make_policy("hesrpt"))
    np.testing.assert_array_equal(np.asarray(res.completion_times),
                                  np.asarray(ref.completion_times))
    with pytest.raises(ValueError, match="trace has"):
        trace_scenario(arr, x)(jax.random.PRNGKey(0), 5, 1.0)


def test_bursty_arrivals_are_bursty():
    """MAP on-off gaps must show positive autocorrelation vs an exponential
    stream of the same mean (that's the point of the scenario)."""
    from repro.core import bursty_arrivals

    key = jax.random.PRNGKey(0)
    arr = np.asarray(bursty_arrivals(key, 4000, 8.0, 0.5, p_stay=0.97))
    gaps = np.diff(arr)
    g = (gaps - gaps.mean()) / gaps.std()
    lag1 = float(np.mean(g[:-1] * g[1:]))
    assert lag1 > 0.1, lag1  # strongly correlated; iid exp would be ~0
    assert np.all(gaps > 0)


def test_cluster_engine_fallbacks_preserved():
    """Heterogeneous-p instances must take the Python path (the plain
    single-class engine rule models one uniform p) and still complete."""
    sched = ClusterScheduler(16, policy="hesrpt")
    sched.add_job(Job("a", size=4.0, p=0.3))
    sched.add_job(Job("b", size=2.0, p=0.7))  # heterogeneous p
    assert not sched._engine_eligible()
    res = sched.run_fluid_to_completion()
    assert res["makespan"] > 0


def test_cluster_knee_delegates_to_engine():
    """KNEE's per-epoch alpha refit (median of the active remaining sizes)
    now runs inside the scan (``engine.knee_rule``): the delegated
    trajectory must match the per-event Python oracle — chips exactly at
    every decision epoch in the quantized regime, flows to float tolerance
    in both regimes."""
    rng = np.random.default_rng(5)
    sizes = rng.pareto(1.5, 13) + 1.0
    for quantize in (True, False):
        def mk(quantize=quantize):
            s = ClusterScheduler(48, policy="knee", quantize=quantize)
            for i, sz in enumerate(sizes):
                s.add_job(Job(f"j{i}", size=float(sz), p=0.45))
            return s

        a, b = mk(), mk()
        assert a._engine_eligible(), "knee must delegate now"
        ra = a.run_fluid_to_completion(use_engine=True)
        rb = b.run_fluid_to_completion(use_engine=False)
        ta = np.array(sorted(ra["completion_times"].values()))
        tb = np.array(sorted(rb["completion_times"].values()))
        np.testing.assert_allclose(ta, tb, rtol=1e-10)
        if quantize:
            ea = [e["chips"] for e in a.events if e["event"] == "allocate"]
            eb = [e["chips"] for e in b.events if e["event"] == "allocate"]
            assert ea == eb  # integer chips exact, event-for-event
