"""Scheduler-layer unit tests: cluster epochs, arrivals, stragglers."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import hesrpt_total_flowtime, optimal_makespan
from repro.sched import ClusterScheduler, Job, StragglerDetector
from repro.sched.estimator import SpeedupEstimator, blended_p


@pytest.mark.slow
def test_cluster_fluid_matches_closed_form():
    rng = np.random.default_rng(0)
    x = np.sort(rng.pareto(1.5, 16) + 1.0)[::-1]
    n = 256
    sched = ClusterScheduler(n, policy="hesrpt")
    for i, xi in enumerate(x):
        sched.add_job(Job(f"j{i}", size=float(xi), p=0.5))
    res = sched.run_fluid_to_completion()
    closed = float(hesrpt_total_flowtime(jnp.asarray(x), 0.5, float(n)))
    assert res["total_flow_time"] <= closed * 1.02  # quantization gap < 2%


def test_cluster_helrpt_equalizes_completions():
    sched = ClusterScheduler(64, policy="helrpt")
    sizes = [9.0, 5.0, 2.0]
    for i, s in enumerate(sizes):
        sched.add_job(Job(f"j{i}", size=s, p=0.5))
    res = sched.run_fluid_to_completion()
    times = list(res["completion_times"].values())
    assert max(times) - min(times) < 0.25 * max(times)  # near-simultaneous
    closed = float(optimal_makespan(jnp.asarray(sizes), 0.5, 64.0))
    assert res["makespan"] <= closed * 1.10


def test_cluster_arrival_reschedules():
    """The paper's §4.3 heuristic: re-run heSRPT on the active set when a
    job arrives mid-run."""
    sched = ClusterScheduler(16, policy="hesrpt")
    sched.add_job(Job("a", size=8.0, p=0.5))
    sched.add_job(Job("b", size=4.0, p=0.5))
    sched.allocations()
    sched.advance_fluid(until_departure=False, dt=0.2)
    sched.add_job(Job("late", size=1.0, p=0.5))
    alloc = sched.allocations()
    assert alloc["late"] > 0
    # smallest remaining job gets the largest share under heSRPT
    act = sched.active_jobs()
    smallest = min(act, key=lambda j: j.remaining).job_id
    assert alloc[smallest] == max(alloc.values())
    res = sched.run_fluid_to_completion()
    assert res["makespan"] > 0


def test_straggler_detector_flags_slow_job():
    det = StragglerDetector(threshold=0.7, patience=2)
    assert not det.report("j", observed_rate=1.0, expected_rate=1.0)
    assert not det.report("j", observed_rate=0.5, expected_rate=1.0)
    assert det.report("j", observed_rate=0.5, expected_rate=1.0)
    assert det.events and det.events[0]["action"] == "evict"
    # healthy reports reset the counter
    assert not det.report("k", 0.5, 1.0)
    assert not det.report("k", 1.0, 1.0)
    assert not det.report("k", 0.5, 1.0)


def test_blended_p_work_weighted():
    a, b = SpeedupEstimator(prior_p=0.2), SpeedupEstimator(prior_p=0.8)
    assert abs(blended_p([a, b], [3.0, 1.0]) - (0.2 * 3 + 0.8) / 4) < 1e-9


def test_arrival_stream_hesrpt_dominates():
    """Paper §4.3 heuristic: online heSRPT (recompute at arrivals) beats
    SRPT and matches-or-beats EQUI on a small Poisson stream."""
    from benchmarks.arrivals import run_stream

    kw = dict(n_jobs=20, rate=2.0, p=0.5, n_chips=64, seed=1)
    f_he = run_stream("hesrpt", **kw)
    f_srpt = run_stream("srpt", **kw)
    f_equi = run_stream("equi", **kw)
    assert f_he <= f_srpt * 1.02
    assert f_he <= f_equi * 1.02


def test_straggler_detection_triggers_resize_decision():
    """Integration: a degraded job (observed rate below the speedup-model
    expectation) is flagged and the scheduler can re-quantize without it."""
    from repro.sched import ClusterScheduler, Job, StragglerDetector

    sched = ClusterScheduler(32, policy="hesrpt")
    for i, s in enumerate([8.0, 4.0, 2.0]):
        sched.add_job(Job(f"j{i}", size=s, p=0.5))
    alloc = sched.allocations()
    det = StragglerDetector(threshold=0.7, patience=2)
    victim = "j1"
    expected = alloc[victim] ** 0.5  # s(k) = k^p model
    flagged = False
    for _ in range(3):
        flagged = det.report(victim, observed_rate=0.3 * expected,
                             expected_rate=expected)
        if flagged:
            break
    assert flagged
    # driver response: evict one chip from the straggler and re-quantize
    sched.n_chips -= 1
    new_alloc = sched.allocations()
    assert sum(new_alloc.values()) <= 31
