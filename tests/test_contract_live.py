"""Path contract: the live scheduler's decisions equal the quantized engine's,
event by event.

The live cell (``v5e_pod_wholechip.live``) asks ``ClusterScheduler`` for one
``allocations()`` decision after every arrival and departure of a job
stream: the compiled policy on padded sizes, then the NumPy
largest-remainder rounding with the min-chips floor.  The sweeps run the
same decision inside the scan (``engine.quantized_rule``).  Each case plays
one seeded stream both ways and demands the same integer chips for every
job at every event, event times to 1e-9 and flow times to 1e-9:

- ``batch``: every job present at t = 0; the scheduler's per-event loop
  (``run_fluid_to_completion(use_engine=False)``) against its delegation
  to the engine (``use_engine=True``);
- ``online``: a Poisson stream replayed as the live cell replays it (an
  arrival is admitted after the fluid advance to its time, a departure
  ends an advance), against ``engine.run(record=True)`` on the same tape.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine, make_policy
from repro.sched import ClusterScheduler, Job

P = 0.5


def _allocs(s):
    return [(e["t"], e["chips"]) for e in s.events if e["event"] == "allocate"]


def _assert_same_events(got, ref):
    assert len(got) == len(ref)
    for (t_got, c_got), (t_ref, c_ref) in zip(got, ref, strict=True):
        assert c_got == c_ref, (t_ref, c_got, c_ref)
        np.testing.assert_allclose(t_got, t_ref, rtol=1e-9, atol=1e-12)


def _batch(policy, min_chips):
    rng = np.random.default_rng(11)
    for _ in range(3):
        sizes = rng.pareto(1.5, 12) + 1.0
        a = ClusterScheduler(48, policy=policy, min_chips=min_chips)
        b = ClusterScheduler(48, policy=policy, min_chips=min_chips)
        for i, x in enumerate(sizes):
            a.add_job(Job(f"j{i}", size=float(x), p=P))
            b.add_job(Job(f"j{i}", size=float(x), p=P))
        ra = a.run_fluid_to_completion(use_engine=True)
        rb = b.run_fluid_to_completion(use_engine=False)
        assert (ra["path"], rb["path"]) == ("engine", "events")
        _assert_same_events(_allocs(a), _allocs(b))
        for k in ("total_flow_time", "makespan"):
            np.testing.assert_allclose(ra[k], rb[k], rtol=1e-9)


def _replay(s, arr, x0):
    """The live cell's loop: apply each event of the tape, then decide."""
    i, m = 0, arr.size
    while i < m or s.active_jobs():
        act = s.active_jobs()
        dep = np.inf
        if act:
            rates = s.job_rates(act)
            rem = np.array([j.remaining for j in act])
            with np.errstate(divide="ignore"):
                dep = float(np.min(np.where(rates > 0, rem / rates, np.inf)))
        if i < m and arr[i] - s.time <= dep:
            if act:
                s.advance_fluid(until_departure=False,
                                dt=max(float(arr[i]) - s.time, 0.0))
            s.time = float(arr[i])
            s.add_job(Job(f"j{i}", size=float(x0[i]), p=P))
            i += 1
        else:
            s.advance_fluid(until_departure=True)
        if s.active_jobs():
            s.allocations()


def _engine_events(res, arr):
    """``(t, {job: chips})`` at each step of a recorded run with a job in
    flight, in the scheduler's job names."""
    order = np.asarray(res.order)
    t_ev = np.asarray(res.trace.times)
    sizes = np.asarray(res.trace.sizes)
    alloc = np.asarray(res.trace.alloc)
    arr_sorted = arr[order]
    out = []
    for e in range(t_ev.size):
        live = (arr_sorted <= t_ev[e] + 1e-12) & (sizes[e] > 0)
        if live.any():
            out.append((float(t_ev[e]), {f"j{order[k]}": int(alloc[e, k])
                                         for k in np.nonzero(live)[0]}))
    return out


def _online(policy, min_chips, n_chips=32, n_jobs=30, rate=1.5):
    rng = np.random.default_rng(5)
    arr = np.cumsum(rng.exponential(1.0 / rate, n_jobs))
    x0 = rng.pareto(1.5, n_jobs) + 1.0
    s = ClusterScheduler(n_chips, policy=policy, min_chips=min_chips)
    _replay(s, arr, x0)
    rule = engine.quantized_rule(make_policy(policy, n_servers=float(n_chips)), n_chips,
                                 min_chips=min_chips, dtype=jnp.float64)
    res = engine.run(jnp.asarray(x0), jnp.asarray(arr), P, rule, record=True)
    live = _allocs(s)
    assert len(live) > n_jobs  # decided at arrivals and at departures
    _assert_same_events(_engine_events(res, arr), live)
    flows = np.array([s.jobs[f"j{i}"].completion_time for i in range(n_jobs)]) - arr
    np.testing.assert_allclose(np.asarray(res.completion_times) - arr, flows, rtol=1e-9)


@pytest.mark.parametrize("stream", ["batch", "online"])
@pytest.mark.parametrize("min_chips", [1, 2])
@pytest.mark.parametrize("policy", ["hesrpt", "equi", "srpt", "helrpt"])
def test_live_decisions_match_the_quantized_engine(policy, min_chips, stream):
    (_batch if stream == "batch" else _online)(policy, min_chips)
