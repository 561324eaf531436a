"""Path contract: a whole-chip tape played through the slot pool equals the
full tape (single class).

The heavy cells (``v5e_pod_wholechip.heavy`` and ``heavy_x4``) run their
finite tapes through ``engine.run_stream``'s pool of W slots, W read off
the spec by ``sweeps._pool_slots``, not through ``engine.run`` over the
whole tape.  Each case below draws one seeded tape and plays it both ways
through the sweep's own wrapper (``arrivals._scenario`` with and without
``n_slots``):

- the trajectory: every job leaves at the same time, bit for bit (the
  pooled scan's completion times are recorded by the ``pooled_scans``
  fixture);
- the read-out: the pooled sums equal the full tape's means to rtol 1e-13
  (the same per-job values, added in the order jobs finish).

Each case asserts that the pool held (no arrival deferred, no fallback),
so it pins the pooled path itself, not the full-tape replay an overflow
takes.  The matrix spans every single-class policy the whole-chip rule
takes, Poisson and bursty arrivals, the min-chips floor (whose queue of
jobs at 0 chips must fit in the pool too) and size-estimation noise (whose
factors ride in the slots).

The heavy cells' executors at their widths pin how many sorts an event trip
pays on each path: 3 on the pool of 256 slots for 256 chips, whose width
leaves the rounding's oversubscription pass out, 4 on the 1000-wide tape.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import make_policy, make_scenario, sweeps
from repro.core.arrivals import OnlineSimResult, PoolSimResult, _scenario
from repro.core.hlo import op_histogram

N_JOBS = 200
N_CHIPS = 32  # the pool is W = 32 slots, as _pool_slots picks for 32 chips
RATE = 2.0
P = 0.5
READOUTS = ("total_flowtime", "mean_flowtime", "mean_slowdown", "makespan")


@pytest.mark.parametrize("sigma_size", [0.0, 0.3])
@pytest.mark.parametrize("min_chips", [1, 2])
@pytest.mark.parametrize("scenario", ["poisson", "bursty"])
@pytest.mark.parametrize("policy", ["hesrpt", "equi", "srpt", "helrpt"])
def test_pooled_tape_equals_the_full_tape(policy, scenario, min_chips, sigma_size,
                                          pooled_scans):
    kw = {"sigma_size": sigma_size} if sigma_size else {}
    spec = sweeps.Sweep.create((policy,), (RATE,), n_jobs=N_JOBS, n_seeds=1, p=P,
                               n_servers=float(N_CHIPS), n_chips=N_CHIPS,
                               min_chips=min_chips, scenario=scenario,
                               scenario_kw=kw,
                               metrics=("mean_flowtime", "mean_slowdown", "makespan"))
    slots = sweeps._pool_slots(spec)
    assert slots == N_CHIPS

    scn = make_scenario(scenario, p=P, **kw)(jax.random.key(7), N_JOBS, RATE)
    pol = make_policy(policy, n_servers=float(N_CHIPS))
    args = dict(n_chips=N_CHIPS, min_chips=min_chips)
    full = _scenario(scn, P, float(N_CHIPS), pol, **args)
    pooled = _scenario(scn, P, float(N_CHIPS), pol, n_slots=slots, **args)
    assert isinstance(full, OnlineSimResult) and isinstance(pooled, PoolSimResult)

    (scan,) = pooled_scans
    assert not bool(pooled.overflow)
    assert int(scan.blocked_steps) == 0 and 1 < int(scan.occupancy_max) <= slots
    np.testing.assert_array_equal(np.asarray(scan.completion_times),
                                  np.asarray(full.completion_times))
    for m in READOUTS:
        np.testing.assert_allclose(float(getattr(pooled, m)), float(getattr(full, m)),
                                   rtol=1e-13, err_msg=m)


@pytest.mark.parametrize("pooled,sorts_per_trip", [(True, 3), (False, 4)])
def test_sorts_per_trip_of_the_heavy_executors(pooled, sorts_per_trip):
    """The heavy cell's program (256 chips, 1000-job tapes, two lanes): the
    pool's W = 256 slots are no more than the 256 jobs the floor of one
    chip serves, so its rounding pays no oversubscription sort; the full
    tape's 1000 entries are more, and it keeps that sort."""
    n_jobs = 1000
    spec = sweeps.Sweep.create(("hesrpt",), (76.8,), n_jobs=n_jobs, n_seeds=2, p=0.5,
                               n_servers=256.0, n_chips=256)
    slots = sweeps._pool_slots(spec) if pooled else None
    assert slots == (256 if pooled else None)
    keys = jax.random.split(jax.random.PRNGKey(0), spec.n_seeds)
    with jax.enable_x64(False):
        f = sweeps._build_fn(spec, "hesrpt", None, False, slots=slots)
        text = jax.jit(f).lower(keys, jnp.asarray(spec.rates, jnp.float32)
                                ).compile().as_text()
    trips = 2 * n_jobs  # one arrival and one departure a job
    sorts = op_histogram(text)["sort"]
    assert sorts_per_trip * trips <= sorts < sorts_per_trip * trips + 2
