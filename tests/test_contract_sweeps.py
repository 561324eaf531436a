"""Path contract: the sweep executor's fast paths equal their references.

Two paths that ledger cells run, each against the path it stands in for:

- ``run_sweep`` on whole chips plays every lane through a slot pool
  (``sweeps._pool_slots``; the heavy cells and the training pod).  Each
  case runs one spec pooled and again with ``_pool_slots`` returning None,
  which compiles the full-tape executor, and demands every metric to rtol
  1e-13, with no lane's pool overflowed (``engine.pool_overflows`` stays
  put).  The specs take the heavy cell's shape (two policies; mean flow
  time, mean slowdown and makespan; with and without a min-chips floor;
  Poisson and bursty) and the pod's (three classes with width limits, with
  and without the slice snap; the class metrics), each over two rates, in
  one call and in one-seed chunks (``chunk_seeds=1``).
- The Figure 4 cell's batches run the carried-rank scan
  (``simulate_online_ranked`` over ``engine.run_ranked``), not the generic
  event scan (``simulate_online`` over ``engine.run``).  Each case draws one
  batch from the cell's sampler (Pareto(1.5) sizes, 1e6 divisible servers)
  and demands the same completion times, bit for bit.
"""

import jax
import numpy as np
import pytest

from repro import tracing
from repro.core import make_policy, make_rank_policy, make_scenario, sweeps
from repro.core.arrivals import simulate_online, simulate_online_ranked
from repro.core.multiclass import ClassSpec

N_CHIPS = 32
HEAVY = dict(n_jobs=120, n_seeds=3, p=0.5, n_servers=float(N_CHIPS), n_chips=N_CHIPS,
             metrics=("mean_flowtime", "mean_slowdown", "makespan"))


def _pod(slices):
    classes = (ClassSpec(p=0.3, mix=0.8, min_chips=1, max_chips=4),
               ClassSpec(p=0.6, mix=0.17, size_scale=4.0, min_chips=4, max_chips=16),
               ClassSpec(p=0.9, mix=0.03, size_scale=16.0, min_chips=16, max_chips=32))
    return sweeps.Sweep.create(
        ("hesrpt_pc", "waterfill"), (0.5, 1.0), n_jobs=120, n_seeds=3,
        n_servers=float(N_CHIPS), n_chips=N_CHIPS, classes=classes,
        snap_slices=slices, scenario="multiclass_poisson", seed=2,
        metrics=("mean_flowtime", "class_flowtime", "class_slowdown"))


SPECS = {
    "heavy": lambda: sweeps.Sweep.create(("hesrpt", "equi"), (1.0, 2.0), **HEAVY),
    "heavy_floor": lambda: sweeps.Sweep.create(("hesrpt", "srpt"), (1.0, 2.0),
                                               min_chips=2, seed=1, **HEAVY),
    "heavy_bursty": lambda: sweeps.Sweep.create(("hesrpt", "helrpt"), (0.5, 1.0),
                                                scenario="bursty", seed=3, **HEAVY),
    "pod": lambda: _pod(True),
    "pod_limits": lambda: _pod(False),
}


@pytest.fixture(scope="module")
def full_tape():
    """Each shape's sweep on the full-tape executor, run once per shape."""
    done: dict[str, sweeps.SweepResult] = {}

    def get(shape, spec):
        if shape not in done:
            with pytest.MonkeyPatch.context() as m:
                m.setattr(sweeps, "_pool_slots", lambda s: None)
                done[shape] = sweeps.run_sweep(spec, log=False)
        return done[shape]

    return get


@pytest.mark.parametrize("chunk_seeds", [None, 1])
@pytest.mark.parametrize("shape", list(SPECS))
def test_pooled_sweep_equals_the_full_tape_sweep(shape, chunk_seeds, full_tape):
    spec = SPECS[shape]()
    assert sweeps._pool_slots(spec) == N_CHIPS
    full = full_tape(shape, spec)
    before = tracing.counts().get("engine.pool_overflows", 0)
    pooled = sweeps.run_sweep(spec, chunk_seeds=chunk_seeds, log=False)
    assert tracing.counts().get("engine.pool_overflows", 0) == before
    for name in spec.policies:
        for m in spec.metrics:
            got, ref = pooled.stats[name][m], full.stats[name][m]
            assert got.shape == ref.shape
            np.testing.assert_allclose(got, ref, rtol=1e-13, err_msg=f"{name} {m}")


@pytest.mark.parametrize("p", [0.05, 0.3, 0.5, 0.9, 0.99])
@pytest.mark.parametrize("policy", ["hesrpt", "equi", "srpt"])
def test_ranked_batch_equals_the_event_scan(policy, p):
    scn = make_scenario("batch", size_alpha=1.5, p=p)(jax.random.key(4), 500, 1.0)
    ranked = simulate_online_ranked(scn.x0, scn.arrival_times, p, 1e6,
                                    make_rank_policy(policy))
    generic = simulate_online(scn.x0, scn.arrival_times, p, 1e6,
                              make_policy(policy, n_servers=1e6))
    assert np.all(np.isfinite(np.asarray(generic.completion_times)))
    np.testing.assert_array_equal(np.asarray(ranked.completion_times),
                                  np.asarray(generic.completion_times))
    np.testing.assert_array_equal(np.asarray(ranked.mean_flowtime),
                                  np.asarray(generic.mean_flowtime))
