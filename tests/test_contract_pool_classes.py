"""Path contract: a multi-class whole-chip tape played through the slot pool
equals the full tape.

The training-pod cell (``v5e_pod_training.mixed``) plays its tapes through
``engine.run_stream``'s pool of W slots (``sweeps._pool_slots``), with each
job's exponent, width limits, policy weight and class id riding in its
slot.  Each case draws one seeded three-class tape (the pod's exponents and
mix, its widths scaled to a 32-chip pool) and plays it both ways through
the sweep's own wrapper (``multiclass._multiclass`` with and without
``n_slots``):

- the trajectory: every job leaves at the same time, bit for bit (the
  ``pooled_scans`` fixture records the pooled scan's completion times);
- the read-out: overall and per-class means equal the full tape's to rtol
  1e-13 (the same per-job values, added in the order jobs finish).

Each case asserts that the pool held, so it pins the pooled path and not
the full-tape replay an overflow takes.  The matrix spans the four
class-aware policies, no width limits, per-class ``[lo, hi]`` limits, and
limits with the power-of-two slice snap, under Poisson and bursty arrivals.
"""

import jax
import numpy as np
import pytest

from repro.core import make_scenario, sweeps
from repro.core.analysis import per_class_mean
from repro.core.arrivals import OnlineSimResult, PoolSimResult
from repro.core.multiclass import ClassSpec, _multiclass

N_JOBS = 200
N_CHIPS = 32
RATE = 1.0
WIDTHS = {"none": ((None, None),) * 3,
          "limits": ((1, 4), (4, 16), (16, 32)),
          "slices": ((1, 4), (4, 16), (16, 32))}


def _classes(limits):
    (lo0, hi0), (lo1, hi1), (lo2, hi2) = WIDTHS[limits]
    return (ClassSpec(p=0.3, mix=0.8, min_chips=lo0, max_chips=hi0),
            ClassSpec(p=0.6, mix=0.17, size_scale=4.0, min_chips=lo1, max_chips=hi1),
            ClassSpec(p=0.9, mix=0.03, size_scale=16.0, min_chips=lo2, max_chips=hi2))


@pytest.mark.parametrize("scenario", ["multiclass_poisson", "multiclass_bursty"])
@pytest.mark.parametrize("limits", ["none", "limits", "slices"])
@pytest.mark.parametrize("policy", ["hesrpt_pc", "waterfill", "hesrpt_sd", "hesrpt_blind"])
def test_pooled_class_tape_equals_the_full_tape(policy, limits, scenario, pooled_scans):
    classes = _classes(limits)
    snap = limits == "slices"
    spec = sweeps.Sweep.create((policy,), (RATE,), n_jobs=N_JOBS, n_seeds=1,
                               n_servers=float(N_CHIPS), n_chips=N_CHIPS,
                               classes=classes, snap_slices=snap, scenario=scenario,
                               metrics=("mean_flowtime", "class_flowtime",
                                        "class_slowdown"))
    slots = sweeps._pool_slots(spec)
    assert slots == N_CHIPS

    scn = make_scenario(scenario, classes=classes)(jax.random.key(11), N_JOBS, RATE)
    args = dict(classes=classes, policy=policy, n_servers=float(N_CHIPS),
                n_chips=N_CHIPS, min_chips=1, snap_slices=snap)
    full = _multiclass(scn, **args)
    pooled = _multiclass(scn, n_slots=slots, **args)
    assert isinstance(full, OnlineSimResult) and isinstance(pooled, PoolSimResult)

    (scan,) = pooled_scans
    assert not bool(pooled.overflow)
    assert int(scan.blocked_steps) == 0 and 1 < int(scan.occupancy_max) <= slots
    np.testing.assert_array_equal(np.asarray(scan.completion_times),
                                  np.asarray(full.completion_times))
    for m in ("total_flowtime", "mean_flowtime", "mean_slowdown", "makespan"):
        np.testing.assert_allclose(float(getattr(pooled, m)), float(getattr(full, m)),
                                   rtol=1e-13, err_msg=m)
    for m, per_job in (("class_flowtime", full.flow_times),
                       ("class_slowdown", full.slowdowns)):
        np.testing.assert_allclose(np.asarray(getattr(pooled, m)),
                                   np.asarray(per_class_mean(per_job, scn.class_ids, 3)),
                                   rtol=1e-13, err_msg=m)
