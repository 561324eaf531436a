"""The sweep subsystem (core/sweeps.py): golden pins, scale layers,
artifacts.

- **Golden pins**: the declarative engine must reproduce the pinned sweep
  outputs BIT-FOR-BIT on f64 — first computed with the historical
  ``load_sweep_raw`` / ``multiclass_sweep`` / ``benchmarks.estimation.sweep``
  implementations (per-experiment jit+vmap closures) immediately before
  the refactor, re-pinned on jax 0.9's draws (see the comment above the
  arrays).  Any drift here means a change moved the numbers, not just the
  plumbing.
- **Chunked execution**: ``lax.map`` over seed-chunks must equal the
  unchunked vmap exactly, for any chunk size (boundary invariance), and
  the ``max_jobs_in_flight`` budget must bound the chunk.
- **Device sharding**: ``shard_map`` over the seed axis must equal the
  single-device run exactly (forced multi-device via ``XLA_FLAGS`` in a
  subprocess — the main process must stay single-device for other tests).
- **Artifacts**: ``SweepResult`` JSON round-trips exactly; ``run_sweep``
  appends records that ``write_bench_json`` flushes to ``BENCH_sweeps.json``.

Hypothesis twins (wider random chunk/grid shapes) live in
tests/test_sweeps_properties.py.
"""

import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.core.multiclass import ClassSpec
from repro.core.sweeps import (
    RUN_LOG,
    Sweep,
    SweepResult,
    resolve_chunk,
    run_sweep,
    write_bench_json,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TWO_CLASSES = (
    ClassSpec(p=0.35, mix=0.5, size_alpha=1.5),
    ClassSpec(p=0.75, mix=0.5, size_alpha=2.2, size_scale=2.0),
)

# ---------------------------------------------------------------- golden pins
# Shapes are [n_rates, n_seeds] (plus [K] for per-class), f64 on the CPU.
# First captured from the pre-refactor implementations (module docstring).
# Re-pinned on jax 0.9, whose draws differ from the ones the first pins
# were taken on: an independent float64 NumPy replay of the same draws
# (its own policies, event loop and estimator; the NumPy rounding oracle
# of sched/quantize.py) reproduces every value below to 1.2e-15 relative,
# and the fixed-input trajectory pin (test_engine.py) still holds.
GOLDEN_SINGLE_HESRPT = np.array([
    [0.3305255914474754, 0.33284621341746423, 0.8083842992911947],
    [0.5169479775364539, 0.49216883986119914, 1.079422489717065],
])
GOLDEN_SINGLE_EQUI = np.array([
    [0.3317718092989504, 0.3349757822764199, 0.8252290339440734],
    [0.5236555343953615, 0.4926587400059765, 1.1313561293749677],
])
GOLDEN_QUANTIZED = np.array([
    [0.7942090697062641, 0.6759029764795415, 0.7153949192387922],
])
GOLDEN_NOISY = np.array([[0.2515858133955599, 0.44570219220566926]])
GOLDEN_MC_FLOW = np.array([
    [0.6453752368261843, 0.5043908385340683, 0.40882248661102216],
    [0.9204287697076904, 0.6960910457964199, 0.5200318314439761],
])
GOLDEN_MC_CLASS_SLOWDOWN = np.array([
    [[1.0862637247853115, 1.0250858519912813],
     [1.1377038815513865, 1.1393404147095827],
     [1.079370426834755, 1.0057900053255033]],
    [[1.592859943516953, 1.3350483850038928],
     [1.5893751956676032, 1.4330124087664915],
     [1.323933591222228, 1.2420393164482095]],
])
GOLDEN_ARMS = {
    "oracle": {0.5: 0.5024510764601353, 4.0: 0.8775762462718971},
    "stale": {0.5: 0.5163461492135095, 4.0: 0.9966471716279237},
    "estimator": {0.5: 0.5098107261606295, 4.0: 0.8993621137141276},
}


def test_golden_pin_single_class_load_sweep():
    from repro.core import load_sweep_raw

    raw = load_sweep_raw(("hesrpt", "equi"), (0.5, 4.0), n_jobs=40,
                         n_seeds=3, p=0.5, n_servers=64.0, seed=0)
    np.testing.assert_array_equal(np.asarray(raw["hesrpt"]),
                                  GOLDEN_SINGLE_HESRPT)
    np.testing.assert_array_equal(np.asarray(raw["equi"]),
                                  GOLDEN_SINGLE_EQUI)


def test_golden_pin_quantized_and_noisy_paths():
    from repro.core import load_sweep_raw

    rq = load_sweep_raw(("hesrpt",), (2.0,), n_jobs=30, n_seeds=3, p=0.5,
                        n_servers=32.0, seed=1, n_chips=32)
    np.testing.assert_array_equal(np.asarray(rq["hesrpt"]), GOLDEN_QUANTIZED)
    rn = load_sweep_raw(("hesrpt",), (1.0,), n_jobs=25, n_seeds=2, p=0.5,
                        n_servers=64.0, seed=2,
                        scenario_kw={"sigma_size": 0.3})
    np.testing.assert_array_equal(np.asarray(rn["hesrpt"]), GOLDEN_NOISY)


def test_golden_pin_multiclass_sweep():
    from repro.core import multiclass_sweep

    out = multiclass_sweep(("hesrpt_pc", "waterfill"), (1.0, 4.0),
                           classes=TWO_CLASSES, n_jobs=30, n_seeds=3,
                           n_servers=64.0, seed=3)
    np.testing.assert_array_equal(
        np.asarray(out["hesrpt_pc"]["mean_flowtime"]), GOLDEN_MC_FLOW)
    np.testing.assert_array_equal(
        np.asarray(out["waterfill"]["class_slowdown"]),
        GOLDEN_MC_CLASS_SLOWDOWN)


def test_golden_pin_estimation_arms():
    from benchmarks.estimation import sweep

    got = sweep(("oracle", "stale", "estimator"), (0.5, 4.0), n_jobs=40,
                n_seeds=3, p0=0.8, p1=0.3, drift_frac=0.5, n_servers=64.0,
                seed=0, discount=0.9, prior_weight=1.0)
    assert got == GOLDEN_ARMS  # exact float equality, not allclose


# --------------------------------------------------------- chunked execution
def _small_spec(**kw):
    base = dict(policies=("hesrpt",), rates=(0.5, 4.0), n_jobs=25, n_seeds=5,
                p=0.5, n_servers=64.0, seed=0)
    base.update(kw)
    pols = base.pop("policies")
    rates = base.pop("rates")
    return Sweep.create(pols, rates, **base)


@pytest.fixture(scope="module")
def small_sweep_ref():
    """One shared unchunked reference run of ``_small_spec()``.

    Several tests below need "the plain vmap answer for the small spec" as
    their comparison baseline; computing it per-test recompiled (and
    re-ran) the same executor under slightly different n_seeds shapes.
    Hoisting it means one compile + one run for the whole module — tests
    that only need *a* reference (not a specific shape) use this spec.
    """
    spec = _small_spec()
    return spec, run_sweep(spec, log=False)


def test_chunked_equals_unchunked_every_chunk_size(small_sweep_ref):
    """Seeded twin of the hypothesis boundary-invariance property: every
    chunk size (including non-divisors of n_seeds, which exercise the pad
    + slice path, and chunk > n_seeds) reproduces the vmap bit-for-bit."""
    spec, res = small_sweep_ref
    ref = res.stats["hesrpt"]["mean_flowtime"]
    for chunk in (1, 2, 3, 4, 5, 7):
        got = run_sweep(spec, chunk_seeds=chunk, log=False)
        np.testing.assert_array_equal(
            got.stats["hesrpt"]["mean_flowtime"], ref)


def test_chunked_equals_unchunked_multiclass_metrics():
    """Per-class metrics carry a trailing [K] axis through the chunk
    reshape/moveaxis; they must survive chunking bit-for-bit too."""
    spec = Sweep.create(("hesrpt_pc",), (1.0, 4.0),
                        scenario="multiclass_poisson", classes=TWO_CLASSES,
                        n_jobs=20, n_seeds=5, n_servers=32.0, seed=1)
    ref = run_sweep(spec, log=False)
    got = run_sweep(spec, chunk_seeds=2, log=False)
    for m in spec.metrics:
        np.testing.assert_array_equal(got.stats["hesrpt_pc"][m],
                                      ref.stats["hesrpt_pc"][m])


def test_max_jobs_in_flight_budget_bounds_chunk(small_sweep_ref):
    spec, ref = small_sweep_ref  # jobs_per_seed = 2 rates * 25 jobs = 50
    assert resolve_chunk(spec, None, 200) == 4  # 200 // 50
    assert resolve_chunk(spec, None, 10) == 1  # floor: one seed per chunk
    assert resolve_chunk(spec, 3, None) == 3
    assert resolve_chunk(spec, None, None) is None
    with pytest.raises(ValueError):
        resolve_chunk(spec, 2, 100)
    res = run_sweep(spec, max_jobs_in_flight=200, log=False)
    assert res.chunk_seeds == 4
    assert res.chunk_seeds * spec.jobs_per_seed() <= 200
    np.testing.assert_array_equal(
        res.stats["hesrpt"]["mean_flowtime"],
        ref.stats["hesrpt"]["mean_flowtime"])


def test_load_sweep_chunk_passthrough_identical():
    """The historical entry point exposes the memory budget and yields the
    same numbers through it."""
    from repro.core import load_sweep_raw

    a = load_sweep_raw(("equi",), (1.0, 4.0), n_jobs=20, n_seeds=5)
    b = load_sweep_raw(("equi",), (1.0, 4.0), n_jobs=20, n_seeds=5,
                       max_jobs_in_flight=80)
    np.testing.assert_array_equal(np.asarray(a["equi"]), np.asarray(b["equi"]))


# ----------------------------------------------------------- device sharding
def test_sharded_equals_single_device_forced_multidevice():
    """shard_map over the seed axis == the single-device run, under 4 fake
    CPU devices.  XLA pins the device count at first init, so the forced
    multi-device world lives in a subprocess (same pattern as
    tests/test_distribution.py)."""
    body = textwrap.dedent(
        f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import sys
        sys.path.insert(0, {os.path.join(REPO, "src")!r})
        import jax
        jax.config.update("jax_enable_x64", True)
        assert jax.device_count() == 4
        import numpy as np
        from repro.core.sweeps import Sweep, run_sweep

        spec = Sweep.create(("hesrpt", "equi"), (0.5, 4.0), n_jobs=25,
                            n_seeds=6, p=0.5, n_servers=64.0, seed=0)
        ref = run_sweep(spec, log=False)
        for kw in ({{}}, {{"chunk_seeds": 1}}):
            got = run_sweep(spec, shard=True, **kw, log=False)
            assert got.device_count == 4 and got.sharded
            for name in spec.policies:
                assert np.array_equal(
                    got.stats[name]["mean_flowtime"],
                    ref.stats[name]["mean_flowtime"]), (name, kw)
        print("SHARDED_OK")
        """
    )
    proc = subprocess.run([sys.executable, "-c", body], capture_output=True,
                          text=True, timeout=600)
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}"
    assert "SHARDED_OK" in proc.stdout


def test_sharded_on_single_device_is_noop_equal(small_sweep_ref):
    """shard=True must also be safe (and exact) on a 1-device host."""
    spec, ref = small_sweep_ref
    got = run_sweep(spec, shard=True, log=False)
    np.testing.assert_array_equal(got.stats["hesrpt"]["mean_flowtime"],
                                  ref.stats["hesrpt"]["mean_flowtime"])


def test_rate_axis_sharded_equals_single_device_forced_multidevice():
    """shard_axis="rates" (the accelerator-lane shape: wide rate grid, few
    seeds) == the single-device run under 4 fake CPU devices, including a
    rate grid that does not divide the device count (5 -> padded to 8)."""
    body = textwrap.dedent(
        f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import sys
        sys.path.insert(0, {os.path.join(REPO, "src")!r})
        import jax
        jax.config.update("jax_enable_x64", True)
        assert jax.device_count() == 4
        import numpy as np
        from repro.core.sweeps import Sweep, run_sweep

        for rates, fused in (((0.5, 1.0, 2.0, 4.0, 8.0), False),
                             ((0.5, 1.0, 2.0, 4.0), True)):
            spec = Sweep.create(("hesrpt",), rates, n_jobs=20, n_seeds=2,
                                p=0.5, n_servers=32.0, seed=0, n_chips=32,
                                fused=fused)
            ref = run_sweep(spec, log=False)
            got = run_sweep(spec, shard=True, shard_axis="rates", log=False)
            assert got.sharded and got.device_count == 4
            assert np.array_equal(got.stats["hesrpt"]["mean_flowtime"],
                                  ref.stats["hesrpt"]["mean_flowtime"]), (
                rates, fused)
        print("RATE_SHARDED_OK")
        """
    )
    proc = subprocess.run([sys.executable, "-c", body], capture_output=True,
                          text=True, timeout=600)
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}"
    assert "RATE_SHARDED_OK" in proc.stdout


def test_rate_axis_shard_validation_and_single_device_noop(small_sweep_ref):
    spec, ref = small_sweep_ref
    with pytest.raises(ValueError, match="shard_axis"):
        run_sweep(spec, shard_axis="policies", log=False)
    got = run_sweep(spec, shard=True, shard_axis="rates", log=False)
    np.testing.assert_array_equal(got.stats["hesrpt"]["mean_flowtime"],
                                  ref.stats["hesrpt"]["mean_flowtime"])


# ------------------------------------------------------- structured artifacts
def test_sweep_result_json_round_trip_exact():
    spec = Sweep.create(("hesrpt_pc",), (1.0,), scenario="multiclass_poisson",
                        classes=TWO_CLASSES, n_jobs=15, n_seeds=2,
                        n_servers=32.0, seed=4)
    res = run_sweep(spec, chunk_seeds=1, log=False)
    back = SweepResult.from_json(res.to_json())
    assert back.spec == res.spec  # classes/scenario_kw re-normalize exactly
    assert back.chunk_seeds == res.chunk_seeds
    assert back.backend == res.backend
    for name in res.stats:
        for m in res.stats[name]:
            np.testing.assert_array_equal(back.stats[name][m],
                                          res.stats[name][m])


def test_sweep_result_record_and_cell_means(small_sweep_ref):
    _, res = small_sweep_ref
    rec = res.record()
    json.dumps(rec)  # JSON-able as-is
    assert rec["kind"] == "sweep"
    assert rec["total_jobs"] == 2 * 25 * 5  # rates * jobs * seeds (1 policy)
    means = rec["cells"]["hesrpt"]["mean_flowtime"]["mean"]
    np.testing.assert_allclose(
        means, np.mean(res.stats["hesrpt"]["mean_flowtime"], axis=1))
    cm = res.cell_means()
    assert set(cm) == {0.5, 4.0}
    np.testing.assert_allclose(cm[0.5]["hesrpt"], means[0])


def test_run_log_accumulates_and_writes_bench_json(tmp_path):
    n0 = len(RUN_LOG)
    run_sweep(_small_spec(n_seeds=2, seed=11))  # log=True default
    assert len(RUN_LOG) == n0 + 1
    path = write_bench_json(str(tmp_path / "BENCH_sweeps.json"))
    data = json.loads(open(path).read())
    assert len(data["records"]) == len(RUN_LOG)
    assert data["records"][-1]["spec"]["seed"] == 11
    assert data["records"][-1]["wall_s"] >= 0.0


def test_spec_validation_errors():
    with pytest.raises(ValueError, match="unknown metric"):
        Sweep.create(("equi",), (1.0,), metrics=("nope",))
    with pytest.raises(ValueError, match="multi-class"):
        Sweep.create(("equi",), (1.0,), metrics=("class_flowtime",))
    with pytest.raises(ValueError, match="unknown arm"):
        Sweep.create(("equi",), (1.0,), arm="psychic")
    with pytest.raises(ValueError, match="p0"):
        # without an explicit p0 the stale arm would silently anchor to
        # the generic default p, not the drift sampler's own p0
        Sweep.create(("equi",), (1.0,), scenario="drift_poisson",
                     arm="stale")
    with pytest.raises(ValueError, match="continuous-only"):
        # the arm cells run the continuous simulators; a quantized arm
        # spec would record n_chips its physics never used
        Sweep.create(("equi",), (1.0,), scenario="drift_poisson",
                     scenario_kw={"p0": 0.8}, arm="stale", n_chips=64)
    with pytest.raises(ValueError, match="snap_slices"):
        Sweep.create(("equi",), (1.0,), snap_slices=True)


def test_executor_cache_reuses_compilation():
    spec = _small_spec(n_seeds=2, seed=21)
    first = run_sweep(spec, log=False)
    again = run_sweep(spec, log=False)
    assert first.compile_s > 0.0
    assert again.compile_s == 0.0  # cache hit: no re-lower/re-compile
    np.testing.assert_array_equal(again.stats["hesrpt"]["mean_flowtime"],
                                  first.stats["hesrpt"]["mean_flowtime"])


def test_sched_scale_reports_through_sweep_result():
    """The decision-epoch timing benchmark reports through the same
    artifact container (dict spec, M-indexed rows) and its record is
    JSON-able for the trajectory file."""
    from benchmarks.sched_scale import run as sched_run

    res = sched_run(ms=(50, 120), repeats=2, n_chips=64, log=False)
    assert isinstance(res, SweepResult)
    assert res.stats["hesrpt"]["theta_us"].shape == (2, 2)
    assert res.stats["hesrpt"]["chips_sum"][1, 0] == 64
    rec = res.record()
    json.dumps(rec)
    assert rec["kind"] == "sched_scale"
    assert rec["total_jobs"] is None  # not a seeds-x-rates sweep
    assert rec["spec"]["ms"] == [50, 120]
    back = SweepResult.from_json(res.to_json())  # dict-spec round-trip
    assert back.spec == res.spec
    np.testing.assert_array_equal(back.stats["hesrpt"]["theta_us"],
                                  res.stats["hesrpt"]["theta_us"])


# ------------------------------------------------------------ scale (nightly)
@pytest.mark.slow
def test_two_million_job_chunked_sweep_on_cpu():
    """The acceptance-criterion scale: 2,000 jobs x 200 seeds x 5 loads
    (2M simulated jobs) through the chunked executor under a 200k
    jobs-in-flight budget — must complete on CPU without OOM."""
    spec = Sweep.create(("hesrpt",), (0.5, 1.0, 2.0, 4.0, 8.0), n_jobs=2000,
                        n_seeds=200, p=0.5, n_servers=256.0, seed=0)
    assert spec.total_jobs() == 2_000_000
    res = run_sweep(spec, max_jobs_in_flight=200_000, log=False)
    assert res.chunk_seeds == 20  # 200_000 // (5 * 2000)
    a = res.stats["hesrpt"]["mean_flowtime"]
    assert a.shape == (5, 200)
    assert np.all(np.isfinite(a))
    assert np.all(a > 0)


def test_jax_single_device_invariant():
    """Guard: no test in this module may leak a forced multi-device world
    into the main process (sharding tests run in subprocesses)."""
    assert jax.device_count() >= 1
