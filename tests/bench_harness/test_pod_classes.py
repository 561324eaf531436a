"""CPU tests of the shared-training-pod cell (``v5e_pod_training.mixed``).

As in ``test_bench_harness.py``, the harness is steered past its look for
a TPU and driven at a tiny size: a checkout whose BENCHMARK.json gains a
cell of the real configuration with a tiny traffic file, run end to end;
a fault planted in the capped allocate must make ``correct`` false; the
generator must draw what the program's sampler draws; and the control
must fail where the program passes.
"""

from __future__ import annotations

import json
import os
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import gen, run
from bench.entries import pod_classes as entry_mod
from bench.reference import pod_classes

ROOT = Path(__file__).resolve().parents[2]
CELL = "v5e_pod_training.tiny_mixed"
TINY = {"entry": "pod_classes", "scenario": "multiclass_poisson", "rates": [9.99],
        "jobs_per_lane": 96, "seeds_per_call": 2, "trace_seconds": 0.3}
SEED = 2**40 + 11


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A checkout whose BENCHMARK.json gains a tiny cell of the training pod:
    its own traffic file, the real configuration, entries, metrics and
    reference, and the limits of the full-size cell."""
    root = tmp_path_factory.mktemp("checkout")
    for d in ("traffic", "limits"):
        (root / "bench" / d).mkdir(parents=True)
    for d in ("entries", "metrics", "reference", "configs"):
        os.symlink(ROOT / "bench" / d, root / "bench" / d)
    os.symlink(ROOT / "src", root / "src")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (root / "bench" / "traffic" / "tiny_mixed.json").write_text(json.dumps(TINY))
    (root / "bench" / "limits" / f"{CELL}.json").write_text(
        (ROOT / "bench" / "limits" / "v5e_pod_training.mixed.json").read_text())
    bench["workloads"].append({"name": CELL, "config": "v5e_pod_training",
                               "traffic": "tiny_mixed", "chips": 1, "why": "tiny"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "v5e_pod_training.mixed" in m.get("workloads", []):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture
def steered(monkeypatch):
    monkeypatch.setattr(run, "devices_for", lambda chips: jax.devices()[:chips])


def _run(root, capsys, *, trace=0):
    rc = run.main(["--workload", CELL, "--seed", str(SEED), "--seconds", "0.5",
                   "--trace", str(trace)], root=root)
    out = capsys.readouterr().out.strip().splitlines()
    return rc, (json.loads(out[-1]) if out else None)


def test_the_cell_is_found_by_name():
    cell = run.load_cell(ROOT, "v5e_pod_training.mixed")
    assert cell.chips == 1 and cell.traffic["entry"] == "pod_classes"
    assert {m["name"] for m in cell.e2e} == {"jobs_per_s.batch", "setup_s"}
    assert {m["name"] for m, _ in cell.per_layer} == {
        "device_idle.batch", "engine.trips_per_job.batch", "alloc.sorts_per_job.batch",
        "sweep.host_ms_per_call.batch", "alloc.device_share.batch",
        "cap.device_share.mixed", "snap.device_share.mixed", "setup.compile_s"}
    assert set(cell.limits) == {"mean_flow_rel", "class_flow_rel"}


def test_tiny_cell_runs_and_is_correct(tiny_root, steered, capsys):
    rc, line = _run(tiny_root, capsys)
    assert rc == 0 and line["correct"] is True, line
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"jobs_per_s.batch", "setup_s"}
    assert set(line["checks"]) == {"mean_flow_rel", "class_flow_rel"}
    rc, line = _run(tiny_root, capsys, trace=1)
    # No TPU plane on the CPU: the device shares and spans read nothing; the
    # counters read the compiled executors, 2 trips a job and 5 sorts a trip.
    assert rc == 0 and line["correct"] is True, line
    assert set(line["metrics"]) == {"engine.trips_per_job.batch",
                                    "alloc.sorts_per_job.batch"}
    assert line["metrics"]["engine.trips_per_job.batch"]["value"] == pytest.approx(
        2.0, abs=0.05)
    assert line["metrics"]["alloc.sorts_per_job.batch"]["value"] == pytest.approx(
        10.0, abs=0.3)


def test_a_dropped_hi_cap_is_not_correct(tiny_root, steered, capsys, monkeypatch):
    from repro.core import engine, sweeps

    real = engine.finish_alloc

    def planted(theta, p, **kw):
        return real(theta, p, **dict(kw, hi=None))

    monkeypatch.setattr(sweeps, "_EXECUTORS", {})  # compile the planted path
    monkeypatch.setattr(engine, "finish_alloc", planted)
    rc, line = _run(tiny_root, capsys)
    assert rc == 0 and line["correct"] is False


def test_control_fails_where_the_program_passes(tiny_root, steered):
    from bench.spans import Spans

    cell = run.load_cell(tiny_root, CELL)
    with jax.enable_x64(False):
        entry = cell.entry.Entry(cell.config, cell.traffic, chips=1, seed=2**35 + 1,
                                 spans=Spans())
        entry.setup()
        entry.window(0.2)
        program, control = entry.check(), entry.check(prec="bfloat16")
    assert all(program[k] <= cell.limits[k] for k in program), program
    assert any(control[k] > cell.limits[k] for k in control), control


def test_generator_draws_what_the_engine_draws():
    from repro.core.multiclass import ClassSpec
    from repro.core.scenarios import make_scenario

    classes = json.loads((ROOT / "bench/configs/v5e_pod_training.json").read_text())[
        "classes"]
    specs = tuple(ClassSpec(p=c["p"], mix=c["mix"], size_alpha=c["size_alpha"],
                            size_scale=c["size_scale"]) for c in classes)
    with jax.enable_x64(False):
        cls, arr, x0 = entry_mod.draw_lanes(77, 3, (9.99, 4.0), 200, classes)
        sampler = make_scenario("multiclass_poisson", classes=specs)
        keys = gen.lane_keys(77, 3)
        for r, rate in enumerate((9.99, 4.0)):
            # The rate is an argument, as the sweep executor passes it.
            scn = jax.jit(jax.vmap(lambda k, rate: sampler(k, 200, rate)))(
                keys, jax.numpy.full(3, rate, jax.numpy.float32))
            np.testing.assert_array_equal(np.asarray(scn.class_ids), cls[r])
            np.testing.assert_array_equal(np.asarray(scn.x0, np.float64), x0[r])
            np.testing.assert_array_equal(
                np.asarray(scn.arrival_times, np.float64), arr[r])
    assert set(np.unique(cls)) == {0, 1, 2}


@pytest.mark.parametrize("n_jobs", [1, 7, 64])
def test_reference_decisions_keep_every_limit(n_jobs):
    rng = np.random.default_rng(n_jobs)
    lo_k, hi_k = np.array([1, 8, 64]), np.array([8, 64, 256])
    for _ in range(50):
        cls = rng.integers(0, 3, n_jobs)
        p = np.array([0.3, 0.6, 0.9])[cls]
        th = pod_classes.theta_pc(rng.pareto(1.5, n_jobs) + 1, p)
        chips = pod_classes.whole_chips(th, lo_k[cls], hi_k[cls], 256)
        snapped, _ = pod_classes.snap(chips, hi_k[cls], 256)
        for c in (chips, snapped):
            on = c > 0
            assert c.sum() <= 256
            assert np.all(c[on] >= lo_k[cls][on]) and np.all(c <= hi_k[cls])
        assert np.all(np.isin(snapped[snapped > 0], pod_classes.SLICES))
        # Admission is a prefix of the descending shares while lo fits.
        served = np.argsort(-th, kind="stable")[: int(np.sum(chips > 0))]
        assert np.all(chips[served] > 0)
