"""CPU tests of the chip benchmark under ``bench/``.

The benchmark itself refuses to run without a TPU; these tests steer past
that check (``run.devices_for``) inside themselves and drive everything
else at tiny sizes: cell discovery, the result line, the reference
against the program, the float64 comparison's control, and faults planted
in the timed path, each of which must make ``correct`` false.
"""

from __future__ import annotations

import gzip
import json
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import devtrace, gen, hlo, run
from bench.reference import fluid

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"

TINY = {
    "tiny_heavy": {"entry": "sweep", "scenario": "poisson", "loads": [0.5, 0.9],
                   "jobs_per_lane": 40, "seeds_per_call": 4, "trace_seconds": 0.3},
    "tiny_batch": {"entry": "sweep", "scenario": "batch", "rates": [1.0],
                   "jobs_per_lane": 20, "seeds_per_call": 2, "trace_seconds": 0.3},
    "tiny_live": {"entry": "live", "loads": [0.9], "jobs_per_tape": 60,
                  "trace_seconds": 0.3},
}
CELLS = {"tiny_heavy": ("v5e_pod_wholechip", "v5e_pod_wholechip.heavy"),
         "tiny_batch": ("paper_fig4", "paper_fig4.batch"),
         "tiny_live": ("v5e_pod_wholechip", "v5e_pod_wholechip.live")}


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A checkout whose BENCHMARK.json gains three cells made of data alone:
    a traffic file each, the real configurations, entries, metrics and
    reference, and the limits of the full-size cell each stands for."""
    root = tmp_path_factory.mktemp("checkout")
    for d in ("traffic", "limits"):
        (root / "bench" / d).mkdir(parents=True)
    for d in ("entries", "metrics", "reference", "configs"):
        os.symlink(ROOT / "bench" / d, root / "bench" / d)
    os.symlink(ROOT / "src", root / "src")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for traffic, (config, like) in CELLS.items():
        name = f"{config}.{traffic}"
        (root / "bench" / "traffic" / f"{traffic}.json").write_text(
            json.dumps(TINY[traffic]))
        (root / "bench" / "limits" / f"{name}.json").write_text(
            (ROOT / "bench" / "limits" / f"{like}.json").read_text())
        bench["workloads"].append({"name": name, "config": config,
                                   "traffic": traffic, "chips": 1, "why": "tiny"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if like in m.get("workloads", []):
                m["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture
def steered(monkeypatch):
    """Let the harness run on the CPU device: the only part of a run that
    these tests skip is the look for a TPU."""
    monkeypatch.setattr(run, "devices_for", lambda chips: jax.devices()[:chips])


def _run(root, capsys, workload, *, trace=0, seed=2**40 + 11):
    rc = run.main(["--workload", workload, "--seed", str(seed), "--seconds", "0.5",
                   "--trace", str(trace)], root=root)
    out = capsys.readouterr().out.strip().splitlines()
    return rc, (json.loads(out[-1]) if out else None)


# ------------------------------------------------------------- discovery
def test_every_cell_is_found_by_name():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    names = [w["name"] for w in bench["workloads"]]
    assert names == ["v5e_pod_wholechip.heavy", "paper_fig4.batch",
                     "v5e_pod_wholechip.live"]
    for name in names:
        cell = run.load_cell(ROOT, name)
        assert {m["name"] for m in cell.e2e} >= {"setup_s"}
        assert len(cell.e2e) >= 2 and cell.per_layer
        assert set(cell.limits) == set(
            {"sweep": ["mean_flow_rel"],
             "live": ["mismatch_share", "max_chip_diff"]}[cell.traffic["entry"]])


def test_no_cell_file_names_an_execution_flag():
    for path in list((ROOT / "bench" / "configs").glob("*.json")) + list(
            (ROOT / "bench" / "traffic").glob("*.json")):
        text = path.read_text().lower()
        for flag in ("fused", "superstep", "chunk", "shard"):
            assert flag not in text, (path, flag)


def test_unknown_cell_and_missing_program_are_refused(tiny_root, tmp_path, capsys):
    assert run.main(["--workload", "nope", "--seed", "1", "--seconds", "1"],
                    root=tiny_root) != 0
    (tmp_path / "bench").mkdir()
    (tmp_path / "BENCHMARK.json").write_text((ROOT / "BENCHMARK.json").read_text())
    assert run.main(["--workload", "paper_fig4.batch", "--seed", "1",
                     "--seconds", "1"], root=tmp_path) != 0
    assert capsys.readouterr().out == ""


def test_no_tpu_no_result(tiny_root, capsys):
    assert jax.devices()[0].platform != "tpu"
    rc, line = _run(tiny_root, capsys, "paper_fig4.tiny_batch")
    assert rc != 0 and line is None


# ------------------------------------------------------- whole runs, tiny
@pytest.mark.parametrize("traffic", list(TINY))
def test_data_only_cell_runs_and_prints_the_result_line(tiny_root, steered, capsys,
                                                        traffic):
    name = f"{CELLS[traffic][0]}.{traffic}"
    rc, line = _run(tiny_root, capsys, name)
    assert rc == 0
    assert list(line) == ["correct", "attempted", "failed", "metrics", "device",
                          "checks"]
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    e2e = {"tiny_heavy": {"jobs_per_s", "setup_s"},
           "tiny_batch": {"jobs_per_s.batch", "setup_s"},
           "tiny_live": {"decision_p50_ms", "decision_p95_ms", "setup_s"}}
    assert set(line["metrics"]) == e2e[traffic]
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 1
    assert all(set(c) == {"value", "limit"} for c in line["checks"].values())

    rc, line = _run(tiny_root, capsys, name, trace=1)
    assert rc == 0 and line["correct"] is True
    # No TPU plane on the CPU: only the counters and spans are read.
    want = {"tiny_heavy": {"engine.trips_per_job", "alloc.sorts_per_job"},
            "tiny_batch": {"engine.trips_per_job.batch", "alloc.sorts_per_job.batch"},
            "tiny_live": {"live.policy_ms", "live.quantize_ms"}}
    assert set(line["metrics"]) == want[traffic]


def test_counters_of_the_whole_chip_and_batch_paths(tiny_root, steered, capsys):
    _, line = _run(tiny_root, capsys, "v5e_pod_wholechip.tiny_heavy", trace=1)
    # 2M trips for M jobs; three sorts per event, one more per lane.
    assert line["metrics"]["engine.trips_per_job"]["value"] == 2.0
    assert line["metrics"]["alloc.sorts_per_job"]["value"] == pytest.approx(6.025)
    _, line = _run(tiny_root, capsys, "paper_fig4.tiny_batch", trace=1)
    assert line["metrics"]["engine.trips_per_job.batch"]["value"] == 2.0
    assert line["metrics"]["alloc.sorts_per_job.batch"]["value"] == pytest.approx(1 / 20)


def test_counters_fail_loudly_when_the_executors_are_not_found(tiny_root, steered,
                                                               monkeypatch):
    """A change to ``run_sweep``'s executor cache must stop a traced run, not
    drop the two counters from its result."""
    from bench.spans import Spans
    from repro.core import sweeps

    cell = run.load_cell(tiny_root, "v5e_pod_wholechip.tiny_heavy")
    with jax.enable_x64(False):
        entry = cell.entry.Entry(cell.config, cell.traffic, chips=1, seed=3,
                                 spans=Spans())
        entry.setup()
    monkeypatch.setattr(sweeps, "_EXECUTORS", {})
    with pytest.raises(RuntimeError, match="_EXECUTORS"):
        entry.executors()


# ---------------------------------------------------------- faults planted
def _fault_finalize(kind):
    from repro.core import arrivals

    real = arrivals._finalize

    def planted(x0, arrival_times, times, p, n_servers):
        out = real(x0, arrival_times, times, p, n_servers)
        if kind == "answer_altered":
            return out._replace(mean_flowtime=out.mean_flowtime * 1.01)
        half = x0.shape[0] // 2
        return out._replace(mean_flowtime=jnp.mean(out.flow_times[:half]))

    return planted


def _fault_state_unchanged(ranks, m, p, *, dtype=None):
    return jnp.zeros(ranks.shape, dtype or jnp.float32)


def _fault_exchange(real):
    def planted(spec, **kw):
        res = real(spec, **kw)
        for by_m in res.stats.values():
            for m, a in by_m.items():
                half = a.shape[1] // 2
                by_m[m] = np.concatenate([a[:, :half], a[:, :half]], axis=1)
        return res

    return planted


@pytest.mark.parametrize("fault", ["answer_altered", "half_batch", "state_unchanged",
                                   "exchange_left_out"])
def test_faults_in_the_sweep_path_are_not_correct(tiny_root, steered, capsys,
                                                  monkeypatch, fault):
    from repro.core import arrivals, policies, sweeps

    monkeypatch.setattr(sweeps, "_EXECUTORS", {})  # compile the planted path
    if fault in ("answer_altered", "half_batch"):
        monkeypatch.setattr(arrivals, "_finalize", _fault_finalize(fault))
    elif fault == "state_unchanged":
        monkeypatch.setattr(policies, "hesrpt_theta_from_ranks",
                            _fault_state_unchanged)
        monkeypatch.setattr(policies, "RANK_POLICIES", dict(
            policies.RANK_POLICIES, hesrpt=_fault_state_unchanged))
    else:
        monkeypatch.setattr(sweeps, "run_sweep", _fault_exchange(sweeps.run_sweep))
    cell = ("paper_fig4.tiny_batch" if fault == "state_unchanged"
            else "v5e_pod_wholechip.tiny_heavy")
    rc, line = _run(tiny_root, capsys, cell)
    assert rc == 0 and line["correct"] is False


@pytest.mark.parametrize("fault", ["answer_altered", "half_batch", "state_unchanged"])
def test_faults_in_the_live_path_are_not_correct(tiny_root, steered, capsys,
                                                 monkeypatch, fault):
    from repro.sched import cluster

    if fault == "answer_altered":
        real = cluster.quantize_allocation

        def planted(theta, n_chips, **kw):
            chips = real(theta, n_chips, **kw)
            if chips.size > 1 and chips[0] > 1:
                chips[0] -= 1
                chips[-1] += 1
            return chips

        monkeypatch.setattr(cluster, "quantize_allocation", planted)
    elif fault == "half_batch":
        real = cluster._policy_theta

        def planted(name, x, *a):
            keep = jnp.arange(x.shape[0]) < (jnp.sum(x > 0) + 1) // 2
            return real(name, jnp.where(keep, x, 0.0), *a)

        monkeypatch.setattr(cluster, "_policy_theta", planted)
    else:
        real = cluster.ClusterScheduler.allocations
        last: list[dict] = []

        def planted(self):
            # The scheduler moves on, but the caller gets the decision before.
            out = real(self)
            stale = last[-1] if last else {k: 0 for k in out}
            last.append(out)
            return {k: stale.get(k, 0) for k in out}

        monkeypatch.setattr(cluster.ClusterScheduler, "allocations", planted)
    rc, line = _run(tiny_root, capsys, "v5e_pod_wholechip.tiny_live")
    assert rc == 0 and line["correct"] is False


# ---------------------------------------------------- reference and control
@pytest.mark.parametrize("x64", [False, True])
def test_reference_agrees_with_run_sweep(x64):
    from repro.core.sweeps import Sweep, run_sweep

    tol = 1e-9 if x64 else 1e-4
    with jax.enable_x64(x64):
        for kw in (dict(policies=("hesrpt",), rates=(64.0, 128.0), scenario="poisson",
                        n_servers=256.0, n_chips=256, p=0.5),
                   dict(policies=("hesrpt", "srpt", "equi"), rates=(1.0,),
                        scenario="batch", n_servers=1e6, p=0.9)):
            spec = Sweep.create(kw.pop("policies"), kw.pop("rates"), n_jobs=60,
                                n_seeds=2, seed=gen.seed32(5), **kw)
            res = run_sweep(spec, log=False)
            arr, x0 = gen.draw_lanes(spec.scenario, spec.seed, 2, spec.rates, 60, 1.5)
            for pol in spec.policies:
                for r in range(len(spec.rates)):
                    for k in range(2):
                        ref = fluid.mean_flow(pol, x0[r, k], arr[r, k], spec.p,
                                              n_servers=spec.n_servers,
                                              n_chips=spec.n_chips)
                        got = res.stats[pol]["mean_flowtime"][r, k]
                        assert abs(got - ref) <= tol * ref, (pol, r, k, got, ref)


def test_generator_draws_what_the_engine_draws():
    from repro.core.scenarios import make_scenario

    with jax.enable_x64(False):
        for scenario in ("poisson", "batch"):
            arr, x0 = gen.draw_lanes(scenario, 77, 3, (64.0, 128.0), 50, 1.5)
            sampler = make_scenario(scenario, size_alpha=1.5, p=0.5)
            keys = gen.lane_keys(77, 3)
            for r, rate in enumerate((64.0, 128.0)):
                scn = jax.jit(jax.vmap(lambda k, rate=rate: sampler(k, 50, rate)))(keys)
                np.testing.assert_array_equal(np.asarray(scn.x0, np.float64), x0[r])
                np.testing.assert_array_equal(
                    np.asarray(scn.arrival_times, np.float64), arr[r])


def test_live_tapes_are_drawn_from_the_seed():
    kw = dict(rate=gen.rate_at_load(0.9, 256.0, 1.5), n_jobs=500, size_alpha=1.5)
    assert kw["rate"] == pytest.approx(76.8)
    big = 2**33 + 5
    arr, x0 = gen.live_tape(big, 0, **kw)
    again = gen.live_tape(big, 0, **kw)
    np.testing.assert_array_equal(arr, again[0])
    np.testing.assert_array_equal(x0, again[1])
    for other in (gen.live_tape(big + 1, 0, **kw), gen.live_tape(big, 1, **kw)):
        assert not np.array_equal(x0, other[1]) and not np.array_equal(arr, other[0])
    # Stratified: one size in each of 500 equal-probability strata, so the
    # sorted sizes sit at the Pareto quantiles and the tape's work varies
    # little from seed to seed.
    u = 1.0 - np.sort(x0) ** -1.5
    np.testing.assert_array_equal(np.floor(u * 500), np.arange(500))
    assert np.all(np.diff(arr) > 0) and np.all(x0 >= 1.0)
    assert arr[-1] == pytest.approx(500 / kw["rate"], rel=0.05)


def test_reference_decisions_equal_cluster_scheduler():
    from repro.sched import ClusterScheduler, Job

    rng = np.random.default_rng(3)
    with jax.enable_x64(True):
        for m in (1, 7, 40, 300):
            s = ClusterScheduler(256, policy="hesrpt")
            x = rng.pareto(1.5, m) + 1
            for i, xi in enumerate(x):
                s.add_job(Job(f"j{i}", size=float(xi), p=0.5))
            got = np.fromiter(s.allocations().values(), np.int64)
            np.testing.assert_array_equal(got, fluid.decide("hesrpt", x, 0.5, 256))
            assert got.sum() == 256


@pytest.mark.parametrize("traffic", list(TINY))
def test_control_fails_where_the_program_passes(tiny_root, steered, traffic):
    """The reference computed in bfloat16, in the program's place, reads
    above a limit the float32 program stays under, at the full cell's
    limits (the chip runs of ``bench/control.py`` at the cells' own sizes
    set them)."""
    from bench.spans import Spans

    cell = run.load_cell(tiny_root, f"{CELLS[traffic][0]}.{traffic}")
    with jax.enable_x64(False):
        entry = cell.entry.Entry(cell.config, cell.traffic, chips=1, seed=2**35 + 1,
                                 spans=Spans())
        entry.setup()
        entry.window(0.2)
        program, control = entry.check(), entry.check(prec="bfloat16")
    assert all(program[k] <= cell.limits[k] for k in program), program
    assert any(control[k] > cell.limits[k] for k in control), control


# ------------------------------------------------------ trace and HLO
def test_trace_reduction_by_hand():
    tr = devtrace.Trace(
        ops={"/device:TPU:0": [("%a.1 = f32[] add(x)", 0.0, 10.0),
                               ("%b = f32[] fusion(y)", 5.0, 10.0),
                               ("%a.1 = f32[] add(x)", 30.0, 10.0),
                               ("%c = f32[] sort(z)", 60.0, 10.0)]},
        spans=[("bench.window", 0.0, 50.0), ("bench.grid", 0.0, 16.0),
               ("bench.grid", 18.0, 32.0), ("run_sweep", 20.0, 5.0)],
    )
    red = devtrace.reduce(tr, known_spans=("bench.window", "bench.grid", "run_sweep"))
    assert red.window_s == pytest.approx(50e-9)
    assert red.busy_s == pytest.approx(25e-9)
    assert red.idle_share == pytest.approx(0.5)
    assert red.device_ops == [("a.1", pytest.approx(20e-9)), ("b", pytest.approx(10e-9))]
    # Idle 15-30 and 40-50, split by the innermost span open at each instant.
    assert dict(red.idle_gaps) == {"bench.grid": pytest.approx(18e-9),
                                   "run_sweep": pytest.approx(5e-9),
                                   "bench.window": pytest.approx(2e-9)}
    assert sum(v for _, v in red.idle_gaps) == pytest.approx(50e-9 - red.busy_s)
    assert devtrace.reduce(devtrace.Trace(spans=tr.spans), known_spans=()) is None
    # A loop's op spans its body: it counts as busy, not in the op list.
    tr.ops["/device:TPU:0"].append(("%while.3 = (f32[]) while(t)", 0.0, 40.0))
    red = devtrace.reduce(tr, known_spans=(), labels={"b": "b:scatter"})
    assert red.busy_s == pytest.approx(40e-9)
    assert [k for k, _ in red.device_ops] == ["a.1", "b:scatter"]


def test_trace_reduction_on_a_recorded_tpu_trace():
    """Two calls of a tiny whole-chip sweep, traced on one TPU v5e."""
    with gzip.open(DATA / "tiny_trace.json.gz", "rt") as f:
        rec = json.load(f)
    tr = devtrace.Trace(ops={k: [tuple(e) for e in v] for k, v in rec["ops"].items()},
                        spans=[tuple(s) for s in rec["spans"]])
    red = devtrace.reduce(tr, known_spans=("bench.window", "bench.grid", "run_sweep"))
    assert 0 < red.busy_s < red.window_s
    assert 0 < red.idle_share < 1
    assert len(red.device_ops) == 10 and all(s > 0 for _, s in red.device_ops)
    assert {k for k, _ in red.idle_gaps} <= {"bench.window", "bench.grid",
                                             "run_sweep", "outside spans"}
    total = sum(s for _, s in red.idle_gaps)
    assert total == pytest.approx(red.window_s - red.busy_s, rel=1e-6)


def test_hlo_counts_on_a_small_compiled_scan():
    def f(x):
        def body(c, _):
            c = jnp.sort(c) * 1.5
            return jnp.sort(-c), None

        return jax.lax.scan(body, x, None, length=7)[0]

    with jax.enable_x64(False):
        text = jax.jit(f).lower(jnp.arange(64.0)).compile().as_text()
    assert hlo.scan_trips(text) == 7
    assert hlo.op_histogram(text)["sort"] == 14


def test_hlo_counts_on_a_tpu_executor():
    """A whole-chip sweep executor compiled for a TPU v5e (12 jobs, so a
    24-trip event scan with three sorts per trip and one arrival sort)."""
    with gzip.open(DATA / "tiny_exec.txt.gz", "rt") as f:
        text = f.read()
    assert hlo.scan_trips(text) == 24
    assert hlo.op_histogram(text)["sort"] == 3 * 24 + 1
    # The inverse-permutation scatters after the sorts sit inside fusions.
    assert "scatter" in set(hlo.fusion_roots(text).values())
