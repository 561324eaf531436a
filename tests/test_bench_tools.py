"""The perf gate and the accelerator lane: tools/bench_diff.py,
benchmarks/backend_lane.py, and the profiler's reconstructed baseline.

bench_diff is what CI runs between the committed ``BENCH_sweeps.json`` and
the freshly regenerated one, so its matching and failure semantics are
pinned here on synthetic records: spec-hash matching must survive falsy
field additions (a baseline written before ``fused`` existed still matches
a new record carrying ``fused: false``), wall regressions only fail above
the noise floor, and any metric-mean drift on a sweep record fails.
"""

import json

import jax.numpy as jnp
import numpy as np
import pytest

from tools.bench_diff import diff, main as bench_diff_main, spec_key


def _rec(wall=1.0, mean=(1.0, 2.0), backend="cpu", kind="sweep", **spec):
    base_spec = dict(policies=["hesrpt"], rates=[0.5, 2.0],
                     scenario="poisson", n_jobs=40, n_seeds=3, seed=0)
    base_spec.update(spec)
    return {
        "kind": kind,
        "spec": base_spec,
        "cells": {"hesrpt": {"mean_flowtime": {"mean": list(mean),
                                               "std": [0.0, 0.0]}}},
        "wall_s": wall,
        "backend": backend,
    }


# -------------------------------------------------------------- spec matching
def test_spec_key_ignores_falsy_field_additions():
    old = _rec()
    new = _rec(fused=False, snap_slices=False, classes=None)
    assert spec_key(old) == spec_key(new)
    assert spec_key(_rec(fused=True)) != spec_key(old)
    assert spec_key(_rec(backend="gpu")) != spec_key(old)
    assert spec_key(_rec(n_jobs=80)) != spec_key(old)


def test_self_diff_passes():
    recs = [_rec(), _rec(n_jobs=80, wall=2.0)]
    failures, _notes = diff(recs, recs)
    assert failures == []


# ------------------------------------------------------------------ the gates
def test_metric_mean_drift_fails():
    failures, _ = diff([_rec()], [_rec(mean=(1.0, 2.0000001))], rtol=1e-9)
    assert len(failures) == 1 and "drift" in failures[0]
    failures, _ = diff([_rec()], [_rec(mean=(1.0, 2.0000001))], rtol=1e-3)
    assert failures == []


def test_wall_regression_fails_only_above_noise_floor():
    failures, _ = diff([_rec(wall=1.0)], [_rec(wall=1.5)])
    assert len(failures) == 1 and "wall-time" in failures[0]
    # below the min-wall floor: smoke-cell timer noise, not a regression
    failures, _ = diff([_rec(wall=0.1)], [_rec(wall=0.4)])
    assert failures == []
    # 30% threshold is a ratio, not absolute
    failures, _ = diff([_rec(wall=1.0)], [_rec(wall=1.25)])
    assert failures == []


def test_lost_coverage_notes_but_passes():
    failures, notes = diff([_rec(), _rec(n_jobs=80)], [_rec()])
    assert failures == []
    assert any("coverage lost" in n for n in notes)


def test_non_sweep_records_skip_metric_gate():
    base = _rec(kind="profile_engine", mean=(1.0, 2.0))
    new = _rec(kind="profile_engine", mean=(5.0, 6.0))
    failures, _ = diff([base], [new])
    assert failures == []  # timings drift freely; only wall/ratio gates apply


def test_cli_parses_options_and_exit_codes(tmp_path):
    base = tmp_path / "base.json"
    new = tmp_path / "new.json"
    base.write_text(json.dumps({"records": [_rec(wall=1.0)]}))
    new.write_text(json.dumps({"records": [_rec(wall=1.4)]}))
    assert bench_diff_main([str(base), str(new)]) == 1
    assert bench_diff_main([str(base), str(new),
                            "--max-time-ratio", "2.0"]) == 0
    assert bench_diff_main([str(base), str(new), "--min-wall", "1.5"]) == 0
    assert bench_diff_main([str(base)]) == 2  # usage


# ------------------------------------------------------------ backend lane
def test_backend_lane_specs_and_records(tmp_path):
    from benchmarks import backend_lane

    specs = backend_lane.lane_specs(smoke=True)
    labels = [label for label, _ in specs]
    assert labels == ["quantized", "quantized-fused", "continuous"]
    by = dict(specs)
    assert by["quantized-fused"].fused and not by["quantized"].fused
    assert by["quantized"]._replace(fused=True) == by["quantized-fused"]
    assert by["continuous"].n_chips is None

    text, records = backend_lane.main(smoke=True)
    assert "bit-for-bit): True" in text
    kinds = [r["kind"] for r in records]
    assert kinds == ["sweep", "sweep", "sweep", "backend_lane"]
    assert [r.get("lane") for r in records[:3]] == labels
    summary = records[-1]
    assert summary["fused_speedup_wall"] > 0
    assert set(summary["lanes"]) == set(labels)
    json.dumps(records)  # artifact-ready as-is

    # append_records merges into an existing artifact and creates one fresh
    path = tmp_path / "BENCH_sweeps.json"
    backend_lane.append_records(records[:1], str(path))
    backend_lane.append_records(records[1:], str(path))
    data = json.loads(path.read_text())
    assert [r["kind"] for r in data["records"]] == kinds


# ------------------------------------------------- profiler's seed baseline
def test_profiler_seed_quantizer_matches_collapsed():
    """The reconstructed 3-sort seed quantizer and the shipped collapsed
    2-sort quantizer are the same function — the mutual-exclusivity proof
    the collapse rests on, checked end to end."""
    from benchmarks.profile_engine import _seed_quantize
    from repro.core.engine import quantize_allocation_jax

    rng = np.random.default_rng(23)
    for n_chips, min_chips in ((16, 1), (64, 3), (8, 2)):
        for _ in range(10):
            m = 12
            w = rng.pareto(1.2, m) + 0.01
            w[rng.random(m) < 0.3] = 0.0
            s = w.sum()
            theta = jnp.asarray(w / s if s > 0 else w)
            np.testing.assert_array_equal(
                np.asarray(_seed_quantize(theta, n_chips,
                                          min_chips=min_chips)),
                np.asarray(quantize_allocation_jax(theta, n_chips,
                                                   min_chips=min_chips)),
            )


def test_profiler_sort_count_helper():
    from benchmarks.profile_engine import _sort_count
    from repro.core.policies import hesrpt

    x = jnp.asarray(np.random.default_rng(0).pareto(1.5, 32) + 1.0)
    assert _sort_count(hesrpt, x, 0.5) == 2
    assert _sort_count(lambda v: v * 2.0, x) == 0
