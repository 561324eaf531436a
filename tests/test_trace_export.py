"""The recorded-trace path (engine ``record=True``) and its Perfetto export.

Two layers:

- the ``EngineTrace`` itself must be physically sane across the
  continuous, quantized and fused rule paths — allocations non-negative
  and within budget at every event, remaining sizes non-increasing per
  job, event times ordered, and each job's last positive-size epoch
  consistent with its reported completion time;
- ``launch/trace_export.py`` must turn that trace into *valid* Chrome
  trace-event JSON (the committed sample artifact included): slices only
  while a job holds an allocation, one completion marker per finished job
  at exactly its completion time, counter tracks present, and the schema
  validator catching each way the format can be malformed.
"""

import json
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core import engine, make_policy, make_scenario
from repro.core.telemetry import DEFAULT_METRICS, make_probe
from repro.launch import trace_export

N_JOBS = 24
SAMPLE = Path(__file__).parent.parent / "examples" / "sample_schedule_trace.json"


def _recorded(kind, seed=0, rate=2.0, n_jobs=N_JOBS, p=0.5):
    scn = make_scenario("poisson", p=p)(jax.random.key(seed), n_jobs, rate)
    dtype = scn.x0.dtype
    pol = make_policy("hesrpt")
    if kind == "continuous":
        rule, unit, fused = engine.continuous_rule(pol, 1.0, dtype=dtype), 1.0, False
    elif kind == "quantized":
        rule, unit, fused = engine.quantized_rule(pol, 64, dtype=dtype), 64.0, False
    else:
        rule, unit, fused = engine.quantized_rule(pol, 64, dtype=dtype), 64.0, True
    res = engine.run(scn.x0, scn.arrival_times, p, rule, record=True,
                     fused=fused)
    return res, unit


# ------------------------------------------------------- trace-path invariants
@pytest.mark.parametrize("kind", ["continuous", "quantized", "fused"])
def test_recorded_trace_is_physically_sane(kind):
    res, unit = _recorded(kind)
    alloc = np.asarray(res.trace.alloc)
    times = np.asarray(res.trace.times)
    sizes = np.asarray(res.trace.sizes)
    assert np.all(alloc >= 0)
    assert np.all(alloc.sum(axis=1) <= unit * (1 + 1e-12))  # never oversubscribed
    if unit != 1.0:  # quantized paths allocate whole chips
        assert np.all(alloc == np.round(alloc))
    assert np.all(np.diff(times) >= 0)
    assert np.all(np.diff(sizes, axis=0) <= 1e-12)  # work only ever completes
    # completion times (input order) match the trace: a departed job's
    # size hits zero by the first event at/after its completion time
    done = np.asarray(res.completion_times)[np.asarray(res.order)]
    assert np.all(np.isfinite(done))
    for j in range(sizes.shape[1]):
        after = times >= done[j] + 1e-9
        assert np.all(sizes[after, j] == 0.0)
        assert np.all(alloc[after, j] == 0.0)


def test_recorded_trace_composes_with_telemetry_bitforbit():
    scn = make_scenario("poisson", p=0.5)(jax.random.key(7), N_JOBS, 2.0)
    rule = engine.continuous_rule(make_policy("hesrpt"), 1.0, dtype=scn.x0.dtype)
    probe = make_probe(DEFAULT_METRICS, mode="series", dtype=scn.x0.dtype)
    plain = engine.run(scn.x0, scn.arrival_times, 0.5, rule, record=True)
    probed = engine.run(scn.x0, scn.arrival_times, 0.5, rule, record=True,
                        telemetry=probe)
    for a, b in zip(plain.trace, probed.trace, strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the probe saw the same epochs the trace recorded
    np.testing.assert_array_equal(np.asarray(probed.trace.times),
                                  np.asarray(probed.telemetry.series["t"]))


# ------------------------------------------------------------------- exporter
@pytest.mark.parametrize("kind", ["continuous", "quantized"])
def test_schedule_to_events_is_valid_and_complete(kind):
    res, unit = _recorded(kind)
    events = trace_export.schedule_to_events(res, alloc_unit=unit, p=0.5)
    trace_export.validate_trace_events(events)  # schema-valid as built
    done = np.asarray(res.completion_times)
    markers = [e for e in events if e["ph"] == "i"]
    assert len(markers) == int(np.sum(np.isfinite(done)))
    # marker timestamps are exactly the completion times (default 1e6 scale)
    got = sorted(e["ts"] for e in markers)
    want = sorted(float(t) * 1e6 for t in done[np.isfinite(done)])
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)
    slices = [e for e in events if e["ph"] == "X"]
    assert slices and all(e["dur"] > 0 for e in slices)
    order = np.asarray(res.order)
    for e in slices:  # no slice outlives its job
        j = e["tid"]
        assert e["ts"] + e["dur"] <= float(done[order[j]]) * 1e6 + 1e-3
    counters = {e["name"] for e in events if e["ph"] == "C"}
    assert {"efficiency", "utilization", "queue"} <= counters


def test_exporter_prefers_telemetry_series_counters():
    scn = make_scenario("poisson", p=0.5)(jax.random.key(1), N_JOBS, 2.0)
    rule = engine.continuous_rule(make_policy("hesrpt"), 1.0, dtype=scn.x0.dtype)
    probe = make_probe(DEFAULT_METRICS, mode="series", dtype=scn.x0.dtype)
    res = engine.run(scn.x0, scn.arrival_times, 0.5, rule, record=True,
                     telemetry=probe)
    events = trace_export.schedule_to_events(
        res, telemetry_series=res.telemetry.series
    )
    trace_export.validate_trace_events(events)
    counters = {e["name"] for e in events if e["ph"] == "C"}
    assert "entropy" in counters  # only the probe computes entropy
    series = {k: np.asarray(v) for k, v in res.telemetry.series.items()}
    live = series["dt"] > 0
    eff = [e for e in events if e["ph"] == "C" and e["name"] == "efficiency"]
    got = np.array([e["args"]["efficiency"] for e in eff[:-1]])  # final flat-line
    np.testing.assert_allclose(got, series["efficiency"][live], atol=1e-12)


def test_export_requires_a_recorded_trace():
    scn = make_scenario("poisson", p=0.5)(jax.random.key(2), 8, 2.0)
    rule = engine.continuous_rule(make_policy("hesrpt"), 1.0, dtype=scn.x0.dtype)
    res = engine.run(scn.x0, scn.arrival_times, 0.5, rule)
    with pytest.raises(ValueError, match="record=True"):
        trace_export.schedule_to_events(res)


# ------------------------------------------------------------ schema validator
def test_validator_rejects_each_malformation():
    ok = {"ph": "X", "pid": 0, "tid": 1, "ts": 0.0, "dur": 1.0, "name": "s"}
    trace_export.validate_trace_events([ok])
    bad_cases = [
        [],  # empty
        [{**ok, "ph": "Q"}],  # unknown phase
        [{k: v for k, v in ok.items() if k != "dur"}],  # missing required key
        [{**ok, "ts": float("nan")}],  # non-finite timestamp
        [{**ok, "ts": -1.0}],  # negative timestamp
        [{**ok, "dur": float("nan")}],  # NaN duration
        [{"ph": "C", "pid": 0, "ts": 0.0, "name": "q", "args": {}}],  # empty counter
        [{"ph": "C", "pid": 0, "ts": 0.0, "name": "q", "args": {"q": "hi"}}],
        ["not a dict"],
    ]
    for events in bad_cases:
        with pytest.raises(ValueError):
            trace_export.validate_trace_events(events)


# ----------------------------------------------------- artifact + CLI round trip
def test_committed_sample_trace_is_valid():
    with open(SAMPLE) as f:
        doc = json.load(f)
    events = doc["traceEvents"]
    trace_export.validate_trace_events(events)
    phases = {e["ph"] for e in events}
    assert {"X", "i", "C", "M"} <= phases
    names = {e["name"] for e in events if e["ph"] == "M"}
    assert "process_name" in names and "thread_name" in names


def test_cli_writes_a_loadable_trace(tmp_path):
    out = tmp_path / "trace.json"
    trace_export.main([
        "--out", str(out), "--jobs", "6", "--rate", "2.0", "--seed", "1",
        "--n-chips", "16",
    ])
    with open(out) as f:
        doc = json.load(f)
    assert doc["displayTimeUnit"] == "ms"
    trace_export.validate_trace_events(doc["traceEvents"])
    assert sum(1 for e in doc["traceEvents"] if e["ph"] == "i") == 6
