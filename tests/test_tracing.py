"""The program's own spans, name scopes and compile counter (``repro.tracing``).

Spans are recorded only while a profiler session is active; on the CPU
``jax.profiler.trace`` makes one.  They change nothing that is computed.
"""

import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.core import sweeps
from repro.core.sweeps import Sweep, run_sweep
from repro.sched import ClusterScheduler, Job

DECISION = ["sched.decision", "sched.table", "sched.put", "sched.call", "sched.fetch",
            "sched.quantize", "sched.commit"]
SWEEP_CALL = ["sweep.executor", "sweep.dispatch", "sweep.wait", "sweep.fetch"]


@pytest.fixture
def profiled(tmp_path):
    """A profiler session, with the recorder emptied before it."""
    tracing.clear()
    with jax.profiler.trace(str(tmp_path)):
        yield


def _scheduler(n_jobs=12):
    s = ClusterScheduler(64, policy="hesrpt")
    for i in range(n_jobs):
        s.add_job(Job(f"j{i}", size=1.0 + 0.5 * i, p=0.5))
    return s


def _tape_chips(n_jobs=100, seed=5):
    """Chips of every decision over a seeded Poisson tape: one decision per
    arrival and per departure, about 2 * n_jobs of them."""
    rng = np.random.default_rng(seed)
    arr = np.cumsum(rng.exponential(1 / 20.0, n_jobs))
    sizes = 1.0 + rng.pareto(1.5, n_jobs)
    s = ClusterScheduler(64, policy="hesrpt")
    out, i = [], 0
    while i < n_jobs or s.active_jobs():
        act = s.active_jobs()
        dep = np.inf
        if act:
            rates = s.job_rates(act)
            rem = np.array([j.remaining for j in act])
            with np.errstate(divide="ignore"):
                dep = float(np.min(np.where(rates > 0, rem / rates, np.inf)))
        if i < n_jobs and arr[i] - s.time <= dep:
            if act:
                s.advance_fluid(until_departure=False, dt=max(float(arr[i]) - s.time, 0.0))
            s.time = float(arr[i])
            s.add_job(Job(f"j{i}", size=float(sizes[i]), p=0.5))
            i += 1
        else:
            s.advance_fluid(until_departure=True)
        if s.active_jobs():
            out.append(s.allocations())
    return out


def _spec(**kw):
    base = dict(n_jobs=16, n_seeds=2, p=0.5, n_servers=64.0)
    base.update(kw)
    return Sweep.create(("hesrpt", "equi"), (2.0, 8.0), **base)


def test_nothing_is_recorded_without_a_profiler():
    tracing.clear()
    assert not jax.profiler.TraceAnnotation.is_enabled()
    assert tracing.span("a") is tracing.span("b")  # one shared no-op context
    _scheduler().allocations()
    run_sweep(_spec(), log=False)
    assert tracing.records() == []


def test_spans_nest_with_parent_and_request(profiled):
    with tracing.span("outer", request=7):
        with tracing.span("inner"):
            with tracing.span("leaf", request=9):
                pass
    outer, inner, leaf = tracing.records()
    assert [r.name for r in (outer, inner, leaf)] == ["outer", "inner", "leaf"]
    assert outer.parent is None and inner.parent == outer.id and leaf.parent == inner.id
    assert (outer.request, inner.request, leaf.request) == (7, 7, 9)
    assert outer.start <= inner.start <= leaf.start <= leaf.end <= inner.end <= outer.end


def test_a_span_closes_on_an_exception(profiled):
    with pytest.raises(ValueError), tracing.span("failing"):
        raise ValueError("x")
    with tracing.span("after"):
        pass
    failing, after = tracing.records()
    assert after.parent is None and failing.end >= failing.start


def test_allocations_records_seven_spans_per_decision(profiled):
    s = _scheduler()
    s.allocations()
    s.allocations()
    recs = tracing.records()
    assert [r.name for r in recs] == DECISION * 2
    for k in (0, 7):
        decision, children = recs[k], recs[k + 1:k + 7]
        assert decision.parent is None
        assert {r.parent for r in children} == {decision.id}
        assert {r.request for r in children} == {decision.request}
        assert all(decision.start <= r.start <= r.end <= decision.end for r in children)
        ends = [r.end for r in children]
        assert ends == sorted(ends)  # the six steps follow one another
    assert recs[7].request == recs[0].request + 1


def test_chips_are_identical_with_tracing_on_and_off(tmp_path):
    off = _tape_chips()
    assert len(off) >= 190
    tracing.clear()
    with jax.profiler.trace(str(tmp_path)):
        on = _tape_chips()
    assert on == off
    assert sum(r.name == "sched.decision" for r in tracing.records()) == len(on)


def test_run_sweep_stats_are_identical_with_tracing_on_and_off(tmp_path):
    spec = _spec()
    off = run_sweep(spec, log=False)
    tracing.clear()
    with jax.profiler.trace(str(tmp_path)):
        on = run_sweep(spec, log=False)
    for pol, by_m in off.stats.items():
        for m, a in by_m.items():
            np.testing.assert_array_equal(on.stats[pol][m], a)
    names = [r.name for r in tracing.records()]
    assert names == ["sweep.prepare"] + SWEEP_CALL * len(spec.policies)
    assert len({r.request for r in tracing.records()}) == 1


def test_compile_events_are_counted_and_a_cached_executor_adds_none():
    def fresh(x):
        return jnp.sin(x) * 3.0 + 1.0

    before = len(tracing.compiles())
    jax.jit(fresh)(jnp.arange(5.0)).block_until_ready()
    new = tracing.compiles()[before:]
    assert {c.event for c in new} >= {"jaxpr_trace", "jaxpr_to_mlir_module",
                                      "backend_compile"}
    assert any("fresh" in c.fun_name for c in new if c.event == "backend_compile")
    assert all(c.seconds >= 0 and c.end > 0 for c in new)

    spec = _spec(seed=123)
    run_sweep(spec, log=False)
    before = len(tracing.compiles())
    run_sweep(spec, log=False)
    assert not [c for c in tracing.compiles()[before:] if c.event == "backend_compile"]


def test_reimporting_the_recorder_does_not_double_the_listener():
    try:
        importlib.reload(tracing)

        def again(x):
            return jnp.cos(x) - 2.0

        before = len(tracing.compiles())
        jax.jit(again)(jnp.arange(3.0)).block_until_ready()
        new = [c for c in tracing.compiles()[before:]
               if c.event == "backend_compile" and "again" in c.fun_name]
        assert len(new) == 1
    finally:
        importlib.reload(tracing)


def _op_names(text, opcode):
    return [m.group(1) for line in text.splitlines()
            if re.search(rf"\b{opcode}\(", line)
            for m in [re.search(r'op_name="([^"]*)"', line)] if m]


def test_whole_chip_executor_sorts_and_scatters_sit_under_engine_allocate():
    spec = Sweep.create(("hesrpt",), (4.0,), n_jobs=16, n_seeds=2, p=0.5,
                        n_servers=64.0, n_chips=64)
    keys = jax.random.split(jax.random.PRNGKey(0), 2)
    lowered = jax.jit(sweeps._build_fn(spec, "hesrpt", None, False)).lower(
        keys, jnp.asarray(spec.rates))
    text = lowered.compile().as_text()
    in_scan = [n for op in ("sort", "scatter") for n in _op_names(text, op)
               if "/while/body/" in n]
    assert in_scan and all("engine.allocate" in n for n in in_scan)
    source = lowered.as_text(debug_info=True)  # every op's location, before fusion
    for scope in ("engine.advance", "engine.sample", "engine.reduce"):
        assert re.search(rf'["/(]{scope}[/)"]', source), scope


def test_ranked_scan_allocate_and_advance_scopes():
    spec = Sweep.create(("hesrpt",), (1.0,), scenario="batch", n_jobs=16, n_seeds=2,
                        p=0.5, n_servers=64.0)
    keys = jax.random.split(jax.random.PRNGKey(0), 2)
    text = jax.jit(sweeps._build_fn(spec, "hesrpt", None, False)).lower(
        keys, jnp.asarray(spec.rates)).as_text(debug_info=True)
    assert "engine.allocate" in text and "engine.advance" in text
