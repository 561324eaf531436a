"""CPU tests of the per-layer readers of what the program records about
itself (``bench/program.py``): its spans, its compile events and the name
scopes of its compiled ops.

The readers read only from a run that captured a device trace.  The CPU
has no device plane, so the whole runs below steer past that, as the
harness tests steer past the look for a TPU: a trace reduction with no ops
stands in for the chip's.
"""

from __future__ import annotations

import importlib.util
import json
import os
from pathlib import Path
from types import SimpleNamespace

import jax
import pytest

from bench import devtrace, run
from bench.devtrace import Reduced
from repro import tracing
from repro.tracing import Compile, Span

ROOT = Path(__file__).resolve().parents[2]
WINDOW = (10.0, 20.0)

SPAN_METRICS = {
    "v5e_pod_wholechip.tiny_live": {"sched.table_ms", "sched.dispatch_ms",
                                    "sched.fetch_ms", "sched.commit_ms",
                                    "setup.compile_s"},
    "paper_fig4.tiny_batch": {"sweep.host_ms_per_call.batch", "setup.compile_s",
                              "alloc.device_share.batch"},
}
TINY = {
    "tiny_batch": ("paper_fig4", "paper_fig4.batch",
                   {"entry": "sweep", "scenario": "batch", "rates": [1.0],
                    "jobs_per_lane": 20, "seeds_per_call": 2, "trace_seconds": 0.3}),
    "tiny_live": ("v5e_pod_wholechip", "v5e_pod_wholechip.live",
                  {"entry": "live", "loads": [0.9], "jobs_per_tape": 60,
                   "trace_seconds": 0.3}),
}


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"), ROOT / "bench" / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _reduced(busy_s=1.0, device_ops=()):
    return Reduced(window_s=2.0, busy_s=busy_s, idle_share=1 - busy_s / 2.0,
                   device_ops=list(device_ops), idle_gaps=[])


def _ctx(trace=_reduced(), entry=None):
    spans = SimpleNamespace(records=[("bench.grid", 9.0, 11.0),
                                     (devtrace.WINDOW_SPAN, *WINDOW)])
    return SimpleNamespace(trace=trace, spans=spans, entry=entry, cell=None)


def _span(i, name, start, end, request, parent=None):
    return Span(i, name, start, end, parent, request)


# Two decisions inside the window and one outside it; durations in seconds.
DECISIONS = [
    _span(0, "sched.decision", 5.0, 5.01, 0),
    _span(1, "sched.table", 5.0, 5.004, 0, 0),
    _span(2, "sched.decision", 11.0, 11.0016, 1),
    _span(3, "sched.table", 11.0, 11.0004, 1, 2),
    _span(4, "sched.put", 11.0004, 11.0005, 1, 2),
    _span(5, "sched.call", 11.0005, 11.0009, 1, 2),
    _span(6, "sched.fetch", 11.0009, 11.0012, 1, 2),
    _span(7, "sched.quantize", 11.0012, 11.0013, 1, 2),
    _span(8, "sched.commit", 11.0013, 11.0014, 1, 2),
    _span(9, "sched.decision", 12.0, 12.0026, 2),
    _span(10, "sched.table", 12.0, 12.0006, 2, 9),
    _span(11, "sched.put", 12.0006, 12.0008, 2, 9),
    _span(12, "sched.call", 12.0008, 12.0014, 2, 9),
    _span(13, "sched.fetch", 12.0014, 12.0019, 2, 9),
    _span(14, "sched.quantize", 12.0019, 12.0020, 2, 9),
    _span(15, "sched.commit", 12.0020, 12.0023, 2, 9),
]
SWEEP_CALLS = [
    _span(0, "sweep.prepare", 11.0, 11.001, 4),
    _span(1, "sweep.executor", 11.001, 11.0012, 4),
    _span(2, "sweep.dispatch", 11.0012, 11.0015, 4),
    _span(3, "sweep.wait", 11.0015, 11.0115, 4),
    _span(4, "sweep.fetch", 11.0115, 11.0120, 4),
    _span(5, "sweep.executor", 11.012, 11.0121, 4),
    _span(6, "sweep.dispatch", 11.0121, 11.0124, 4),
    _span(7, "sweep.wait", 11.0124, 11.0224, 4),
    _span(8, "sweep.fetch", 11.0224, 11.0227, 4),
    _span(9, "sweep.prepare", 25.0, 25.5, 5),  # after the window
]


@pytest.fixture
def recorded(monkeypatch):
    """Hand the readers these records in place of the recorder's."""
    def use(spans=(), compiles=()):
        monkeypatch.setattr(tracing, "records", lambda: list(spans))
        monkeypatch.setattr(tracing, "compiles", lambda: list(compiles))
    return use


@pytest.mark.parametrize("name, want", [
    ("sched.table_ms", 0.5),       # medians of 0.4 and 0.6 ms
    ("sched.dispatch_ms", 0.65),   # put + call: 0.5 and 0.8 ms
    ("sched.fetch_ms", 0.4),       # 0.3 and 0.5 ms
    ("sched.commit_ms", 0.2),      # 0.1 and 0.3 ms
])
def test_decision_readers_read_the_window_only(recorded, name, want):
    recorded(DECISIONS)
    assert _reader(name)(_ctx()) == pytest.approx(want)


def test_sweep_host_ms_per_call(recorded):
    recorded(SWEEP_CALLS)
    # (1.0 prepare + 0.3 executors + 0.6 dispatches + 0.8 fetches) / 2 calls
    got = _reader("sweep.host_ms_per_call.batch")(_ctx())
    assert got == pytest.approx(1.35)


def test_compile_seconds_are_the_union_before_the_window(recorded):
    recorded(compiles=[
        Compile("jaxpr_trace", 2.0, "outer", 4.0),      # 2.0 - 4.0
        Compile("jaxpr_trace", 0.5, "inner", 3.5),      # inside the outer one
        Compile("backend_compile", 1.0, "outer", 6.0),  # 5.0 - 6.0
        Compile("backend_compile", 3.0, "late", 14.0),  # ends in the window
    ])
    assert _reader("setup.compile_s")(_ctx()) == pytest.approx(3.0)


@pytest.mark.parametrize("name", ["sched.table_ms", "sched.dispatch_ms", "sched.fetch_ms",
                                  "sched.commit_ms", "sweep.host_ms_per_call.batch",
                                  "setup.compile_s"])
def test_readers_return_none_when_nothing_was_recorded(recorded, name):
    recorded(spans=[_span(0, "sched.table", 1.0, 2.0, 0), _span(1, "sweep.fetch", 21, 22, 1)],
             compiles=[Compile("backend_compile", 1.0, "late", 15.0)])
    assert _reader(name)(_ctx()) is None
    recorded(DECISIONS + SWEEP_CALLS, [Compile("backend_compile", 1.0, "f", 5.0)])
    ctx = _ctx()
    ctx.trace = None  # a run that captured no device trace
    assert _reader(name)(ctx) is None


def test_readers_report_nothing_for_a_program_without_its_own_spans(monkeypatch):
    import builtins

    real_import = builtins.__import__

    def no_tracing(name, globals=None, locals=None, fromlist=(), level=0):
        if name == "repro" and fromlist and "tracing" in fromlist:
            raise ImportError("no repro.tracing")
        return real_import(name, globals, locals, fromlist, level)

    monkeypatch.setattr(builtins, "__import__", no_tracing)
    for name in ("sched.table_ms", "sweep.host_ms_per_call.batch", "setup.compile_s"):
        assert _reader(name)(_ctx()) is None


# A compiled text in the TPU's manner: the scatter fusion carries no
# metadata of its own, the ops inside it do.
TEXT = """HloModule m

%fused_scatter (p0: s32[8], p1: s32[8]) -> s32[8] {
  %p0 = s32[8]{0} parameter(0)
  %p1 = s32[8]{0} parameter(1)
  %r = s32[8]{0} reshape(%p1), metadata={op_name="f/body/engine.allocate/broadcast"}
  ROOT %scatter.1 = s32[8]{0} scatter(%p0, %r, %p1), to_apply=%add
}

%fused_mixed (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %a = f32[8]{0} exponential(%p0), metadata={op_name="jit(f)/while/body/engine.allocate/pow"}
  ROOT %b = f32[8]{0} add(%a, %p0), metadata={op_name="jit(f)/while/body/engine.advance/add"}
}

ENTRY %main (x: f32[8], y: s32[8]) -> s32[8] {
  %x = f32[8]{0} parameter(0)
  %y = s32[8]{0} parameter(1)
  %sort.60 = s32[8]{0} sort(%y), dimensions={0}, metadata={op_name="f/body/engine.allocate/sort"}
  %fusion.118 = s32[8]{0} fusion(%y, %sort.60), kind=kCustom, calls=%fused_scatter
  %fusion.7 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_mixed
  %fusion.130 = f32[8]{0} add(%x, %x), metadata={op_name="f/body/engine.advance/gather"}
  ROOT %sort.61 = s32[8]{0} sort(%sort.60), metadata={op_name="f/vmap(engine.allocate)/sort"}
}
"""
OPS = [("fusion.118:scatter", 0.5), ("sort.60", 0.1), ("fusion.7", 0.2),
       ("fusion.130:reshape", 0.1), ("sort.61", 0.05)]


@pytest.mark.parametrize("name", ["alloc.device_share", "alloc.device_share.batch"])
def test_alloc_device_share_reads_the_scopes_of_the_top_ops(name):
    entry = SimpleNamespace(executors=lambda: [(TEXT, 1, 8)])
    ctx = _ctx(trace=_reduced(busy_s=1.0, device_ops=OPS), entry=entry)
    # fusion.118 by what it holds, sort.60 and sort.61 by their own names;
    # fusion.7 holds ops of both scopes and fusion.130 is the advance's.
    assert _reader(name)(ctx) == pytest.approx(0.65)

    other = TEXT.replace('%fusion.130 = f32[8]{0} add(%x, %x), metadata={op_name="f/'
                         'body/engine.advance/gather"}',
                         '%fusion.130 = f32[8]{0} add(%x, %x)').replace(
        '%sort.60 = s32[8]{0} sort(%y), dimensions={0}, metadata={op_name="f/body/'
        'engine.allocate/sort"}',
        '%sort.60 = s32[8]{0} sort(%y), dimensions={0}, metadata={op_name="jit(f)/'
        'engine.advance/sort"}')
    entry.executors = lambda: [(TEXT, 1, 8), (other, 1, 8)]
    # Two executors disagree on sort.60: it no longer counts.
    assert _reader(name)(ctx) == pytest.approx(0.55)

    assert _reader(name)(_ctx(trace=None, entry=entry)) is None
    bare = SimpleNamespace(executors=lambda: [(TEXT.replace("engine.allocate", "x"), 1, 8)])
    assert _reader(name)(_ctx(trace=_reduced(device_ops=OPS), entry=bare)) is None


# ------------------------------------------------------- whole runs, tiny
@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A checkout whose BENCHMARK.json gains a tiny live and a tiny batch
    cell, each listed wherever the full-size cell it stands for is."""
    root = tmp_path_factory.mktemp("checkout")
    for d in ("traffic", "limits"):
        (root / "bench" / d).mkdir(parents=True)
    for d in ("entries", "metrics", "reference", "configs"):
        os.symlink(ROOT / "bench" / d, root / "bench" / d)
    os.symlink(ROOT / "src", root / "src")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for traffic, (config, like, mix) in TINY.items():
        name = f"{config}.{traffic}"
        (root / "bench" / "traffic" / f"{traffic}.json").write_text(json.dumps(mix))
        (root / "bench" / "limits" / f"{name}.json").write_text(
            (ROOT / "bench" / "limits" / f"{like}.json").read_text())
        bench["workloads"].append({"name": name, "config": config,
                                   "traffic": traffic, "chips": 1, "why": "tiny"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if like in m.get("workloads", []):
                m["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.mark.parametrize("cell", list(SPAN_METRICS))
def test_a_traced_tiny_run_prints_the_program_metrics(tiny_root, monkeypatch, capsys,
                                                      cell):
    from repro.core import sweeps

    monkeypatch.setattr(run, "devices_for", lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(sweeps, "_EXECUTORS", {})  # compiled by this process's code
    real = devtrace.reduce
    monkeypatch.setattr(devtrace, "reduce",
                        lambda trace, **kw: real(trace, **kw) or _reduced(busy_s=0.5))
    rc = run.main(["--workload", cell, "--seed", str(2**40 + 17), "--seconds", "0.5",
                   "--trace", "1"], root=tiny_root)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is True
    metrics = line["metrics"]
    assert set(metrics) >= SPAN_METRICS[cell]
    for name in SPAN_METRICS[cell] - {"alloc.device_share.batch"}:
        assert metrics[name]["value"] > 0, name
    # No device ops stand in for the chip's: none is under the scope.
    if "alloc.device_share.batch" in metrics:
        assert metrics["alloc.device_share.batch"]["value"] == 0.0


def test_alloc_device_share_on_a_compiled_whole_chip_executor():
    """The sorts and scatters of a real executor's scan are the allocate's."""
    import re

    import jax.numpy as jnp

    from repro.core import sweeps
    from repro.core.sweeps import Sweep

    spec = Sweep.create(("hesrpt",), (4.0,), n_jobs=16, n_seeds=2, p=0.5,
                        n_servers=64.0, n_chips=64)
    keys = jax.random.split(jax.random.PRNGKey(1), 2)
    text = jax.jit(sweeps._build_fn(spec, "hesrpt", None, False)).lower(
        keys, jnp.asarray(spec.rates)).compile().as_text()
    in_scan = [m.group(1) for m in re.finditer(
        r"%((?:sort|scatter)[\w.]*) = [^\n]*op_name=\"[^\"]*/while/body/", text)]
    assert in_scan
    ops = [(name, 0.1) for name in in_scan] + [("no-such-op", 0.5)]
    entry = SimpleNamespace(executors=lambda: [(text, 2, 16)])
    busy = 0.1 * len(in_scan) + 0.5
    got = _reader("alloc.device_share")(_ctx(trace=_reduced(busy, ops), entry=entry))
    assert got == pytest.approx(0.1 * len(in_scan) / busy)
