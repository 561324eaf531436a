"""Drive the scheduler's sweep engine once on one TPU chip, and check it.

Run from the root of a checkout::

    python chip_smoke.py               # phases (a)-(e) on one chip
    python chip_smoke.py --four-chips  # the sharded sweeps on four chips only

Everything runs in this one process; it starts no child that touches JAX.
The cluster is the one of ``benchmarks/backend_lane.py``: 256 chips, speedup
``s(k) = k**0.5``, heSRPT, 1000 jobs per seed, 8 seeds, and a Poisson rate
grid whose heaviest rate keeps about 200 jobs in flight (nearly 300 at the
peak), so the whole-chip rule often has more jobs than chips.

Phases (each prints one JSON line):

- (a) ``continuous``: the continuous sweep on the path ``run_sweep`` picks
  (the carried-rank scan), against the Theorem 8 closed form on the batch
  scenario and against the ``ClusterScheduler`` per-event loop on two seeds
  of the Poisson stream;
- (b) ``superstep``: the same stream with ``superstep=True``, against (a);
- (c) ``quantized``: the whole-chip sweep; one seed's stream event for
  event against the NumPy oracle's decision on the same state, and its
  trajectory against ``benchmarks/quantized.py::cross_check``;
- (d) ``fused``: the fused allocate with the Pallas kernel compiled for the
  chip (``tpu_custom_call`` in the compiled executor), against the oracle
  as in (c), and against the unfused rule on every state (c) recorded;
- (e) ``cluster``: ``ClusterScheduler.run_fluid_to_completion`` on a
  256-chip cluster, which must run on the engine, against the per-event
  loop.

Precision.  Every phase runs on the chip in float32 (``jax_enable_x64``
off inside the phase); every reference runs on the host CPU in float64.
The TPU has no native float64: compiled for a described v5e chip, the
float64 executors are 10-15x larger after optimisation and compile 10-130x
slower than float32, and the Pallas kernel refuses 64-bit types.  The
float32 contract checked here, against float64 references fed the same
float32-exact inputs:

- mean flows within ``FLOW_RTOL`` relative;
- whole chips equal to the float64 decision on the same state at every
  event, except where float32 breaks a tie of the largest-remainder
  rounding the other way: one chip per job at most, on at most
  ``MAX_TIE_SHARE`` of the events, each counted.  How many events tie
  depends on the cluster: at p = 0.5 the brackets are ``(2r - 1) / m**2``,
  so at some active counts ``m`` many jobs' shares tie exactly.  This
  configuration ties on about 1% of events in float32 on the CPU; a
  smaller one, 40 jobs on 64 chips, ties on most of them.

A whole float32 trajectory drifts from the float64 one: near-equal
remaining sizes swap ranks, and nearly simultaneous departures become one
event or two.  Phase (c) reports that drift against the per-event oracle
(differing events, largest chip difference, worst per-job flow) beside the
mean flow it bounds.

The last line of standard output is ``{"ok": true, "device": {...}}``.  Any
failed phase raises, so the script exits non-zero without that line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
# The references run on the host CPU beside the chip, so keep that backend
# when the platform list is pinned; and keep the TPU library's logs out of
# the shared temporary directory.
if os.environ.get("JAX_PLATFORMS") and "cpu" not in os.environ[
        "JAX_PLATFORMS"].split(","):
    os.environ["JAX_PLATFORMS"] += ",cpu"
os.environ.setdefault("TPU_LOG_DIR", "disabled")

N_CHIPS = 256
P = 0.5
N_JOBS = 1000
N_SEEDS = 8
RATES = (4.0, 16.0, 64.0, 128.0)
PRECISION = "float32"

#: Relative tolerance of a float32 mean flow against its float64 reference.
FLOW_RTOL = 1e-3
#: Largest share of events whose chips may differ from the float64 decision
#: by a broken rounding tie (about 1% in float32 on the CPU).
MAX_TIE_SHARE = 0.05


def _emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def _require(ok: bool, what: str, record: dict) -> None:
    if not ok:
        raise AssertionError(f"{what}: {json.dumps(record)}")


def _rel(a, b) -> float:
    import numpy as np

    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.abs(b)))


def _spec(**kw):
    from repro.core.sweeps import Sweep

    kw.setdefault("scenario", "poisson")
    rates = kw.pop("rates", RATES)
    return Sweep.create(
        ("hesrpt",), rates, n_jobs=N_JOBS, n_seeds=N_SEEDS, p=P,
        n_servers=float(N_CHIPS), seed=0, **kw,
    )


def _timed_sweep(spec, **kw):
    """Run twice: the first call compiles, the second is the timed one."""
    from repro.core.sweeps import run_sweep

    first = run_sweep(spec, log=False, **kw)
    again = run_sweep(spec, log=False, **kw)
    return again, first.compile_s


def _draws(spec, rate: float):
    """The float32 jobs every seed of ``spec`` draws at ``rate``, as the
    sweep draws them (same keys, vmapped over seeds), in float64 numpy."""
    import jax
    import numpy as np

    from repro.core.scenarios import make_scenario

    sampler = make_scenario(spec.scenario, size_alpha=spec.size_alpha, p=spec.p)
    keys = jax.random.split(jax.random.PRNGKey(spec.seed), spec.n_seeds)
    scn = jax.jit(jax.vmap(lambda k: sampler(k, spec.n_jobs, rate)))(keys)
    return (np.asarray(scn.arrival_times, np.float64),
            np.asarray(scn.x0, np.float64))


def _on_host(fn, *args, **kw):
    """Run a reference on the host CPU in float64."""
    import jax

    with jax.enable_x64(True), jax.default_device(jax.devices("cpu")[0]):
        return fn(*args, **kw)


def _phase_line(phase: str, compile_s: float, wall_s: float, **agree) -> dict:
    return {"phase": phase, "precision": PRECISION,
            "compile_s": compile_s, "wall_s": wall_s, **agree}


# ------------------------------------------------------------------ phases
def phase_continuous():
    """(a) the continuous sweep: Thm 8 on the batch, per-event loop online."""
    import numpy as np

    from benchmarks.arrivals import run_stream_reference
    from repro.core.flowtime import hesrpt_mean_flowtime

    batch = _spec(scenario="batch", rates=(1.0,))
    res_b, compile_b = _timed_sweep(batch)
    _arr, sizes = _draws(batch, 1.0)
    thm8 = [
        _on_host(lambda x: float(hesrpt_mean_flowtime(
            np.sort(x)[::-1].copy(), P, float(N_CHIPS))), x)
        for x in sizes
    ]
    thm8_rel = _rel(res_b.stats["hesrpt"]["mean_flowtime"][0], thm8)

    spec = _spec()
    res, compile_s = _timed_sweep(spec)
    arr, sizes = _draws(spec, RATES[-1])
    oracle = [
        float(np.mean(_on_host(run_stream_reference, "hesrpt", arr[s],
                               sizes[s], p=P, n_chips=N_CHIPS,
                               quantize=False)))
        for s in (0, 1)
    ]
    oracle_rel = _rel(res.stats["hesrpt"]["mean_flowtime"][-1, :2], oracle)
    rec = _phase_line(
        "a-continuous", compile_b + compile_s, res_b.wall_s + res.wall_s,
        thm8_batch_mean_flow_rel=thm8_rel,
        oracle_poisson_mean_flow_rel=oracle_rel,
        rtol=FLOW_RTOL,
    )
    _emit(rec)
    _require(thm8_rel <= FLOW_RTOL and oracle_rel <= FLOW_RTOL,
             "continuous sweep disagrees with its references", rec)
    return res


def phase_superstep(continuous):
    """(b) the closed-form superstep sweep against (a)."""
    res, compile_s = _timed_sweep(_spec(superstep=True))
    rel = _rel(res.stats["hesrpt"]["mean_flowtime"],
               continuous.stats["hesrpt"]["mean_flowtime"])
    rec = _phase_line("b-superstep", compile_s, res.wall_s,
                      vs_continuous_mean_flow_rel=rel, rtol=FLOW_RTOL)
    _emit(rec)
    _require(rel <= FLOW_RTOL, "superstep sweep disagrees with (a)", rec)


def _recorded(arrivals, sizes, *, fused: bool):
    """One whole-chip stream on the chip, per-event trajectory recorded."""
    import jax.numpy as jnp

    from repro.core import make_policy, simulate_online_quantized

    pol = make_policy("hesrpt", n_servers=float(N_CHIPS))
    return simulate_online_quantized(
        jnp.asarray(sizes), jnp.asarray(arrivals), P, N_CHIPS, pol,
        record=True, fused=fused,
    )


def _states(eng, arrivals):
    """Each recorded event's remaining sizes, zero where a job has not
    arrived or is done ([events, jobs], float64), and the live mask."""
    import numpy as np

    arr = np.asarray(arrivals)[np.asarray(eng.order)]
    times = np.asarray(eng.trace.times, np.float64)
    sizes = np.asarray(eng.trace.sizes, np.float64)
    live = (arr[None, :] <= times[:, None] + 1e-12) & (sizes > 0)
    return np.where(live, sizes, 0.0), live


def _ties(diffs) -> dict:
    """Tally per-event chip differences (one int array per event)."""
    differ = sum(bool(d.any()) for d in diffs)
    return {"events": len(diffs), "tie_events": differ,
            "tie_share": differ / len(diffs), "max_tie_share": MAX_TIE_SHARE,
            "max_chip_diff": max(int(d.max()) for d in diffs)}


def _ties_ok(tally: dict) -> bool:
    return tally["max_chip_diff"] <= 1 and tally["tie_share"] <= MAX_TIE_SHARE


def _decisions(eng, arrivals) -> dict:
    """Each event's chips against the float64 NumPy oracle's decision
    (``policies.hesrpt`` then ``sched/quantize.py``) on the same state.

    The ranks are equal by construction, so a difference can only be a tie
    of the largest-remainder rounding broken the other way: at most one
    chip per job.  Exact ties occur at some job counts (48, 80, 96, ...,
    where the brackets' fractional parts coincide).
    """
    import jax
    import numpy as np

    from repro.core.policies import hesrpt
    from repro.sched.quantize import quantize_allocation

    x, live = _states(eng, arrivals)
    alloc = np.asarray(eng.trace.alloc, np.int64)

    def decide():
        theta_of = jax.jit(hesrpt)
        return [
            np.abs(alloc[e][live[e]] - quantize_allocation(
                np.asarray(theta_of(x[e], P))[live[e]], N_CHIPS))
            for e in range(len(x)) if live[e].any()
        ]

    return _ties(_on_host(decide))


def _same_states(eng, arrivals) -> dict:
    """Every recorded state of ``eng`` allocated on the chip twice: by the
    fused rule (the Pallas kernel) and by the unfused rule (policy, then
    the jnp quantizer).  Same inputs, so the trajectories cannot drift."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.engine import quantized_rule
    from repro.core.policies import hesrpt

    x = jnp.asarray(_states(eng, arrivals)[0], jnp.float32)
    rule = quantized_rule(hesrpt, N_CHIPS, dtype=jnp.float32)
    fused = jax.jit(jax.vmap(lambda xv: rule.fused_variant(xv, P)[0]))(x)
    unfused = jax.jit(jax.vmap(lambda xv: rule(xv, P)[0]))(x)
    return _ties(list(np.abs(np.asarray(fused, np.int64)
                             - np.asarray(unfused, np.int64))))


def phase_quantized():
    """(c) the whole-chip sweep, event for event against the NumPy oracle."""
    from benchmarks.quantized import cross_check

    spec = _spec(n_chips=N_CHIPS)
    res, compile_s = _timed_sweep(spec)
    arr, sizes = _draws(spec, RATES[-1])
    _out, eng = _recorded(arr[0], sizes[0], fused=False)
    dec = _decisions(eng, arr[0])
    cc = cross_check(("hesrpt",), p=P, n_chips=N_CHIPS,
                     trace=(arr[0], sizes[0]))
    mean_rel = _rel(res.stats["hesrpt"]["mean_flowtime"][-1, 0],
                    cc["ref_mean_flow"]["hesrpt"])
    rec = _phase_line(
        "c-quantized", compile_s, res.wall_s, decisions_vs_f64=dec,
        oracle_trajectory={
            "events": cc["n_events"], "differing_events": cc["mismatch_events"],
            "max_chip_diff": cc["max_chip_diff"],
            "worst_job_flow_rel": cc["worst_flow_rel"],
        },
        oracle_mean_flow_rel=mean_rel, rtol=FLOW_RTOL,
    )
    _emit(rec)
    _require(_ties_ok(dec) and mean_rel <= FLOW_RTOL,
             "quantized sweep disagrees with the oracle", rec)
    return res, eng


def phase_fused(quantized, unfused):
    """(d) the fused allocate, Pallas kernel compiled, against (c)."""
    import jax
    import jax.numpy as jnp

    from repro.core.sweeps import _executor

    spec = _spec(n_chips=N_CHIPS, fused=True)
    res, compile_s = _timed_sweep(spec)
    keys = jax.random.split(jax.random.PRNGKey(spec.seed), spec.n_seeds)
    compiled, _ = _executor(spec, "hesrpt", keys, jnp.asarray(spec.rates),
                            None, False)
    kernel = "tpu_custom_call" in compiled.as_text()

    arr, sizes = _draws(spec, RATES[-1])
    _out, eng = _recorded(arr[0], sizes[0], fused=True)
    dec = _decisions(eng, arr[0])
    states = _same_states(unfused, arr[0])
    mean_rel = _rel(res.stats["hesrpt"]["mean_flowtime"],
                    quantized.stats["hesrpt"]["mean_flowtime"])
    rec = _phase_line(
        "d-fused", compile_s, res.wall_s, tpu_custom_call=kernel,
        decisions_vs_f64=dec, states_of_c_vs_unfused=states,
        vs_unfused_mean_flow_rel=mean_rel, rtol=FLOW_RTOL,
    )
    _emit(rec)
    _require(kernel, "the fused executor holds no Pallas kernel", rec)
    _require(_ties_ok(dec) and _ties_ok(states)
             and mean_rel <= FLOW_RTOL,
             "fused allocate disagrees with the oracle or the unfused rule",
             rec)


def phase_cluster():
    """(e) ClusterScheduler on the engine, against its per-event loop.

    A batch keeps every job's rank, so events pair one to one and chips
    may differ only at ties of the largest-remainder rounding.
    """
    from benchmarks.quantized import compare_events
    from repro.sched import ClusterScheduler, Job

    _arr, sizes = _draws(_spec(scenario="batch", rates=(1.0,)), 1.0)

    def scheduler():
        s = ClusterScheduler(N_CHIPS, policy="hesrpt")
        for i, x in enumerate(sizes[0]):
            s.add_job(Job(f"j{i}", size=float(x), p=P))
        return s

    t0 = time.perf_counter()
    scheduler().run_fluid_to_completion()
    first_s = time.perf_counter() - t0
    eng = scheduler()
    t0 = time.perf_counter()
    out = eng.run_fluid_to_completion()
    wall_s = time.perf_counter() - t0
    ref = scheduler()
    ref_out = _on_host(ref.run_fluid_to_completion, use_engine=False)

    def allocs(s):
        return [(e["t"], e["chips"]) for e in s.events
                if e["event"] == "allocate"]

    differ, max_diff, _t = compare_events(allocs(eng), allocs(ref))
    share = differ / len(allocs(ref))
    mean_rel = _rel(out["mean_flow_time"], ref_out["mean_flow_time"])
    rec = _phase_line(
        "e-cluster", max(first_s - wall_s, 0.0), wall_s, path=out["path"],
        events=len(allocs(ref)), tie_events=differ, tie_share=share,
        max_tie_share=MAX_TIE_SHARE, max_chip_diff=max_diff,
        mean_flow_rel=mean_rel, rtol=FLOW_RTOL,
    )
    _emit(rec)
    _require(out["path"] == "engine", "ClusterScheduler did not use the engine",
             rec)
    _require(max_diff <= 1 and share <= MAX_TIE_SHARE
             and mean_rel <= FLOW_RTOL,
             "ClusterScheduler engine disagrees with its per-event loop", rec)


def phase_four_chips():
    """Sharded quantized and fused sweeps on 4 chips == one chip, bit for bit."""
    import numpy as np

    differ = []
    for fused in (False, True):
        spec = _spec(n_chips=N_CHIPS, fused=fused)
        one, one_compile = _timed_sweep(spec)
        for axis in ("rates", "seeds"):
            four, compile_s = _timed_sweep(spec, shard=True, shard_axis=axis)
            a = four.stats["hesrpt"]
            b = one.stats["hesrpt"]
            rec = {
                "phase": f"four-chips-{'fused' if fused else 'quantized'}"
                         f"-{axis}",
                "precision": PRECISION, "compile_s": compile_s,
                "wall_s": four.wall_s, "one_chip_compile_s": one_compile,
                "one_chip_wall_s": one.wall_s,
                "bit_equal_to_one_chip": all(
                    np.array_equal(a[m], b[m]) for m in spec.metrics),
                "cells_equal": {m: int(np.sum(a[m] == b[m]))
                                for m in spec.metrics},
                "cells": int(b["mean_flowtime"].size),
                "mean_flow_max_rel": _rel(a["mean_flowtime"],
                                          b["mean_flowtime"]),
            }
            _emit(rec)
            if not rec["bit_equal_to_one_chip"]:
                differ.append(rec["phase"])
    _require(not differ, "sharded sweeps differ from one chip",
             {"phases": differ})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded sweeps on four chips")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no checkout around {ROOT} (src/repro is missing)",
              file=sys.stderr)
        return 1

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX sees {devices[0].platform}); "
              "this script runs only on the chip", file=sys.stderr)
        return 1
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: --four-chips needs 4 chips, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 1

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    # References run in float64 on the host; each phase turns x64 off on
    # the chip (see the module docstring).
    jax.config.update("jax_enable_x64", True)
    with jax.enable_x64(False):
        if args.four_chips:
            phase_four_chips()
        else:
            continuous = phase_continuous()
            phase_superstep(continuous)
            quantized, unfused = phase_quantized()
            phase_fused(quantized, unfused)
            phase_cluster()
    _emit({"ok": True, "device": {"platform": devices[0].platform,
                                  "kind": devices[0].device_kind,
                                  "count": len(devices)}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
