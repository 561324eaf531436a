"""Benchmark aggregator: one section per paper table/figure + beyond-paper
benches.  ``python -m benchmarks.run [--quick] [--smoke]
[--profile-dir DIR]``.

``--quick`` shrinks the expensive sweeps; ``--smoke`` is the CI tier-1
gate: every section that exercises the allocation engine runs at tiny
sizes (seconds, not minutes) so the sweeps cannot silently rot, and the
long-running extras (speedup timings, kernel micro-bench) are skipped.

``--profile-dir DIR`` wraps the whole run in ``jax.profiler.start_trace``:
the ``StepTraceAnnotation`` markers ``core/sweeps.py`` emits around each
compiled executor call (named by policy/scenario) then land in a
Perfetto-loadable trace under ``DIR`` — open it at https://ui.perfetto.dev
to see per-policy device time next to XLA's own slices.
"""

from __future__ import annotations

import sys
import time

import jax

from repro.compile_cache import enable_compile_cache

# Scheduler math (closed forms vs simulation) wants f64; model/kernel code
# pins its own dtypes explicitly so this only affects the core benchmarks.
jax.config.update("jax_enable_x64", True)
# The benchmark sections recompile the same engine scans every run.
enable_compile_cache()


def _section(title):
    print("\n" + "=" * 72)
    print(title)
    print("=" * 72, flush=True)


def main() -> None:
    smoke = "--smoke" in sys.argv
    quick = smoke or "--quick" in sys.argv
    profile_dir = None
    if "--profile-dir" in sys.argv:
        profile_dir = sys.argv[sys.argv.index("--profile-dir") + 1]
        jax.profiler.start_trace(profile_dir)
    t0 = time.time()

    _section("Fig 3 — heSRPT 3-job trace (s(k)=k^0.5, N=500)")
    from benchmarks import fig3_trace

    text, _ = fig3_trace.main()
    print(text)

    _section("Thm 8 — simulator vs closed-form optimal total flow time")
    from benchmarks import theorem8

    text, worst = theorem8.main()
    print(text)
    assert worst < 1e-6, "Theorem 8 closed form mismatch"

    _section("Thm 2 — heLRPT makespan closed form + tradeoff vs heSRPT")
    from benchmarks import makespan

    text, ok = makespan.main()
    print(text)
    assert ok, "Theorem 2 checks failed"

    _section("Fig 4 — heSRPT vs SRPT/EQUI/HELL/KNEE "
             + ("(quick)" if quick else "(paper scale: M=500, 10 seeds)"))
    from benchmarks import fig4_policies

    text, _ = fig4_policies.main(quick=quick)
    print(text)

    _section("Beyond paper — Poisson arrival stream at heavy traffic "
             + ("(smoke)" if smoke else
                "(quick)" if quick else "(1000 jobs x 100 seeds, lax.scan)"))
    from benchmarks import arrivals

    text, _ = arrivals.main(quick=quick, smoke=smoke)
    print(text)

    _section("Beyond paper — quantized whole-chips allocation at scale "
             + ("(smoke)" if smoke else
                "(quick)" if quick else "(1000 jobs x 20 seeds, lax.scan)"))
    from benchmarks import quantized

    text, _ = quantized.main(quick=quick, smoke=smoke)
    print(text)

    _section("Beyond paper — multi-class workloads (per-class p, slowdown) "
             + ("(smoke)" if smoke else
                "(quick)" if quick else "(1000 jobs x 10 seeds, K=2..4)"))
    from benchmarks import multiclass

    text, _ = multiclass.main(quick=quick, smoke=smoke)
    print(text)

    _section("Beyond paper — online p-hat estimation vs oracle/stale on "
             "p-drift " + ("(smoke)" if smoke else
                           "(quick)" if quick else
                           "(500 jobs x 20 seeds, 3 arms x 2 scenarios)"))
    from benchmarks import estimation

    text, _ = estimation.main(quick=quick, smoke=smoke)
    print(text)

    _section("Beyond paper — in-scan telemetry: streaming probes at sweep "
             "scale " + ("(smoke)" if smoke else
                         "(quick)" if quick else "(500 jobs x 20 seeds)"))
    from benchmarks import telemetry

    text, _ = telemetry.main(quick=quick, smoke=smoke)
    print(text)

    _section("Beyond paper — bounded-slot streaming engine: horizon scaling, "
             "load ladder, oracle " + ("(smoke)" if smoke else
                                       "(quick)" if quick else
                                       "(64k events, 1000 jobs x 10 seeds)"))
    from benchmarks import streaming

    text, _ = streaming.main(quick=quick, smoke=smoke)
    print(text)

    _section("Beyond paper — scan-body profile: sort counts + fused allocate "
             + ("(smoke)" if smoke else "(M=4096 components, M=1024 scan)"))
    from benchmarks import profile_engine

    text, _ = profile_engine.main(smoke=smoke)
    print(text)

    if not smoke:
        _section("Beyond paper — scheduler decision cost at cluster scale")
        from benchmarks import sched_scale

        text, _ = sched_scale.main()
        print(text)

        _section("Beyond paper — kernel micro-bench (CPU; TPU story = roofline)")
        from benchmarks import kernels_bench

        text, _ = kernels_bench.main()
        print(text)

    # Every run_sweep call above logged a structured record (spec, per-cell
    # stats, wall/compile time, backend); flush them so the perf trajectory
    # accumulates — CI uploads this file as a workflow artifact.
    from repro.core import sweeps

    path = sweeps.write_bench_json()
    print(f"\nwrote {len(sweeps.RUN_LOG)} sweep records to {path}")
    if profile_dir is not None:
        jax.profiler.stop_trace()
        print(f"profiler trace written under {profile_dir}")
    print(f"all benchmarks done in {time.time() - t0:.1f}s")


if __name__ == "__main__":
    main()
