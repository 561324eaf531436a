"""Beyond paper: the integer-chips (quantized-allocation) regime at scale.

The paper's theta* treats the N servers as continuously divisible; a real
TPU pool hands out whole chips.  Historically that regime could only be
swept through the per-event Python ``ClusterScheduler`` loop — one JAX
dispatch per event.  The scan-based allocation engine (``core/engine.py``)
runs the same decision epoch (policy -> largest-remainder quantization with
a min-chips floor -> advance to next event) as a pure ``lax.scan`` step, so
the whole sweep — >=1000 jobs x >=20 seeds x 3 loads — is ONE jit+vmap
device call per policy (``load_sweep`` with ``n_chips=``).

Sections:

- heavy-traffic sweep of quantized heSRPT/EQUI, plus the quantization
  efficiency gap vs the continuous fluid at identical sample paths;
- scenario-registry showcase: the same quantized engine under bursty MAP
  arrivals and under size-estimation noise (``core/scenarios.py``);
- event-for-event cross-check: the engine's chips/epoch trajectory vs the
  per-event ``ClusterScheduler(quantize=True)`` loop on small instances
  (exact integer chips agreement; epoch times to float tolerance).
"""

from __future__ import annotations

import time

import numpy as np

POLICIES = ("hesrpt", "equi")
RATES = (0.5, 2.0, 8.0)


# --------------------------------------------------- per-event reference loop
def run_stream_events(policy: str, arrivals, sizes, *, p=0.5, n_chips=64,
                      min_chips=1):
    """Per-event Python loop over ``ClusterScheduler(quantize=True)`` —
    one shared implementation with the continuous cross-check
    (``benchmarks.arrivals.run_stream_reference``), so the subtle oracle
    details (admission epsilon, departure nudge, idle advance) exist once.

    Returns ``(flows, allocs)``: per-job flow times (input order) and the
    list of allocation events ``(t, {job_id: chips})`` — the ground truth
    the engine's quantized trajectory is compared against event-for-event.
    """
    from benchmarks.arrivals import run_stream_reference

    return run_stream_reference(policy, arrivals, sizes, p=p,
                                n_chips=n_chips, quantize=True,
                                min_chips=min_chips, return_events=True)


def engine_events(eng_result, arrivals):
    """Extract ``(t, {job_id: chips})`` per event from an engine trace,
    skipping idle/no-op steps (empty active set), in the reference loop's
    job naming."""
    order = np.asarray(eng_result.order)
    tr = eng_result.trace
    t_ev = np.asarray(tr.times)
    sizes_tr = np.asarray(tr.sizes)
    alloc = np.asarray(tr.alloc)
    arr_sorted = np.asarray(arrivals)[order]
    out = []
    for e in range(len(t_ev)):
        live = (arr_sorted <= t_ev[e] + 1e-12) & (sizes_tr[e] > 0)
        if not live.any():
            continue
        out.append((float(t_ev[e]),
                    {f"j{order[k]}": int(alloc[e, k])
                     for k in np.nonzero(live)[0]}))
    return out


def compare_events(allocs_a, allocs_b) -> tuple[int, int, float]:
    """Pair two ``(t, {job_id: chips})`` event lists in order by their set of
    live jobs; return ``(differing, max_chip_diff, worst_time_rel)``.

    ``differing`` counts paired events whose chips differ plus the events
    either side has and the other lacks; ``allocs_b`` is the reference.
    """
    import difflib

    differing, max_diff, worst_t, n_paired = 0, 0, 0.0, 0
    blocks = difflib.SequenceMatcher(
        None, [frozenset(c) for _, c in allocs_a],
        [frozenset(c) for _, c in allocs_b], autojunk=False,
    ).get_matching_blocks()
    for i, j, size in blocks:
        for (t_a, c_a), (t_b, c_b) in zip(
            allocs_a[i:i + size], allocs_b[j:j + size], strict=True
        ):
            differing += c_a != c_b
            max_diff = max(max_diff, max(abs(c_a[k] - c_b[k]) for k in c_b))
            worst_t = max(worst_t, abs(t_a - t_b) / max(t_b, 1e-12))
        n_paired += size
    differing += len(allocs_a) + len(allocs_b) - 2 * n_paired
    return differing, max_diff, worst_t


def cross_check(policies=("hesrpt", "equi", "srpt"), *, n_jobs=12, rate=1.0,
                p=0.5, n_chips=64, seed=0, trace=None) -> dict:
    """Engine quantized trajectory vs the ClusterScheduler per-event loop.

    The engine runs on the default device in the caller's precision; the
    per-event loop always runs on the host CPU in float64.  ``trace`` is an
    ``(arrivals, sizes)`` pair to check instead of the seeded
    ``stream_trace(n_jobs, rate, seed)``.

    Events are paired in order by their set of live jobs.  In float64 every
    event pairs and chips agree *exactly*; epoch times and per-job flow
    times to float tolerance (the reference loop advances with a +1e-15
    nudge the scan does not need).  A lower-precision engine may break
    near-ties the other way: a largest-remainder round, or which of two
    nearly simultaneous departures is an event of its own.  So the result
    also counts ``mismatch_events`` (paired events whose chips differ, plus
    events either side has and the other lacks) and ``max_chip_diff`` (the
    largest per-job chip difference over paired events).
    """
    import jax
    import jax.numpy as jnp

    from benchmarks.arrivals import stream_trace
    from repro.core import make_policy, simulate_online_quantized

    arrivals, sizes = stream_trace(n_jobs, rate, seed) if trace is None else trace
    n_jobs = len(sizes)
    worst_t, worst_flow, mismatch, n_events, max_diff = 0.0, 0.0, 0, 0, 0
    ref_mean = {}
    for name in policies:
        with jax.enable_x64(True), jax.default_device(jax.devices("cpu")[0]):
            flows_ref, allocs_ref = run_stream_events(
                name, arrivals, sizes, p=p, n_chips=n_chips)
        res, eng = simulate_online_quantized(
            jnp.asarray(sizes), jnp.asarray(arrivals), p, n_chips,
            make_policy(name, n_servers=float(n_chips)), record=True)
        differing, diff, t_rel = compare_events(
            engine_events(eng, arrivals), allocs_ref
        )
        mismatch += differing
        max_diff = max(max_diff, diff)
        worst_t = max(worst_t, t_rel)
        n_events += len(allocs_ref)
        flows = np.asarray(res.flow_times, np.float64)
        ref = np.asarray(flows_ref, np.float64)
        worst_flow = max(worst_flow, float(np.max(np.abs(flows - ref) / ref)))
        ref_mean[name] = float(np.mean(ref))
    return {"chips_exact": mismatch == 0, "n_events": n_events,
            "mismatch_events": mismatch, "max_chip_diff": max_diff,
            "worst_epoch_time_rel": worst_t, "worst_flow_rel": worst_flow,
            "ref_mean_flow": ref_mean}


# --------------------------------------------------------------- the sweeps
def sweep(policies=POLICIES, rates=RATES, *, n_jobs=1000, n_seeds=20,
          p=0.5, n_chips=256, min_chips=1, seed=0):
    """Quantized heavy-traffic sweep: a thin spec over ``core/sweeps.py``
    (one compiled device call per policy), formatted as the historical
    ``{rate: {policy: mean}}`` table."""
    from repro.core.sweeps import Sweep, run_sweep

    spec = Sweep.create(policies, rates, n_jobs=n_jobs, n_seeds=n_seeds, p=p,
                        n_servers=float(n_chips), seed=seed, n_chips=n_chips,
                        min_chips=min_chips)
    return run_sweep(spec).cell_means()


def quantization_gap(rates=RATES, *, n_jobs=1000, n_seeds=20, p=0.5,
                     n_chips=256, seed=0, quantized=None) -> dict:
    """Mean-flow-time ratio quantized/continuous for heSRPT on identical
    sample paths — the price of whole chips.  Pass an existing quantized
    ``load_sweep`` result (with an ``"hesrpt"`` column) as ``quantized`` to
    avoid re-running the expensive whole-chips scan."""
    from repro.core import load_sweep

    q = quantized
    if q is None:
        q = load_sweep(("hesrpt",), rates, n_jobs=n_jobs, n_seeds=n_seeds,
                       p=p, n_servers=float(n_chips), seed=seed,
                       n_chips=n_chips)
    c = load_sweep(("hesrpt",), rates, n_jobs=n_jobs, n_seeds=n_seeds, p=p,
                   n_servers=float(n_chips), seed=seed)
    return {r: q[r]["hesrpt"] / c[r]["hesrpt"] for r in q}


def scenario_rows(rates=RATES, *, n_jobs=300, n_seeds=10, p=0.5,
                  n_chips=256, seed=0) -> dict:
    """The scenario registry driving the quantized engine: Poisson vs
    bursty MAP arrivals vs Poisson with size-estimation noise."""
    from repro.core import load_sweep

    out = {}
    for label, kw in (
        ("poisson", {}),
        ("bursty", {"scenario": "bursty"}),
        ("noisy-sizes", {"scenario_kw": {"sigma_size": 0.5}}),
    ):
        out[label] = load_sweep(
            ("hesrpt",), rates, n_jobs=n_jobs, n_seeds=n_seeds, p=p,
            n_servers=float(n_chips), seed=seed, n_chips=n_chips, **kw)
    return out


def main(quick: bool = False, smoke: bool = False):
    rates = RATES
    if smoke:
        n_jobs, n_seeds, s_jobs, s_seeds = 80, 4, 60, 4
    elif quick:
        n_jobs, n_seeds, s_jobs, s_seeds = 300, 10, 200, 8
    else:
        n_jobs, n_seeds, s_jobs, s_seeds = 1000, 20, 300, 10

    t0 = time.perf_counter()
    res = sweep(rates=rates, n_jobs=n_jobs, n_seeds=n_seeds)
    sweep_s = time.perf_counter() - t0
    lines = [f"{n_jobs} jobs x {n_seeds} seeds x {len(rates)} loads x "
             f"{len(POLICIES)} policies, whole-chips allocation "
             f"(one jit+vmap lax.scan call per policy, {sweep_s:.1f}s "
             f"incl. compile)"]
    lines.append(f"{'arrival rate':>12s} " + " ".join(f"{q:>10s}"
                                                      for q in POLICIES))
    ok = True
    for rate, row in res.items():
        lines.append(f"{rate:12.1f} " + " ".join(f"{row[q]:10.4f}"
                                                 for q in POLICIES))
        ok &= row["hesrpt"] <= row["equi"] * 1.02
    lines.append(f"quantized heSRPT <= quantized EQUI at every load: {ok}")

    gap = quantization_gap(rates=rates, n_jobs=n_jobs, n_seeds=n_seeds,
                           quantized=res)
    lines.append("whole-chips / continuous mean flow time (heSRPT): "
                 + "  ".join(f"{r:g}: {g:.3f}" for r, g in gap.items()))

    scn = scenario_rows(rates=rates, n_jobs=s_jobs, n_seeds=s_seeds)
    lines.append(f"scenario registry x quantized engine ({s_jobs} jobs x "
                 f"{s_seeds} seeds, heSRPT mean flow time):")
    for label, rows in scn.items():
        lines.append(f"  {label:>12s} " + " ".join(
            f"{rows[r]['hesrpt']:10.4f}" for r in rows))

    cc = cross_check()
    lines.append(
        f"event-for-event vs ClusterScheduler(quantize=True), 12-job "
        f"Poisson x 3 policies: chips exact over {cc['n_events']} events: "
        f"{cc['chips_exact']}, epoch-time rel err {cc['worst_epoch_time_rel']:.1e}, "
        f"flow rel err {cc['worst_flow_rel']:.1e}")
    assert cc["chips_exact"], "quantized engine diverged from ClusterScheduler"
    assert cc["worst_flow_rel"] < 1e-9, cc
    return "\n".join(lines), {"sweep": res, "gap": gap, "scenarios": scn,
                              "cross_check": cc}


if __name__ == "__main__":
    import jax

    # Same rationale as benchmarks/run.py: cross-checks against the f64
    # ClusterScheduler path need f64.
    jax.config.update("jax_enable_x64", True)
    print(main(quick=True)[0])
