"""Profile the allocation scan body: where does a quantized heSRPT event go?

The engine's per-event hot path is sort-dominated.  This harness attributes
per-event cost to its pieces — the policy's size sort/rank, the
largest-remainder quantizer, and the assembled allocate — across the
optimization trajectory this repo shipped:

  ========  =========  =================================================
  variant   sorts/ev   what it is
  ========  =========  =================================================
  seed          5      policy sorts + the first quantizer port (separate
                       trim and leftover argsorts, scatter inverses),
                       reconstructed here so the win stays attributable
                       after the code moved on
  unfused       4      policy sorts (size order and its inverse) +
                       collapsed quantizer — what ``engine.quantized_rule``
                       ships today
  fused         3      ``kernels/alloc.py`` ref pass sharing one sorted
                       order (rank-space oversubscription cut)
  pallas        0      the Pallas kernel: O(M^2) comparison counting, no
                       sort primitive at all (interpret mode on CPU, so
                       its wall time here is NOT representative — the
                       sort count and the TPU roofline are the story)
  ========  =========  =================================================

Wall times come from ``jax.block_until_ready`` over jitted calls; sort
counts are *measured from the compiled HLO* via
``core.hlo.op_histogram`` (trip-count-aware, so the full
``engine.run`` scan reports sorts *per event*, not per program).  The
headline acceptance number is the fused-vs-seed per-event allocate
speedup on CPU (target >= 1.5x, driven by the sort-count reduction).

A second section profiles the *closed-form superstep* path
(``core/superstep.py``) against the per-event scans on two lanes — a
pre-arrived batch (zero scan steps: the Thm-3/8 closed form directly) and
a Poisson arrival stream (M+1 scan steps vs the generic/ranked 2M) — with
events-per-second and scan-trip-count columns, and logs one
``kind="profile_superstep"`` record per lane carrying the
``superstep_speedup_wall`` ratio (targets: >= 10x batch, >= 1.5x Poisson
vs the generic scan).

``python -m benchmarks.profile_engine [--smoke] [--json]``; also runs as a
section of ``benchmarks/run.py`` (including ``--smoke``), logging a
``kind="profile_engine"`` record into the ``BENCH_sweeps.json`` trajectory.
"""

from __future__ import annotations

import time

import numpy as np


# ------------------------------------------------- the seed's 3-sort quantizer
def _seed_quantize(theta, n_chips: int, *, min_chips: int = 1):
    """The first ``quantize_allocation_jax`` port: separate trim/leftover
    argsorts (3 sorts per call), each inverted by a scatter.  Kept verbatim
    here — not in core — purely so the profiler can measure the collapse
    against its true baseline.
    """
    import jax
    import jax.numpy as jnp

    theta = jnp.asarray(theta)
    M = theta.shape[0]

    def inv_rank(order):
        return jnp.zeros(M, jnp.int32).at[order].set(jnp.arange(M, dtype=jnp.int32))

    if n_chips <= 0 or min_chips <= 0 or M == 0:
        return jnp.zeros(M, jnp.int32)
    cap = n_chips // min_chips

    active0 = theta > 0
    n_active = jnp.sum(active0, dtype=jnp.int32)
    desc = inv_rank(jnp.argsort(jnp.where(active0, -theta, jnp.inf)))
    servable = active0 & (desc < cap)
    over = n_active * min_chips > n_chips
    sub = jnp.where(servable, theta, 0.0)
    tot = jnp.sum(sub)
    theta_eff = jnp.where(over, jnp.where(tot > 0, sub / tot, 0.0), theta)
    active = theta_eff > 0

    raw = theta_eff * n_chips
    fl = jnp.floor(raw)
    frac = raw - fl
    base = jnp.where(active, jnp.maximum(fl, min_chips), 0.0).astype(jnp.int32)

    K = jnp.maximum(jnp.sum(base) - n_chips, 0)
    capj = jnp.maximum(base - min_chips, 0) * (base > min_chips)

    def bisect(_, lohi):
        lo, hi = lohi
        mid = (lo + hi) // 2
        ge = jnp.sum(jnp.minimum(capj, mid)) >= K
        return jnp.where(ge, lo, mid + 1), jnp.where(ge, mid, hi)

    n_bits = (n_chips + 1).bit_length()
    lo, _hi = jax.lax.fori_loop(
        0, n_bits, bisect, (jnp.int32(0), jnp.int32(n_chips))
    )
    r_star = lo
    full = jnp.minimum(capj, jnp.maximum(r_star - 1, 0))
    extra_needed = K - jnp.sum(full)
    elig = capj >= jnp.maximum(r_star, 1)
    # The two argsorts the shipped quantizer collapses into one:
    erank = inv_rank(jnp.argsort(jnp.where(elig, frac, jnp.inf)))
    extra = (elig & (erank < extra_needed)).astype(jnp.int32)
    base = base - full - extra

    remainder = n_chips - jnp.sum(base)
    frank = inv_rank(jnp.argsort(jnp.where(active, -frac, jnp.inf)))
    return base + (active & (frank < remainder)).astype(jnp.int32)


# --------------------------------------------------------------- measurement
def _time(f, *args, repeats=5, inner=1):
    """Per-repeat wall times (us) of a compiled call, warm (post-compile).

    Each repeat times ``inner`` back-to-back calls and reports the per-call
    average — sub-millisecond calls are otherwise swamped by scheduler
    jitter on a shared machine.
    """
    import jax

    jax.block_until_ready(f(*args))  # compile + warm
    out = np.zeros(repeats)
    for r in range(repeats):
        t0 = time.perf_counter()
        for _ in range(inner):
            jax.block_until_ready(f(*args))
        out[r] = (time.perf_counter() - t0) * 1e6 / inner
    return out


def _sort_count(f, *args) -> float:
    """``sort`` ops in the compiled HLO (while bodies x trip count)."""
    import jax

    from repro.core.hlo import op_histogram

    hlo = jax.jit(f).lower(*args).compile().as_text()
    return op_histogram(hlo).get("sort", 0.0)


def run(m: int = 4096, engine_m: int = 1024, p: float = 0.5,
        n_chips: int = 1024, min_chips: int = 1, repeats: int = 5,
        log: bool = True):
    """Profile components at job count ``m`` and the full scan at
    ``engine_m``; returns ``(rows, engine_rows, result)`` where ``rows`` is
    ``[(name, sorts_per_call, us_min, us_per_repeat)]``.
    """
    import jax
    import jax.numpy as jnp

    from repro.core import engine
    from repro.core.flowtime import speedup
    from repro.core.policies import hesrpt
    from repro.core.sweeps import RUN_LOG, SweepResult
    from repro.kernels.alloc import hesrpt_alloc_fused, hesrpt_alloc_fused_ref

    t_start = time.perf_counter()
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.pareto(1.5, m) + 1.0)  # f64 under run.py's x64 flag
    pj = jnp.asarray(p, x.dtype)
    theta0 = hesrpt(x, p)

    rule = engine.quantized_rule(
        hesrpt, n_chips, min_chips=min_chips, dtype=x.dtype
    )
    fused_rule = getattr(rule, "fused_variant")  # noqa: B009

    def alloc_seed(x_act, pv):
        theta = hesrpt(x_act, pv).astype(x.dtype)
        chips = _seed_quantize(theta, n_chips, min_chips=min_chips)
        return chips, speedup(chips.astype(x.dtype), pv)

    # The compiled kernel on TPU, which takes float32 sizes only; the
    # interpreter elsewhere (its wall time there is not the kernel's).
    on_tpu = jax.default_backend() == "tpu"
    x_pallas = x.astype(jnp.float32) if on_tpu else x

    def alloc_pallas(x_act, pv):
        _theta, chips = hesrpt_alloc_fused(
            x_act, pv, n_chips, min_chips=min_chips,
            impl="pallas" if on_tpu else "interpret",
        )
        return chips, speedup(chips.astype(x_act.dtype), pv)

    components = [
        ("policy_theta", lambda xv, pv: hesrpt(xv, pv), (x, pj)),
        ("quantize_seed",
         lambda th: _seed_quantize(th, n_chips, min_chips=min_chips),
         (theta0,)),
        ("quantize_collapsed",
         lambda th: engine.quantize_allocation_jax(
             th, n_chips, min_chips=min_chips),
         (theta0,)),
        ("alloc_seed", alloc_seed, (x, pj)),
        ("alloc_unfused", rule, (x, pj)),
        ("alloc_fused_ref", fused_rule, (x, pj)),
        ("alloc_pallas" if on_tpu else "alloc_pallas_interp", alloc_pallas,
         (x_pallas, pj.astype(x_pallas.dtype))),
    ]
    # Ratios use the min over repeats: on a shared machine the mean is
    # contaminated by scheduler interference, while the min approaches the
    # true (uninterfered) cost of the compiled call.
    rows = []
    for name, f, args in components:
        jf = jax.jit(f)
        us = _time(jf, *args, repeats=repeats, inner=8)
        sorts = _sort_count(f, *args)
        rows.append((name, sorts, float(us.min()), us))

    # Full event scan, unfused vs fused: per-event wall time and — via the
    # trip-count-aware histogram — per-event sort count from the compiled
    # while loop (minus the one-time arrival-order sort outside the scan).
    xe = jnp.asarray(rng.pareto(1.5, engine_m) + 1.0)
    arr = jnp.zeros(engine_m, xe.dtype)
    n_events = engine_m  # pre_arrived horizon

    engine_rows = []
    for name, fused in (("engine_unfused", False), ("engine_fused", True)):
        def f_run(x0, at, *, _fused=fused):
            return engine.run(
                x0, at, p, rule, pre_arrived=True, fused=_fused
            ).completion_times

        us = _time(jax.jit(f_run), xe, arr, repeats=repeats)
        sorts_ev = (_sort_count(f_run, xe, arr) - 1.0) / n_events
        engine_rows.append(
            (name, sorts_ev, float(us.min()) / n_events, us / n_events)
        )

    by_name = {name: (sorts, best) for name, sorts, best, _ in rows}
    speedup_vs_seed = by_name["alloc_seed"][1] / by_name["alloc_fused_ref"][1]
    speedup_vs_unfused = (
        by_name["alloc_unfused"][1] / by_name["alloc_fused_ref"][1]
    )
    engine_speedup = engine_rows[0][2] / engine_rows[1][2]

    stats: dict[str, np.ndarray] = {}
    for name, sorts, _mean, us in rows:
        stats[f"{name}_us"] = us.reshape(1, -1)
        stats[f"{name}_sorts"] = np.array([[sorts]])
        stats[f"{name}_us_p50"] = np.array([[float(np.percentile(us, 50))]])
        stats[f"{name}_us_p95"] = np.array([[float(np.percentile(us, 95))]])
    for name, sorts_ev, _mean, us_ev in engine_rows:
        stats[f"{name}_us_per_event"] = us_ev.reshape(1, -1)
        stats[f"{name}_sorts_per_event"] = np.array([[sorts_ev]])
        stats[f"{name}_us_per_event_p50"] = np.array(
            [[float(np.percentile(us_ev, 50))]]
        )
        stats[f"{name}_us_per_event_p95"] = np.array(
            [[float(np.percentile(us_ev, 95))]]
        )
    stats["alloc_speedup_vs_seed"] = np.array([[speedup_vs_seed]])
    stats["alloc_speedup_vs_unfused"] = np.array([[speedup_vs_unfused]])
    stats["engine_speedup"] = np.array([[engine_speedup]])

    result = SweepResult(
        spec={
            "kind": "profile_engine",
            "m": m,
            "engine_m": engine_m,
            "p": p,
            "n_chips": n_chips,
            "min_chips": min_chips,
            "repeats": repeats,
            "policy": "hesrpt",
        },
        stats={"hesrpt": stats},
        wall_s=time.perf_counter() - t_start,
        compile_s=0.0,
        backend=jax.default_backend(),
        device_count=jax.device_count(),
        chunk_seeds=None,
        sharded=False,
    )
    if log:
        RUN_LOG.append(result.record())
    return rows, engine_rows, result


def run_superstep_lanes(m: int = 1000, p: float = 0.5,
                        n_servers: float = 64.0, rate: float = 1.0,
                        repeats: int = 5, log: bool = True):
    """Closed-form superstep vs the per-event scans, two lanes.

    - ``batch``: pre-arrived M jobs.  The generic scan walks M departure
      events; the superstep path is the zero-scan batch closed form
      (Thm 3/8 vectorized) — acceptance target >= 10x wall.
    - ``poisson``: M Poisson arrivals.  Generic and ranked scans walk
      2M events (admit + departure); the superstep scan walks M+1 steps
      (one per arrival, departures analytic) — target >= 1.5x end-to-end
      vs the generic scan (the ranked ratio is recorded for honesty: it
      already dodges the per-event sort, so the superstep's win there is
      the halved trip count and the transcendental-free body).

    Wall ratios land in ``BENCH_sweeps.json`` as ``superstep_speedup_wall``
    under ``kind="profile_superstep"`` records (one per lane).  Those ride
    tools/bench_diff.py's wall-time gate; the speedup *metrics* are
    machine-relative, deliberately outside the drift gate (same convention
    as the fused-allocate ratios above).
    """
    import jax
    import jax.numpy as jnp

    from repro.core import engine
    from repro.core.policies import make_policy, make_rank_policy
    from repro.core.scenarios import pareto_sizes, poisson_arrivals
    from repro.core.superstep import run_superstep
    from repro.core.sweeps import RUN_LOG, SweepResult

    key = jax.random.PRNGKey(0)
    kx, ka = jax.random.split(key)
    x = pareto_sizes(kx, m).astype(jnp.float64)
    rule = engine.continuous_rule(
        make_policy("hesrpt"), n_servers=n_servers, dtype=x.dtype
    )
    rank_pol = make_rank_policy("hesrpt")

    lanes = []
    for lane, arr, pre in (
        ("batch", jnp.zeros(m, x.dtype), True),
        ("poisson", poisson_arrivals(ka, m, rate).astype(x.dtype), False),
    ):
        t_start = time.perf_counter()
        n_events = m if pre else 2 * m  # generic scan horizon
        n_steps_ss = 0 if pre else m + 1  # superstep trips (+1 drain step)
        # run_ranked has no pre_arrived shortcut — its batch lane walks
        # the full 2M admit+departure horizon (recorded as its trip count).
        n_trips_ranked = 2 * m

        def f_generic(x0, at, *, _pre=pre):
            return engine.run(
                x0, at, p, rule, pre_arrived=_pre
            ).completion_times

        def f_ranked(x0, at):
            return engine.run_ranked(x0, at, p, n_servers, rank_pol)

        def f_superstep(x0, at, *, _pre=pre):
            return run_superstep(
                x0, at, p, n_servers, "hesrpt", pre_arrived=_pre
            ).completion_times

        variants = [
            ("generic", f_generic, n_events),
            ("ranked", f_ranked, n_trips_ranked),
            ("superstep", f_superstep, n_steps_ss),
        ]
        rows, stats = [], {}
        for name, f, trips in variants:
            import jax as _jax

            us = _time(_jax.jit(f), x, arr, repeats=repeats)
            best = float(us.min())
            ev_per_s = n_events / (best * 1e-6)  # events resolved, not trips
            rows.append((name, trips, best, ev_per_s, us))
            stats[f"{name}_us"] = us.reshape(1, -1)
            stats[f"{name}_scan_trips"] = np.array([[float(trips)]])
            stats[f"{name}_events_per_s"] = np.array([[ev_per_s]])
        by = {name: best for name, _t, best, _e, _u in rows}
        stats["superstep_speedup_wall"] = np.array(
            [[by["generic"] / by["superstep"]]]
        )
        stats["superstep_speedup_vs_ranked"] = np.array(
            [[by["ranked"] / by["superstep"]]]
        )
        result = SweepResult(
            spec={
                "kind": "profile_superstep",
                "lane": lane,
                "m": m,
                "p": p,
                "n_servers": n_servers,
                "rate": None if pre else rate,
                "repeats": repeats,
                "policy": "hesrpt",
            },
            stats={"hesrpt": stats},
            wall_s=time.perf_counter() - t_start,
            compile_s=0.0,
            backend=jax.default_backend(),
            device_count=jax.device_count(),
            chunk_seeds=None,
            sharded=False,
        )
        if log:
            RUN_LOG.append(result.record())
        lanes.append((lane, rows, result))
    return lanes


def main(smoke: bool = False):
    if smoke:
        rows, engine_rows, res = run(
            m=512, engine_m=256, repeats=5, n_chips=256
        )
        ss_lanes = run_superstep_lanes(m=1000, repeats=3)
    else:
        rows, engine_rows, res = run()
        ss_lanes = run_superstep_lanes()
    spec = res.spec
    lines = [
        f"components at M={spec['m']}, n_chips={spec['n_chips']}, "
        f"p={spec['p']} ({res.backend}, over {spec['repeats']} repeats):",
        f"{'component':>22s} {'sorts/call':>10s} {'us_min':>10s} "
        f"{'us_p50':>10s} {'us_p95':>10s}",
    ]
    for name, sorts, best, us in rows:
        p50, p95 = np.percentile(us, [50, 95])
        lines.append(
            f"{name:>22s} {sorts:10.0f} {best:10.1f} {p50:10.1f} {p95:10.1f}"
        )
    lines.append("")
    lines.append(f"full event scan at M={spec['engine_m']} (pre-arrived, "
                 f"{spec['engine_m']} events):")
    lines.append(f"{'variant':>22s} {'sorts/ev':>10s} {'us_min':>10s} "
                 f"{'us_p50':>10s} {'us_p95':>10s}")
    for name, sorts_ev, best_ev, us_ev in engine_rows:
        p50, p95 = np.percentile(us_ev, [50, 95])
        lines.append(
            f"{name:>22s} {sorts_ev:10.1f} {best_ev:10.1f} "
            f"{p50:10.1f} {p95:10.1f}"
        )
    st = res.stats["hesrpt"]
    vs_seed = float(st["alloc_speedup_vs_seed"][0, 0])
    vs_unfused = float(st["alloc_speedup_vs_unfused"][0, 0])
    eng = float(st["engine_speedup"][0, 0])
    lines.append("")
    lines.append(
        f"allocate speedup (fused ref vs seed 4-sort): {vs_seed:.2f}x "
        f"[target >= 1.5x: {'PASS' if vs_seed >= 1.5 else 'MISS'}]"
    )
    lines.append(
        f"allocate speedup (fused ref vs shipped unfused): "
        f"{vs_unfused:.2f}x"
    )
    lines.append(f"engine.run speedup (fused vs unfused): {eng:.2f}x")

    for lane, lrows, lres in ss_lanes:
        lst = lres.stats["hesrpt"]
        ss_m = lres.spec["m"]
        lines.append("")
        lines.append(
            f"superstep lane '{lane}' at M={ss_m} (continuous heSRPT, "
            f"N={lres.spec['n_servers']:.0f}):"
        )
        lines.append(
            f"{'variant':>22s} {'scan-trips':>10s} {'us_min':>10s} "
            f"{'events/s':>12s}"
        )
        for name, trips, best, ev_per_s, _us in lrows:
            lines.append(
                f"{name:>22s} {trips:10d} {best:10.1f} {ev_per_s:12.3g}"
            )
        wall = float(lst["superstep_speedup_wall"][0, 0])
        vs_ranked = float(lst["superstep_speedup_vs_ranked"][0, 0])
        target = 10.0 if lane == "batch" else 1.5
        lines.append(
            f"superstep speedup (vs generic scan): {wall:.2f}x "
            f"[target >= {target:.1f}x: "
            f"{'PASS' if wall >= target else 'MISS'}]"
        )
        lines.append(
            f"superstep speedup (vs ranked scan):  {vs_ranked:.2f}x"
        )
    return "\n".join(lines), res


if __name__ == "__main__":
    import json
    import sys

    import jax

    jax.config.update("jax_enable_x64", True)
    text, res = main(smoke="--smoke" in sys.argv)
    if "--json" in sys.argv:
        print(json.dumps(res.record(), indent=1))
    else:
        print(text)
