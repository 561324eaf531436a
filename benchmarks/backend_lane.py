"""Accelerator benchmark lane: the same sweep specs on every backend.

Runs a fixed set of canonical sweep lanes on whatever backend jax sees —
CPU in CI, GPU/TPU when the container has one — and accumulates
backend-tagged throughput rows into ``BENCH_sweeps.json``, so the artifact
carries one comparable trajectory per backend instead of a CPU-only story.

Lanes (fixed specs; ``--smoke``/``--quick`` shrink sizes, not shapes):

- ``quantized`` — whole-chips heSRPT sweep on the unfused engine;
- ``quantized-fused`` — the identical spec through the ``kernels/alloc.py``
  fused allocate (``Sweep.create(..., fused=True)``), chip-exact, so the
  wall-clock delta is pure engine speed;
- ``continuous`` — the paper's divisible regime (no quantizer sorts to
  collapse; it rides along as the baseline lane).

The lane shape is a *wide rate grid with few seeds* — the accelerator
sweet spot — so multi-device hosts shard the rate axis
(``run_sweep(..., shard_axis="rates")``) where the CI smoke sweeps shard
seeds.  ``fused_speedup_wall`` records the fused/unfused wall ratio on
whatever backend ran.  On TPU the lanes run in float32, the chip path's
precision (see ``chip_smoke.py``); elsewhere in float64.

``python -m benchmarks.backend_lane [--smoke|--quick] [--no-append]
[--out BENCH_sweeps.json] [--json]``
"""

from __future__ import annotations

import json

import numpy as np

RATES_FULL = tuple(float(r) for r in np.geomspace(0.25, 16.0, 24).round(4))
RATES_QUICK = tuple(float(r) for r in np.geomspace(0.25, 16.0, 12).round(4))
RATES_SMOKE = (0.5, 1.0, 2.0, 4.0, 8.0)
N_CHIPS = 256


def lane_specs(smoke: bool = False, quick: bool = False):
    """The canonical lanes as ``(label, Sweep)`` pairs."""
    from repro.core.sweeps import Sweep

    if smoke:
        rates, n_jobs, n_seeds = RATES_SMOKE, 60, 2
    elif quick:
        rates, n_jobs, n_seeds = RATES_QUICK, 300, 4
    else:
        rates, n_jobs, n_seeds = RATES_FULL, 1000, 8
    common = dict(n_jobs=n_jobs, n_seeds=n_seeds, p=0.5,
                  n_servers=float(N_CHIPS), seed=0)
    return [
        ("quantized",
         Sweep.create(("hesrpt",), rates, n_chips=N_CHIPS, **common)),
        ("quantized-fused",
         Sweep.create(("hesrpt",), rates, n_chips=N_CHIPS, fused=True,
                      **common)),
        ("continuous", Sweep.create(("hesrpt",), rates, **common)),
    ]


def run_lanes(smoke: bool = False, quick: bool = False):
    """Run every lane on the current backend; returns ``[(label, result)]``.

    Multi-device hosts shard the rate axis; the results are identical to
    the single-device run (property-tested), only the wall clock moves.
    """
    import jax

    from repro.core.sweeps import run_sweep

    shard = jax.device_count() > 1
    out = []
    for label, spec in lane_specs(smoke=smoke, quick=quick):
        res = run_sweep(spec, shard=shard, shard_axis="rates", log=False)
        out.append((label, res))
    return out


def lane_records(lanes) -> list[dict]:
    """Backend-tagged rows for ``BENCH_sweeps.json``: one sweep record per
    lane (spec + cells + wall, ``lane`` added) plus one ``backend_lane``
    summary row carrying throughput and the fused/unfused wall ratio."""
    records = []
    by_label = {}
    for label, res in lanes:
        rec = res.record()
        rec["lane"] = label
        records.append(rec)
        by_label[label] = res
    q = by_label.get("quantized")
    qf = by_label.get("quantized-fused")
    summary = {
        "kind": "backend_lane",
        "backend": q.backend if q else "unknown",
        "device_count": q.device_count if q else 0,
        "lanes": {
            label: {
                "wall_s": res.wall_s,
                "compile_s": res.compile_s,
                "jobs_per_s": (
                    res.spec.total_jobs() * len(res.spec.policies)
                    / max(res.wall_s, 1e-9)
                ),
                "sharded": res.sharded,
            }
            for label, res in lanes
        },
        # Fused/unfused wall ratio for the identical quantized spec.
        "fused_speedup_wall": (
            q.wall_s / max(qf.wall_s, 1e-9) if q and qf else None
        ),
    }
    records.append(summary)
    return records


def append_records(records: list[dict], path: str = "BENCH_sweeps.json") -> str:
    """Merge ``records`` into the artifact at ``path`` (create if absent)."""
    try:
        with open(path) as f:
            data = json.load(f)
    except (FileNotFoundError, json.JSONDecodeError):
        data = {"records": []}
    data.setdefault("records", []).extend(records)
    with open(path, "w") as f:
        json.dump(data, f, indent=1)
    return path


def main(smoke: bool = False, quick: bool = False):
    lanes = run_lanes(smoke=smoke, quick=quick)
    records = lane_records(lanes)
    summary = records[-1]
    lines = [
        f"backend lane: {summary['backend']} x{summary['device_count']} "
        f"(rate-axis sharding: {lanes[0][1].sharded})",
        f"{'lane':>18s} {'rates':>6s} {'seeds':>6s} {'wall s':>8s} "
        f"{'compile s':>10s} {'jobs/s':>10s}",
    ]
    for label, res in lanes:
        row = summary["lanes"][label]
        lines.append(
            f"{label:>18s} {len(res.spec.rates):6d} {res.spec.n_seeds:6d} "
            f"{row['wall_s']:8.2f} {row['compile_s']:10.2f} "
            f"{row['jobs_per_s']:10.0f}"
        )
    lines.append(
        f"fused/unfused quantized wall ratio: "
        f"{summary['fused_speedup_wall']:.2f}x ({summary['backend']})"
    )
    # Exactness across the lane: fused and unfused quantized sweeps must
    # agree bit-for-bit (same spec, same seeds, same chips).
    q = dict(lanes)["quantized"]
    qf = dict(lanes)["quantized-fused"]
    exact = all(
        np.array_equal(q.stats["hesrpt"][m], qf.stats["hesrpt"][m])
        for m in q.spec.metrics
    )
    lines.append(f"fused == unfused sweep outputs (bit-for-bit): {exact}")
    assert exact, "fused backend lane diverged from unfused sweep"
    return "\n".join(lines), records


if __name__ == "__main__":
    import sys

    import jax

    # The Pallas allocate takes float32 only, so the chip runs float32.
    jax.config.update("jax_enable_x64", jax.default_backend() != "tpu")
    text, records = main(smoke="--smoke" in sys.argv,
                         quick="--quick" in sys.argv)
    if "--json" in sys.argv:
        print(json.dumps(records[-1], indent=1))
    else:
        print(text)
    if "--no-append" not in sys.argv:
        out = "BENCH_sweeps.json"
        if "--out" in sys.argv:
            out = sys.argv[sys.argv.index("--out") + 1]
        path = append_records(records, out)
        print(f"appended {len(records)} backend-tagged records to {path}")
