"""Entry ``sweep``: grids of seeded job streams through ``run_sweep``.

The configuration names the cluster and the policies, and one grid holds a
sweep spec for each of its speedup exponents ``p``; the traffic names the
scenario, the arrival rates or loads, the jobs per lane and the seeds
per call.
The window plays the whole grid again and again, on the path
``run_sweep`` picks, sharded over the seeds when the cell has more than
one chip.  Every call of a run plays the grid its seed drew:
``run_sweep`` compiles one executor per spec, seed included, so a new seed
per call would trace and load an executor inside the window.

The check re-draws the jobs of every lane, key for key, plays them
through the float64 reference, and compares each lane's mean flow time
with the one the window's last grid returned.
"""

from __future__ import annotations

import time

import numpy as np

from bench import gen
from bench.reference import fluid


class Entry:
    # One throughput under two names, so that cells of different spread
    # carry bounds of their own; a cell reports the one BENCHMARK.json gives it.
    e2e = ("jobs_per_s", "jobs_per_s.batch")
    host_spans = ("run_sweep", "bench.grid")

    def __init__(self, cfg: dict, mix: dict, *, chips: int, seed: int, spans):
        from repro.core.sweeps import Sweep

        self.spans = spans
        self.shard = chips > 1
        self.scenario = mix["scenario"]
        self.size_alpha = float(cfg["size_alpha"])
        self.specs = [
            Sweep.create(
                tuple(cfg["policies"]), tuple(gen.rates(mix, cfg)),
                scenario=mix["scenario"], n_jobs=mix["jobs_per_lane"],
                n_seeds=mix["seeds_per_call"], seed=gen.seed32(seed), p=p,
                n_servers=cfg["n_servers"], size_alpha=self.size_alpha,
                n_chips=cfg.get("n_chips"), min_chips=cfg.get("min_chips", 1),
            )
            for p in cfg["p_values"]
        ]
        self.jobs_per_grid = sum(len(s.policies) * s.total_jobs() for s in self.specs)
        self.checked = [(si, pol, r, k) for si, s in enumerate(self.specs)
                        for pol in s.policies for r in range(len(s.rates))
                        for k in range(s.n_seeds)]
        self.results = None
        self._texts = None

    def _grid(self):
        from repro.core import sweeps

        return [sweeps.run_sweep(s, shard=self.shard, log=False) for s in self.specs]

    def setup(self) -> None:
        self._grid()  # each executor compiled (or loaded) and run once

    def window(self, seconds: float) -> dict:
        t0 = time.perf_counter()
        deadline = t0 + seconds
        grids = 0
        while True:
            with self.spans("bench.grid"):
                self.results = self._grid()
            grids += 1
            if time.perf_counter() >= deadline:
                break
        wall = time.perf_counter() - t0
        bad = sum(int(np.sum(~np.isfinite(a)))
                  for res in self.results for by_m in res.stats.values()
                  for a in by_m.values())
        rate = grids * self.jobs_per_grid / wall
        return {
            "metrics": {"jobs_per_s": rate, "jobs_per_s.batch": rate},
            "attempted": grids * self.jobs_per_grid,
            "failed": grids * bad * self.specs[0].n_jobs,
        }

    def check(self, prec: str | None = None) -> dict:
        """``mean_flow_rel``: the largest relative gap of a lane's
        mean flow time from the float64 reference's.  With ``prec`` the
        reference computed in that precision stands in for the program."""
        draws = {}
        worst = 0.0
        for si, pol, r, k in self.checked:
            spec = self.specs[si]
            if spec.rates not in draws:
                draws[spec.rates] = gen.draw_lanes(
                    self.scenario, spec.seed, spec.n_seeds, spec.rates,
                    spec.n_jobs, self.size_alpha)
            arr, x0 = draws[spec.rates]
            kw = dict(n_servers=spec.n_servers, n_chips=spec.n_chips,
                      min_chips=spec.min_chips)
            ref = fluid.mean_flow(pol, x0[r, k], arr[r, k], spec.p, **kw)
            if prec is None:
                got = float(self.results[si].stats[pol]["mean_flowtime"][r, k])
            else:
                got = fluid.mean_flow(pol, x0[r, k], arr[r, k], spec.p,
                                      prec=prec, **kw)
            rel = abs(got - ref) / abs(ref)
            worst = max(worst, rel if np.isfinite(rel) else np.inf)
        return {"mean_flow_rel": worst}

    def op_labels(self) -> dict[str, str]:
        """``fusion.124`` -> ``fusion.124:scatter`` where every executor
        the window ran agrees on what the fusion of that name computes."""
        from bench import hlo

        seen: dict[str, set[str]] = {}
        for text, _, _ in self.executors():
            for name, root in hlo.fusion_roots(text).items():
                seen.setdefault(name, set()).add(root)
        return {k: f"{k}:{next(iter(v))}" for k, v in seen.items() if len(v) == 1}

    def executors(self) -> list[tuple[str, int, int]]:
        """``(compiled text, lanes, jobs per lane)`` of every executor the
        window ran, from ``run_sweep``'s executor cache.  Raises when an
        executor of the grid is not found there, so that the counters read
        from them fail loudly rather than drop out of the result."""
        from repro.core import sweeps

        if self._texts is not None:
            return self._texts
        out = []
        for spec in self.specs:
            want = spec._replace(policies=())
            found = [compiled for key, compiled in list(sweeps._EXECUTORS.items())
                     if isinstance(key, tuple) and len(key) > 5 and key[0] == want
                     and key[1] in spec.policies and key[5] == self.shard]
            if len(found) != len(spec.policies):
                raise RuntimeError(
                    f"found {len(found)} of the {len(spec.policies)} executors of a "
                    "grid spec in core/sweeps.py's _EXECUTORS: its key has changed")
            out += [(c.as_text(), spec.n_seeds * len(spec.rates), spec.n_jobs)
                    for c in found]
        self._texts = out
        return out
