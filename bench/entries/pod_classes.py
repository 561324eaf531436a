"""Entry ``pod_classes``: a shared training pod's job classes through ``run_sweep``.

The configuration names the pod, the policy and the job classes, each
with its share of arrivals, speedup exponent, Pareto sizes and width
limits (``min_chips``/``max_chips``, slice sizes); the traffic names the
scenario (``multiclass_poisson``), the arrival rates, the jobs per lane
and the seeds per call.  The window plays the grid again and again, as
the sweep entry does, on the path ``run_sweep`` picks: per-job exponents,
capped whole-chip rounding and the slice snap.

The check re-draws every lane's jobs, key for key as the program's
``multiclass_poisson`` sampler is documented to draw them (the key split
three ways: class marks from the mix, Exp(rate) gaps summed, inverse-CDF
Pareto sizes), plays them through the float64 reference
``bench/reference/pod_classes.py``, and compares the lanes of the
window's last grid with it.
"""

from __future__ import annotations

import numpy as np

from bench import gen
from bench.entries.sweep import Entry as SweepEntry
from bench.reference import pod_classes

CLASS_MIN_JOBS = 5  # a class's mean flow is compared where a lane has this many


def draw_lanes(seed: int, n_seeds: int, rates, n_jobs: int, classes):
    """Class ids, arrival times and sizes of every lane, each
    ``[n_rates, n_seeds, n_jobs]``: float64 copies of what the device drew."""
    import jax
    import jax.numpy as jnp

    mixes = jnp.asarray([c["mix"] for c in classes])
    alphas = jnp.asarray([c["size_alpha"] for c in classes])
    scales = jnp.asarray([c["size_scale"] for c in classes])

    def one(key, rate):
        k_cls, k_arr, k_size = jax.random.split(key, 3)
        cls = jax.random.choice(k_cls, len(classes), (n_jobs,),
                                p=mixes / jnp.sum(mixes)).astype(jnp.int32)
        arr = jnp.cumsum(jax.random.exponential(k_arr, (n_jobs,)) / rate)
        u = jax.random.uniform(k_size, (n_jobs,),
                               minval=jnp.finfo(jnp.result_type(float)).tiny,
                               maxval=1.0)
        return cls, arr, scales[cls] * u ** (-1.0 / alphas[cls])

    keys = gen.lane_keys(seed, n_seeds)
    draw = jax.jit(jax.vmap(one))
    out = [draw(keys, jnp.full(n_seeds, rate, jnp.result_type(float))) for rate in rates]
    cls, arr, x0 = (np.stack([np.asarray(o[i]) for o in out]) for i in range(3))
    return cls.astype(np.int64), arr.astype(np.float64), x0.astype(np.float64)


class Entry(SweepEntry):
    def __init__(self, cfg: dict, mix: dict, *, chips: int, seed: int, spans):
        from repro.core.multiclass import ClassSpec
        from repro.core.sweeps import Sweep

        if tuple(cfg["slices"]) != pod_classes.SLICES:
            raise ValueError(f"the reference snaps to {pod_classes.SLICES}, "
                             f"the configuration names {cfg['slices']}")
        self.spans = spans
        self.shard = chips > 1
        self.scenario = mix["scenario"]
        self.classes = cfg["classes"]
        self.n_chips = int(cfg["n_chips"])
        self.snap_slices = bool(cfg["snap_slices"])
        specs = tuple(
            ClassSpec(p=c["p"], mix=c["mix"], size_alpha=c["size_alpha"],
                      size_scale=c["size_scale"], min_chips=c["min_chips"],
                      max_chips=c["max_chips"])
            for c in self.classes)
        self.specs = [Sweep.create(
            tuple(cfg["policies"]), tuple(gen.rates(mix, cfg)),
            scenario=mix["scenario"], n_jobs=mix["jobs_per_lane"],
            n_seeds=mix["seeds_per_call"], seed=gen.seed32(seed),
            n_servers=cfg["n_servers"], n_chips=self.n_chips,
            min_chips=cfg["min_chips"], snap_slices=self.snap_slices,
            classes=specs, metrics=("mean_flowtime", "class_flowtime"),
        )]
        self.jobs_per_grid = sum(len(s.policies) * s.total_jobs() for s in self.specs)
        self.results = None
        self._texts = None

    def check(self, prec: str | None = None) -> dict:
        """``mean_flow_rel``: the largest relative gap of a lane's mean flow
        time from the float64 reference's; ``class_flow_rel``: the same for
        each class's mean flow time, over the classes with at least
        ``CLASS_MIN_JOBS`` jobs in the lane.  With ``prec`` the reference
        computed in that precision stands in for the program."""
        spec = self.specs[0]
        cls, arr, x0 = draw_lanes(spec.seed, spec.n_seeds, spec.rates, spec.n_jobs,
                                  self.classes)
        p, lo, hi = (np.asarray([c[k] for c in self.classes])[cls]
                     for k in ("p", "min_chips", "max_chips"))
        kw = dict(n_chips=self.n_chips, snap_slices=self.snap_slices)
        worst = {"mean_flow_rel": 0.0, "class_flow_rel": 0.0}

        def gap(got, ref):
            rel = abs(got - ref) / abs(ref)
            return rel if np.isfinite(rel) else np.inf

        for pol in spec.policies:
            stats = self.results[0].stats[pol] if prec is None else None
            for r in range(len(spec.rates)):
                for k in range(spec.n_seeds):
                    lane = (x0[r, k], arr[r, k], p[r, k], lo[r, k], hi[r, k])
                    ref = pod_classes.flows(*lane, **kw)
                    if prec is None:
                        got_mean = float(stats["mean_flowtime"][r, k])
                        got_class = np.asarray(stats["class_flowtime"][r, k], np.float64)
                    else:
                        got = pod_classes.flows(*lane, prec=prec, **kw)
                        got_mean = float(np.mean(got))
                        got_class = np.asarray([np.mean(got[cls[r, k] == c]) if
                                                np.any(cls[r, k] == c) else np.nan
                                                for c in range(len(self.classes))])
                    worst["mean_flow_rel"] = max(worst["mean_flow_rel"],
                                                 gap(got_mean, float(np.mean(ref))))
                    for c in range(len(self.classes)):
                        mine = cls[r, k] == c
                        if mine.sum() >= CLASS_MIN_JOBS:
                            worst["class_flow_rel"] = max(
                                worst["class_flow_rel"],
                                gap(float(got_class[c]), float(np.mean(ref[mine]))))
        return worst
