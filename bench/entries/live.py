"""Entry ``live``: one scheduler answering every event of a job stream.

A tape of arrivals is replayed event by event into
``ClusterScheduler(n_chips, policy=...)``: each arrival (``add_job``) and
each departure (``advance_fluid`` to the next one) is followed by one timed
``allocations()`` call, the decision a running cluster asks for.  The loop
is closed: the next departure depends on the chips just decided, so the
stream cannot run late.  Tapes follow each other until the window ends,
each drawn from the seed and the tape's index (``gen.live_tape``).

In a traced run, spans are put around the scheduler's two module-level
calls inside a decision: the compiled policy through its result on the
host (``live.policy``), and the rounding to whole chips on the host
(``live.quantize``).

The check decides every state the window's decisions saw again with the
float64 reference, and compares the chips.
"""

from __future__ import annotations

import contextlib
import time

import numpy as np

from bench import gen
from bench.reference import fluid


class Entry:
    e2e = ("decision_p50_ms", "decision_p95_ms")
    host_spans = ("live.decision", "live.policy", "live.quantize", "bench.tape")

    def __init__(self, cfg: dict, mix: dict, *, chips: int, seed: int, spans):
        del chips
        self.spans = spans
        self.seed = int(seed)
        self.n_chips = int(cfg["n_chips"])
        self.min_chips = int(cfg.get("min_chips", 1))
        self.policy = cfg["policies"][0]
        self.p = float(cfg["p_values"][0])
        self.n_jobs = int(mix["jobs_per_tape"])
        (self.rate,) = gen.rates(mix, cfg)
        self.size_alpha = float(cfg["size_alpha"])
        self.decisions: list[tuple[np.ndarray, np.ndarray]] = []

    def _scheduler(self):
        from repro.sched import ClusterScheduler

        return ClusterScheduler(self.n_chips, policy=self.policy,
                                min_chips=self.min_chips)

    def setup(self) -> None:
        from repro.sched import Job

        # One decision at each padded size a tape can reach (the scheduler
        # pads the active sizes to a power of two, at least 8).
        count = 1
        while count <= self.n_jobs:
            s = self._scheduler()
            for i in range(count):
                s.add_job(Job(f"w{i}", size=1.0 + i, p=self.p))
            s.allocations()
            count = 2 * count + 1 if count > 1 else 9

    def _events(self, s, arr, x0):
        """Apply the tape's events to ``s`` one at a time, yielding after each."""
        from repro.sched import Job

        i, m = 0, arr.size
        while i < m or s.active_jobs():
            act = s.active_jobs()
            dep = np.inf
            if act:
                rates = s.job_rates(act)
                rem = np.array([j.remaining for j in act])
                with np.errstate(divide="ignore"):
                    dep = float(np.min(np.where(rates > 0, rem / rates, np.inf)))
            if i < m and arr[i] - s.time <= dep:
                if act:
                    s.advance_fluid(until_departure=False,
                                    dt=max(float(arr[i]) - s.time, 0.0))
                s.time = float(arr[i])
                s.add_job(Job(f"j{i}", size=float(x0[i]), p=self.p))
                i += 1
            else:
                s.advance_fluid(until_departure=True)
            if s.active_jobs():
                yield

    @contextlib.contextmanager
    def _layer_spans(self):
        """Spans around the policy call and the rounding, traced runs only."""
        if not self.spans.traced:
            yield
            return
        from repro.sched import cluster

        policy, quantize = cluster._policy_theta, cluster.quantize_allocation

        def policy_span(*a, **kw):
            with self.spans("live.policy"):
                return np.asarray(policy(*a, **kw))

        def quantize_span(*a, **kw):
            with self.spans("live.quantize"):
                return quantize(*a, **kw)

        cluster._policy_theta, cluster.quantize_allocation = policy_span, quantize_span
        try:
            yield
        finally:
            cluster._policy_theta, cluster.quantize_allocation = policy, quantize

    def window(self, seconds: float) -> dict:
        self.decisions = []
        lat: list[float] = []
        deadline = time.perf_counter() + seconds
        tape = 0
        with self._layer_spans():
            while time.perf_counter() < deadline:
                arr, x0 = gen.live_tape(self.seed, tape, rate=self.rate,
                                        n_jobs=self.n_jobs, size_alpha=self.size_alpha)
                tape += 1
                s = self._scheduler()
                with self.spans("bench.tape"):
                    for _ in self._events(s, arr, x0):
                        rem = np.array([j.remaining for j in s.active_jobs()])
                        with self.spans("live.decision"):
                            t0 = time.perf_counter()
                            out = s.allocations()
                            lat.append(time.perf_counter() - t0)
                        self.decisions.append(
                            (rem, np.fromiter(out.values(), np.int64, len(out))))
                        if time.perf_counter() >= deadline:
                            break
        ms = 1e3 * np.asarray(lat)
        return {
            "metrics": {"decision_p50_ms": float(np.percentile(ms, 50)),
                        "decision_p95_ms": float(np.percentile(ms, 95))},
            "attempted": len(lat),
            "failed": sum(r.size != c.size for r, c in self.decisions),
        }

    def check(self, prec: str | None = None) -> dict:
        """Over every decision of the window: ``mismatch_share``, the share
        whose chips differ from the float64 reference's on the same state,
        and ``max_chip_diff``, the largest difference for one job.  With
        ``prec`` the reference computed in that precision stands in for
        the program."""
        differ, worst = 0, 0
        for rem, chips in self.decisions:
            ref = fluid.decide(self.policy, rem, self.p, self.n_chips, self.min_chips)
            got = chips if prec is None else fluid.decide(
                self.policy, rem, self.p, self.n_chips, self.min_chips, prec=prec)
            if got.size != ref.size:
                differ += 1
                worst = max(worst, self.n_chips)
                continue
            d = int(np.max(np.abs(got - ref), initial=0))
            differ += d > 0
            worst = max(worst, d)
        return {"mismatch_share": differ / max(len(self.decisions), 1),
                "max_chip_diff": float(worst)}
