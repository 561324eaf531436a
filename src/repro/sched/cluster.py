"""ClusterScheduler: the paper's policies driving a real chip pool.

The scheduler owns the job table (remaining work, fitted p-hat) and, at every
*decision epoch* (job departure, arrival, failure — Thm 3 says allocations
only need to change at departures; arrivals/failures are the production
extensions, flagged as the paper's §4.3 heuristic), recomputes:

    theta = policy(remaining_sizes, p)        # heSRPT / heLRPT / SRPT / ...
    chips = quantize(theta, N)                # largest-remainder (+ slices)

``run_fluid_to_completion`` delegates the whole fluid trajectory to the
scan-based allocation engine (``core/engine.py``) whenever the instance fits
the engine's pure-function model — one jit'd device call instead of one
Python epoch at a time, with the same integer-chips quantization
(``core.engine.quantize_allocation_jax``) and power-of-two slice snapping
(``core.engine.snap_to_slices_jax``), both property-tested against the
NumPy ``sched/quantize.py`` oracles used by the per-event path.
``class_aware=True`` is the multi-class regime: per-job speedup exponents,
``core.multiclass`` policies, per-job-``p`` fluid physics — this instance
of the per-event loop is the NumPy oracle the multi-class engine path is
cross-checked against (``benchmarks/multiclass.py``).  ``use_estimator=
True`` is the online-estimation regime: the policy allocates with the
blended (single-class) or per-class-pooled (class-aware) p-hat fit from
observed throughput, while the fluid physics keep each job's true
exponent; the engine runs it as a *stateful* allocation rule
(``core/estimation.py`` — recursive WLS carried through the scan), with
this per-event loop demoted to the cross-check oracle (flows agree to
~1e-10 given the identical observation schedule: one observation per job
per epoch, after the advance).  KNEE's per-epoch alpha refit — the last
Python-only policy path — now delegates too (``core.engine.knee_rule``
recomputes the masked median inside the scan).  The per-event Python path
(``allocations`` / ``advance_fluid``) remains both oracle and fallback
for heterogeneous p without ``class_aware`` (and KNEE under
``use_estimator``); ``sched/elastic.py`` uses it to drive real training
jobs through ``report_progress``.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field

import jax
import numpy as np

from repro.core.policies import make_policy
from repro.sched.estimator import SpeedupEstimator, blended_p, pooled_p_hat
from repro.sched.quantize import quantize_allocation, snap_to_slices


@functools.partial(jax.jit, static_argnames="name")
def _policy_theta(name: str, x, p, n_servers, alpha):
    """One compiled policy call per (policy, padded length)."""
    return make_policy(name, n_servers=n_servers, alpha=alpha)(x, p)


@dataclass
class Job:
    job_id: str
    size: float  # total work units (e.g. training steps x step cost)
    p: float = 0.7  # true speedup exponent (the fluid physics)
    remaining: float = -1.0
    arrival_time: float = 0.0
    chips: float = 0  # whole chips normally; fractional when quantize=False
    completion_time: float | None = None
    class_id: int = 0  # job class (multi-class workloads; 0 = default class)
    # What the estimator believes before any observation.  None = the true
    # p (the historical default); set it away from ``p`` to simulate a
    # scheduler whose prior is stale/wrong.
    prior_p: float | None = None
    estimator: SpeedupEstimator = field(default_factory=SpeedupEstimator)

    def __post_init__(self):
        if self.remaining < 0:
            self.remaining = self.size
        self.estimator.prior_p = self.p if self.prior_p is None else self.prior_p


class ClusterScheduler:
    def __init__(
        self,
        n_chips: int,
        *,
        policy: str = "hesrpt",
        min_chips: int = 1,
        snap_slices: bool = False,
        use_estimator: bool = False,
        quantize: bool = True,
        rel_tol: float = 1e-9,
        class_aware: bool = False,
        class_weights: dict[int, float] | None = None,
        est_discount: float = 1.0,
        est_prior_weight: float = 1.0,
    ):
        self.n_chips = n_chips
        self.policy_name = policy
        self.min_chips = min_chips
        self.snap_slices = snap_slices
        self.use_estimator = use_estimator
        # quantize=False keeps the paper's continuously-divisible allocation
        # (fractional chips) — the fluid reference that core/arrivals.py is
        # cross-checked against.
        self.quantize = quantize
        # Same role as the engine's rel_tol: a departure must not be kept
        # alive by float residue (~eps * size) from the linear advance.
        self.rel_tol = rel_tol
        # class_aware=True is the multi-class regime: ``policy`` must be a
        # ``core.multiclass`` name (hesrpt_pc / waterfill / hesrpt_sd /
        # hesrpt_blind), allocations see the per-job exponent vector, and
        # the fluid physics use each job's own p — this is the per-event
        # NumPy oracle the multi-class engine path is cross-checked against.
        self.class_aware = class_aware
        self.class_weights = class_weights or {}
        # Estimation knobs (use_estimator=True): exponential forgetting and
        # ridge prior strength, applied to every job's estimator on
        # admission so the table is uniform (per-job priors still come
        # from ``Job.prior_p``).
        self.est_discount = est_discount
        self.est_prior_weight = est_prior_weight
        self.jobs: dict[str, Job] = {}
        self.time = 0.0
        self.events: list[dict] = []

    # ------------------------------------------------------------- job table
    def add_job(self, job: Job) -> None:
        job.arrival_time = self.time
        if self.use_estimator:
            job.estimator.discount = self.est_discount
            job.estimator.prior_weight = self.est_prior_weight
        self.jobs[job.job_id] = job
        self.events.append({"t": self.time, "event": "arrival", "job": job.job_id})

    def active_jobs(self) -> list[Job]:
        return [j for j in self.jobs.values() if j.remaining > 0]

    def effective_p(self) -> float:
        act = self.active_jobs()
        if not act:
            return 0.7
        if self.use_estimator:
            return blended_p([j.estimator for j in act], [j.remaining for j in act])
        return float(np.mean([j.p for j in act]))

    def _class_inputs(self, act: list[Job], dtype):
        """Per-job exponent vector and policy weight vector for an active
        set — ONE construction shared by the per-event oracle path and the
        engine delegation, so the exactness contract between them (chips
        equal event-for-event) cannot drift apart."""
        import jax.numpy as jnp

        from repro.core import multiclass as mc

        p_vec = jnp.asarray([j.p for j in act], dtype)
        class_w = jnp.asarray(
            [self.class_weights.get(j.class_id, 1.0) for j in act], dtype
        )
        w = mc.policy_weights(
            self.policy_name,
            x0=jnp.asarray([j.size for j in act], dtype),
            class_w=class_w,
        )
        return p_vec, w

    def _class_priors(self):
        """Per-class ridge prior (mean ``prior_p`` over the class's jobs)
        and prior weight, for classes ``0..K-1`` over the WHOLE job table —
        one definition shared by the per-event oracle and the engine
        delegation, so the pooled fits agree."""
        K = max(j.class_id for j in self.jobs.values()) + 1
        prior_p, prior_w = [], []
        for k in range(K):
            ests = [j.estimator for j in self.jobs.values() if j.class_id == k]
            prior_p.append(
                float(np.mean([e.prior_p for e in ests])) if ests else 0.7
            )
            prior_w.append(
                float(np.mean([e.prior_weight for e in ests])) if ests else 1.0
            )
        return K, prior_p, prior_w

    def _class_p_hat(self, act: list[Job]) -> np.ndarray:
        """Estimated per-job exponent vector for an active set: each job
        gets its class's *pooled* p-hat (``sched.estimator.pooled_p_hat``
        over every job of the class, departed ones included — observations
        don't expire with their job)."""
        K, prior_p, prior_w = self._class_priors()
        p_k = np.empty(K)
        for k in range(K):
            ests = [j.estimator for j in self.jobs.values() if j.class_id == k]
            p_k[k] = pooled_p_hat(ests, prior_p[k], prior_w[k])
        return p_k[[j.class_id for j in act]]

    def _class_theta(self, act: list[Job]) -> np.ndarray:
        """Class-aware theta: the SAME jnp allocation function the engine's
        scan rule calls (``core.multiclass.class_theta``), on the per-job
        exponent vector — identical ops, identical bits, so the engine
        cross-check can demand exact chips.  With ``use_estimator`` the
        policy sees the per-class pooled p-hat instead of the truth (the
        physics in ``job_rates`` keep each job's true exponent)."""
        import jax.numpy as jnp

        from repro.core import multiclass as mc

        x = jnp.asarray([j.remaining for j in act])
        p_vec, w = self._class_inputs(act, x.dtype)
        if self.use_estimator:
            p_vec = jnp.asarray(self._class_p_hat(act), x.dtype)
        theta = mc.class_theta(
            self.policy_name, x, p_vec, n_servers=float(self.n_chips), w=w
        )
        return np.asarray(theta, dtype=np.float64)

    # ------------------------------------------------------ decision epochs
    def allocations(self) -> dict[str, float]:
        """Recompute theta -> chips for the current active set (int-valued
        when quantizing, fractional chips when ``quantize=False``)."""
        import jax.numpy as jnp

        act = self.active_jobs()
        if not act:
            return {}
        p = self.effective_p()
        if self.class_aware:
            theta = self._class_theta(act)
        else:
            # The active count changes at nearly every epoch.  Padding the
            # sizes to a power of two with zeros, which every policy treats
            # as inactive (as the engine's scan does), lets the compiled
            # policy be reused instead of recompiled at each new count.
            rem = [j.remaining for j in act]
            xp = np.zeros(max(8, 1 << (len(act) - 1).bit_length()))
            xp[: len(act)] = rem
            theta = np.asarray(_policy_theta(
                self.policy_name.lower(), jnp.asarray(xp), p,
                float(self.n_chips),
                float(np.median(rem) * p / self.n_chips),
            ), dtype=np.float64)[: len(act)]
        if self.quantize:
            chips = quantize_allocation(theta, self.n_chips, min_chips=self.min_chips)
            if self.snap_slices:
                chips = snap_to_slices(chips, self.n_chips)
            chips = [int(c) for c in chips]
        else:
            chips = [float(c) for c in theta * self.n_chips]
        out = {}
        for j, c in zip(act, chips, strict=True):
            j.chips = c
            out[j.job_id] = c
        self.events.append(
            {"t": self.time, "event": "allocate", "chips": dict(out), "p": p}
        )
        return out

    # --------------------------------------------------------- progress I/O
    def report_progress(self, job_id: str, work_done: float,
                        wall_dt: float = 0.0) -> None:
        job = self.jobs[job_id]
        job.remaining = max(job.remaining - work_done, 0.0)
        if wall_dt > 0:
            self.time += 0.0  # wall time tracked by the driver
            job.estimator.observe(job.chips, work_done / wall_dt)
        if job.remaining == 0 and job.completion_time is None:
            job.completion_time = self.time
            self.events.append({"t": self.time, "event": "depart", "job": job_id})

    # --------------------------------------------------------- fluid model
    def job_rates(self, act: list[Job]) -> np.ndarray:
        """Per-job fluid service rates s(chips_j).  Class-aware and
        estimator modes use each job's own TRUE exponent (the estimator
        may be wrong about p, the physics never are); the plain
        single-class mode keeps the historical blended-p behaviour."""
        if self.class_aware or self.use_estimator:
            return np.array([max(j.chips, 0) ** j.p for j in act])
        p = self.effective_p()
        return np.array([max(j.chips, 0) ** p for j in act])

    def advance_fluid(self, *, until_departure: bool = True, dt: float = 0.0):
        """Advance the fluid simulation: each job progresses at s(chips) =
        chips^p.  Used by benchmarks and the arrival-stream experiments."""
        act = self.active_jobs()
        if not act:
            return 0.0
        rates = self.job_rates(act)
        if until_departure:
            with np.errstate(divide="ignore"):
                tt = np.where(rates > 0, [j.remaining for j in act] / rates, np.inf)
            step = float(np.min(tt))
        else:
            step = dt
        if not np.isfinite(step):
            raise RuntimeError("no job can make progress (all rates zero)")
        # Float residue (rem - (rem/rate)*rate can land ~eps above zero)
        # must not keep the departing job alive for a micro-epoch — same
        # relative-tolerance clamp as the engine scan.
        tol = self.rel_tol * max(j.size for j in self.jobs.values())
        self.time += step
        for j, r in zip(act, rates, strict=True):
            j.remaining = max(j.remaining - step * r, 0.0)
            if j.remaining <= tol:
                j.remaining = 0.0
            if j.remaining == 0 and j.completion_time is None:
                j.completion_time = self.time
                self.events.append({"t": self.time, "event": "depart", "job": j.job_id})
        if self.use_estimator and step > 0:
            # The observation schedule the engine's stateful rule mirrors:
            # after each epoch, every job that held chips and made progress
            # observes its realized fluid throughput (work/dt == rate).
            for j, r in zip(act, rates, strict=True):
                j.estimator.observe(j.chips, r)
        return step

    def _engine_eligible(self) -> bool:
        """The engine scans any rule expressible as ``(init, observe,
        allocate)`` — since the stateful-rule refactor that includes the
        online speedup estimator (``core/estimation.py``), so
        ``use_estimator=True`` delegates too; only the per-epoch KNEE
        alpha refit remains Python-only.  Slice snapping is engine-native
        (``snap_to_slices_jax``), and ``class_aware`` instances delegate
        with the per-job exponent vector (any p mix) as long as the policy
        is a pure ``core.multiclass`` rule; the plain single-class mode
        still needs uniform p (its blended-p physics are not a pure
        per-job rule — the estimator mode has no such constraint, its
        physics are per-job true p).

        The engine runs in the caller's precision: float64 under
        ``jax_enable_x64``, float32 otherwise (the TPU's native width).  In
        float32, whole-chip decisions at near-ties of the largest-remainder
        rounding may differ from the float64 per-event loop."""
        from repro.core.multiclass import MULTICLASS_POLICY_NAMES

        act = self.active_jobs()
        if self.class_aware:
            return self.policy_name.lower() in MULTICLASS_POLICY_NAMES
        if self.use_estimator:
            # per-job true-p physics: any p mix delegates.  KNEE is the one
            # exception: ``estimating_rule`` wraps a static Policy, and
            # KNEE's per-epoch alpha refit is not threaded through it.
            return self.policy_name.lower() != "knee"
        return len({j.p for j in act}) <= 1

    def _run_fluid_engine(self) -> dict:
        """One device call for the whole trajectory: delegate the epoch loop
        (allocate -> advance -> repeat) to ``core.engine.run`` with the
        quantized (or continuous) allocation rule."""
        import jax.numpy as jnp

        from repro.core import engine as _engine

        act = self.active_jobs()
        ids = [j.job_id for j in act]
        x0 = jnp.asarray([j.remaining for j in act])
        dtype = jnp.result_type(x0.dtype, jnp.float32)
        est_kw = {}
        if self.use_estimator:
            # Batch case: arrival sort is the identity, so the per-job
            # estimator vectors in `act` order satisfy the stateful rule's
            # sorted-order contract; pre-existing observation histories
            # (report_progress) seed the sufficient statistics.
            from repro.core import estimation as est

            est_kw = dict(
                prior_p=jnp.asarray([j.estimator.prior_p for j in act], dtype),
                prior_weight=jnp.asarray(
                    [j.estimator.prior_weight for j in act], dtype
                ),
                discount=jnp.asarray(
                    [j.estimator.discount for j in act], dtype
                ),
                init_state=est.est_state_from_history(
                    [j.estimator.history for j in act], dtype
                ),
            )
        if self.class_aware:
            from repro.core import multiclass as mc

            # Batch case: arrival sort is the identity, so per-job vectors
            # in `act` order satisfy the rule's sorted-order contract.
            p_arg, w = self._class_inputs(act, dtype)
            p = float(np.mean([j.p for j in act]))  # event-log annotation
            if self.use_estimator:
                from repro.core import estimation as est

                K, prior_p_k, prior_w_k = self._class_priors()
                # Departed jobs' observations still inform their class's
                # pooled p-hat (exactly as the oracle's _class_p_hat pools
                # the WHOLE job table): fold them in as static [K] stats.
                inact = [j for j in self.jobs.values() if j.remaining <= 0]
                base = None
                if inact:
                    base = est.pool_by_class(
                        est.est_state_from_history(
                            [j.estimator.history for j in inact], dtype
                        ),
                        jnp.asarray([j.class_id for j in inact], jnp.int32),
                        K,
                    )
                rule = est.estimating_class_rule(
                    self.policy_name,
                    class_ids=jnp.asarray(
                        [j.class_id for j in act], jnp.int32
                    ),
                    n_classes=K,
                    prior_p=jnp.asarray(prior_p_k, dtype),
                    prior_weight=jnp.asarray(prior_w_k, dtype),
                    discount=est_kw["discount"],
                    dtype=dtype,
                    n_servers=float(self.n_chips),
                    n_chips=self.n_chips if self.quantize else None,
                    min_chips=self.min_chips,
                    snap_slices=self.snap_slices,
                    w=w,
                    init_state=est_kw["init_state"],
                    base_class_state=base,
                )
            else:
                rule = mc.class_rule(
                    self.policy_name,
                    n_servers=float(self.n_chips),
                    n_chips=self.n_chips if self.quantize else None,
                    min_chips=self.min_chips,
                    snap_slices=self.snap_slices,
                    dtype=dtype,
                    w=w,
                )
        else:
            pol = make_policy(self.policy_name, n_servers=float(self.n_chips))
            if self.use_estimator:
                from repro.core import estimation as est

                # Physics: each job's true exponent; the rule allocates
                # with the blended p-hat it carries through the scan.
                p_arg = jnp.asarray([j.p for j in act], dtype)
                p = self.effective_p()  # event-log annotation (initial)
                rule = est.estimating_rule(
                    pol,
                    float(self.n_chips),
                    dtype=dtype,
                    n_chips=self.n_chips if self.quantize else None,
                    min_chips=self.min_chips,
                    snap_slices=self.snap_slices,
                    **est_kw,
                )
            elif self.policy_name.lower() == "knee":
                # KNEE refits its alpha from the active set at every epoch;
                # the engine rule recomputes the same masked median inside
                # the scan (``core.engine.knee_rule``), which retired the
                # last Python-only policy path.
                p_arg = p = self.effective_p()
                rule = _engine.knee_rule(
                    float(self.n_chips),
                    n_chips=self.n_chips if self.quantize else None,
                    min_chips=self.min_chips,
                    snap_slices=self.snap_slices,
                    dtype=dtype,
                )
            elif self.quantize:
                p_arg = p = self.effective_p()
                rule = _engine.quantized_rule(
                    pol, self.n_chips, min_chips=self.min_chips, dtype=dtype,
                    snap_slices=self.snap_slices,
                )
            else:
                p_arg = p = self.effective_p()
                rule = _engine.continuous_rule(
                    pol, float(self.n_chips), dtype=dtype
                )
        res = _engine.run(
            x0,
            jnp.zeros(len(act), dtype),
            p_arg,
            rule,
            pre_arrived=True,
            horizon=len(act),
            rel_tol=self.rel_tol,
            t0=self.time,
            record=True,
        )
        times = np.asarray(res.completion_times, dtype=np.float64)
        if not np.all(np.isfinite(times)):
            raise RuntimeError("scheduler failed to converge (engine)")
        # Replay the trajectory into the event log / job table the Python
        # path would have produced (engine trace order == `act` order here:
        # every job is pre-arrived, so the engine's arrival sort is the
        # identity permutation).
        alloc = np.asarray(res.trace.alloc)
        sizes = np.asarray(res.trace.sizes)
        t_ev = np.asarray(res.trace.times)
        last_chips: dict[str, float] = {}
        for e in range(alloc.shape[0]):
            live = sizes[e] > 0
            if not live.any():
                break
            # Continuous mode records theta in the trace; the event log keeps
            # the Python path's unit (fractional *chips*, i.e. theta * N).
            chips = {
                ids[i]: (int(alloc[e, i]) if self.quantize
                         else float(alloc[e, i]) * self.n_chips)
                for i in range(len(ids))
                if live[i]
            }
            last_chips.update(chips)
            self.events.append(
                {"t": float(t_ev[e]), "event": "allocate", "chips": chips, "p": p}
            )
        for i, j in enumerate(act):
            j.remaining = 0.0
            j.chips = last_chips.get(j.job_id, 0)
            j.completion_time = float(times[i])
        for t, jid in sorted((float(times[i]), ids[i]) for i in range(len(ids))):
            self.events.append({"t": t, "event": "depart", "job": jid})
        self.time = float(np.max(times))
        return self._summary()

    def _summary(self) -> dict:
        times = {j.job_id: j.completion_time for j in self.jobs.values()}
        flows = {
            jid: t - self.jobs[jid].arrival_time for jid, t in times.items()
        }
        return {
            "completion_times": times,
            "total_flow_time": float(sum(flows.values())),
            "mean_flow_time": float(np.mean(list(flows.values()))),
            "makespan": float(max(times.values())),
        }

    def run_fluid_to_completion(self, *, use_engine: bool = True) -> dict:
        """Run the current job table to completion in the fluid model.

        Delegates to the scan engine when eligible (one jit'd device call);
        ``use_engine=False`` forces the per-event Python epoch loop
        (allocate -> advance to next departure -> repeat), which is the
        oracle the engine path is tested against event-for-event.  The
        summary's ``"path"`` says which ran: ``"engine"`` or ``"events"``.
        """
        if use_engine and self.active_jobs() and self._engine_eligible():
            return {**self._run_fluid_engine(), "path": "engine"}
        guard = 0
        while self.active_jobs():
            self.allocations()
            self.advance_fluid(until_departure=True)
            guard += 1
            if guard > 10 * len(self.jobs) + 100:
                raise RuntimeError("scheduler failed to converge")
        return {**self._summary(), "path": "events"}
