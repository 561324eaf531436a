"""Elastic training jobs under the heSRPT cluster scheduler.

Each ``ElasticJob`` is a real JAX training job (model, optimizer, data
stream) that can be RESIZED between scheduler epochs: its state is
checkpointed to disk, a new mesh is built over the newly-assigned device
subset, and the state is restored with the new mesh's shardings
(``train/checkpoint.py`` is deliberately mesh-agnostic).  Data parallelism
inside a job is an explicit ``shard_map`` (params replicated, batch sharded,
gradient ``psum``), which is also where gradient compression (int8 / top-k
with error feedback) intercepts the collective.

``ElasticClusterDriver`` couples the jobs to ``ClusterScheduler``: at every
departure epoch it asks the policy (heSRPT by default) for chip counts,
reassigns devices, resizes jobs, and advances the fluid clock while the jobs
do real training work.  Flow time accounting matches the paper's model:
job i on k chips progresses at rate s(k) = k^p work-units per unit time, and
allocations change only at departures (Thm 3).
"""

from __future__ import annotations

import os
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.data.pipeline import DataConfig, ShardedSyntheticStream
from repro.models import ModelOptions, build_model
from repro.sched.cluster import ClusterScheduler, Job
from repro.sched.stragglers import StragglerDetector
from repro.train import checkpoint
from repro.train.compression import init_error_state, make_grad_reducer
from repro.train.optimizer import OptimizerConfig, apply_updates, init_opt_state


@dataclass
class ElasticJobConfig:
    job_id: str
    model_cfg: object  # ModelConfig (smoke-scale)
    total_steps: int
    seq_len: int = 32
    batch_per_chip: int = 2
    p: float = 0.7  # speedup exponent handed to the scheduler
    lr: float = 1e-3
    compression: str | None = None  # None | int8 | topk
    seed: int = 0


class ElasticJob:
    def __init__(self, cfg: ElasticJobConfig, ckpt_root: str):
        self.cfg = cfg
        self.ckpt_dir = os.path.join(ckpt_root, cfg.job_id)
        self.model = build_model(
            cfg.model_cfg, ModelOptions(activation_dtype="float32", remat="none")
        )
        self.opt_cfg = OptimizerConfig(
            lr=cfg.lr, warmup_steps=5, total_steps=cfg.total_steps, clip_norm=1.0
        )
        params = self.model.init(jax.random.PRNGKey(cfg.seed))
        self.state = {
            "params": params,
            "opt": init_opt_state(params),
            "err": init_error_state(params),
        }
        self.steps_done = 0
        self.losses: list[float] = []
        self.resizes = 0
        self.mesh: Mesh | None = None
        self.devices: tuple = ()
        self._step_fn = None

    # ------------------------------------------------------------- resizing
    def ensure_devices(self, devices) -> None:
        devices = tuple(devices)
        if devices == self.devices and self._step_fn is not None:
            return
        if self.mesh is not None:
            # REAL resize path: state -> disk -> restore under the new mesh.
            checkpoint.save(self.ckpt_dir, self.state, step=self.steps_done)
            self.resizes += 1
        self.devices = devices
        self.mesh = Mesh(np.array(devices), ("data",))
        rep = NamedSharding(self.mesh, P())
        shardings = jax.tree.map(lambda _: rep, self.state)
        if checkpoint.exists(self.ckpt_dir) and self.resizes > 0:
            self.state = checkpoint.restore(self.ckpt_dir, self.state, shardings)
        else:
            self.state = jax.device_put(self.state, rep)
        self._step_fn = self._build_step()

    def _build_step(self):
        model, opt_cfg = self.model, self.opt_cfg
        reducer = make_grad_reducer(self.cfg.compression, "data")

        def local_step(params, opt, err, batch):
            (loss, _), grads = jax.value_and_grad(model.loss_fn, has_aux=True)(
                params, batch
            )
            grads, err = reducer(grads, err)
            params, opt, _ = apply_updates(params, grads, opt, opt_cfg)
            return params, opt, err, jax.lax.pmean(loss, "data")

        shmapped = jax.shard_map(
            local_step,
            mesh=self.mesh,
            in_specs=(P(), P(), P(), P("data")),
            out_specs=(P(), P(), P(), P()),
            check_vma=False,
        )
        return jax.jit(shmapped)

    # ------------------------------------------------------------- training
    def run_steps(self, n: int) -> int:
        n = min(n, self.cfg.total_steps - self.steps_done)
        if n <= 0 or self._step_fn is None:
            return 0
        gb = len(self.devices) * self.cfg.batch_per_chip
        stream = ShardedSyntheticStream(
            DataConfig(
                self.cfg.model_cfg.vocab_size, self.cfg.seq_len, gb,
                seed=self.cfg.seed,
            ),
            family=self.cfg.model_cfg.family,
            model_cfg=self.cfg.model_cfg,
        )
        for _ in range(n):
            batch = {
                k: jnp.asarray(v) for k, v in stream.batch(self.steps_done).items()
            }
            p, o, e, loss = self._step_fn(
                self.state["params"], self.state["opt"], self.state["err"], batch
            )
            self.state = {"params": p, "opt": o, "err": e}
            self.losses.append(float(loss))
            self.steps_done += 1
        return n

    @property
    def done(self) -> bool:
        return self.steps_done >= self.cfg.total_steps


class ElasticClusterDriver:
    """Couples ClusterScheduler epochs to real elastic training jobs."""

    def __init__(
        self,
        job_cfgs: list[ElasticJobConfig],
        devices,
        *,
        policy: str = "hesrpt",
        ckpt_root: str = "/tmp/repro_elastic",
        straggler_detector: StragglerDetector | None = None,
    ):
        self.devices = list(devices)
        self.scheduler = ClusterScheduler(len(self.devices), policy=policy)
        self.jobs: dict[str, ElasticJob] = {}
        for jc in job_cfgs:
            self.jobs[jc.job_id] = ElasticJob(jc, ckpt_root)
            self.scheduler.add_job(
                Job(jc.job_id, size=float(jc.total_steps), p=jc.p)
            )
        self.detector = straggler_detector
        self.allocation_log: list[dict] = []

    def run(self, max_epochs: int = 100) -> dict:
        sched = self.scheduler
        for _ in range(max_epochs):
            act = sched.active_jobs()
            if not act:
                break
            alloc = sched.allocations()
            # contiguous device assignment, largest allocation first
            cursor = 0
            order = sorted(alloc, key=lambda j: -alloc[j])
            for jid in order:
                k = alloc[jid]
                if k <= 0:
                    continue
                devs = self.devices[cursor : cursor + k]
                cursor += k
                self.jobs[jid].ensure_devices(devs)
            self.allocation_log.append({"t": sched.time, "alloc": dict(alloc)})

            # fluid epoch: until the fastest-finishing job departs
            p = sched.effective_p()
            rates = {j.job_id: max(j.chips, 0) ** p for j in act}
            dt = min(
                j.remaining / rates[j.job_id] for j in act if rates[j.job_id] > 0
            )
            for j in act:
                steps = int(round(rates[j.job_id] * dt))
                steps = min(steps, int(round(j.remaining)))
                if j.remaining - steps < 0.5:  # finish the departing job exactly
                    steps = int(round(j.remaining))
                done = self.jobs[j.job_id].run_steps(steps)
                sched.time += 0.0
                sched.report_progress(j.job_id, float(done))
            sched.time += dt
            for j in act:
                if j.remaining <= 0 and j.completion_time is None:
                    j.completion_time = sched.time
        flows = {
            jid: (j.completion_time or sched.time) - j.arrival_time
            for jid, j in sched.jobs.items()
        }
        return {
            "total_flow_time": float(sum(flows.values())),
            "mean_flow_time": float(np.mean(list(flows.values()))),
            "makespan": float(max(flows.values())),
            "losses": {jid: job.losses for jid, job in self.jobs.items()},
            "resizes": {jid: job.resizes for jid, job in self.jobs.items()},
            "allocations": self.allocation_log,
        }
