"""Cluster scheduler built on the paper's policies: quantization, online
p-estimation, decision epochs, straggler mitigation."""

from repro.sched.cluster import ClusterScheduler, Job
from repro.sched.estimator import SpeedupEstimator, blended_p, pooled_p_hat
from repro.sched.quantize import quantize_allocation, snap_to_slices
from repro.sched.stragglers import StragglerDetector

__all__ = [
    "ClusterScheduler",
    "Job",
    "SpeedupEstimator",
    "StragglerDetector",
    "blended_p",
    "pooled_p_hat",
    "quantize_allocation",
    "snap_to_slices",
]
