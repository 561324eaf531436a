"""Cross-cutting model options and the parallelism handle models receive."""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
from jax.sharding import NamedSharding, PartitionSpec


@dataclass(frozen=True)
class ParallelConfig:
    """Mesh handle threaded into model code that needs explicit collectives
    (shard_map MoE).  ``data_axes`` may span ("pod", "data") on the multi-pod
    mesh; ``model_axis`` is the tensor-parallel axis."""

    mesh: Any  # jax.sharding.Mesh (unhashable; never a jit static arg)
    data_axes: tuple[str, ...] = ("data",)
    model_axis: str = "model"


@dataclass(frozen=True)
class ModelOptions:
    """How to execute a model — orthogonal to *what* the model is (cfg)."""

    attn_impl: str = "auto"  # ops.attention impl: auto | ref | pallas | interpret
    mixer_impl: str = "auto"  # ops.ssd / ops.rglru impl
    moe_impl: str = "dense"  # dense | ragged | ragged_local
    remat: str = "full"  # full | none (activation checkpointing per block)
    activation_dtype: str = "bfloat16"
    parallel: ParallelConfig | None = None
    # Sequence parallelism at block boundaries: activations (and hence the
    # per-layer tensors remat saves for backward) are sharded over the model
    # axis on the seq dim.  Cuts saved-activation memory by the TP degree at
    # the cost of boundary all-gathers where attention needs the full seq.
    seq_shard: bool = False


def constrain_seq(x, parallel: ParallelConfig | None):
    """Shard [B, S, ...] activations: batch over data axes, seq over model."""
    if parallel is None or x.ndim < 2:
        return x
    b, s = x.shape[0], x.shape[1]
    axes = parallel.data_axes
    nb = 1
    for a in axes:
        nb *= parallel.mesh.shape[a]
    nm = parallel.mesh.shape[parallel.model_axis]
    batch_part = (axes if len(axes) > 1 else axes[0]) if (nb > 1 and b % nb == 0) else None
    seq_part = parallel.model_axis if (nm > 1 and s % nm == 0) else None
    spec = PartitionSpec(batch_part, seq_part, *(None,) * (x.ndim - 2))
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(parallel.mesh, spec)
    )


def constrain_batch(x, parallel: ParallelConfig | None):
    """Pin an activation's leading (batch) dim to the data axes.  GSPMD
    propagation occasionally drops batch sharding across gathers/reshapes
    (observed: the embedding gather) — one constraint per block boundary
    keeps activations batch-sharded everywhere without over-constraining."""
    if parallel is None:
        return x
    b = x.shape[0]
    axes = parallel.data_axes
    n = 1
    for a in axes:
        n *= parallel.mesh.shape[a]
    if n <= 1 or b % n:
        return x
    spec = PartitionSpec(axes if len(axes) > 1 else axes[0], *(None,) * (x.ndim - 1))
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(parallel.mesh, spec)
    )
