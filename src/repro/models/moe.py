"""Mixture-of-Experts MLP: top-k routing with two dispatch implementations.

``impl="dense"`` — every token through every expert, weighted combine.  Pure
einsum, partitions under plain GSPMD with zero custom collectives, but wastes
``n_experts / top_k`` x compute.  This is the BASELINE the roofline tables
record (and what the perf log hillclimbs away from).

``impl="ragged"`` — TPU-native dropless dispatch: tokens are routed
*shard-locally* under ``shard_map`` (no token ever crosses the data axis),
sorted by expert id, and pushed through ``jax.lax.ragged_dot`` grouped GEMMs
(MXU-friendly, FLOPs = active params only).  Expert weights are
tensor-parallel over the model axis on the ``d_ff`` dim; the down-projection
partial sums are combined with one ``psum`` over the model axis — the same
collective volume as a dense TP MLP.

Both implementations return (output, aux_loss) where aux_loss is the
standard switch-style load-balance loss  E * sum_e f_e * p_e.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models.layers import split_tree, uniform_scale_init


def moe_init(rng, cfg, dtype):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    rr, rg, ru, rd = split_tree(rng, 4)
    return {
        "router": uniform_scale_init(rr, (d, e), dtype),
        "gate": uniform_scale_init(rg, (e, d, f), dtype),
        "up": uniform_scale_init(ru, (e, d, f), dtype),
        "down": uniform_scale_init(rd, (e, f, d), dtype),
    }


def _route(p, x, cfg):
    """Router: top-k expert ids + renormalized weights + load-balance loss."""
    logits = jnp.einsum("bsd,de->bse", x, p["router"].astype(x.dtype))
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    w, ids = jax.lax.top_k(probs, cfg.top_k)  # [B,S,k]
    w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
    # Load balance: fraction of routed assignments vs mean router prob.
    e = cfg.n_experts
    assign = jnp.sum(jax.nn.one_hot(ids, e, dtype=jnp.float32), axis=(-2,))  # [B,S,e]
    f_e = jnp.mean(assign, axis=(0, 1)) / cfg.top_k
    p_e = jnp.mean(probs, axis=(0, 1))
    aux = e * jnp.sum(f_e * p_e)
    return w, ids, aux


def _expert_sharded(t, cfg, parallel):
    """Constrain a [B,S,E,F] expert intermediate to (experts -> data,
    d_ff -> model).  With expert weights sharded over data, this makes GSPMD
    keep the expert GEMMs where the weights live and move ACTIVATIONS
    (all-gather x over data, ~MBs) instead of gathering expert weights
    (~GBs per layer) — pjit-native expert parallelism."""
    if parallel is None:
        return t
    P = jax.sharding.PartitionSpec
    mesh = parallel.mesh
    nd = 1
    for a in parallel.data_axes:
        nd *= mesh.shape[a]
    nm = mesh.shape[parallel.model_axis]
    e_part = None
    if nd > 1 and cfg.n_experts % nd == 0:
        e_part = (parallel.data_axes if len(parallel.data_axes) > 1
                  else parallel.data_axes[0])
    f_part = parallel.model_axis if (nm > 1 and t.shape[-1] % nm == 0) else None
    if e_part is None and f_part is None:
        return t
    spec = P(None, None, e_part, f_part)
    return jax.lax.with_sharding_constraint(
        t, jax.sharding.NamedSharding(mesh, spec)
    )


def moe_apply_dense(p, x, cfg, parallel=None):
    """All-experts einsum baseline.  x [B,S,D] -> [B,S,D].  With ``parallel``
    given, intermediates are expert-sharded (see _expert_sharded)."""
    w, ids, aux = _route(p, x, cfg)
    cw = jnp.einsum(
        "bske,bsk->bse",
        jax.nn.one_hot(ids, cfg.n_experts, dtype=x.dtype),
        w.astype(x.dtype),
    )  # combine weights [B,S,E]
    g = jnp.einsum("bsd,edf->bsef", x, p["gate"].astype(x.dtype))
    g = _expert_sharded(g, cfg, parallel)
    u = jnp.einsum("bsd,edf->bsef", x, p["up"].astype(x.dtype))
    u = _expert_sharded(u, cfg, parallel)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    # Fold the combine weights into h BEFORE the down-projection and contract
    # (e, f) jointly: the naive two-step 'bsef,efd->bsed' then 'bsed,bse->bsd'
    # materializes a (tokens x E x D) intermediate whose all-reduce/reshard
    # dominated the whole step (~26 TB/device for qwen3) — measured in
    # EXPERIMENTS.md §Perf.
    hw = h * cw[..., None]
    out = jnp.einsum("bsef,efd->bsd", hw, p["down"].astype(x.dtype))
    return out, aux


def _moe_local_ragged(x, router, wg, wu, wd, *, cfg, model_axis, aux_axes=()):
    """Shard-local dropless MoE.  x [b_loc, S, D]; wg/wu/wd are the LOCAL
    d_ff shards (full expert and d_model dims).  Runs inside shard_map."""
    b, s, d = x.shape
    k, e = cfg.top_k, cfg.n_experts
    w, ids, aux = _route({"router": router}, x, cfg)

    t = b * s
    x_flat = x.reshape(t, d)
    flat_ids = ids.reshape(t * k)
    order = jnp.argsort(flat_ids, stable=True)
    xs = jnp.take(x_flat, order // k, axis=0)  # [t*k, D] sorted by expert
    group_sizes = jnp.bincount(flat_ids, length=e).astype(jnp.int32)

    g = jax.lax.ragged_dot(xs, wg.astype(x.dtype), group_sizes)
    u = jax.lax.ragged_dot(xs, wu.astype(x.dtype), group_sizes)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    part = jax.lax.ragged_dot(h, wd.astype(x.dtype), group_sizes)  # [t*k, D]
    if model_axis is not None:
        part = jax.lax.psum(part, model_axis)  # combine d_ff-shard partials

    inv = jnp.argsort(order, stable=True)
    y = jnp.take(part, inv, axis=0).reshape(t, k, d)
    out = jnp.einsum("tkd,tk->td", y, w.reshape(t, k).astype(x.dtype))
    if aux_axes:
        aux = jax.lax.pmean(aux, aux_axes)
    return out.reshape(b, s, d), aux


def moe_apply_ragged(p, x, cfg, parallel):
    """shard_map wrapper: tokens stay on their data shard; experts are
    d_ff-tensor-parallel over the model axis."""
    P = jax.sharding.PartitionSpec
    dp, mp = parallel.data_axes, parallel.model_axis
    fn = functools.partial(
        _moe_local_ragged, cfg=cfg, model_axis=mp, aux_axes=tuple(dp) + (mp,)
    )
    out, aux = jax.shard_map(
        fn,
        mesh=parallel.mesh,
        in_specs=(
            P(dp, None, None),
            P(None, None),
            P(None, None, mp),
            P(None, None, mp),
            P(None, mp, None),
        ),
        out_specs=(P(dp, None, None), P()),
        check_vma=False,
    )(x, p["router"], p["gate"], p["up"], p["down"])
    return out, aux


def moe_apply(p, x, cfg, *, impl="dense", parallel=None):
    if impl == "ragged" and parallel is not None:
        return moe_apply_ragged(p, x, cfg, parallel)
    if impl == "ragged_local":
        # Single-device ragged path (tests): no mesh, no psum.
        return _moe_local_ragged(
            x, p["router"], p["gate"], p["up"], p["down"], cfg=cfg, model_axis=None
        )
    if impl == "dense_ep":
        return moe_apply_dense(p, x, cfg, parallel)
    return moe_apply_dense(p, x, cfg)
