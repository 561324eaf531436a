"""RG-LRU linear-recurrence kernel for TPU (RecurrentGemma's mixer).

The recurrence  h_t = a_t * h_{t-1} + g_t  is elementwise over the width W —
pure VPU work with zero arithmetic intensity headroom, so the only thing that
matters is doing it in ONE pass over HBM.  XLA lowers ``associative_scan`` to
a log-depth tree (O(S log S) HBM traffic) and ``lax.scan`` to a length-S loop
of tiny kernels; this kernel instead streams (time_block x width_block) tiles
through VMEM with the running state carried in fp32 scratch — O(S) traffic,
one kernel launch.

Gate nonlinearities (sigmoids, sqrt(1-a^2)) are computed *outside* by the
caller (``ops.rglru``): XLA fuses them into the surrounding elementwise ops,
and the kernel stays a pure first-order recurrence, reusable for any gated
linear RNN.

Grid: (batch, width_blocks, time_blocks) with time innermost ("arbitrary");
the [1, width_block] state resets at t-block 0 and carries across t-blocks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(
    a_ref,  # [1, T, Wb] decay in (0, 1]
    g_ref,  # [1, T, Wb] gated input
    y_ref,  # [1, T, Wb]
    h_scr,  # [1, Wb] f32
    *,
    block_t: int,
):
    tb = pl.program_id(2)

    @pl.when(tb == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    a = a_ref[0].astype(jnp.float32)  # [T, Wb]
    g = g_ref[0].astype(jnp.float32)

    def step(t, h):
        h = a[t][None, :] * h + g[t][None, :]
        y_ref[0, t] = h[0].astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, block_t, step, h_scr[...])
    h_scr[...] = h


@functools.partial(
    jax.jit, static_argnames=("block_t", "block_w", "interpret", "return_state")
)
def rglru_scan(
    a: jax.Array,  # [B, S, W] per-step decay
    g: jax.Array,  # [B, S, W] per-step gated input
    *,
    block_t: int = 256,
    block_w: int = 512,
    interpret: bool = False,
    return_state: bool = False,
):
    """First-order recurrence h_t = a_t h_{t-1} + g_t, streamed in one pass.
    Pads S with a = 1, g = 0 (identity steps) and W with zeros."""
    B, S, W = a.shape
    block_t = min(block_t, max(S, 8))
    block_w = min(block_w, max(W, 8))
    pad_t = -S % block_t
    pad_w = -W % block_w
    if pad_t or pad_w:
        a = jnp.pad(a, ((0, 0), (0, pad_t), (0, pad_w)), constant_values=1.0)
        g = jnp.pad(g, ((0, 0), (0, pad_t), (0, pad_w)))
    S_p, W_p = S + pad_t, W + pad_w

    grid = (B, W_p // block_w, S_p // block_t)
    y = pl.pallas_call(
        functools.partial(_rglru_kernel, block_t=block_t),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_t, block_w), lambda b, w, t: (b, t, w)),
            pl.BlockSpec((1, block_t, block_w), lambda b, w, t: (b, t, w)),
        ],
        out_specs=pl.BlockSpec((1, block_t, block_w), lambda b, w, t: (b, t, w)),
        out_shape=jax.ShapeDtypeStruct((B, S_p, W_p), a.dtype),
        scratch_shapes=[pltpu.VMEM((1, block_w), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(a, g)
    out = y[:, :S, :W]
    if return_state:
        return out, out[:, -1, :].astype(jnp.float32)
    return out
