"""Mamba2 SSD (state-space duality) chunked-scan kernel for TPU.

The SSD insight: the SSM recurrence over a chunk of Q timesteps is a
low-rank-structured matmul, so within a chunk the computation runs on the MXU
as (Q x N)(N x Q) and (Q x Q)(Q x P) matmuls ("the dual/attention form"), and
only the chunk -> chunk state carry is sequential.

TPU adaptation: the chunk axis is the innermost "arbitrary" grid dimension;
the (P x N) state carries across chunks in fp32 VMEM scratch (no cross-SM
shared-memory staging as on GPU — one core just revisits the scratch).  Chunk
length defaults to 128 so all matmuls are MXU-aligned.

Layout: x [B, S, H, P], dt [B, S, H], a [H], b/c [B, S, N] (ngroups = 1).
Outputs y [B, S, H, P] and the final state [B, H, P, N] (fed to decode).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(
    x_ref,  # [1, Q, 1, P]
    dt_ref,  # [1, Q, 1]
    a_ref,  # [1]
    b_ref,  # [1, Q, N]
    c_ref,  # [1, Q, N]
    y_ref,  # [1, Q, 1, P]
    state_ref,  # [1, 1, P, N]  final-state output (written at last chunk)
    h_scr,  # [P, N] f32 carried state
    *,
    n_chunks: int,
    seq_len: int,
    block_q: int,
):
    ch = pl.program_id(2)

    @pl.when(ch == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[0, :, 0, :].astype(jnp.float32)  # [Q, P]
    dt = dt_ref[0, :, 0].astype(jnp.float32)  # [Q]
    a = a_ref[0].astype(jnp.float32)  # scalar
    b = b_ref[0].astype(jnp.float32)  # [Q, N]
    c = c_ref[0].astype(jnp.float32)  # [Q, N]

    # Mask padded timesteps (same masking the oracle's recurrence implies:
    # dt = 0 -> no state update, no output contribution).
    t_pos = ch * block_q + jax.lax.iota(jnp.int32, block_q)
    valid = (t_pos < seq_len).astype(jnp.float32)
    dt = dt * valid

    da = a * dt  # [Q] per-step log-decay (a < 0)
    s = jnp.cumsum(da)  # inclusive cumsum: decay from step u..t is exp(s_t - s_u)

    # Intra-chunk (dual/attention form): scores[t, u] = exp(s_t - s_u) * <c_t, b_u>
    # for u <= t, multiplied by dt_u; y_intra = scores @ x.
    cb = jax.lax.dot_general(
        c, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # [Q, Q]
    decay = jnp.exp(s[:, None] - s[None, :])
    lower = (
        jax.lax.broadcasted_iota(jnp.int32, (block_q, block_q), 0)
        >= jax.lax.broadcasted_iota(jnp.int32, (block_q, block_q), 1)
    )
    scores = jnp.where(lower, cb * decay, 0.0) * dt[None, :]
    y = jax.lax.dot_general(
        scores, x, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # [Q, P]

    # Inter-chunk: y_t += c_t . (exp(s_t) * h_prev)
    h_prev = h_scr[...]  # [P, N]
    y += jnp.exp(s)[:, None] * jax.lax.dot_general(
        c, h_prev, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )

    # State update: h = exp(s_Q) h_prev + sum_u exp(s_Q - s_u) dt_u x_u b_u^T.
    total = s[block_q - 1]
    w = jnp.exp(total - s) * dt  # [Q]
    upd = jax.lax.dot_general(
        x * w[:, None], b, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # [P, N]
    h_scr[...] = jnp.exp(total) * h_prev + upd

    y_ref[0, :, 0, :] = y.astype(y_ref.dtype)

    @pl.when(ch == n_chunks - 1)
    def _flush():
        state_ref[0, 0] = h_scr[...]


@functools.partial(jax.jit, static_argnames=("block_q", "interpret", "return_state"))
def ssd_scan(
    x: jax.Array,  # [B, S, H, P]
    dt: jax.Array,  # [B, S, H]
    a: jax.Array,  # [H]
    b: jax.Array,  # [B, S, N]
    c: jax.Array,  # [B, S, N]
    d: jax.Array,  # [H]
    *,
    block_q: int = 128,
    interpret: bool = False,
    return_state: bool = False,
):
    """Chunked SSD forward.  Pads S to a block multiple (masked via dt = 0)."""
    B, S, H, P = x.shape
    N = b.shape[-1]
    block_q = min(block_q, max(S, 8))
    pad = -S % block_q
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0)))
    S_p = S + pad
    n_chunks = S_p // block_q

    grid = (B, H, n_chunks)
    kernel = functools.partial(
        _ssd_kernel, n_chunks=n_chunks, seq_len=S, block_q=block_q
    )
    y, state = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, 1, P), lambda bi, h, ch: (bi, ch, h, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bi, h, ch: (bi, ch, h)),
            pl.BlockSpec((1,), lambda bi, h, ch: (h,)),
            pl.BlockSpec((1, block_q, N), lambda bi, h, ch: (bi, ch, 0)),
            pl.BlockSpec((1, block_q, N), lambda bi, h, ch: (bi, ch, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, 1, P), lambda bi, h, ch: (bi, ch, h, 0)),
            pl.BlockSpec((1, 1, P, N), lambda bi, h, ch: (bi, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S_p, H, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(x, dt, a, b, c)
    y = y[:, :S] + d.astype(x.dtype)[None, None, :, None] * x[:, :S]
    return (y, state) if return_state else y
