"""Tiled flash-attention forward (causal / sliding-window / GQA) for TPU.

TPU adaptation notes (vs the CUDA flash-attention algorithm):
- Tiling is expressed via ``BlockSpec`` so the HBM->VMEM movement is explicit;
  one (block_q x head_dim) query tile and one (block_k x head_dim) KV tile are
  resident in VMEM per grid step, plus fp32 running-max / running-sum / output
  accumulator scratch.
- The KV axis is the innermost ("arbitrary") grid dimension: the scratch
  accumulator carries across KV tiles, mirroring the online-softmax recurrence
  rather than warp-level shuffles.
- Block shapes default to 128 so the matmuls land on MXU-aligned
  (128 x head_dim x 128) shapes.

Only the forward pass is a kernel: the models use remat for the backward, and
the dry-run/roofline path exercises the XLA reference implementation (this
container lowers kernels only in ``interpret=True`` tests).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import NEG_INF


def _flash_kernel(
    q_ref,  # [1, 1, bq, D]
    k_ref,  # [1, 1, bk, D]
    v_ref,  # [1, 1, bk, D]
    o_ref,  # [1, 1, bq, D]
    m_scr,  # [bq, 1] f32 running max
    l_scr,  # [bq, 1] f32 running denominator
    acc_scr,  # [bq, D] f32 output accumulator
    *,
    scale: float,
    causal: bool,
    window: int,
    kv_len: int,
    q_offset: int,
    block_q: int,
    block_k: int,
    n_kv_blocks: int,
):
    iq = pl.program_id(2)
    jk = pl.program_id(3)

    @pl.when(jk == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32) * scale  # [bq, D]
    k = k_ref[0, 0].astype(jnp.float32)  # [bk, D]
    v = v_ref[0, 0].astype(jnp.float32)

    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # [bq, bk]

    q_pos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    q_pos = q_pos + q_offset
    k_pos = jk * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = k_pos < kv_len  # padded KV columns
    if causal:
        mask &= k_pos <= q_pos
    if window > 0:
        mask &= k_pos > q_pos - window
    logits = jnp.where(mask, logits, NEG_INF)

    m_prev = m_scr[...]  # [bq, 1]
    m_cur = jnp.max(logits, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(logits - m_new)  # [bq, bk]
    correction = jnp.exp(m_prev - m_new)  # [bq, 1]
    l_new = correction * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
    acc = acc_scr[...] * correction + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc

    @pl.when(jk == n_kv_blocks - 1)
    def _flush():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "window", "q_offset", "scale", "block_q", "block_k", "interpret",
    ),
)
def flash_attention(
    q: jax.Array,  # [B, Hq, Sq, D]
    k: jax.Array,  # [B, Hkv, Skv, D]
    v: jax.Array,  # [B, Hkv, Skv, D]
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
    scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Flash-attention forward.  Pads Sq/Skv up to block multiples; padded KV
    columns are masked inside the kernel, padded query rows are sliced off."""
    B, Hq, Sq, D = q.shape
    _, Hkv, Sk, _ = k.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    group = Hq // Hkv
    scale = (D ** -0.5) if scale is None else scale

    block_q = min(block_q, max(Sq, 8))
    block_k = min(block_k, max(Sk, 8))
    sq_pad = -Sq % block_q
    sk_pad = -Sk % block_k
    if sq_pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, sq_pad), (0, 0)))
    if sk_pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, sk_pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, sk_pad), (0, 0)))
    Sq_p, Sk_p = Sq + sq_pad, Sk + sk_pad
    n_q_blocks = Sq_p // block_q
    n_kv_blocks = Sk_p // block_k

    grid = (B, Hq, n_q_blocks, n_kv_blocks)
    kernel = functools.partial(
        _flash_kernel,
        scale=scale,
        causal=causal,
        window=window,
        kv_len=Sk,
        q_offset=q_offset,
        block_q=block_q,
        block_k=block_k,
        n_kv_blocks=n_kv_blocks,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, i, j, g=group: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, i, j, g=group: (b, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq_p, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :Sq]
