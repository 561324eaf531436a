"""Fused heSRPT allocation: ranks -> Thm-7 brackets -> whole chips, one pass.

This is the kernel that finally connects the Pallas stack to the scheduling
core.  The engine's per-event hot path (``core/engine.py``) spends its time
deriving the *same* sorted order over and over: the policy sorts remaining
sizes for the descending ranks, then ``quantize_allocation_jax`` sorts theta
for the oversubscription cut and sorts fractional parts for the
largest-remainder round.  For heSRPT both re-derivations are redundant:

- Theorem 7's brackets ``theta_r = (r/m)^c - ((r-1)/m)^c`` (``c = 1/(1-p) >
  1``) are *strictly increasing in rank r*, so the descending-theta position
  of the job ranked ``r`` is simply ``m - r`` — the oversubscription cut
  needs no theta sort at all;
- the quantizer's trim pass (min-chips floor overflow) and leftover pass
  (largest fractional remainders) are mutually exclusive, so one sort on a
  conditionally-selected key serves both (the same collapse
  ``quantize_allocation_jax`` itself now uses).

``hesrpt_alloc_fused_ref`` is that algorithm in pure jnp: **3 argsorts per
event** (sizes, the ranks' inverse permutation, fractional parts) where the
unfused rule pays 4, and no scatter, exact vs
``policies.hesrpt`` + ``engine.quantize_allocation_jax`` by construction —
every floating-point sum runs over the original index order, every integer
step is order-independent, and the one shared sort uses the exact keys and
stable tie-breaks of the sorts it replaces.

``_alloc_pallas`` is the Pallas kernel: **0 argsorts**.  TPUs have no sort
primitive worth using at M ~ 10^3, so ranks and sort positions come from
O(M^2) comparison counting — ``pos_i = #{j : key_j < key_i or (key_j ==
key_i and j < i)}`` — which reproduces a *stable* argsort's positions as
exact integers, chunked over columns so the pairwise tile stays small.  The
whole job vector lives in VMEM (single program, no grid): an [M] f32/f64
vector is tiny next to the matmul workloads the other kernels tile.

Exactness caveats (documented, property-tested):

- The ``m - r`` oversubscription cut assumes the Thm-7 brackets are
  strictly increasing *as floats*.  A tie can only appear when adjacent
  brackets collide at the ulp level (extreme ``p`` -> subnormal brackets);
  the cut then orders tied jobs by rank where the unfused sort orders them
  by index.  Reachable only under ``m * min_chips > n_chips`` AND a tie
  straddling the cut — measure-zero for the sweeps this repo runs.
- The Pallas path pads M to the lane width; the padded zeros cannot change
  any sum's value, but XLA may reshape the reduction tree of the one fp sum
  (the oversubscription renormalizer), which can move chips on knife-edge
  inputs.  The ref path keeps the unpadded reduction and is bit-exact.

``impl`` follows ``kernels/ops.py``: ``auto`` (pallas on TPU, ref
elsewhere), ``ref``, ``pallas``, ``interpret``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.policies import hesrpt, hesrpt_theta_from_ranks
from repro.core.ranking import in_stable_prefix, ranks_from_order, size_order_desc

IMPLS = ("auto", "ref", "pallas", "interpret")


def _resolve(impl: str) -> str:
    if impl not in IMPLS:
        raise ValueError(f"impl must be one of {IMPLS}, got {impl!r}")
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    return impl


# ------------------------------------------------------------ jnp reference
def _quantize_from_ranks(
    theta: jax.Array,
    ranks: jax.Array,
    m: jax.Array,
    n_chips: int,
    *,
    min_chips: int = 1,
) -> jax.Array:
    """``quantize_allocation_jax`` given the policy's ranks: one sort saved.

    Bit-exact vs the unfused quantizer for rank-monotone theta (heSRPT):
    the descending-theta position of the job ranked ``r`` is ``m - r``, so
    the oversubscription cut is rank arithmetic instead of an argsort.  All
    other steps are the unfused quantizer's ops in the unfused order.
    """
    M = theta.shape[0]
    if n_chips <= 0 or min_chips <= 0 or M == 0:
        return jnp.zeros(M, jnp.int32)
    cap = n_chips // min_chips

    active0 = theta > 0
    n_active = jnp.sum(active0, dtype=jnp.int32)
    # Rank-space oversubscription cut: keep the cap largest-theta jobs ==
    # the cap highest ranks (theta strictly increasing in rank, see module
    # docstring) — replaces quantize_allocation_jax's theta argsort.
    servable = active0 & (ranks > m - cap)
    over = n_active * min_chips > n_chips
    sub = jnp.where(servable, theta, 0.0)
    tot = jnp.sum(sub)
    theta_eff = jnp.where(over, jnp.where(tot > 0, sub / tot, 0.0), theta)
    active = theta_eff > 0

    raw = theta_eff * n_chips
    fl = jnp.floor(raw)
    frac = raw - fl
    base = jnp.where(active, jnp.maximum(fl, min_chips), 0.0).astype(jnp.int32)

    K = jnp.maximum(jnp.sum(base) - n_chips, 0)
    capj = jnp.maximum(base - min_chips, 0) * (base > min_chips)

    def bisect(_, lohi):
        lo, hi = lohi
        mid = (lo + hi) // 2
        ge = jnp.sum(jnp.minimum(capj, mid)) >= K
        return jnp.where(ge, lo, mid + 1), jnp.where(ge, mid, hi)

    n_bits = (n_chips + 1).bit_length()
    lo, _hi = jax.lax.fori_loop(
        0, n_bits, bisect, (jnp.int32(0), jnp.int32(n_chips))
    )
    r_star = lo
    full = jnp.minimum(capj, jnp.maximum(r_star - 1, 0))
    extra_needed = K - jnp.sum(full)
    elig = capj >= jnp.maximum(r_star, 1)
    trim = K > 0
    key = jnp.where(
        trim, jnp.where(elig, frac, jnp.inf), jnp.where(active, -frac, jnp.inf)
    )
    order = jnp.argsort(key)
    extra = (elig & in_stable_prefix(key, order, extra_needed)).astype(jnp.int32)
    base = base - full - extra

    remainder = n_chips - jnp.sum(base)
    base = base + (active & in_stable_prefix(key, order, remainder)).astype(jnp.int32)
    return base


def hesrpt_alloc_fused_ref(
    x: jax.Array, p, n_chips: int, *, min_chips: int = 1
) -> tuple[jax.Array, jax.Array]:
    """Fused heSRPT theta + chips in pure jnp, sharing one sorted order.

    Returns ``(theta, chips)``: ``theta`` bit-for-bit ``policies.hesrpt(x,
    p)`` (identical op sequence), ``chips`` exact vs
    ``quantize_allocation_jax(theta, n_chips, min_chips=min_chips)``.
    """
    active = x > 0
    order = size_order_desc(x)
    ranks = ranks_from_order(order, active)
    m = jnp.sum(active)
    theta = hesrpt_theta_from_ranks(ranks, m, p, dtype=x.dtype)
    chips = _quantize_from_ranks(theta, ranks, m, n_chips, min_chips=min_chips)
    return theta, chips


# ------------------------------------------------------------ Pallas kernel
def _alloc_kernel(
    x_ref,  # [1, Mp] remaining sizes (padded with zeros)
    p_ref,  # [1, 1] speedup exponent
    theta_ref,  # [1, Mp] out: Thm-7 allocation fractions
    chips_ref,  # [1, Mp] out: int32 whole-chip allocation
    *,
    M: int,
    n_chips: int,
    min_chips: int,
    block_c: int,
):
    # Every value in the kernel is 32-bit: the TPU kernel compiler has no
    # 64-bit types, and under jax_enable_x64 an integer reduction or a bare
    # Python scalar handed to jnp.where would come out 64-bit.  So integer
    # sums name their dtype, where-fills are typed constants, and the
    # bisection is a static loop (no loop counter).  Sums reduce the lane
    # axis only: Mosaic lowers a reduction to a rank-0 result through a
    # proxy jnp.sum that promotes int32 to int64 under x64.
    i32 = jnp.int32
    Mp = x_ref.shape[1]
    n_blocks = Mp // block_c
    col = jax.lax.broadcasted_iota(i32, (1, Mp), 1)
    izero = jnp.zeros((), i32)

    def isum(a):
        return jnp.sum(a.astype(i32), axis=1, dtype=i32, keepdims=True)

    def positions(key):
        """Stable-argsort position of every column of ``key`` ([1, Mp]).

        O(M^2) comparison counting, chunked so the pairwise tile is
        [block_c, Mp]; the static Python loop unrolls (no sort primitive).
        """
        pos = jnp.zeros((1, Mp), i32)
        for b in range(n_blocks):
            kj = jnp.swapaxes(key[:, b * block_c : (b + 1) * block_c], 0, 1)
            jrow = jax.lax.broadcasted_iota(i32, (block_c, 1), 0) + b * block_c
            before = (kj < key) | ((kj == key) & (jrow < col))
            pos = pos + jnp.sum(
                before.astype(i32), axis=0, dtype=i32, keepdims=True
            )
        return pos

    x = x_ref[...]
    dtype = x.dtype
    zero = jnp.zeros((), dtype)
    inf = jnp.asarray(jnp.inf, dtype)
    active = (x > 0) & (col < M)
    ranks = jnp.where(active, positions(jnp.where(active, -x, inf)) + 1, izero)
    m = isum(active)

    # Thm-7 brackets — the exact op sequence of hesrpt_theta_from_ranks.
    p = p_ref[...]
    rf = ranks.astype(dtype)
    c = 1.0 / (1.0 - p)
    m_safe = jnp.maximum(m, 1).astype(dtype)
    hi = (rf / m_safe) ** c
    lo = ((rf - 1.0) / m_safe) ** c
    theta = jnp.where(active, hi - lo, zero)
    theta_ref[...] = theta

    if n_chips <= 0 or min_chips <= 0:
        chips_ref[...] = jnp.zeros((1, Mp), i32)
        return

    # Largest-remainder quantization: _quantize_from_ranks, positions()
    # replacing its one argsort.
    cap = n_chips // min_chips
    active0 = theta > 0
    n_active = isum(active0)
    servable = active0 & (ranks > m - cap)
    over = n_active * min_chips > n_chips
    sub = jnp.where(servable, theta, zero)
    tot = jnp.sum(sub, axis=1, keepdims=True)
    theta_eff = jnp.where(over, jnp.where(tot > 0, sub / tot, zero), theta)
    active_q = theta_eff > 0

    raw = theta_eff * n_chips
    fl = jnp.floor(raw)
    frac = raw - fl
    base = jnp.where(active_q, jnp.maximum(fl, float(min_chips)), zero)
    base = base.astype(i32)

    K = jnp.maximum(isum(base) - n_chips, 0)
    capj = jnp.maximum(base - min_chips, 0) * (base > min_chips).astype(i32)

    lo_ = jnp.zeros((1, 1), i32)
    hi_ = jnp.full((1, 1), n_chips, i32)
    for _ in range((n_chips + 1).bit_length()):
        mid = jax.lax.shift_right_logical(lo_ + hi_, i32(1))
        ge = isum(jnp.minimum(capj, mid)) >= K
        lo_, hi_ = jnp.where(ge, lo_, mid + 1), jnp.where(ge, mid, hi_)
    r_star = lo_
    full = jnp.minimum(capj, jnp.maximum(r_star - 1, 0))
    extra_needed = K - isum(full)
    elig = capj >= jnp.maximum(r_star, 1)
    trim = K > 0
    key_q = jnp.where(
        trim, jnp.where(elig, frac, inf), jnp.where(active_q, -frac, inf)
    )
    pos = positions(key_q)
    extra = (elig & (pos < extra_needed)).astype(i32)
    base = base - full - extra

    remainder = n_chips - isum(base)
    chips_ref[...] = base + (active_q & (pos < remainder)).astype(i32)


@functools.partial(
    jax.jit, static_argnames=("n_chips", "min_chips", "block_c", "interpret")
)
def _alloc_pallas(
    x: jax.Array,
    p,
    *,
    n_chips: int,
    min_chips: int = 1,
    block_c: int = 128,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    if not interpret and x.dtype != jnp.float32:
        raise TypeError(
            f"the compiled Pallas allocate takes float32 sizes, got {x.dtype}: "
            "the TPU kernel compiler has no 64-bit types.  Pass float32 "
            "sizes, or impl='ref' for the jnp allocate in any precision."
        )
    M = x.shape[0]
    pad = -M % block_c if M else block_c
    Mp = max(M + pad, block_c)
    xp = jnp.pad(x.reshape(1, M), ((0, 0), (0, Mp - M)))
    pv = jnp.asarray(p, x.dtype).reshape(1, 1)
    kernel = functools.partial(
        _alloc_kernel, M=M, n_chips=n_chips, min_chips=min_chips, block_c=block_c
    )
    theta, chips = pl.pallas_call(
        kernel,
        out_shape=[
            jax.ShapeDtypeStruct((1, Mp), x.dtype),
            jax.ShapeDtypeStruct((1, Mp), jnp.int32),
        ],
        interpret=interpret,
    )(xp, pv)
    return theta[0, :M], chips[0, :M]


# ----------------------------------------------------------------- dispatch
def hesrpt_alloc_fused(
    x: jax.Array, p, n_chips: int, *, min_chips: int = 1, impl: str = "auto"
) -> tuple[jax.Array, jax.Array]:
    """Fused heSRPT allocate: ``(theta, chips)`` in one pass over ``x``.

    ``theta`` matches ``policies.hesrpt`` bit-for-bit and ``chips`` matches
    ``engine.quantize_allocation_jax`` exactly (see module docstring for
    the two documented caveats).  ``impl="auto"`` takes the Pallas kernel
    on TPU and the 2-sort jnp reference elsewhere.
    """
    impl = _resolve(impl)
    if impl == "ref":
        return hesrpt_alloc_fused_ref(x, p, n_chips, min_chips=min_chips)
    return _alloc_pallas(
        x, p, n_chips=n_chips, min_chips=min_chips,
        interpret=(impl == "interpret"),
    )


def hesrpt_theta_fused(x: jax.Array, p, *, impl: str = "auto") -> jax.Array:
    """Fused continuous-regime theta (no quantization).

    The ref path *is* ``policies.hesrpt`` — the continuous rule has no
    redundant sort to collapse — so continuous flows are bit-for-bit
    unchanged; the Pallas path exists so accelerator sweeps stay on-chip.
    """
    impl = _resolve(impl)
    if impl == "ref":
        return hesrpt(x, p)
    theta, _ = _alloc_pallas(x, p, n_chips=0, interpret=(impl == "interpret"))
    return theta
