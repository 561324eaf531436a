"""Fractional allocation -> whole chips.

The paper's theta* treats the N servers as a continuously divisible resource
(heSRPT Thm 7); a TPU cluster hands out whole chips (and prefers power-of-two
mesh slices).  ``quantize_allocation`` is largest-remainder apportionment with
a minimum-chips floor; ``quantize_capped`` holds every served job within its
own width limits ``[lo, hi]``; ``snap_to_slices`` optionally restricts every
job to ICI-friendly slice sizes {1, 2, 4, 8, ...}, under a per-job ceiling.

Invariants (property-tested in tests/test_quantize.py, which also checks
exact agreement with the vectorized-jnp ports
``core.engine.quantize_allocation_jax`` / ``core.engine.snap_to_slices_jax``
— these NumPy versions are the oracles):
- conservation: sum(chips) == n_chips when every active job can hold >= min
  chips (else the smallest-theta jobs are queued with 0),
- monotone: chips_i is within 1 (or one slice) of theta_i * n_chips
  whenever the min-chips floor does not bind,
- active jobs with theta > 0 get >= min_chips whenever capacity allows.

All sorts are stable so tie-breaking (by job index) is well-defined and
reproducible by the jnp port; chips are only ever granted to active jobs.
"""

from __future__ import annotations

import numpy as np

from repro.core.engine import DEFAULT_SLICES


def quantize_allocation(
    theta: np.ndarray, n_chips: int, *, min_chips: int = 1, lo=None, hi=None
) -> np.ndarray:
    """Largest-remainder rounding of ``theta * n_chips`` (theta sums to <= 1).

    Per-job width limits ``lo``/``hi`` (slice sizes; ``lo`` defaults to
    ``min_chips``, ``hi`` to ``n_chips``) take :func:`quantize_capped`."""
    if lo is not None or hi is not None:
        return quantize_capped(theta, n_chips, min_chips=min_chips, lo=lo, hi=hi)
    theta = np.asarray(theta, dtype=np.float64)
    active = theta > 0
    n_active = int(active.sum())
    chips = np.zeros(theta.shape, dtype=np.int64)
    if n_active == 0 or n_chips <= 0:
        return chips

    if n_active * min_chips > n_chips:
        # Oversubscribed: serve the largest-theta jobs, queue the rest.
        order = np.argsort(-theta, kind="stable")
        servable = order[: n_chips // min_chips]
        sub = np.zeros_like(theta)
        sub[servable] = theta[servable]
        tot = sub.sum()
        if tot <= 0:
            return chips
        return quantize_allocation(sub / tot, n_chips, min_chips=min_chips)

    raw = theta * n_chips
    base = np.floor(raw).astype(np.int64)
    base = np.where(active, np.maximum(base, min_chips), 0)
    overflow = int(base.sum()) - n_chips
    if overflow > 0:
        # The min-chips floor oversubscribed: trim from the largest holdings.
        for _ in range(overflow):
            cand = np.where(base > min_chips, base - raw, -np.inf)
            j = int(np.argmax(cand))
            base[j] -= 1
    remainder = n_chips - int(base.sum())
    if remainder > 0:
        frac = np.where(active, raw - np.floor(raw), -1.0)
        # Give the leftover chips to the largest fractional parts (active
        # jobs only — a theta summing well below 1 must not leak chips to
        # departed jobs).
        order = np.argsort(-frac, kind="stable")
        for j in order[: min(remainder, n_active)]:
            base[j] += 1
    return base


def quantize_capped(theta, n_chips: int, *, min_chips: int = 1, lo=None, hi=None):
    """Whole chips with every served job inside its limits ``lo <= chips <= hi``.

    1. Admission: walk the active jobs by descending theta (ties by index)
       and serve them while their ``lo`` still fit in ``n_chips``; queue
       the rest at 0 and renormalize theta over the served jobs if any
       was queued.
    2. Capped water-fill: ``raw = min(lam * theta * n_chips, hi)`` with the
       ``lam`` that makes ``raw`` sum to ``n_chips`` (every job at ``hi``
       when the ``hi`` sum to less): starting from ``lam = 1 / sum(theta)``,
       cap the jobs over their ``hi``, recompute ``lam`` over the rest,
       repeat until no new job caps.  Where no job caps, ``raw`` is
       ``theta * n_chips`` itself.
    3. Rounding: ``floor(raw)`` clipped to ``[lo, hi]``; an overflow is
       trimmed one chip at a time from the job with the largest
       ``base - raw`` still above its ``lo``; leftover chips go one each
       to the largest fractional parts among jobs below ``hi``.
    """
    theta = np.asarray(theta, dtype=np.float64)
    m = theta.size
    lo = np.broadcast_to(np.asarray(min_chips if lo is None else lo, np.int64), (m,))
    hi = np.broadcast_to(np.asarray(n_chips if hi is None else hi, np.int64), (m,))
    chips = np.zeros(m, dtype=np.int64)
    active = theta > 0
    if not active.any() or n_chips <= 0:
        return chips

    served = np.zeros(m, dtype=bool)
    need = 0
    for j in np.argsort(-theta, kind="stable"):
        if not active[j] or need + lo[j] > n_chips:
            break
        served[j] = True
        need += int(lo[j])
    if int(lo[active].sum()) > n_chips:
        tot = theta[served].sum()
        theta = np.where(served, theta / tot, 0.0)
        active = served

    t_n = theta * n_chips
    capped = np.zeros(m, dtype=bool)
    scale = n_chips / t_n[active].sum()
    while True:
        over = active & ~capped & (t_n * scale > hi)
        if not over.any():
            break
        capped |= over
        rest = t_n[active & ~capped].sum()
        free = n_chips - hi[capped].sum()
        scale = free / rest if rest > 0 else 0.0
    raw = np.where(capped, hi.astype(np.float64), t_n * scale if capped.any() else t_n)

    base = np.where(active, np.clip(np.floor(raw), lo, hi), 0).astype(np.int64)
    for _ in range(int(base.sum()) - n_chips):
        j = int(np.argmax(np.where(base > lo, base - raw, -np.inf)))
        base[j] -= 1
    remainder = n_chips - int(base.sum())
    if remainder > 0:
        room = active & (base < hi)
        frac = np.where(room, raw - np.floor(raw), -1.0)
        for j in np.argsort(-frac, kind="stable")[: min(remainder, int(room.sum()))]:
            base[j] += 1
    return base


def snap_to_slices(
    chips: np.ndarray, n_chips: int, *, slices=DEFAULT_SLICES, hi=None
) -> np.ndarray:
    """Snap each job's count DOWN to the largest slice size <= count, then
    hand leftovers (largest-first) to jobs whose next slice step fits and,
    with a per-job ceiling ``hi``, is at most ``hi``."""
    slices = sorted(slices)
    chips = np.asarray(chips, dtype=np.int64).copy()

    def snap_down(c):
        out = 0
        for s in slices:
            if s <= c:
                out = s
        return out

    snapped = np.array([snap_down(int(c)) for c in chips], dtype=np.int64)
    left = n_chips - int(snapped.sum())
    # upgrade greedily: job with the largest lost allocation first
    while left > 0:
        best, best_j = 0, -1
        for j in range(len(snapped)):
            if snapped[j] == 0 and chips[j] == 0:
                continue
            nxt = next((s for s in slices if s > snapped[j]), None)
            if nxt is None or (hi is not None and nxt > hi[j]):
                continue
            step = nxt - snapped[j]
            lost = chips[j] - snapped[j]
            if step <= left and lost >= best:
                best, best_j = lost, j
        if best_j < 0:
            break
        nxt = next(s for s in slices if s > snapped[best_j])
        left -= nxt - snapped[best_j]
        snapped[best_j] = nxt
    return snapped
