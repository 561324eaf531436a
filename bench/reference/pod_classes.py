"""Plain NumPy reference of a shared training pod's scheduler, in float64.

It imports nothing of the program.  Jobs come in classes, and a job of
class ``k`` has its own speedup ``s(c) = c ** p_k`` on ``c`` chips and
its own width limits: it holds no chips, or a count in ``[lo_k, hi_k]``
(both slice sizes).  At every arrival and departure the pod decides again:

- **policy**: class-aware heSRPT (``hesrpt_pc``): the active jobs are
  ranked by remaining size, largest first (ties by index), and job ``i``
  of rank ``r`` among ``m`` takes the bracket ``(r/m)^c_i - ((r-1)/m)^c_i``
  with ``c_i = 1 / (1 - p_i)``, renormalized to sum to 1;
- **admission**: walking the jobs by descending share (ties by index),
  serve them while their ``lo`` still fit in the pod; queue the rest at 0
  chips and renormalize the shares over the served jobs if any was queued;
- **capped water-fill**: ``raw = min(lam * theta * n_chips, hi)``, with
  ``lam`` found by the plain iteration (cap every job over its ``hi``,
  recompute ``lam`` over the rest, repeat); where no job caps, ``raw`` is
  ``theta * n_chips``;
- **rounding**: ``floor(raw)`` clipped to ``[lo, hi]``; an overflow is
  trimmed one chip at a time from the job with the largest ``base - raw``
  above its ``lo`` (ties to the lowest index); leftover chips go one each
  to the largest fractional parts among the jobs below ``hi`` (ties to the
  lowest index);
- **slice snap**: each count is snapped down to a slice size, then the
  chips left over go, one upgrade at a time, to the job that lost most
  (ties to the highest index) among those whose next slice fits in what is
  left and is at most its ``hi``;
- **fluid advance**: every job progresses at ``s(chips)`` until the next
  arrival or departure, as in :mod:`bench.reference.fluid`.

``prec="bfloat16"`` rounds every computed value to bfloat16: the control
that a sound comparison must reject.
"""

from __future__ import annotations

import numpy as np

from bench.reference.fluid import ranks_desc, rounder

SLICES = (1, 2, 4, 8, 16, 32, 64, 128, 256)


def theta_pc(x: np.ndarray, p: np.ndarray, q=rounder(None)) -> np.ndarray:
    """Class-aware heSRPT shares of the jobs ``x`` (every one active)."""
    m = x.size
    r = ranks_desc(x).astype(np.float64)
    c = q(1.0 / q(1.0 - p))
    th = q(q(q(r / m) ** c) - q(q((r - 1.0) / m) ** c))
    return q(th / q(th.sum()))


def whole_chips(theta, lo, hi, n_chips: int, q=rounder(None)) -> np.ndarray:
    """Admission, capped water-fill and rounding of the shares ``theta``
    (every job active) to whole chips within ``[lo, hi]``, or 0 if queued."""
    m = theta.size
    served = np.zeros(m, bool)
    need = 0
    for j in np.argsort(-theta, kind="stable"):
        if need + lo[j] > n_chips:
            break
        served[j] = True
        need += int(lo[j])
    if int(lo.sum()) > n_chips:
        theta = np.where(served, q(theta / q(theta[served].sum())), 0.0)
    active = theta > 0

    t_n = q(theta * n_chips)
    capped = np.zeros(m, bool)
    lam = q(n_chips / q(t_n[active].sum()))
    while True:
        over = active & ~capped & (q(t_n * lam) > hi)
        if not over.any():
            break
        capped |= over
        rest = q(t_n[active & ~capped].sum())
        lam = q((n_chips - int(hi[capped].sum())) / rest) if rest > 0 else 0.0
    raw = np.where(capped, hi, q(t_n * lam) if capped.any() else t_n)

    base = np.where(active, np.clip(np.floor(raw), lo, hi), 0).astype(np.int64)
    frac = q(raw - np.floor(raw))
    excess = int(base.sum()) - n_chips
    while excess > 0:  # one round: each job above its lo gives at most one
        elig = np.flatnonzero(base > lo)
        pick = elig[np.argsort(-q(base[elig] - raw[elig]), kind="stable")][:excess]
        base[pick] -= 1
        excess -= pick.size
    left = n_chips - int(base.sum())
    room = np.flatnonzero(active & (base < hi))
    base[room[np.argsort(-frac[room], kind="stable")][:left]] += 1
    return base


def snap(chips, hi, n_chips: int) -> tuple[np.ndarray, int]:
    """Slice snap of ``chips`` within the ceilings ``hi``; also returns the
    number of upgrade rounds it took."""
    sl = np.asarray(SLICES)
    idx = np.searchsorted(sl, chips, side="right") - 1
    snapped = np.where(idx >= 0, sl[np.maximum(idx, 0)], 0)
    left = n_chips - int(snapped.sum())
    rounds = 0
    while left > 0:
        nxt_i = np.searchsorted(sl, snapped, side="right")
        nxt = sl[np.minimum(nxt_i, sl.size - 1)]
        lost = chips - snapped
        elig = ((nxt_i < sl.size) & (nxt - snapped <= left) & (nxt <= hi)
                & (lost >= 0) & ~((snapped == 0) & (chips == 0)))
        if not elig.any():
            break
        cand = np.flatnonzero(elig)
        j = cand[lost[cand] == lost[cand].max()][-1]
        left -= int(nxt[j] - snapped[j])
        snapped[j] = nxt[j]
        rounds += 1
    return snapped, rounds


def simulate(x0, arrivals, p, lo, hi, *, n_chips: int, snap_slices: bool = True,
             rel_tol: float = 1e-9, prec: str | None = None):
    """Completion times (input order) of one job stream played to the end,
    and the snap's upgrade rounds at each event (0 where nothing is active)."""
    q = rounder(prec)
    x0 = q(x0)
    arrivals = q(arrivals)
    M = x0.size
    order = np.argsort(arrivals, kind="stable")
    arr, x = arrivals[order], x0[order].copy()
    p, lo, hi = (np.asarray(a)[order] for a in (p, lo, hi))
    tol = rel_tol * float(np.max(x0))
    done = np.full(M, np.inf)
    rounds = []
    t, i = 0.0, 0
    for _ in range(2 * M + 1):
        act = np.flatnonzero(x[:i] > 0)
        rate = np.zeros(act.size)
        n = 0
        if act.size:
            chips = whole_chips(theta_pc(x[act], p[act], q), lo[act], hi[act],
                                n_chips, q)
            if snap_slices:
                chips, n = snap(chips, hi[act], n_chips)
            rate = q(chips.astype(np.float64) ** p[act])
        rounds.append(n)
        with np.errstate(divide="ignore"):
            tt = np.where(rate > 0, q(x[act] / np.where(rate > 0, rate, 1.0)), np.inf)
        dt_dep = float(tt.min()) if act.size else np.inf
        t_arr = float(arr[i]) if i < M else np.inf
        dt_arr = max(t_arr - t, 0.0)
        dt = min(dt_dep, dt_arr)
        if not np.isfinite(dt):
            break
        t_new = t_arr if dt_arr <= dt_dep else float(q(t + dt))
        x_act = q(x[act] - q(dt * rate))
        gone = x_act <= tol
        if dt_dep <= dt_arr:
            gone[int(np.argmin(tt))] = True
        x_act[gone] = 0.0
        x[act] = x_act
        done[act[gone]] = t_new
        t = t_new
        i = max(i, int(np.searchsorted(arr, t, side="right")))
    out = np.empty(M)
    out[order] = done
    return out, np.asarray(rounds, np.int64)


def flows(x0, arrivals, p, lo, hi, **kw) -> np.ndarray:
    """Flow time (completion minus arrival) of every job, input order."""
    done, _ = simulate(x0, arrivals, p, lo, hi, **kw)
    return done - rounder(kw.get("prec"))(arrivals)
