"""Plain NumPy reference of the scheduler's semantics, in float64.

It imports nothing of the program.  It restates, from the paper (Berg,
Vesilo, Harchol-Balter, arXiv:1903.09346) and the program's documented
contract, what a run must produce:

- the policies' closed forms on the remaining sizes (heSRPT Theorem 7,
  SRPT, EQUI), ranks by descending remaining size, ties by index;
- largest-remainder rounding of ``theta * n_chips`` to whole chips with a
  ``min_chips`` floor: when more jobs are active than the floor allows,
  the largest-theta jobs are served and the rest queued at 0 chips;
- the fluid advance: every active job progresses at ``s(k) = k**p`` with
  ``k`` its servers or chips, each event is the next arrival or the next
  departure, whichever comes first, and the allocation is recomputed at
  every event.

``prec`` rounds every computed value to a lower precision: ``None`` keeps
float64; ``"bfloat16"`` makes the control that a sound comparison must
reject (each value rounded to bfloat16 after every operation).
"""

from __future__ import annotations

import numpy as np


def rounder(prec: str | None):
    """``q(a)``: ``a`` rounded to ``prec`` and held as float64."""
    if prec is None:
        return lambda a: np.asarray(a, np.float64)
    if prec != "bfloat16":
        raise ValueError(f"the control rounds to bfloat16, not {prec!r}")
    import ml_dtypes

    return lambda a: np.asarray(
        np.asarray(a, np.float64).astype(ml_dtypes.bfloat16), np.float64)


def ranks_desc(x: np.ndarray) -> np.ndarray:
    """Rank 1 for the largest active job, ``m`` for the smallest; 0 when
    inactive (``x <= 0``).  Ties keep index order."""
    active = x > 0
    order = np.argsort(np.where(active, -x, np.inf), kind="stable")
    ranks = np.zeros(x.shape, np.int64)
    ranks[order] = np.arange(1, x.size + 1)
    return np.where(active, ranks, 0)


def theta(policy: str, x: np.ndarray, p: float, q=rounder(None)) -> np.ndarray:
    """Share of the system each job gets (sums to 1 over active jobs)."""
    active = x > 0
    m = int(active.sum())
    if m == 0:
        return np.zeros(x.shape)
    if policy == "hesrpt":
        r = ranks_desc(x).astype(np.float64)
        c = q(1.0 / (1.0 - p))
        hi = q(q(r / m) ** c)
        lo = q(q(np.maximum(r - 1.0, 0.0) / m) ** c)
        return np.where(active, q(hi - lo), 0.0)
    if policy == "equi":
        return np.where(active, q(1.0 / m), 0.0)
    if policy == "srpt":
        out = np.zeros(x.shape)
        out[int(np.argmin(np.where(active, x, np.inf)))] = 1.0
        return out
    raise ValueError(f"the reference has no policy {policy!r}")


def whole_chips(th: np.ndarray, n_chips: int, min_chips: int = 1,
                q=rounder(None)) -> np.ndarray:
    """Largest-remainder rounding of ``th * n_chips`` with a floor."""
    th = np.asarray(th, np.float64)
    active = th > 0
    n_active = int(active.sum())
    chips = np.zeros(th.shape, np.int64)
    if n_active == 0:
        return chips
    if n_active * min_chips > n_chips:
        keep = np.argsort(-th, kind="stable")[: n_chips // min_chips]
        sub = np.zeros_like(th)
        sub[keep] = th[keep]
        return whole_chips(q(sub / q(sub.sum())), n_chips, min_chips, q)
    raw = q(th * n_chips)
    base = np.where(active, np.maximum(np.floor(raw), min_chips), 0).astype(np.int64)
    for _ in range(int(base.sum()) - n_chips):
        j = int(np.argmax(np.where(base > min_chips, base - raw, -np.inf)))
        base[j] -= 1
    left = n_chips - int(base.sum())
    if left > 0:
        frac = np.where(active, q(raw - np.floor(raw)), -1.0)
        base[np.argsort(-frac, kind="stable")[: min(left, n_active)]] += 1
    return base


def decide(policy: str, x: np.ndarray, p: float, n_chips: int,
           min_chips: int = 1, prec: str | None = None) -> np.ndarray:
    """One live decision: whole chips for the remaining sizes ``x``."""
    q = rounder(prec)
    return whole_chips(theta(policy, q(x), p, q), n_chips, min_chips, q)


def simulate(policy: str, x0, arrivals, p: float, *, n_servers: float,
             n_chips: int | None = None, min_chips: int = 1,
             rel_tol: float = 1e-9, prec: str | None = None) -> np.ndarray:
    """Completion times (input order) of one job stream played to the end.

    Continuous when ``n_chips`` is None (job ``i`` runs on
    ``theta_i * n_servers`` servers), else on whole chips.  A departing
    job is the one that finishes first at the current rates; a job whose
    remaining size falls to ``rel_tol * max(x0)`` departs with it.
    """
    q = rounder(prec)
    x0 = q(x0)
    arrivals = q(arrivals)
    M = x0.size
    order = np.argsort(arrivals, kind="stable")
    arr = arrivals[order]
    x = x0[order].copy()
    idx = np.arange(M)
    tol = rel_tol * float(np.max(x0))
    done = np.full(M, np.inf)
    t, i = 0.0, 0
    for _ in range(2 * M + 1):
        active = (idx < i) & (x > 0)
        x_act = np.where(active, x, 0.0)
        th = theta(policy, x_act, p, q)
        if n_chips is None:
            rate = q(q(th * n_servers) ** p)
        else:
            rate = q(whole_chips(th, n_chips, min_chips, q).astype(np.float64) ** p)
        with np.errstate(divide="ignore", invalid="ignore"):
            tt = np.where(active & (rate > 0), q(x / rate), np.inf)
        dt_dep = float(tt.min())
        t_arr = float(arr[i]) if i < M else np.inf
        dt_arr = max(t_arr - t, 0.0)
        dt = min(dt_dep, dt_arr)
        if not np.isfinite(dt):
            break
        admit = dt_arr <= dt_dep
        t_new = t_arr if admit else float(q(t + dt))
        x_new = np.where(active, q(x - q(dt * rate)), x)
        departing = (idx == int(np.argmin(tt))) & active & (dt_dep <= dt_arr)
        x_new = np.where(departing | (active & (x_new <= tol)), 0.0, x_new)
        done = np.where(active & (x_new == 0.0) & ~np.isfinite(done), t_new, done)
        x, t = x_new, t_new
        i = max(i, int(np.searchsorted(arr, t, side="right")))
    out = np.empty(M)
    out[order] = done
    return out


def mean_flow(policy: str, x0, arrivals, p: float, **kw) -> float:
    """Mean of completion minus arrival over all jobs of the stream."""
    done = simulate(policy, x0, arrivals, p, **kw)
    return float(np.mean(done - rounder(kw.get("prec"))(arrivals)))
