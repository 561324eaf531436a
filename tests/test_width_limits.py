"""Per-job width limits on whole chips: ``ClassSpec.min_chips/max_chips``.

- The capped jnp quantizer and the ceiling-bounded slice snap
  (``core.engine``) against their NumPy oracles (``sched.quantize``), chip
  for chip in float64, over seeded draws at M in {1, 7, 64} and 16 or 256
  chips, with random slice-size limits ``lo <= hi``, oversubscribed pods
  and pods whose ``hi`` sum to less than the chips.
- Without limits, the quantizer and snap give what they gave before the
  limits existed (a pin of their outputs), and uniform limits that never
  bind give the limit-free answer.
- ``Sweep.create`` refuses limits that are not slice sizes, that have
  ``lo > hi`` or ``hi > n_chips``, or that come without ``n_chips``.
- A small ``run_sweep`` of a three-class pod agrees with the benchmark's
  float64 reference ``bench/reference/pod_classes.py``.
- The compiled multi-class executor carries the ``engine.cap`` and
  ``engine.snap`` scopes under ``engine.allocate``; the limit-free
  whole-chip executor carries neither.
"""

import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import (
    DEFAULT_SLICES,
    quantize_allocation_jax,
    snap_to_slices_jax,
)
from repro.core.multiclass import ClassSpec, as_specs
from repro.core.policies import hesrpt
from repro.core.sweeps import Sweep, SweepResult, _build_fn, run_sweep
from repro.sched.quantize import quantize_allocation, snap_to_slices

SIZES = [(m, n) for m in (1, 7, 64) for n in (16, 256)]

_quantize = jax.jit(
    lambda th, lo, hi, n: quantize_allocation_jax(th, n, lo=lo, hi=hi),
    static_argnums=3,
)
_snap = jax.jit(
    lambda c, hi, n: snap_to_slices_jax(
        c, n, slices=tuple(s for s in DEFAULT_SLICES if s <= n), hi=hi),
    static_argnums=2,
)


def _parts(rng, total: int, count: int, lo: int, hi: int) -> np.ndarray:
    """``count`` whole numbers in ``[lo, hi]`` that sum to ``total``."""
    w = rng.integers(lo, hi + 1, count)
    while w.sum() != total:
        j = rng.integers(count)
        w[j] += 1 if w.sum() < total and w[j] < hi else -1 if w[j] > lo else 0
    return w


def _draws(m: int, n_chips: int, seed: int, count: int = 60):
    """Shares that sum to exactly 1 (whole weights over a power of two) and
    slice-size limits, in three regimes: floors that fit, floors that fit
    under ceilings summing to less than the pod, and oversubscribed floors.
    Every sum the quantizers take is then exact, so NumPy and XLA, which
    add in different orders, must agree to the chip.  Oversubscribed draws
    serve a set of wide shares summing to a power of two (the jobs after
    them ask for the whole pod), so the renormalized shares stay exact."""
    rng = np.random.default_rng(seed)
    sl = np.array([s for s in DEFAULT_SLICES if s <= n_chips])
    for i in range(count):
        regime = i % 3 if m > 1 else i % 2
        if m > n_chips or regime == 2:  # oversubscribed
            b = int(rng.integers(1, min(m, 5)))
            a = int(np.ceil(np.log2(b * 64)))
            while (m - b) * 63 < 2**a:  # the narrow shares must reach 2**a
                b -= 1
                a = int(np.ceil(np.log2(b * 64)))
            w = np.concatenate([_parts(rng, 2**a, b, 64, 127),
                                _parts(rng, 2**a, m - b, 1, 63)]).astype(float)
            lo = np.concatenate([rng.choice(sl[sl <= n_chips // b], b),
                                 np.full(m - b, sl[-1])])
            perm = rng.permutation(m)
            w, lo = w[perm], lo[perm]
        else:
            w = rng.integers(1, 64, m).astype(float)
            w[rng.random(m) < 0.2] = 0.0
            if w.sum() == 0:
                w[0] = 1.0
            w[np.argmax(w)] += (1 << (int(w.sum()) - 1).bit_length()) - w.sum()
            lo = rng.choice(sl, m)
            while lo[w > 0].sum() > n_chips:
                lo[np.argmax(np.where(w > 0, lo, 0))] //= 2
        up = rng.integers(0, 3 if regime == 1 else sl.size, m)
        hi = sl[np.minimum(np.searchsorted(sl, lo) + up, sl.size - 1)]
        yield w / w.sum(), lo, hi


@pytest.mark.parametrize("m,n_chips", SIZES)
def test_capped_quantizer_matches_the_oracle_chip_for_chip(m, n_chips):
    for th, lo, hi in _draws(m, n_chips, seed=m * 1000 + n_chips):
        want = quantize_allocation(th, n_chips, lo=lo, hi=hi)
        got = np.asarray(_quantize(jnp.asarray(th), jnp.asarray(lo), jnp.asarray(hi),
                                   n_chips))
        np.testing.assert_array_equal(got, want, err_msg=f"{th} {lo} {hi}")


@pytest.mark.parametrize("m,n_chips", SIZES)
def test_capped_quantizer_keeps_every_limit(m, n_chips):
    for th, lo, hi in _draws(m, n_chips, seed=m * 7 + n_chips):
        chips = quantize_allocation(th, n_chips, lo=lo, hi=hi)
        on = chips > 0
        assert chips.sum() <= n_chips
        assert np.all(chips[on] >= lo[on]) and np.all(chips <= np.where(th > 0, hi, 0))
        # Served jobs are the longest prefix by share whose floors fit.
        order = np.argsort(-th, kind="stable")[: int((th > 0).sum())]
        fits = int(np.sum(np.cumsum(lo[order]) <= n_chips))
        np.testing.assert_array_equal(np.sort(np.flatnonzero(on)), np.sort(order[:fits]))
        # The pod is full unless every served job sits at its ceiling.
        if chips.sum() < n_chips:
            np.testing.assert_array_equal(chips[on], hi[on])


@pytest.mark.parametrize("m,n_chips", SIZES)
def test_snap_with_ceilings_matches_the_oracle(m, n_chips):
    slices = tuple(s for s in DEFAULT_SLICES if s <= n_chips)
    for th, lo, hi in _draws(m, n_chips, seed=m * 31 + n_chips):
        chips = quantize_allocation(th, n_chips, lo=lo, hi=hi)
        want = snap_to_slices(chips, n_chips, slices=slices, hi=hi)
        got = np.asarray(_snap(jnp.asarray(chips), jnp.asarray(hi), n_chips))
        np.testing.assert_array_equal(got, want)
        on = want > 0
        assert np.all(want[on] >= lo[on]) and np.all(want <= hi)
        assert np.all(np.isin(want[on], slices)) and want.sum() <= n_chips


@pytest.mark.parametrize("min_chips", [1, 2, 4])
def test_limits_that_never_bind_give_the_limit_free_answer(min_chips):
    rng = np.random.default_rng(min_chips)
    for m in (1, 7, 64, 300):
        for _ in range(10):
            x = np.where(rng.random(m) < 0.25, 0.0, rng.pareto(1.5, m) + 1)
            th = hesrpt(jnp.asarray(x), 0.5)
            free = np.asarray(quantize_allocation_jax(th, 256, min_chips=min_chips))
            capped = np.asarray(quantize_allocation_jax(
                th, 256, lo=jnp.full(m, min_chips), hi=jnp.full(m, 256)))
            np.testing.assert_array_equal(capped, free)


def test_limit_free_outputs_are_unchanged():
    """The quantizer and the snap without limits, jnp and NumPy, give the
    outputs they gave before the limits existed: a sha256 of 360 seeded
    decisions of each, recorded on the code before the change.  The shares
    are whole weights over their sum, so no transcendental function, and
    no machine's own rounding of one, enters the pin."""
    h = hashlib.sha256()
    rng = np.random.default_rng(2024)
    q = jax.jit(quantize_allocation_jax, static_argnums=1, static_argnames="min_chips")
    for m in (1, 7, 64):
        for n_chips in (16, 256):
            for min_chips in (1, 2, 4):
                for _ in range(20):
                    w = np.where(rng.random(m) < 0.25, 0, rng.integers(1, 1000, m))
                    theta = w / max(w.sum(), 1)
                    a = np.asarray(q(jnp.asarray(theta), n_chips, min_chips=min_chips))
                    b = quantize_allocation(theta, n_chips, min_chips=min_chips)
                    c = np.asarray(snap_to_slices_jax(jnp.asarray(a), n_chips))
                    d = snap_to_slices(b, n_chips)
                    for arr in (a, b, c, d):
                        h.update(np.asarray(arr, np.int64).tobytes())
    assert h.hexdigest() == (
        "16f28ca893bf0dea2650958c5c85d5ab8e07269d4176e193b79eb08dae32c607")


# ------------------------------------------------------------------ sweeps
POD = (
    ClassSpec(p=0.3, mix=0.8, size_scale=1.0, min_chips=1, max_chips=4),
    ClassSpec(p=0.6, mix=0.17, size_scale=4.0, min_chips=4, max_chips=16),
    ClassSpec(p=0.9, mix=0.03, size_scale=16.0, min_chips=16, max_chips=64),
)


@pytest.mark.parametrize("limits,n_chips,match", [
    ((3, 8), 64, "not a slice size"),
    ((16, 8), 64, "min_chips <= max_chips"),
    ((8, 128), 64, "max_chips <= n_chips"),
    ((1, 8), None, "need n_chips"),
])
def test_sweep_refuses_bad_limits(limits, n_chips, match):
    bad = POD[:2] + (POD[2]._replace(min_chips=limits[0], max_chips=limits[1]),)
    with pytest.raises(ValueError, match=match):
        Sweep.create(("hesrpt_pc",), (2.0,), scenario="multiclass_poisson",
                     n_jobs=16, n_seeds=1, n_chips=n_chips, classes=bad)


def test_class_specs_still_build_from_positional_rows():
    old = ClassSpec(*(0.4, 0.5, 1.5, 2.0, 1.0, 4.0))
    assert old.min_chips is None and old.max_chips is None
    spec = Sweep.create(("hesrpt_pc",), (2.0,), scenario="multiclass_poisson",
                        n_jobs=16, n_seeds=1, n_chips=64, snap_slices=True,
                        classes=POD)
    res = SweepResult(spec=spec, stats={}, wall_s=0.0, compile_s=0.0, backend="cpu",
                      device_count=1, chunk_seeds=None, sharded=False)
    again = SweepResult.from_json(res.to_json()).spec
    assert as_specs(again.classes) == POD


def test_run_sweep_of_a_three_class_pod_matches_the_reference():
    from bench import gen
    from bench.entries.pod_classes import draw_lanes
    from bench.reference import pod_classes

    classes = [dict(c._asdict(), size_alpha=1.5) for c in POD]
    spec = Sweep.create(("hesrpt_pc",), (2.5,), scenario="multiclass_poisson",
                        n_jobs=64, n_seeds=4, seed=gen.seed32(5), n_servers=64.0,
                        n_chips=64, snap_slices=True, classes=POD,
                        metrics=("mean_flowtime", "class_flowtime"))
    res = run_sweep(spec, log=False).stats["hesrpt_pc"]
    cls, arr, x0 = draw_lanes(spec.seed, 4, spec.rates, 64, classes)
    per = {k: np.asarray([c[k] for c in classes]) for k in ("p", "min_chips", "max_chips")}
    for k in range(4):
        c = cls[0, k]
        flows = pod_classes.flows(x0[0, k], arr[0, k], per["p"][c], per["min_chips"][c],
                                  per["max_chips"][c], n_chips=64)
        got = float(res["mean_flowtime"][0, k])
        assert abs(got - flows.mean()) <= 1e-5 * flows.mean(), (k, got, flows.mean())
        for j in np.unique(c):
            want = flows[c == j].mean()
            assert abs(res["class_flowtime"][0, k, j] - want) <= 1e-5 * want


def _op_names(spec, policy):
    with jax.enable_x64(False):
        f = _build_fn(spec, policy, None, False)
        text = jax.jit(f).lower(jnp.zeros((2, 2), jnp.uint32),
                                jnp.asarray(spec.rates, jnp.float32)).compile().as_text()
    return set(re.findall(r'op_name="([^"]*)"', text))


def test_capped_executor_carries_the_cap_and_snap_scopes():
    spec = Sweep.create(("hesrpt_pc",), (2.0,), scenario="multiclass_poisson",
                        n_jobs=24, n_seeds=2, n_chips=64, snap_slices=True,
                        classes=POD)
    names = _op_names(spec, "hesrpt_pc")
    for scope in ("engine.cap", "engine.snap"):
        inside = [n for n in names if re.search(rf"[/(]{re.escape(scope)}[/)]", n)]
        assert inside, scope
        assert all(re.search(r"engine\.allocate[/)].*" + re.escape(scope), n)
                   for n in inside), scope
    free = Sweep.create(("hesrpt",), (64.0,), n_jobs=24, n_seeds=2, n_chips=256,
                        n_servers=256.0)
    assert not any("engine.cap" in n or "engine.snap" in n
                   for n in _op_names(free, "hesrpt"))
