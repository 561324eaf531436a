"""Property tests for chip quantization: the NumPy oracle's invariants and
exact agreement between ``core.engine.quantize_allocation_jax`` (the
vectorized scan-friendly port) and ``sched.quantize.quantize_allocation``
(the oracle) across random theta / n_chips / min_chips.

Exactness strategy: the main largest-remainder path is purely elementwise
(identical fp ops in NumPy and jnp), so random float thetas agree exactly.
The oversubscribed branch renormalizes by an internal *sum*, whose
summation order could differ between backends — the dyadic strategy below
draws theta as ``w / 2**k`` (exactly representable, exactly summable), so
even that branch admits no rounding slack and ties are exercised on
purpose (equal weights), pinning the stable tie-break order.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import (
    DEFAULT_SLICES,
    quantize_allocation_jax,
    snap_to_slices_jax,
)
from repro.core.hlo import op_histogram
from repro.sched.quantize import quantize_allocation, snap_to_slices

hypothesis = pytest.importorskip(
    "hypothesis", reason="hypothesis not installed (pip install -e '.[dev]')"
)
from hypothesis import given, settings  # noqa: E402
from hypothesis import strategies as st  # noqa: E402

# A no-hypothesis seeded-fuzz fallback of the exact-agreement property lives
# in tests/test_engine.py (this module is skipped wholesale without
# hypothesis, matching tests/test_properties.py).


@st.composite
def float_thetas(draw):
    m = draw(st.integers(1, 24))
    w = np.array(draw(st.lists(
        st.floats(0.0, 1e3, allow_nan=False, allow_infinity=False),
        min_size=m, max_size=m,
    )))
    zero = np.array(draw(st.lists(st.booleans(), min_size=m, max_size=m)))
    w = np.where(zero, 0.0, w)
    s = w.sum()
    return w / s if s > 0 else w


@st.composite
def dyadic_thetas(draw):
    """theta = w / 2**k: exactly representable and exactly summable, with
    deliberate ties (repeated weights)."""
    m = draw(st.integers(1, 16))
    w = np.array(draw(st.lists(st.integers(0, 64), min_size=m, max_size=m)),
                 dtype=np.float64)
    tot = int(w.sum())
    if tot == 0:
        return w
    scale = 1 << (tot - 1).bit_length()  # next power of two >= sum
    return w / scale


chip_counts = st.integers(1, 300)
min_chip_counts = st.integers(1, 5)


@settings(max_examples=150, deadline=None)
@given(theta=float_thetas(), n_chips=chip_counts, min_chips=min_chip_counts)
def test_jax_quantizer_matches_numpy_oracle_floats(theta, n_chips, min_chips):
    ref = quantize_allocation(theta, n_chips, min_chips=min_chips)
    got = np.asarray(
        quantize_allocation_jax(jnp.asarray(theta), n_chips, min_chips=min_chips)
    )
    np.testing.assert_array_equal(got.astype(np.int64), ref)


@settings(max_examples=150, deadline=None)
@given(theta=dyadic_thetas(), n_chips=st.integers(1, 64),
       min_chips=st.integers(1, 4))
def test_jax_quantizer_matches_numpy_oracle_dyadic_ties(
    theta, n_chips, min_chips
):
    ref = quantize_allocation(theta, n_chips, min_chips=min_chips)
    got = np.asarray(
        quantize_allocation_jax(jnp.asarray(theta), n_chips, min_chips=min_chips)
    )
    np.testing.assert_array_equal(got.astype(np.int64), ref)


@settings(max_examples=150, deadline=None)
@given(theta=float_thetas(), n_chips=chip_counts, min_chips=min_chip_counts)
def test_conservation(theta, n_chips, min_chips):
    """sum(chips) == n_chips whenever any job is active and the floor is
    satisfiable for at least one job; never more than n_chips."""
    chips = quantize_allocation(theta, n_chips, min_chips=min_chips)
    n_active = int((theta > 0).sum())
    assert chips.sum() <= n_chips
    if n_active == 0 or n_chips < min_chips:
        assert chips.sum() == 0
    else:
        assert chips.sum() == n_chips


@settings(max_examples=150, deadline=None)
@given(theta=float_thetas(), n_chips=chip_counts, min_chips=min_chip_counts)
def test_min_chips_floor(theta, n_chips, min_chips):
    """Served jobs get >= min_chips; inactive jobs get nothing; when
    capacity allows (no oversubscription) *every* active job is served."""
    chips = quantize_allocation(theta, n_chips, min_chips=min_chips)
    active = theta > 0
    assert np.all(chips[~active] == 0)
    served = chips > 0
    assert np.all(chips[served] >= min_chips)
    if int(active.sum()) * min_chips <= n_chips:
        assert np.all(served[active])


@settings(max_examples=200, deadline=None)
@given(theta=float_thetas(), n_chips=chip_counts, min_chips=min_chip_counts)
def test_within_one_of_raw_when_floor_does_not_bind(theta, n_chips, min_chips):
    """Largest-remainder property: |chips - theta * n_chips| <= 1 for every
    job the min-chips floor did not touch, provided the floor forced no
    overflow trim and the pool was not oversubscribed."""
    active = theta > 0
    n_active = int(active.sum())
    if n_active == 0 or n_active * min_chips > n_chips:
        return  # oversubscribed: within-1 is vacuous (jobs are queued at 0)
    raw = theta * n_chips
    base0 = np.where(active, np.maximum(np.floor(raw), min_chips), 0)
    if base0.sum() > n_chips:
        return  # floor bound -> trim may move a job far from raw (documented)
    chips = quantize_allocation(theta, n_chips, min_chips=min_chips)
    unfloored = active & (np.floor(raw) >= min_chips)
    assert np.all(np.abs(chips[unfloored] - raw[unfloored]) <= 1.0)
    assert np.all(chips[active & ~unfloored] == min_chips)


@settings(max_examples=100, deadline=None)
@given(theta=float_thetas(), min_chips=st.integers(1, 5))
def test_oversubscription_queues_smallest_theta(theta, min_chips):
    """More active jobs than the floor can hold: exactly floor(N/min) jobs
    are served (the largest thetas), the rest queue at 0 chips."""
    active = theta > 0
    n_active = int(active.sum())
    if n_active < 2:
        return
    n_chips = min_chips * (n_active - 1)  # can't serve everyone
    chips = quantize_allocation(theta, n_chips, min_chips=min_chips)
    served = chips > 0
    assert served.sum() <= n_chips // min_chips
    assert chips.sum() == n_chips
    # every served job's theta >= every queued active job's theta
    if served.any() and (active & ~served).any():
        assert theta[served].min() >= theta[active & ~served].max() - 1e-12


# ------------------------------------------------------------ slice snapping
@st.composite
def chip_vectors(draw):
    """A plausible post-quantization allocation plus the pool it came from:
    ``n_chips >= sum(chips)`` (with slack so upgrades are reachable)."""
    m = draw(st.integers(1, 16))
    chips = np.array(
        draw(st.lists(st.integers(0, 300), min_size=m, max_size=m)),
        dtype=np.int64,
    )
    slack = draw(st.integers(0, 64))
    return chips, max(int(chips.sum()) + slack, 1)


@settings(max_examples=200, deadline=None)
@given(cv=chip_vectors())
def test_snap_jax_matches_numpy_oracle(cv):
    """Exact agreement of the while_loop jnp port with the greedy NumPy
    oracle, including its `>=` (last-index-wins) tie-break."""
    chips, n_chips = cv
    ref = snap_to_slices(chips, n_chips)
    got = np.asarray(snap_to_slices_jax(jnp.asarray(chips), n_chips))
    np.testing.assert_array_equal(got.astype(np.int64), ref)


@settings(max_examples=200, deadline=None)
@given(cv=chip_vectors())
def test_snap_postconditions(cv):
    """Power-of-two membership, conservation, and no chips conjured for
    jobs that held none."""
    chips, n_chips = cv
    snapped = snap_to_slices(chips, n_chips)
    assert set(np.unique(snapped)) <= set(DEFAULT_SLICES) | {0}
    assert snapped.sum() <= n_chips
    assert np.all(snapped[chips == 0] == 0)
    # snap-down is a lower bound before upgrades: never below the largest
    # slice <= chips unless an upgrade moved it *up*.
    down = np.array([max([s for s in DEFAULT_SLICES if s <= c], default=0)
                     for c in chips])
    assert np.all(snapped >= down)


# ------------------------------------------- width at or below the served cap
# An array of width M <= n_chips // min_chips cannot hold more active jobs
# than the floor serves, so the quantizer leaves the oversubscription pass
# out of its program.  Seeded, not Hypothesis-drawn: Pareto shares, no
# subnormals, some of them tied on purpose.
WIDTH_CASES = [
    # (M, n_chips, min_chips): the heavy pool (M = cap), the floor of 2, the
    # boundary on either side, and a narrow array far below the cap.
    (256, 256, 1), (257, 256, 1), (128, 256, 2), (129, 256, 2), (40, 256, 1),
    (3, 7, 2), (4, 7, 2),
]


def _draws(M, seed, n=24):
    """``n`` thetas over ``M`` entries (1..M active, every one in the
    second; Pareto(1.2) shares, a third of the draws with tied shares) and
    distinct job ids as ties."""
    rng = np.random.default_rng(seed)
    thetas = np.zeros((n, M))
    for i in range(n):
        k = M if i == 1 else int(rng.integers(1, M + 1))
        w = rng.pareto(1.2, k) + 0.01
        if i % 3 == 0:
            w = np.round(w, 1) + 0.1
        thetas[i, rng.choice(M, k, replace=False)] = w / w.sum()
    ties = np.stack([rng.choice(10 * M, M, replace=False) for _ in range(n)])
    return thetas, ties


def _quantize_many(thetas, ties, n_chips, min_chips):
    """The vmapped quantizer over rows of ``thetas`` (``ties`` None: no tie)."""
    def one(th, t):
        return quantize_allocation_jax(th, n_chips, min_chips=min_chips, tie=t)

    ties = None if ties is None else jnp.asarray(ties)
    return np.asarray(jax.jit(jax.vmap(one))(jnp.asarray(thetas), ties))


def _oracle_in_tie_order(theta, tie, n_chips, min_chips):
    """The NumPy oracle, whose ties break by index, on the entries in the
    order of ``tie`` (job id), mapped back to their own positions."""
    order = np.arange(theta.shape[0]) if tie is None else np.argsort(tie, kind="stable")
    chips = np.zeros(theta.shape[0], np.int64)
    chips[order] = quantize_allocation(theta[order], n_chips, min_chips=min_chips)
    return chips


@pytest.mark.parametrize("with_tie", [False, True])
@pytest.mark.parametrize("M,n_chips,min_chips", WIDTH_CASES)
def test_width_at_most_cap_equals_the_oversubscription_path(M, n_chips, min_chips,
                                                            with_tie):
    """The chips of M entries equal the first M of the same thetas padded
    with inactive entries past the cap (where the oversubscription pass is
    in the program), and the NumPy oracle's."""
    thetas, ties = _draws(M, seed=1000 * M + 10 * n_chips + min_chips)
    ties = ties if with_tie else None
    got = _quantize_many(thetas, ties, n_chips, min_chips)

    wide = 2 * (n_chips // min_chips) + 1
    padded = np.zeros((thetas.shape[0], wide))
    padded[:, :M] = thetas
    pad_ties = None
    if ties is not None:
        pad_ties = np.concatenate(
            [ties, 10 * M + np.arange(wide - M)[None].repeat(len(ties), 0)], axis=1)
    ref = _quantize_many(padded, pad_ties, n_chips, min_chips)
    np.testing.assert_array_equal(got, ref[:, :M])
    assert np.all(ref[:, M:] == 0)

    for i, theta in enumerate(thetas):
        tie = None if ties is None else ties[i]
        np.testing.assert_array_equal(
            got[i].astype(np.int64),
            _oracle_in_tie_order(theta, tie, n_chips, min_chips), err_msg=str(i))


@pytest.mark.parametrize("with_tie", [False, True])
@pytest.mark.parametrize("M,n_chips,min_chips", WIDTH_CASES)
def test_oversubscription_sort_only_above_the_cap(M, n_chips, min_chips, with_tie):
    """The vmapped quantizer compiles to one sort (the trim and leftover
    pass) at widths up to ``n_chips // min_chips`` and to two above it."""
    def one(th, t):
        return quantize_allocation_jax(th, n_chips, min_chips=min_chips,
                                       tie=t if with_tie else None)

    text = jax.jit(jax.vmap(one)).lower(
        jnp.zeros((4, M)), jnp.zeros((4, M), jnp.int32)).compile().as_text()
    want = 1 if M <= n_chips // min_chips else 2
    assert op_histogram(text).get("sort", 0) == want


@pytest.mark.parametrize("n_chips,min_chips", [(0, 1), (4, 5)])
def test_degenerate_pools(n_chips, min_chips):
    theta = np.array([0.5, 0.5])
    np.testing.assert_array_equal(
        quantize_allocation(theta, n_chips, min_chips=min_chips), [0, 0]
    )
    np.testing.assert_array_equal(
        np.asarray(quantize_allocation_jax(jnp.asarray(theta), n_chips,
                                           min_chips=min_chips)),
        [0, 0],
    )
