"""The fused heSRPT allocation kernel (kernels/alloc.py) and its engine wiring.

- **Exactness vs the unfused pipeline**: ``hesrpt_alloc_fused`` (ref and
  Pallas-interpret) must return theta bit-for-bit ``policies.hesrpt`` and
  chips exactly ``engine.quantize_allocation_jax`` over seeded random
  cases, including oversubscribed regimes (static shape combos are fixed
  so interpret-mode Pallas compiles once per combo, not per case).
- **Event-for-event engine agreement**: ``engine.run(..., fused=True)``
  must reproduce the unfused run's full recorded trajectory — every
  epoch's integer chips, event times, and completion times — bit-for-bit,
  with and without slice snapping, and for the continuous regime.
- **Golden pin**: the fused sweep reproduces the pre-refactor quantized
  sweep output (the same array tests/test_sweeps.py pins for the unfused
  path) — the fused engine changes the op schedule, never the numbers.
- **Sort counts**: the optimization's whole point, measured from compiled
  HLO via ``core.hlo.op_histogram`` — 2 sorts for the policy
  (the size order and its inverse permutation), 4 for the unfused
  allocate, 3 fused, 0 for the Pallas kernel, and the
  engine's scan body pays exactly one fewer sort per event when fused.

Hypothesis twins of the quantizer invariants (conservation, min-chips
floor, within-1) run against the *interpret-mode Pallas kernel* when
hypothesis is installed; the seeded-fuzz fallback below keeps the same
invariants exercised in tier-1 without it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine, make_policy
from repro.core.policies import hesrpt
from repro.kernels.alloc import hesrpt_alloc_fused, hesrpt_theta_fused

# (M, n_chips, min_chips): fixed static combos — one interpret-mode compile
# each — spanning plenty-of-chips, tight, floored, and oversubscribed.
COMBOS = (
    (6, 16, 1),
    (12, 64, 1),
    (16, 32, 3),   # floor binds: trims exercised
    (16, 8, 1),    # oversubscribed: 16 active > 8 chips
    (9, 8, 2),     # oversubscribed with min_chips > 1
)
PS = (0.2, 0.5, 0.8)


def _sizes(rng, m, zero_frac=0.3):
    x = rng.pareto(1.5, m) + 0.01
    x[rng.random(m) < zero_frac] = 0.0
    return jnp.asarray(x)


# ------------------------------------------------------ exactness vs unfused
@pytest.mark.parametrize("impl", ["ref", "interpret"])
def test_fused_matches_unfused_pipeline_exactly(impl):
    """theta bit-for-bit vs policies.hesrpt, chips exact vs
    quantize_allocation_jax, across all static combos x seeded draws."""
    rng = np.random.default_rng(7)
    for m, n_chips, min_chips in COMBOS:
        for trial in range(10):
            x = _sizes(rng, m)
            p = PS[trial % len(PS)]
            theta_ref = hesrpt(x, p)
            chips_ref = engine.quantize_allocation_jax(
                theta_ref, n_chips, min_chips=min_chips
            )
            theta, chips = hesrpt_alloc_fused(
                x, p, n_chips, min_chips=min_chips, impl=impl
            )
            msg = f"{impl} m={m} chips={n_chips}/{min_chips} trial={trial}"
            np.testing.assert_array_equal(
                np.asarray(theta), np.asarray(theta_ref), err_msg=msg
            )
            np.testing.assert_array_equal(
                np.asarray(chips), np.asarray(chips_ref), err_msg=msg
            )


@pytest.mark.parametrize("impl", ["ref", "interpret"])
def test_fused_theta_only_matches_policy(impl):
    rng = np.random.default_rng(3)
    x = _sizes(rng, 16)
    np.testing.assert_array_equal(
        np.asarray(hesrpt_theta_fused(x, 0.5, impl=impl)),
        np.asarray(hesrpt(x, 0.5)),
    )


def test_fused_zero_and_degenerate_cases():
    for impl in ("ref", "interpret"):
        theta, chips = hesrpt_alloc_fused(
            jnp.zeros(8), 0.5, 16, impl=impl
        )
        assert np.all(np.asarray(chips) == 0)
        assert np.all(np.asarray(theta) == 0)
    # n_chips=0 static early-out (the theta-only path)
    _theta, chips = hesrpt_alloc_fused(
        jnp.asarray([2.0, 1.0]), 0.5, 0, impl="ref"
    )
    assert np.all(np.asarray(chips) == 0)


# --------------------------------------------------- engine: event-for-event
def _stream(m, seed, rate=2.0):
    rng = np.random.default_rng(seed)
    sizes = jnp.asarray(rng.pareto(1.5, m) + 0.5)
    arrivals = jnp.asarray(np.cumsum(rng.exponential(1.0 / rate, m)))
    return sizes, arrivals


@pytest.mark.parametrize("snap", [False, True])
def test_engine_fused_quantized_trace_bit_for_bit(snap):
    """fused=True reproduces the unfused engine's recorded trajectory —
    chips at every event, event times, completions — exactly."""
    x0, arr = _stream(40, seed=11)
    rule = engine.quantized_rule(
        hesrpt, 32, min_chips=1, snap_slices=snap, dtype=jnp.float64
    )
    ref = engine.run(x0, arr, 0.5, rule, record=True)
    got = engine.run(x0, arr, 0.5, rule, record=True, fused=True)
    np.testing.assert_array_equal(
        np.asarray(got.trace.alloc), np.asarray(ref.trace.alloc)
    )
    np.testing.assert_array_equal(
        np.asarray(got.trace.times), np.asarray(ref.trace.times)
    )
    np.testing.assert_array_equal(
        np.asarray(got.completion_times), np.asarray(ref.completion_times)
    )


def test_engine_fused_continuous_bit_for_bit():
    """The continuous fused path IS the policy (no sorts to collapse) —
    outputs must be identical, not merely close."""
    x0, arr = _stream(30, seed=5)
    rule = engine.continuous_rule(hesrpt, 64.0, dtype=jnp.float64)
    ref = engine.run(x0, arr, 0.5, rule)
    got = engine.run(x0, arr, 0.5, rule, fused=True)
    np.testing.assert_array_equal(
        np.asarray(got.completion_times), np.asarray(ref.completion_times)
    )


def test_engine_fused_rejects_rules_without_variant():
    x0, arr = _stream(10, seed=0)
    rule = engine.quantized_rule(
        make_policy("equi", n_servers=32.0), 32, dtype=jnp.float64
    )
    with pytest.raises(ValueError, match="fused_variant"):
        engine.run(x0, arr, 0.5, rule, fused=True)


# ----------------------------------------------------------------- golden pin
# The quantized sweep output pinned in tests/test_sweeps.py (GOLDEN_QUANTIZED
# there, re-pinned on jax 0.9's draws): the fused engine must reproduce it
# bit-for-bit.
GOLDEN_QUANTIZED_FUSED = np.array([
    [0.7942090697062641, 0.6759029764795415, 0.7153949192387922],
])


def test_fused_sweep_reproduces_golden_pin():
    from repro.core.sweeps import Sweep, run_sweep

    spec = Sweep.create(("hesrpt",), (2.0,), n_jobs=30, n_seeds=3, p=0.5,
                        n_servers=32.0, seed=1, n_chips=32, fused=True)
    res = run_sweep(spec, log=False)
    np.testing.assert_array_equal(
        res.stats["hesrpt"]["mean_flowtime"], GOLDEN_QUANTIZED_FUSED
    )


def test_sweep_fused_requires_hesrpt_quantized():
    from repro.core.sweeps import Sweep

    with pytest.raises(ValueError):
        Sweep.create(("hesrpt", "equi"), (1.0,), n_jobs=10, n_seeds=2,
                     p=0.5, n_servers=32.0, seed=0, n_chips=32, fused=True)
    with pytest.raises(ValueError):  # continuous regime has no fused rule
        Sweep.create(("hesrpt",), (1.0,), n_jobs=10, n_seeds=2, p=0.5,
                     n_servers=32.0, seed=0, fused=True)


# ---------------------------------------------------------------- sort counts
def _sorts(f, *args) -> float:
    from repro.core.hlo import op_histogram

    hlo = jax.jit(f).lower(*args).compile().as_text()
    return op_histogram(hlo).get("sort", 0.0)


def test_sort_counts_measured_from_hlo():
    """The collapse, in compiled-HLO sort ops: policy 2, unfused allocate 4,
    fused ref 3, Pallas kernel 0."""
    from repro.kernels.alloc import hesrpt_alloc_fused_ref

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.pareto(1.5, 64) + 1.0)
    assert _sorts(hesrpt, x, 0.5) == 2
    assert _sorts(
        lambda xv, pv: engine.quantize_allocation_jax(hesrpt(xv, pv), 16),
        x, 0.5,
    ) == 4
    assert _sorts(
        lambda xv, pv: hesrpt_alloc_fused_ref(xv, pv, 16)[1], x, 0.5
    ) == 3
    assert _sorts(
        lambda xv, pv: hesrpt_alloc_fused(xv, pv, 16, impl="interpret")[1],
        x, 0.5,
    ) == 0


def test_engine_scan_pays_one_fewer_sort_per_event_fused():
    """Trip-count-aware histogram of the compiled scan: 4 sorts/event
    unfused vs 3 fused (+1 one-time arrival-order sort outside the loop)."""
    m = 16
    x0, arr = _stream(m, seed=2)
    rule = engine.quantized_rule(hesrpt, 16, dtype=jnp.float64)

    def scan_sorts(fused):
        def f(x0v, arrv):
            return engine.run(
                x0v, arrv, 0.5, rule, pre_arrived=True, fused=fused
            ).completion_times

        return _sorts(f, x0, arr)

    assert scan_sorts(False) == 1 + 4 * m
    assert scan_sorts(True) == 1 + 3 * m


# ------------------------------------- quantizer invariants, fused kernel
def _invariants(x, p, n_chips, min_chips, impl):
    theta, chips = hesrpt_alloc_fused(
        x, p, n_chips, min_chips=min_chips, impl=impl
    )
    theta = np.asarray(theta)
    chips = np.asarray(chips)
    active = theta > 0
    n_active = int(active.sum())
    # conservation
    assert chips.sum() <= n_chips
    if n_active == 0 or n_chips < min_chips:
        assert chips.sum() == 0
    else:
        assert chips.sum() == n_chips
    # min-chips floor
    assert np.all(chips[~active] == 0)
    assert np.all(chips[chips > 0] >= min_chips)
    if n_active * min_chips <= n_chips:
        assert np.all(chips[active] > 0)
    # within-1 of raw when the floor does not bind (largest-remainder)
    if 0 < n_active * min_chips <= n_chips:
        raw = theta * n_chips
        base0 = np.where(active, np.maximum(np.floor(raw), min_chips), 0)
        if base0.sum() <= n_chips:
            unfloored = active & (np.floor(raw) >= min_chips)
            assert np.all(np.abs(chips[unfloored] - raw[unfloored]) <= 1.0)
            # Floored jobs sit at the floor, +1 at most: a floored job can
            # still win a leftover chip on a large fractional part.
            floored = chips[active & ~unfloored]
            assert np.all((floored >= min_chips) & (floored <= min_chips + 1))


def test_seeded_fuzz_fused_kernel_invariants():
    """No-hypothesis fallback of the property twins
    (tests/test_alloc_fused_properties.py): the interpret Pallas kernel
    (and ref) satisfy conservation / floor / within-1 over seeded draws on
    the fixed static combos."""
    rng = np.random.default_rng(19)
    for m, n_chips, min_chips in COMBOS:
        for trial in range(8):
            x = _sizes(rng, m)
            p = PS[trial % len(PS)]
            for impl in ("ref", "interpret"):
                _invariants(x, p, n_chips, min_chips, impl)
