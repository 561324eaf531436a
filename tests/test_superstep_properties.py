"""Hypothesis property tests for the closed-form superstep path.

Wider-random twins of the seeded-fuzz checks in tests/test_superstep.py:
superstep == generic per-event scan across arbitrary size/arrival draws —
including exact size ties, coincident arrivals, and arrivals landing
exactly on a departure instant — plus batch closed-form exactness against
Theorem 3 / Theorem 8.  Skipped wholesale when hypothesis is absent (same
convention as tests/test_quantize.py).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine as eng
from repro.core.flowtime import (
    hesrpt_completion_times,
    hesrpt_total_flowtime,
    speedup,
)
from repro.core.policies import make_policy
from repro.core.superstep import batch_result_closed_form, run_superstep

hypothesis = pytest.importorskip(
    "hypothesis", reason="hypothesis not installed (pip install -e '.[dev]')"
)
from hypothesis import given, settings  # noqa: E402
from hypothesis import strategies as st  # noqa: E402


POLICIES = ("hesrpt", "equi", "srpt")


def _generic(x0, arr, p, n, pol):
    rule = eng.continuous_rule(
        make_policy(pol), n_servers=n, dtype=jnp.float64
    )
    return eng.run(x0, arr, p, rule)


def _assert_match(pol, got, want, tol=1e-10):
    got, want = np.asarray(got), np.asarray(want)
    if pol == "srpt":
        got, want = np.sort(got), np.sort(want)
    np.testing.assert_allclose(got, want, rtol=0, atol=tol)


@st.composite
def online_instances(draw):
    """Random online instance with deliberate tie mass.

    Sizes come from a coarse grid half the time (forcing exact remaining-
    size ties) and arrivals are rounded to a 0.25 grid (forcing coincident
    arrivals and arrival-on-departure events).
    """
    m = draw(st.integers(2, 16))
    gridded = draw(st.booleans())
    if gridded:
        xs = draw(st.lists(
            st.sampled_from([0.5, 1.0, 1.0, 2.0, 2.0, 4.0]),
            min_size=m, max_size=m,
        ))
    else:
        xs = draw(st.lists(
            st.floats(1e-2, 1e2, allow_nan=False, allow_infinity=False),
            min_size=m, max_size=m,
        ))
    raw = draw(st.lists(st.floats(0.0, 8.0), min_size=m, max_size=m))
    arr = np.sort(np.round(np.asarray(raw) / 0.25) * 0.25)
    p = draw(st.sampled_from([0.1, 0.3, 0.5, 0.7, 0.9]))
    n = draw(st.sampled_from([1.0, 4.0, 16.0]))
    return np.asarray(xs), arr, p, n


@settings(max_examples=60, deadline=None)
@given(inst=online_instances(), pol=st.sampled_from(POLICIES))
def test_superstep_matches_generic(inst, pol):
    """Superstep == generic scan on arbitrary draws (ties included)."""
    xs, arr, p, n = inst
    x = jnp.asarray(xs, jnp.float64)
    a = jnp.asarray(arr, jnp.float64)
    gen = _generic(x, a, p, n, pol)
    ss = run_superstep(x, a, p, n, pol)
    _assert_match(pol, ss.completion_times, gen.completion_times)


@settings(max_examples=40, deadline=None)
@given(inst=online_instances(), pol=st.sampled_from(POLICIES))
def test_arrival_on_departure_instant(inst, pol):
    """Append one arrival exactly at the first job's solo departure time —
    the superstep must fire the departure at that instant, like the
    generic scan's simultaneous admit+departure events."""
    xs, arr, p, n = inst
    x0 = float(xs[0])
    t_dep = float(arr[0]) + x0 / float(speedup(jnp.asarray(n), p))
    x = jnp.asarray(np.concatenate([xs, [1.0]]), jnp.float64)
    a = jnp.asarray(np.sort(np.concatenate([arr, [t_dep]])), jnp.float64)
    gen = _generic(x, a, p, n, pol)
    ss = run_superstep(x, a, p, n, pol)
    _assert_match(pol, ss.completion_times, gen.completion_times)


@settings(max_examples=60, deadline=None)
@given(
    xs=st.lists(
        st.floats(1e-2, 1e3, allow_nan=False, allow_infinity=False),
        min_size=1, max_size=32,
    ),
    p=st.floats(0.05, 0.95),
    n=st.sampled_from([1.0, 8.0, 64.0]),
)
def test_batch_closed_form_is_thm3(xs, p, n):
    """batch_result_closed_form == Theorem 3 floats, and its sum is the
    Theorem 8 optimal total flow time, in f64."""
    x = jnp.sort(jnp.asarray(xs, jnp.float64))[::-1]
    bc = batch_result_closed_form(x, p, "hesrpt", n_servers=n)
    np.testing.assert_array_equal(
        np.asarray(bc.completion_times),
        np.asarray(hesrpt_completion_times(x, p, n)),
    )
    np.testing.assert_allclose(
        float(jnp.sum(bc.completion_times)),
        float(hesrpt_total_flowtime(x, p, n)),
        rtol=1e-12,
    )
