"""CPU tests of the ``engine.slots_per_lane`` readers (``bench/carried.py``):
the per-lane width of the job state an executor's event scan carries, read
from its compiled text.  The readers read only from a run that captured a
device trace; a stand-in trace lets them read here."""

from __future__ import annotations

import importlib.util
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from bench import carried

ROOT = Path(__file__).resolve().parents[2]
READERS = ("engine.slots_per_lane", "engine.slots_per_lane.batch")


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"), ROOT / "bench" / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _ctx(texts, trace=True):
    entry = SimpleNamespace(executors=lambda: [(t, 2, 0) for t in texts])
    return SimpleNamespace(trace=object() if trace else None, entry=entry)


def _executor_text(spec, name, slots=None):
    from repro.core import sweeps

    keys = jax.random.split(jax.random.PRNGKey(0), spec.n_seeds)
    with jax.enable_x64(False):
        f = sweeps._build_fn(spec, name, None, False, slots=slots)
        return jax.jit(f).lower(keys, jnp.asarray(spec.rates, jnp.float32)
                               ).compile().as_text()


@pytest.fixture(scope="module")
def heavy_like():
    """The heavy cell's program at its widths (256 chips, 1000-job tapes),
    two lanes: the full tape's executor and the pooled one."""
    from repro.core import sweeps

    spec = sweeps.Sweep.create(("hesrpt",), (76.8,), n_jobs=1000, n_seeds=2, p=0.5,
                               n_servers=256.0, n_chips=256)
    slots = sweeps._pool_slots(spec)
    assert slots == 256
    return _executor_text(spec, "hesrpt"), _executor_text(spec, "hesrpt", slots)


@pytest.mark.parametrize("name", READERS)
def test_full_tape_and_pool_widths_of_a_heavy_like_executor(heavy_like, name):
    full, pooled = heavy_like
    assert _reader(name)(_ctx([full])) == 1000
    assert _reader(name)(_ctx([pooled])) == 256
    # The tape a pool admits from rides in the loop too, read and not written.
    assert "[2,1000]" in pooled


def test_width_of_a_batch_executor():
    from repro.core import sweeps

    spec = sweeps.Sweep.create(("hesrpt",), (1.0,), scenario="batch", n_jobs=500,
                               n_seeds=2, p=0.5, n_servers=1e6)
    assert sweeps._pool_slots(spec) is None
    text = _executor_text(spec, "hesrpt")
    assert _reader("engine.slots_per_lane.batch")(_ctx([text, text])) == 500


@pytest.mark.parametrize("name", READERS)
def test_nothing_found_reads_none(name):
    with jax.enable_x64(False):
        loopless = jax.jit(lambda x: jnp.sort(x) * 2).lower(
            jnp.arange(8.0)).compile().as_text()
    assert carried.scan_width(loopless) is None
    assert _reader(name)(_ctx([loopless])) is None
    assert _reader(name)(_ctx([])) is None
    assert _reader(name)(_ctx([loopless], trace=False)) is None
