"""Shared test configuration.

- float64 is enabled: the closed-form vs simulator comparisons need it.
- The persistent compilation cache is the one every entry point shares
  (``repro.compile_cache``): the suite compiles hundreds of distinct XLA
  programs, and repeat runs read them back from disk.
- NOTE: we deliberately do NOT set XLA_FLAGS here; the sharding tests that
  need several fake devices spawn subprocesses with their own flags so
  ordinary tests see the real single-CPU device.
- Tests that fail today are listed with their cause in
  ``tests/KNOWN_FAILURES.md``.  They are not marked: they fail visibly.
"""

import jax
import pytest

from repro.compile_cache import enable_compile_cache

jax.config.update("jax_enable_x64", True)
enable_compile_cache()


@pytest.fixture
def pooled_scans(monkeypatch):
    """Record every ``engine.run_stream`` call made while the test runs,
    with its per-job completion times (``record_times=True``): the list of
    ``StreamResult``s a pooled sweep path scanned, so a test can compare
    the pool's trajectory with the full tape's as well as its read-out."""
    from repro.core import engine

    seen = []
    run_stream = engine.run_stream

    def recording(*args, **kw):
        res = run_stream(*args, **{**kw, "record_times": True})
        seen.append(res)
        return res

    monkeypatch.setattr(engine, "run_stream", recording)
    return seen
