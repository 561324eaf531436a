"""Compile the main path's Pallas allocate for a described TPU v5e chip.

Nothing runs: the TPU compiler installed with JAX compiles for a chip that is
described, not attached, and refuses what the chip's compiler would refuse
(here: 64-bit types inside the kernel, which interpret mode accepts).  The
topology is described in a module fixture, never at import, so every
pytest-xdist worker collects the same tests and only the worker running this
file loads the TPU library.  The persistent compilation cache is off around
these compiles: a described-chip executable cannot be read back without the
chip.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro.kernels.alloc as alloc
from repro.core.sweeps import Sweep, _build_fn


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        cache_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield desc
        jax.config.update("jax_enable_compilation_cache", cache_on)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("m", [1024, 4096])
def test_alloc_pallas_compiles_under_x64(one_chip, m):
    """f32 sizes under the suite's x64: every value in the kernel is 32-bit."""
    assert jax.config.jax_enable_x64
    x = _shape((m,), jnp.float32, one_chip)
    p = _shape((), jnp.float32, one_chip)
    compiled = jax.jit(
        lambda x, p: alloc._alloc_pallas(x, p, n_chips=256)
    ).lower(x, p).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_sweep_executor_compiles_with_pallas(one_chip, monkeypatch):
    """The vmapped scan of a fused quantized sweep, kernel inside, in the
    chip path's float32.  ``impl="auto"`` would see the CPU backend here and
    take the jnp reference, so the test forces the kernel."""
    monkeypatch.setattr(alloc, "_resolve", lambda impl: "pallas")
    spec = Sweep.create(
        ("hesrpt",), (16.0, 128.0), n_jobs=1000, n_seeds=2, p=0.5,
        n_servers=256.0, n_chips=256, fused=True,
    )
    with jax.enable_x64(False):
        f = _build_fn(spec, "hesrpt", None, False)
        compiled = jax.jit(f).lower(
            _shape((2, 2), jnp.uint32, one_chip),
            _shape((2,), jnp.float32, one_chip),
        ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_alloc_pallas_refuses_float64(one_chip):
    """f64 sizes fail loudly, naming the dtype, instead of reaching a
    kernel the chip cannot compile or quietly taking the reference."""
    x = _shape((1024,), jnp.float64, one_chip)
    p = _shape((), jnp.float64, one_chip)
    with pytest.raises(TypeError, match="float64"):
        jax.jit(
            lambda x, p: alloc._alloc_pallas(x, p, n_chips=256)
        ).lower(x, p)
    with pytest.raises(TypeError, match="float64"):
        jax.jit(
            lambda x: alloc.hesrpt_alloc_fused(x, 0.5, 256, impl="pallas")
        ).lower(x)



def test_training_pod_executor_compiles_with_cap_and_snap_scopes(one_chip):
    """The shared-training-pod sweep executor at its benchmark size (16 lanes
    of 2048 jobs, three classes with width limits, slice snap), in float32:
    the capped allocate and the snap sit under their own name scopes."""
    import re

    from repro.core.multiclass import ClassSpec

    classes = (
        ClassSpec(p=0.3, mix=0.8, size_scale=1.0, min_chips=1, max_chips=8),
        ClassSpec(p=0.6, mix=0.17, size_scale=8.0, min_chips=8, max_chips=64),
        ClassSpec(p=0.9, mix=0.03, size_scale=64.0, min_chips=64, max_chips=256),
    )
    spec = Sweep.create(
        ("hesrpt_pc",), (9.99,), scenario="multiclass_poisson", n_jobs=2048,
        n_seeds=16, n_servers=256.0, n_chips=256, snap_slices=True,
        classes=classes, metrics=("mean_flowtime", "class_flowtime"),
    )
    with jax.enable_x64(False):
        f = _build_fn(spec, "hesrpt_pc", None, False)
        text = jax.jit(f).lower(
            _shape((16, 2), jnp.uint32, one_chip),
            _shape((1,), jnp.float32, one_chip),
        ).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in ("engine.cap", "engine.snap"):
        assert any(re.search(rf"engine\.allocate[/)].*{re.escape(scope)}[/)]", n)
                   for n in names), scope


def test_four_chip_heavy_executor_compiles_sharded_over_seeds(topo, monkeypatch):
    """The four-chip heavy cell's executor at its benchmark size (32 lanes of
    1000 jobs at load 0.9, whole chips, float32), sharded over the seeds on
    the described 2x2 v5e: each chip holds 8 lanes and no collective runs.
    ``run_sweep`` builds its mesh from ``jax.devices()``, the CPU here, so
    the test hands it the described chips."""
    import re

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    monkeypatch.setattr(jax, "devices", lambda *a: list(topo.devices))
    mesh = Mesh(np.asarray(topo.devices), ("seeds",))
    spec = Sweep.create(
        ("hesrpt",), (76.8,), n_jobs=1000, n_seeds=32, p=0.5,
        n_servers=256.0, n_chips=256, min_chips=1,
    )
    with jax.enable_x64(False):
        f = _build_fn(spec, "hesrpt", None, True)
        text = jax.jit(f).lower(
            _shape((32, 2), jnp.uint32, NamedSharding(mesh, P("seeds"))),
            _shape((1,), jnp.float32, NamedSharding(mesh, P())),
        ).compile().as_text()
    assert re.search(r"u32\[8,2\]", text)
    for op in ("all-reduce", "all-gather", "all-to-all", "collective-permute"):
        assert op + "(" not in text, op
