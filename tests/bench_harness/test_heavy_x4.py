"""CPU tests of the four-chip heavy cell (``v5e_pod_wholechip.heavy_x4``).

The cell plays the one-chip heavy cell's grid sharded over its seeds.  Its
discovery is checked against the one-chip cell's files, and a tiny copy of
it runs end to end in a subprocess that sees four CPU devices (the device
count is fixed when JAX starts, so the test process cannot change its
own): it must read ``correct: true``, give every lane exactly what one
device gives, and read ``correct: false`` when the lanes come back out of
order, as a shard mix-up would return them.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench import run

ROOT = Path(__file__).resolve().parents[2]
CELL = "v5e_pod_wholechip.heavy_x4"
TINY = {"entry": "sweep", "scenario": "poisson", "loads": [0.9],
        "jobs_per_lane": 40, "seeds_per_call": 8, "trace_seconds": 0.3}

SCRIPT = r"""
import json, sys
from pathlib import Path

import jax
import numpy as np

from bench import run
from bench.spans import Spans
from repro.core import sweeps

root, seed = Path(sys.argv[1]), int(sys.argv[2])
run.devices_for = lambda chips: jax.devices()[:chips]


def line(workload):
    import contextlib, io

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = run.main(["--workload", workload, "--seed", str(seed), "--seconds", "0.3",
                       "--trace", "0"], root=root)
    return rc, json.loads(buf.getvalue().strip().splitlines()[-1])


def lanes(workload):
    cell = run.load_cell(root, workload)
    with jax.enable_x64(False):
        entry = cell.entry.Entry(cell.config, cell.traffic, chips=cell.chips,
                                 seed=seed, spans=Spans())
        entry.setup()
        entry.window(0.1)
    return entry.shard, np.asarray(entry.results[0].stats["hesrpt"]["mean_flowtime"])


out = {"devices": len(jax.devices())}
out["rc"], got = line("v5e_pod_wholechip.tiny_x4")
out["correct"], out["chips"] = got["correct"], got["device"]["count"]
out["metrics"] = sorted(got["metrics"])
shard4, four = lanes("v5e_pod_wholechip.tiny_x4")
shard1, one = lanes("v5e_pod_wholechip.tiny_one")
out["shard"] = [shard4, shard1]
out["bit_equal"] = bool(np.array_equal(four, one))
real = sweeps.run_sweep


def rolled(spec, **kw):
    res = real(spec, **kw)
    for by_m in res.stats.values():
        by_m["mean_flowtime"] = np.roll(by_m["mean_flowtime"], 1, axis=-1)
    return res


sweeps.run_sweep = rolled
out["rolled_rc"], got = line("v5e_pod_wholechip.tiny_x4")
out["rolled_correct"] = got["correct"]
print(json.dumps(out))
"""


def test_the_four_chip_cell_is_found_by_name():
    cell = run.load_cell(ROOT, CELL)
    heavy = run.load_cell(ROOT, "v5e_pod_wholechip.heavy")
    assert cell.chips == 4 and heavy.chips == 1
    assert cell.config == heavy.config
    # The same traffic; only the traced stretch is shorter, as four chips
    # run each call faster and each writes its own device plane.
    assert ({k: v for k, v in cell.traffic.items() if k != "trace_seconds"}
            == {k: v for k, v in heavy.traffic.items() if k != "trace_seconds"})
    assert cell.traffic["trace_seconds"] < heavy.traffic["trace_seconds"]
    assert cell.limits == heavy.limits == {"mean_flow_rel": 0.005}
    assert {m["name"] for m in cell.e2e} == {"jobs_per_s", "setup_s"}
    assert ({m["name"] for m, _ in cell.per_layer}
            == {m["name"] for m, _ in heavy.per_layer})
    # 32 seeds split evenly over the four chips.
    assert cell.traffic["seeds_per_call"] % cell.chips == 0


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A checkout whose BENCHMARK.json gains the four-chip cell at a tiny
    size and the same traffic on one chip."""
    root = tmp_path_factory.mktemp("checkout")
    for d in ("traffic", "limits"):
        (root / "bench" / d).mkdir(parents=True)
    for d in ("entries", "metrics", "reference", "configs"):
        os.symlink(ROOT / "bench" / d, root / "bench" / d)
    os.symlink(ROOT / "src", root / "src")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (root / "bench" / "traffic" / "tiny_x4.json").write_text(json.dumps(TINY))
    for name, chips in (("v5e_pod_wholechip.tiny_x4", 4), ("v5e_pod_wholechip.tiny_one", 1)):
        (root / "bench" / "limits" / f"{name}.json").write_text(
            (ROOT / "bench" / "limits" / f"{CELL}.json").read_text())
        bench["workloads"].append({"name": name, "config": "v5e_pod_wholechip",
                                   "traffic": "tiny_x4", "chips": chips, "why": "tiny"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if CELL in m.get("workloads", []):
                m["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_tiny_four_chip_cell_is_sharded_exact_and_checked(tiny_root):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]))
    proc = subprocess.run([sys.executable, "-c", SCRIPT, str(tiny_root), str(2**40 + 7)],
                          env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["devices"] == 4 and out["chips"] == 4
    assert out["rc"] == 0 and out["correct"] is True, out
    assert out["metrics"] == ["jobs_per_s", "setup_s"]
    assert out["shard"] == [True, False]
    assert out["bit_equal"] is True
    assert out["rolled_rc"] == 0 and out["rolled_correct"] is False
