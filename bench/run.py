"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``.  Everything
else is found by name:

- ``bench/configs/<config>.json``: the cluster (the entry ``configs`` names
  the file);
- ``bench/traffic/<traffic>.json``: the job streams, and ``entry``: which
  served path plays them;
- ``bench/entries/<entry>.py``: that path, a class ``Entry`` with
  ``setup()``, ``window(seconds)`` and ``check(prec=None)``;
- ``bench/limits/<cell>.json``: the limit of each number ``check`` returns;
- ``bench/metrics/<metric>.py``: one reader ``read(ctx)`` per per-layer
  metric, which returns ``None`` when it finds nothing to read.

A run sets up (compiles or loads every program and runs each once: that
is ``setup_s``), measures for ``--seconds`` (``--trace 0``) or traces a
shorter stretch (``--trace 1``, length ``trace_seconds`` of the traffic),
reads the peak device memory, and then checks what the window produced
against the float64 reference in ``bench/reference``.  The last line of
standard output is one JSON object; the numbers checked, each with its
limit, are the last lines of standard error and the last key of that
object.  With no TPU, or another number of chips than the cell asks for,
it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


class Refused(Exception):
    """The run cannot measure this cell here; nothing is printed to stdout."""


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    entry: object  # the entry module
    per_layer: list  # (metric entry, reader module) pairs this cell reports
    e2e: list  # end_to_end entries this cell reports


def _module(path: Path, name: str):
    if not path.is_file():
        raise Refused(f"missing {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _json(path: Path) -> dict:
    if not path.is_file():
        raise Refused(f"missing {path}")
    return json.loads(path.read_text())


def load_cell(root: Path, name: str) -> Cell:
    """Find every part of cell ``name`` under ``root`` by its name."""
    bench = _json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json; "
                      f"known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _json(root / configs[w["config"]]["file"])
    traffic = _json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    entry = _module(root / "bench" / "entries" / f"{traffic['entry']}.py",
                    f"bench_entry_{traffic['entry']}")
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])
           and (m["name"] == "setup_s" or m["name"] in entry.Entry.e2e)]
    reported = {m["name"] for m in e2e}
    per_layer = [
        (m, _module(root / "bench" / "metrics" / f"{m['name']}.py",
                    "bench_metric_" + m["name"].replace(".", "_")))
        for m in bench["per_layer"]
        if (name in m["workloads"] if "workloads" in m else m["moves"] in reported)
    ]
    return Cell(name=name, chips=int(w["chips"]), config=config, traffic=traffic,
                limits=_json(root / "bench" / "limits" / f"{name}.json"),
                entry=entry, per_layer=per_layer, e2e=e2e)


def devices_for(chips: int):
    """The chips of this run; refuses anything but exactly ``chips`` TPUs."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"no TPU: JAX sees {devs[0].platform}; this benchmark "
                      "measures the chip only")
    if len(devs) != chips:
        raise Refused(f"the cell asks for {chips} chip(s), JAX sees {len(devs)}")
    return devs


def _peak_bytes(devs) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def measure(cell: Cell, args, devs, root: Path) -> dict:
    """Set up, measure or trace, check; return the result object."""
    from bench import devtrace
    from bench.spans import Spans

    spans = Spans(traced=bool(args.trace))
    entry = cell.entry.Entry(cell.config, cell.traffic, chips=cell.chips,
                             seed=args.seed, spans=spans)
    entry.setup()
    setup_s = time.perf_counter() - T0
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    breakdown = None
    if args.trace:
        seconds = min(args.seconds, float(cell.traffic["trace_seconds"]))
        with devtrace.capture(str(root / ".bench_trace" / cell.name)) as cap:
            with spans(devtrace.WINDOW_SPAN):
                out = entry.window(seconds)
        labels = entry.op_labels() if hasattr(entry, "op_labels") else {}
        red = devtrace.reduce(cap.trace, known_spans=(devtrace.WINDOW_SPAN,)
                              + tuple(entry.host_spans), labels=labels)
        ctx = SimpleNamespace(trace=red, spans=spans, entry=entry, cell=cell)
        metrics = {}
        for m, reader in cell.per_layer:
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if red is not None:
            device.update(busy_s=red.busy_s, window_s=red.window_s)
            breakdown = {"device_ops": [list(kv) for kv in red.device_ops],
                         "idle_gaps": [list(kv) for kv in red.idle_gaps]}
    else:
        with spans(devtrace.WINDOW_SPAN):
            out = entry.window(args.seconds)
        values = dict(out["metrics"], setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.e2e}
    device["memory_peak_bytes"] = _peak_bytes(devs)
    got = entry.check()
    checks = {}
    for key, value in got.items():
        if key not in cell.limits:
            raise Refused(f"bench/limits/{cell.name}.json has no limit for {key!r}")
        checks[key] = {"value": value, "limit": cell.limits[key]}
    correct = out["failed"] == 0 and all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values())
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, root: Path = ROOT) -> int:
    args = parse(argv)
    try:
        if not (root / "src" / "repro").is_dir():
            raise Refused(f"no program beside the benchmark: {root}/src/repro "
                          "is missing")
        cell = load_cell(root, args.workload)
        devs = devices_for(cell.chips)
        import jax

        from repro.compile_cache import enable_compile_cache

        enable_compile_cache()
        # The chip runs float32 (the TPU has no native float64); the
        # reference runs float64 in NumPy.
        with jax.enable_x64(False):
            result = measure(cell, args, devs, root)
    except Refused as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for key, c in result["checks"].items():
        print(f"check {key} = {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # Import the benchmark as the package ``bench`` and the program from
    # this checkout's ``src``, not this directory's modules by bare name.
    here = str(Path(__file__).resolve().parent)
    sys.path[:] = [str(ROOT), str(ROOT / "src")] + [p for p in sys.path if p != here]
    sys.exit(main())
