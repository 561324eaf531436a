"""Readings that the limits in ``bench/limits/`` are set from.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 3

For each seed, in one process on the chip: set the cell up, run a short
window at the cell's own size, and print one JSON line with the numbers
``check`` compares, twice: for the program (``program``) and for the
control (``control``), the float64 reference computed in bfloat16 and put
in the program's place.  A limit lies above every sound reading of the
program and below every reading of the control.  The benchmark's own runs
never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    here = str(Path(__file__).resolve().parent)
    sys.path[:] = [str(ROOT), str(ROOT / "src")] + [p for p in sys.path if p != here]
    from bench import run
    from bench.spans import Spans

    try:
        cell = run.load_cell(ROOT, args.workload)
        run.devices_for(cell.chips)
    except run.Refused as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    import jax

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    with jax.enable_x64(False):
        for seed in (int(s) for s in args.seeds.split(",")):
            t0 = time.perf_counter()
            entry = cell.entry.Entry(cell.config, cell.traffic, chips=cell.chips,
                                     seed=seed, spans=Spans())
            entry.setup()
            out = entry.window(args.seconds)
            t1 = time.perf_counter()
            program = entry.check()
            t2 = time.perf_counter()
            control = entry.check(prec="bfloat16")
            print(json.dumps({"workload": cell.name, "seed": seed,
                              "program": program, "control": control,
                              "failed": out["failed"], "attempted": out["attempted"],
                              "window_s": t1 - t0, "check_s": t2 - t1}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
