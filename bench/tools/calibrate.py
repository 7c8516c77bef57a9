#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/tools/calibrate.py --workload pop_gsvq_ft \
        --seeds 101 102 ... --control-seeds 201 202 203 \
        [--fault-seeds 301 302 303 --faults half_the_cohort ...] \
        [--highest-seeds 401]

For each seed, in one process: the cell's set-up, a short window at the
cell's own load, then every number the comparison can read, for the
program's answers; for each control seed the same numbers for the
control's answers (the reference in the program's place, at the
precision below the configuration's); for each fault seed and fault the
program's answers with that fault planted (``faults.py``); for each
highest seed the program's answers with every matmul at
``Precision.HIGHEST``, to tell its rounding from other departures. One
JSON line per reading, with the exact checks' numbers among them.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--fault-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--faults", nargs="*", default=[])
    ap.add_argument("--highest-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--numerics", nargs="*", default=["control"],
                    help="what the control seeds put in the program's "
                         "place (a name of the reference's NUMERICS)")
    args = ap.parse_args(argv)
    import contextlib
    import gc
    from bench.harness.loader import load_cell
    from bench.harness.runner import devices_for
    from bench.tools.faults import FAULTS, Patcher
    from repro.launch.compile_cache import enable_compile_cache
    import jax
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = load_cell(args.workload, ROOT)
    devices_for(cell.chips)
    limits = json.loads((ROOT / "bench" / "limits" / f"{cell.name}.json")
                        .read_text())
    runs = [(s, "program") for s in args.seeds] + \
        [(s, n) for s in args.control_seeds for n in args.numerics] + \
        [(s, "fault:" + f) for s in args.fault_seeds for f in args.faults] + \
        [(s, "program_highest") for s in args.highest_seeds]
    for seed, kind in runs:
        t0 = time.perf_counter()
        with contextlib.ExitStack() as stack:
            if kind.startswith("fault:"):
                FAULTS[kind[6:]](stack.enter_context(Patcher()))
            if kind == "program_highest":
                stack.enter_context(jax.default_matmul_precision("highest"))
            drv = cell.driver.Driver(cell, seed, limits)
            drv.setup()
            drv.window(args.seconds, lambda _: contextlib.nullcontext())
            drv.release()
        t1 = time.perf_counter()
        ans = drv.control_answers(kind) if kind in cell.reference.NUMERICS \
            else drv.answers()
        out = {"workload": cell.name, "seed": seed, "kind": kind}
        readings = drv.readings(ans)
        out.update(readings)
        out.update({c.name: c.value for c in drv.compare(readings)})
        out["run_s"] = t1 - t0
        out["check_s"] = time.perf_counter() - t1
        print(json.dumps(out), flush=True)
        del drv, ans
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
