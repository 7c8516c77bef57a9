#!/usr/bin/env python3
"""Sweep the offered rate of an open-loop cell, once, to find its knee.

    python3 bench/tools/knee.py --workload ingest_vq_open --seed 7 \
        --seconds 8 --rates 100 200 400 800

One process, one set-up; for each rate a fresh service and a window of
``--seconds``. Prints per rate: the p50/p95/p99 offer-to-decoded
latency, the median latency of the first and the last quarter of the
offers (a backlog that grows shows as a last quarter far above the
first), refusals, the run's length past the window (the drain), and
how many full (generation 2) garbage collections ran in the window and
the longest of them, since a pause of the host stalls every offer.
"""
import argparse
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    import contextlib
    import jax
    from bench.harness.loader import load_cell
    from bench.harness.runner import devices_for
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = load_cell(args.workload, ROOT)
    devices_for(cell.chips)
    drv = cell.driver.Driver(cell, args.seed, {})
    drv.setup()
    pauses = []
    started = {}

    def timed(phase, info):
        if info["generation"] != 2:
            return
        if phase == "start":
            started["t"] = time.perf_counter()
        elif "t" in started:
            pauses.append(time.perf_counter() - started.pop("t"))
    gc.callbacks.append(timed)
    for rate in args.rates:
        pauses.clear()
        drv.mix = dict(drv.mix, rate=rate)
        drv.service = drv.make_service()
        drv.window(args.seconds, lambda _: contextlib.nullcontext())
        lat = drv.latency * 1e3
        q = max(1, len(lat) // 4)
        print(json.dumps({
            "rate": rate, "offers": int(len(lat)),
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": float(np.percentile(lat, 95)),
            "p99_ms": float(np.percentile(lat, 99)),
            "first_quarter_p50_ms": float(np.median(lat[:q])),
            "last_quarter_p50_ms": float(np.median(lat[-q:])),
            "refused": int(drv.refused), "undecoded": int(drv.undecoded),
            "overrun_s": drv.elapsed - args.seconds,
            "offer_ms_p50": float(np.median(drv.offer_s)) * 1e3,
            "gc_full": len(pauses),
            "gc_full_max_ms": max(pauses, default=0.0) * 1e3}),
            flush=True)
    gc.callbacks.remove(timed)
    return 0


if __name__ == "__main__":
    sys.exit(main())
