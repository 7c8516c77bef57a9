#!/usr/bin/env python3
"""One traced run of a cell, with its idle time split by program span.

    python3 bench/tools/spans.py --workload pop_gsvq_ft --seed <n> \
        --seconds 20

Runs the cell exactly as ``bench/run.py --trace 1`` does and prints its
result line. The trace the per-layer readers see is also reduced by
``bench/harness/spans.py``; a second JSON line gives, for each
``octopus/`` span that starts in the window, its count, median and total
time, then the device's idle seconds charged to the innermost span of
either prefix, the share of them charged to ``octopus/`` spans, and the
names of every ``octopus/`` host event the trace holds.
"""
import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def run_with_spans(name: str, seed: int, seconds: float, *,
                   started: float, root: Path = ROOT, devs=None):
    """One traced run of cell ``name``: its result line's object, and
    the program spans of the same trace as a dict."""
    import jax
    from bench.harness import spans, trace
    from bench.harness.runner import run
    read = trace.from_xplane
    seen = []

    def read_both(directory):
        """The runner's read of the trace, and the program spans of the
        same file while it is still there."""
        tr = spans.from_xplane(directory)
        device = f"{trace.DEVICE_PREFIX}{(devs or jax.devices())[0].id}"
        seen.append((spans.reduce(tr, device), sorted(
            {e.name for e in tr.host
             if e.name.startswith(spans.PROGRAM_PREFIX)})))
        return read(directory)

    trace.from_xplane = read_both
    try:
        out = run(name, seed, seconds, True, started=started, root=root,
                  devs=devs)
    finally:
        trace.from_xplane = read
    red, names = seen[0]
    return out, {
        "workload": name, "seed": seed,
        "window_s": red.window_s, "idle_s": red.idle_s,
        "spans": {n: {"n": len(d), "median_ms": red.median_ms(n),
                      "total_s": sum(d)}
                  for n, d in sorted(red.durations.items())},
        "idle_gaps": red.idle_gaps,
        "program_idle_share": red.program_idle_share,
        "octopus_host_events": names}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    out, split = run_with_spans(args.workload, args.seed, args.seconds,
                                started=STARTED)
    print(json.dumps(out), flush=True)
    print(json.dumps(split), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
