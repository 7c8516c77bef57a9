"""One run of one cell: set-up, the measured window, the comparison with
the reference, and the result line.

    setup_s   process start -> end of set-up (weights, data, warm-up of
              every shape the window uses; compilation when the cache is
              cold)
    window    ``--seconds`` of the cell's traffic; with ``--trace 1``
              under the profiler, and then the per-layer metrics instead
              of the end-to-end ones
    check     after the window, once the device's peak memory has been
              read and the program's state dropped: the numbers that
              decide ``correct``, each beside its limit

The last lines on standard error are the compared numbers, one per line;
the last line on standard output is the result, whose last key,
``checks``, repeats them.
"""
from __future__ import annotations

import contextlib
import gc
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace

from . import trace as trace_mod
from . import work
from .checks import CompileClock
from .loader import ROOT, load_cell


class NoDevice(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


def devices_for(chips: int):
    import jax
    if jax.default_backend() != "tpu":
        raise NoDevice(f"needs a TPU, JAX found {jax.default_backend()!r}")
    devs = jax.devices()
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def peak_for(bench_dir: Path, kind: str) -> dict:
    peaks = json.loads((bench_dir / "peaks.json").read_text())
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json "
                       f"(have {sorted(peaks)})")
    return peaks[kind]


def memory_peak(devs) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


def result_line(correct, attempted, failed, metrics, device, checks,
                breakdown=None) -> dict:
    """The contract's last line, ``checks`` last."""
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in checks}
    return out


def run(name: str, seed: int, seconds: float, traced: bool, *,
        started: float, root: Path = ROOT, bench_dir: Path = None,
        devs=None, err=sys.stderr) -> dict:
    """Run cell ``name`` once; returns the result line's object.

    ``devs``: the devices to run on; by default the cell's chips of a
    TPU, and ``NoDevice`` without one."""
    import jax
    cell = load_cell(name, root, bench_dir)
    limits = json.loads((cell.bench_dir / "limits" / f"{name}.json")
                        .read_text())
    if devs is None:
        devs = devices_for(cell.chips)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    # every program, however quick to build, goes to the persistent cache,
    # so that only a cell's first run in a checkout compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    clock = CompileClock()
    tmp = None
    try:
        drv = cell.driver.Driver(cell, seed, limits)
        drv.setup()
        setup_s = time.perf_counter() - started
        before = clock.programs
        tmp = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
        span = jax.profiler.TraceAnnotation if traced else \
            (lambda _: contextlib.nullcontext())
        if traced:
            jax.profiler.start_trace(tmp)
        with span(trace_mod.WINDOW_SPAN):
            counts = drv.window(seconds, span)
        if traced:
            jax.profiler.stop_trace()
        in_window = clock.programs - before
        print(f"programs compiled or loaded in the window: {in_window}",
              file=err)
        peak_bytes = memory_peak(devs)
        e2e = drv.end_to_end()
        observed = drv.observed()
        drv.release()
        gc.collect()
        readings = drv.readings(drv.answers())
        print(f"readings {json.dumps(readings)}", file=err)
        checks = drv.compare(readings)

        d0 = devs[0]
        device = {"platform": d0.platform, "kind": d0.device_kind,
                  "count": len(devs), "memory_peak_bytes": peak_bytes}
        breakdown = None
        if traced:
            metrics, reduced = per_layer(cell, tmp, devs, observed, e2e)
            device["busy_s"] = reduced.busy_s
            device["window_s"] = reduced.window_s
            breakdown = {"device_ops": [list(x) for x in reduced.device_ops],
                         "idle_gaps": [list(x) for x in reduced.idle_gaps]}
        else:
            values = dict(e2e, setup_s=setup_s)
            metrics = {m["name"]: {"value": values[m["name"]],
                                   "unit": m["unit"]}
                       for m in cell.end_to_end}
    finally:
        clock.close()
        if tmp:
            shutil.rmtree(tmp, ignore_errors=True)
    for c in checks:
        print(f"check {c.name} {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAILED'}", file=err)
    return result_line(all(c.ok for c in checks), counts["attempted"],
                       counts["failed"], metrics, device, checks, breakdown)


def per_layer(cell, tmp, devs, observed, e2e):
    """Each per-layer reader, over the trace's reduction."""
    kernels = sorted({k for r in cell.readers.values()
                      for k in getattr(r, "KERNELS", ())})
    tr = trace_mod.from_xplane(tmp)
    planes = [f"{trace_mod.DEVICE_PREFIX}{d.id}" for d in devs]
    reduced = trace_mod.reduce(tr, planes, kernels)
    ctx = SimpleNamespace(
        trace=reduced, obs=observed, e2e=e2e, model=cell.config["model"],
        chips=len(devs), work=work,
        peak=peak_for(cell.bench_dir, devs[0].device_kind))
    metrics = {}
    for m in cell.per_layer:
        value = cell.readers[m["name"]].read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics, reduced
