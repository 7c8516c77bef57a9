"""The four-chip population cell's path on four virtual CPU devices (in
a child process, which sets the device count before JAX starts): a
sound run is correct, and a run whose uplink exchange between chips is
left out is not."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]

CHILD = textwrap.dedent("""
    import json, sys, time
    sys.path[:0] = [{root!r}, {src!r}, {here!r}]
    import jax
    import numpy as np
    from benchtiny import population_metrics, tiny_root
    from pathlib import Path
    root = tiny_root({tmp!r})
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["workloads"].append({{"name": "tiny_pop_x4", "config": "tiny_vq",
                           "traffic": "tiny_population", "chips": 4,
                           "why": "tiny"}})
    for m in population_metrics(b):
        m["workloads"].append("tiny_pop_x4")
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    (root / "bench/limits/tiny_pop_x4.json").write_text(json.dumps(
        {{"code_gap_mean": 1e-3, "merge_norm_gap": 1e-3}}))
    import repro.launch.compile_cache as cc
    cc.enable_compile_cache = lambda: ""
    if {fault!r}:
        from bench.tools.faults import exchange_left_out
        exchange_left_out(setattr)
    from bench.harness.runner import run
    out = run("tiny_pop_x4", 5, 0.3, False, started=time.perf_counter(),
              root=root, devs=jax.devices()[:4])
    print(json.dumps(out))
""")


@pytest.mark.parametrize("fault,correct", [(False, True), (True, False)])
def test_four_device_population(tmp_path, fault, correct):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = CHILD.format(root=str(ROOT), src=str(ROOT / "src"),
                        here=str(HERE), tmp=str(tmp_path / "r"), fault=fault)
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["device"]["count"] == 4
    assert out["correct"] is correct
