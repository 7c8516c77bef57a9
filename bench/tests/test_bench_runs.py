"""Whole runs of tiny cells on the CPU, the chip check skipped: the last
line's keys, the control coming out not correct, and the timed path
broken underneath in each way a cell can break, each read as not
correct."""
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
from benchtiny import ROOT, tiny_root  # noqa: E402

sys.path[:0] = [str(ROOT), str(ROOT / "src")]

KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


@pytest.fixture
def runner(monkeypatch, tmp_path):
    """``run(cell, seconds)`` on a tiny copy of the benchmark, with the
    process's compile cache left as it was."""
    import time
    from bench.harness import runner as R
    monkeypatch.setattr("repro.launch.compile_cache.enable_compile_cache",
                        lambda: "")
    before = jax.config.jax_persistent_cache_min_compile_time_secs
    root = tiny_root(tmp_path)

    def run(cell, seconds=0.5, seed=3):
        return R.run(cell, seed, seconds, False, started=time.perf_counter(),
                     root=root, devs=jax.devices()[:1])
    yield run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", before)


@pytest.mark.parametrize("cell,e2e", [
    ("tiny_pop", {"clients_per_s", "uplink_bytes_per_sample", "setup_s"}),
    ("tiny_ingest", {"offer_to_decoded_p95_ms", "setup_s"})])
def test_last_line(runner, cell, e2e):
    out = runner(cell)
    line = json.loads(json.dumps(out))
    assert list(line) == KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == e2e
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert all(set(c) == {"value", "limit"} for c in line["checks"].values())


def test_traced_line_puts_breakdown_before_checks():
    from bench.harness.checks import Compared
    from bench.harness.runner import result_line
    line = result_line(True, 1, 0, {}, {}, [Compared("x", 0.0, 0)],
                       {"device_ops": [], "idle_gaps": []})
    assert list(line) == KEYS[:5] + ["breakdown", "checks"]


def test_population_uplink_bytes_are_exact(runner):
    out = runner("tiny_pop")
    # 16x16 images -> 16 positions of 4-bit codes (K=16): 8 B per image,
    # and each client's 4 images fill whole 32-bit words (no pad)
    assert out["metrics"]["uplink_bytes_per_sample"]["value"] == 8.0


def _control(monkeypatch, cell, tmp_path):
    """Run ``cell`` with the driver's control answers in the program's."""
    import time
    from bench.harness import loader
    monkeypatch.setattr("repro.launch.compile_cache.enable_compile_cache",
                        lambda: "")
    root = tiny_root(tmp_path)
    c = loader.load_cell(cell, root)
    cls = c.driver.Driver
    monkeypatch.setattr(cls, "answers", lambda self: self.control_answers())
    from bench.harness.runner import run
    return run(cell, 4, 0.5, False, started=time.perf_counter(), root=root,
               devs=jax.devices()[:1])


@pytest.mark.parametrize("cell", ["tiny_pop", "tiny_gsvq_pop",
                                  "tiny_ingest"])
def test_control_is_not_correct(monkeypatch, tmp_path, cell):
    before = jax.config.jax_persistent_cache_min_compile_time_secs
    try:
        out = _control(monkeypatch, cell, tmp_path)
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          before)
    assert out["correct"] is False


# ------------------------------------------------------------ faults

@pytest.mark.parametrize("cell,fault", [
    ("tiny_pop", "merge_unchanged"), ("tiny_pop", "finetune_unchanged"),
    ("tiny_pop", "half_the_cohort"), ("tiny_pop", "code_altered"),
    ("tiny_gsvq_pop", "merge_unchanged"),
    ("tiny_gsvq_pop", "finetune_unchanged"),
    ("tiny_gsvq_pop", "half_the_cohort"), ("tiny_gsvq_pop", "code_altered"),
    ("tiny_ingest", "decode_altered"), ("tiny_ingest", "half_decoded"),
    ("tiny_ingest", "tick_unchanged")])
def test_a_broken_timed_path_is_not_correct(runner, monkeypatch, cell,
                                            fault):
    from bench.tools.faults import FAULTS
    FAULTS[fault](monkeypatch.setattr)
    out = runner(cell, seconds=0.3)
    assert out["correct"] is False
