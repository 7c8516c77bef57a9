"""A copy of the benchmark at a size the CPU runs in seconds.

``tiny_root(tmp)`` copies ``bench/`` under ``tmp`` and adds, as files of
their own, a tiny configuration, tiny traffic mixes, their limits and a
``BENCHMARK.json`` naming cells over them. It edits no file of the copy:
a new cell is files and entries, nothing else.
"""
from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent

TINY_MODEL = {"kind": "image", "in_channels": 3, "hidden": 8,
              "n_res_blocks": 1, "latent_dim": 8, "codebook_size": 16,
              "n_groups": 1, "n_slices": 1, "apply_in": True,
              "encoder_in": True, "alpha": 1.0, "beta": 0.25, "lam": 0.01}
TINY_GSVQ = dict(TINY_MODEL, n_groups=4, n_slices=2)
INGEST_METRICS = [("offer_host_ms", "ms"),
                  ("decode_records_per_dispatch", "records"),
                  ("decode_roofline", "%"), ("ingest_mfu", "%"),
                  ("device_idle.ingest", "%")]


def _write(path: Path, obj) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(obj, indent=2) + "\n")


def population_metrics(b: dict) -> list:
    """The metrics of ``b`` that population cells report, by what they
    move, whatever the population cells are named."""
    pop = {"clients_per_s", "uplink_bytes_per_sample"}
    return [m for m in b["end_to_end"] + b["per_layer"]
            if m["name"] in pop or m.get("moves") in pop]


def tiny_root(tmp) -> Path:
    tmp = Path(tmp)
    bench = tmp / "bench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    for name, model in (("tiny_vq", TINY_MODEL), ("tiny_gsvq", TINY_GSVQ)):
        _write(bench / "configs" / f"{name}.json", {
            "name": name, "reference": "dvqae", "model": model,
            "input": {"image": 16, "channels": 3},
            "client": {"lr": 1e-4, "gamma": 0.99}, "samples_per_client": 4,
            "population": {"clients": 50}})
    _write(bench / "traffic" / "tiny_population.json", {
        "driver": "population", "participants": 8, "cohort": 4,
        "pool_clients": 8, "identities": 3,
        "check_block": 4})
    _write(bench / "traffic" / "tiny_ingest.json", {
        "driver": "ingest", "rate": 40.0, "payload_pool": 4,
        "identities": 3, "zipf_s": 1.1, "n_shards": 2,
        "decode_policy": [1, 3, 1], "check_records": 4, "drain_s": 1.0})
    for cell in ("tiny_pop", "tiny_gsvq_pop"):
        _write(bench / "limits" / f"{cell}.json",
               {"code_gap_mean": 1e-5, "merge_norm_gap": 1e-5})
    _write(bench / "limits" / "tiny_ingest.json", {"decode_max_abs_err": 0})
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    b["configs"] += [
        {"name": n, "source": "test", "file": f"bench/configs/{n}.json",
         "reduced": [], "why": "tiny"} for n in ("tiny_vq", "tiny_gsvq")]
    b["workloads"] += [
        {"name": "tiny_pop", "config": "tiny_vq",
         "traffic": "tiny_population", "chips": 1, "why": "tiny"},
        {"name": "tiny_gsvq_pop", "config": "tiny_gsvq",
         "traffic": "tiny_population", "chips": 1, "why": "tiny"},
        {"name": "tiny_ingest", "config": "tiny_vq",
         "traffic": "tiny_ingest", "chips": 1, "why": "tiny"}]
    for m in population_metrics(b):
        m["workloads"] += ["tiny_pop", "tiny_gsvq_pop"]
    # the ingest driver has no cell on the chip yet: its tiny cell brings
    # the end-to-end and per-layer entries that cell would have
    b["end_to_end"].append(
        {"name": "offer_to_decoded_p95_ms", "unit": "ms", "better": "lower",
         "bound": 0.25, "source": "host_clock", "workloads": ["tiny_ingest"]})
    b["per_layer"] += [
        {"name": n, "unit": u, "better": "higher", "source": "device_trace",
         "layer": "ingest", "moves": "offer_to_decoded_p95_ms",
         "workloads": ["tiny_ingest"]} for n, u in INGEST_METRICS]
    _write(tmp / "BENCHMARK.json", b)
    return tmp


def run_tiny(tmp, cell: str, seed: int = 3, seconds: float = 0.5):
    """One run of a tiny cell on the CPU, the chip check skipped."""
    import time
    import jax
    from bench.harness.runner import run
    root = tiny_root(tmp)
    return run(cell, seed, seconds, False, started=time.perf_counter(),
               root=root, devs=jax.devices()[:1])
