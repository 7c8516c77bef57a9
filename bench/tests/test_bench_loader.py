"""Every file BENCHMARK.json names loads by name; unknown names fail; a
new cell, mix and metric come from new files alone."""
import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
from benchtiny import ROOT, tiny_root  # noqa: E402

sys.path[:0] = [str(ROOT), str(ROOT / "src")]
from bench.harness import loader  # noqa: E402

BENCH = loader.load_benchmark(ROOT)


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_loads(cell):
    c = loader.load_cell(cell, ROOT)
    assert c.name == cell and c.chips in (1, 4)
    assert {m["name"] for m in c.end_to_end} >= {"setup_s"}
    assert len(c.end_to_end) >= 2 and c.per_layer
    for m in c.per_layer:
        assert callable(c.readers[m["name"]].read)
    assert callable(c.driver.Driver)
    assert (ROOT / "bench" / "limits" / f"{cell}.json").is_file()


def test_names_and_keys_follow_the_rules():
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    names += [c["name"] for c in BENCH["configs"]]
    names += [w["name"] for w in BENCH["workloads"]]
    assert len(names) == len(set(names))
    assert all(loader.NAME.match(n) for n in names)
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
    for c in BENCH["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]


@pytest.mark.parametrize("kind,mutate", [
    ("workload", lambda b: None),
    ("config", lambda b: b["workloads"][0].update(config="nope")),
    ("traffic", lambda b: b["workloads"][0].update(traffic="nope")),
    ("metric", lambda b: b["per_layer"][0].update(name="nope")),
])
def test_unknown_name_is_an_error(tmp_path, kind, mutate):
    root = tiny_root(tmp_path)
    b = json.loads((root / "BENCHMARK.json").read_text())
    mutate(b)
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    cell = "no_such_cell" if kind == "workload" else b["workloads"][0]["name"]
    with pytest.raises(loader.LoadError):
        loader.load_cell(cell, root)


def test_new_cell_mix_and_metric_need_only_new_files(tmp_path):
    root = tiny_root(tmp_path)
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file() and "tiny" not in p.name}
    (root / "bench" / "metrics" / "throwaway_count.py").write_text(
        "def read(ctx):\n    return 1.0\n")
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["per_layer"].append({
        "name": "throwaway_count", "unit": "records", "better": "higher",
        "source": "program_counter", "layer": "test", "moves": "setup_s",
        "workloads": ["tiny_pop"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    cell = loader.load_cell("tiny_pop", root)
    assert cell.mix["driver"] == "population"
    assert cell.config["name"] == "tiny_vq"
    assert cell.readers["throwaway_count"].read(None) == 1.0
    after = {p: p.read_bytes() for p in before}
    assert after == before
