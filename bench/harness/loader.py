"""Find everything a cell needs by the names in ``BENCHMARK.json``.

Each configuration, traffic mix, traffic driver and per-layer metric is
a file of its own, found by name:

    bench/configs/<config>.json     sizes of one configuration
    bench/configs/<reference>.py    its plain reference (named in the file)
    bench/traffic/<mix>.json        parameters of one traffic mix
    bench/drivers/<driver>.py       the generator a mix names
    bench/metrics/<metric>.py       the reader of one per-layer metric

so a later change adds a cell, a mix or a metric by adding files and
entries, never by editing one that is here.
"""
from __future__ import annotations

import importlib.util
import json
import re
import sys
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


class LoadError(ValueError):
    """A name that BENCHMARK.json or a cell file uses has no file."""


@dataclass
class Cell:
    """One entry of ``workloads`` with everything it names, loaded."""
    name: str
    chips: int
    config: dict
    mix: dict
    driver: ModuleType
    reference: ModuleType
    end_to_end: List[dict]
    per_layer: List[dict]
    readers: Dict[str, ModuleType] = field(default_factory=dict)
    bench_dir: Path = BENCH_DIR


def _check_name(kind: str, name: str) -> str:
    if not isinstance(name, str) or not NAME.match(name):
        raise LoadError(f"{kind} name {name!r} is not a valid name")
    return name


def _json(path: Path, kind: str, name: str) -> dict:
    if not path.is_file():
        raise LoadError(f"no {kind} named {name!r} (looked for {path})")
    with path.open() as f:
        return json.load(f)


def load_module(path: Path, kind: str, name: str) -> ModuleType:
    """Import one file as a module of its own (names may hold dots)."""
    if not path.is_file():
        raise LoadError(f"no {kind} named {name!r} (looked for {path})")
    mod_name = "bench_" + kind + "_" + re.sub(r"\W", "_", name)
    mod = sys.modules.get(mod_name)
    if mod is not None and getattr(mod, "__file__", None) == str(path):
        return mod
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_benchmark(root: Path = ROOT) -> dict:
    return _json(Path(root) / "BENCHMARK.json", "benchmark", "BENCHMARK.json")


def applies(metric: dict, cell: str, e2e_names=None) -> bool:
    """Whether ``metric`` is reported in ``cell``: its ``workloads`` list
    when it has one; otherwise every cell (end-to-end), or every cell
    that reports the end-to-end metric it ``moves`` (per-layer)."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    if e2e_names is None:
        return True
    return metric["moves"] in e2e_names


def load_cell(name: str, root: Path = ROOT,
              bench_dir: Optional[Path] = None) -> Cell:
    """The cell ``name`` of ``root``'s BENCHMARK.json, with its config,
    mix, driver, reference and metric readers loaded from ``bench_dir``
    (default: ``<root>/bench``)."""
    bench = load_benchmark(root)
    bench_dir = Path(bench_dir) if bench_dir else Path(root) / "bench"
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise LoadError(f"no workload named {name!r} in BENCHMARK.json "
                        f"(have {sorted(by_name)})")
    w = by_name[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}.get(w["config"])
    if cfg_entry is None:
        raise LoadError(f"workload {name!r} names config {w['config']!r}, "
                        f"which BENCHMARK.json does not list")
    config = _json(Path(root) / cfg_entry["file"], "config", w["config"])
    mix_name = _check_name("traffic", w["traffic"])
    mix = _json(bench_dir / "traffic" / f"{mix_name}.json", "traffic",
                mix_name)
    driver_name = _check_name("driver", mix["driver"])
    driver = load_module(bench_dir / "drivers" / f"{driver_name}.py",
                         "driver", driver_name)
    ref_name = _check_name("reference", config["reference"])
    reference = load_module(bench_dir / "configs" / f"{ref_name}.py",
                            "reference", ref_name)
    e2e = [m for m in bench["end_to_end"] if applies(m, name)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if applies(m, name, e2e_names)]
    readers = {m["name"]: load_module(
        bench_dir / "metrics" / f"{_check_name('metric', m['name'])}.py",
        "metric", m["name"]) for m in per_layer}
    return Cell(name=name, chips=int(w["chips"]),
                config=config, mix=mix, driver=driver, reference=reference,
                end_to_end=e2e, per_layer=per_layer, readers=readers,
                bench_dir=bench_dir)
