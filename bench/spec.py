"""Load and check ``BENCHMARK.json`` and the files it names.

Everything that belongs to one configuration, one mix, one cell or one
per-layer metric is a file of its own, found by name:

    bench/configs/<config>.json   the model, as run, with its source
    bench/mixes/<mix>.json        the training job (the traffic)
    bench/limits/<cell>.json      the correctness limits of one cell
    bench/metrics/<metric>.py     the reader of one per-layer metric
    bench/families/<family>.py    how a model family meets the program

A later change adds a cell or a metric by adding files and entries; no
file here needs an edit for it.
"""
from __future__ import annotations

import importlib.util
import json
import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List, Optional

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")
SOURCES_E2E = ("host_clock", "device_trace")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
MIX_KEYS = ("why", "batch_per_chip", "chunk_steps", "data", "e2train",
            "train")
CONFIG_KEYS = ("source", "family", "reference", "arch", "assumed",
               "reduced", "flop_per_image")
LIMIT_KEYS = ("limits", "readings")


class SpecError(ValueError):
    """A benchmark file is missing or breaks the benchmark's rules."""


def load_json(path: Path) -> Any:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing file {path}") from None
    except json.JSONDecodeError as e:
        raise SpecError(f"{path}: not JSON: {e}") from None


def check_name(what: str, name: Any) -> str:
    if not isinstance(name, str) or not NAME.fullmatch(name):
        raise SpecError(f"{what} {name!r}: a name is 1-64 letters, digits, "
                        "'_', '.' or '-', not starting with '.' or '-'")
    return name


def check_line(what: str, text: Any) -> str:
    if (not isinstance(text, str) or not 1 <= len(text) <= 200
            or "\n" in text or "\t" in text):
        raise SpecError(f"{what}: 1-200 characters on one line, no tab")
    return text


def _unique(what: str, names: List[str]) -> None:
    dup = {n for n in names if names.count(n) > 1}
    if dup:
        raise SpecError(f"{what}: duplicate names {sorted(dup)}")


def validate(bench: Dict[str, Any]) -> None:
    """The rules of ``BENCHMARK.json`` that hold whatever the files say."""
    need = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    if set(bench) != need:
        raise SpecError(f"BENCHMARK.json keys {sorted(bench)} != "
                        f"{sorted(need)}")
    for word in bench["command"]:
        check_line("command word", word)
    if not 1 <= int(bench["run_seconds"]) <= 51:
        raise SpecError("run_seconds is 1 to 51")
    configs = bench["configs"]
    for c in configs:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            raise SpecError(f"config entry keys {sorted(c)}")
        check_name("config", c["name"])
        check_line(f"config {c['name']} source", c["source"])
        check_line(f"config {c['name']} why", c["why"])
        for k in c["reduced"]:
            check_name("reduced key", k)
    _unique("configs", [c["name"] for c in configs])
    names = {c["name"] for c in configs}
    cells = bench["workloads"]
    for w in cells:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            raise SpecError(f"workload entry keys {sorted(w)}")
        for key in ("name", "config", "traffic"):
            check_name(f"workload {key}", w[key])
        check_line(f"workload {w['name']} why", w["why"])
        if w["config"] not in names:
            raise SpecError(f"workload {w['name']}: unknown config "
                            f"{w['config']!r}")
        if w["chips"] not in (1, 4):
            raise SpecError(f"workload {w['name']}: chips is 1 or 4")
    _unique("workloads", [w["name"] for w in cells])
    pairs = [f"{w['config']}/{w['traffic']}" for w in cells]
    _unique("config and traffic pairs", pairs)
    cell_names = {w["name"] for w in cells}
    metrics = bench["end_to_end"] + bench["per_layer"]
    _unique("metrics", [m["name"] for m in metrics])
    e2e = {m["name"] for m in bench["end_to_end"]}
    if "setup_s" not in e2e:
        raise SpecError("end_to_end needs setup_s")
    for m in bench["end_to_end"]:
        _check_metric(m, {"name", "unit", "better", "bound", "source"},
                      cell_names)
        if m["source"] not in SOURCES_E2E:
            raise SpecError(f"{m['name']}: end-to-end source is one of "
                            f"{SOURCES_E2E}")
        if not 0.01 <= float(m["bound"]) <= 0.25:
            raise SpecError(f"{m['name']}: bound is 0.01 to 0.25")
    for m in bench["per_layer"]:
        _check_metric(m, {"name", "unit", "better", "source", "layer",
                          "moves"}, cell_names)
        check_line(f"{m['name']} layer", m["layer"])
        if m["moves"] not in e2e:
            raise SpecError(f"{m['name']}: moves unknown metric "
                            f"{m['moves']!r}")


def _check_metric(m, keys, cell_names) -> None:
    extra = set(m) - keys - {"workloads"}
    if set(m) - {"workloads"} != keys:
        raise SpecError(f"metric {m.get('name')!r}: keys {sorted(m)}; "
                        f"unexpected {sorted(extra)}")
    check_name("metric", m["name"])
    if not UNIT.fullmatch(m["unit"]):
        raise SpecError(f"{m['name']}: unit {m['unit']!r}")
    if m["better"] not in ("lower", "higher"):
        raise SpecError(f"{m['name']}: better is lower or higher")
    if m["source"] not in SOURCES:
        raise SpecError(f"{m['name']}: source {m['source']!r}")
    unknown = set(m.get("workloads", ())) - cell_names
    if unknown:
        raise SpecError(f"{m['name']}: unknown workloads {sorted(unknown)}")


@dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with the files it names."""
    name: str
    chips: int
    config_name: str
    mix_name: str
    config: Dict[str, Any]
    mix: Dict[str, Any]
    limits: Dict[str, Any]
    end_to_end: List[Dict[str, Any]] = field(default_factory=list)
    per_layer: List[Dict[str, Any]] = field(default_factory=list)


class Benchmark:
    """``BENCHMARK.json`` at ``root`` and the benchmark directory
    ``root/bench``."""

    def __init__(self, root: Path, bench_dir: Optional[Path] = None):
        self.root = Path(root)
        self.dir = Path(bench_dir) if bench_dir else self.root / "bench"
        self.spec = load_json(self.root / "BENCHMARK.json")
        validate(self.spec)

    def file(self, *parts: str) -> Path:
        return self.dir.joinpath(*parts)

    def config(self, name: str) -> Dict[str, Any]:
        entry = next((c for c in self.spec["configs"] if c["name"] == name),
                     None)
        if entry is None:
            raise SpecError(f"unknown config {name!r}")
        cfg = load_json(self.root / entry["file"])
        missing = [k for k in CONFIG_KEYS if k not in cfg]
        if missing:
            raise SpecError(f"config {name}: missing keys {missing}")
        if list(cfg["reduced"]) != list(entry["reduced"]):
            raise SpecError(f"config {name}: reduced differs from "
                            "BENCHMARK.json")
        if not self.file("families", f"{cfg['family']}.py").is_file():
            raise SpecError(f"config {name}: no family file for "
                            f"{cfg['family']!r}")
        return cfg

    def mix(self, name: str) -> Dict[str, Any]:
        check_name("mix", name)
        mix = load_json(self.file("mixes", f"{name}.json"))
        missing = [k for k in MIX_KEYS if k not in mix]
        if missing:
            raise SpecError(f"mix {name}: missing keys {missing}")
        if int(mix["batch_per_chip"]) < 1 or int(mix["chunk_steps"]) < 1:
            raise SpecError(f"mix {name}: batch and chunk must be >= 1")
        return mix

    def limits(self, cell: str) -> Dict[str, Any]:
        lim = load_json(self.file("limits", f"{cell}.json"))
        missing = [k for k in LIMIT_KEYS if k not in lim]
        if missing:
            raise SpecError(f"limits {cell}: missing keys {missing}")
        return lim

    def cell(self, name: str) -> Cell:
        w = next((w for w in self.spec["workloads"] if w["name"] == name),
                 None)
        if w is None:
            raise SpecError(f"unknown workload {name!r}")
        e2e = [m for m in self.spec["end_to_end"] if name in
               m.get("workloads", [name])]
        moved = {m["name"] for m in e2e}
        per_layer = [m for m in self.spec["per_layer"]
                     if (name in m["workloads"] if "workloads" in m
                         else m["moves"] in moved)]
        return Cell(name=name, chips=w["chips"], config_name=w["config"],
                    mix_name=w["traffic"], config=self.config(w["config"]),
                    mix=self.mix(w["traffic"]), limits=self.limits(name),
                    end_to_end=e2e, per_layer=per_layer)

    def reader(self, metric: str):
        """The ``read(record, trace)`` function of one per-layer metric."""
        return _module(self.file("metrics", f"{check_name('metric', metric)}"
                                 ".py"), f"bench_metric_{metric}").read

    def reference(self, name: str):
        """The plain reference module a configuration names."""
        return _module(self.file("reference", f"{check_name('reference', name)}"
                                 ".py"), f"bench_reference_{name}")

    def family(self, name: str):
        return _module(self.file("families", f"{check_name('family', name)}"
                                 ".py"), f"bench_family_{name}")


def _module(path: Path, modname: str):
    if not path.is_file():
        raise SpecError(f"missing file {path}")
    spec = importlib.util.spec_from_file_location(modname.replace(".", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
