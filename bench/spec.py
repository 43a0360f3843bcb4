"""Resolve ``BENCHMARK.json`` entries to the files that make them up.

A cell names a configuration and a traffic mix; each resolves by name to a
file under ``bench/``, the cell's own load (an open loop's rate) to
``bench/cells/<cell>.json``, and each per-layer metric to a reader
``bench/metrics/<metric>.py``.  A configuration file names its
architecture's module, ``"reference": "<name>"`` for
``bench/references/<name>.py``: the program checks, the weight layout,
the plain reference and control, and the cost model.  Adding a
configuration, an architecture, a mix or a metric therefore means adding
files and entries, never editing one.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import pathlib
import re
from typing import Dict, List, Optional

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES_E2E = ("host_clock", "device_trace")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
MODULE_RE = re.compile(r"^[A-Za-z_][A-Za-z0-9_]{0,63}$")
#: what an architecture's module in ``references/`` defines
REFERENCE_FUNCTIONS = ("program_checks", "weight_layout", "logit_gaps",
                       "matmul_weights_per_token", "attn_flops_per_pair",
                       "kv_block_bytes", "primary_pools")


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    """The parsed ``BENCHMARK.json`` at the root of the checkout."""
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                   f"(have {[w['name'] for w in bench['workloads']]})")


def config_entry(bench: dict, name: str) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return c
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def config_file(bench: dict, name: str, root: pathlib.Path = ROOT) -> dict:
    """The configuration as it is run (``configs[].file``)."""
    return load_json(root / config_entry(bench, name)["file"])


def reference(m: dict):
    """The module of configuration ``m``'s architecture,
    ``references/<m["reference"]>.py``."""
    name = m.get("reference")
    if not isinstance(name, str) or not MODULE_RE.match(name):
        raise KeyError(f"config {m.get('name')}: reference {name!r} names "
                       "no module of bench/references")
    return importlib.import_module(f"bench.references.{name}")


def reference_problems(m: dict) -> List[str]:
    """What keeps configuration ``m``'s architecture module from
    resolving; empty when it does."""
    what = f"config {m.get('name')}"
    name = m.get("reference")
    if name is None:
        return [f"{what}: no reference"]
    try:
        mod = reference(m)
    except KeyError as e:
        return [e.args[0]]
    except ModuleNotFoundError as e:
        if e.name != f"bench.references.{name}":
            raise
        return [f"{what}: no module references/{name}.py"]
    lack = [f for f in REFERENCE_FUNCTIONS
            if not callable(getattr(mod, f, None))]
    return [f"{what}: references/{name}.py lacks {lack}"] if lack else []


def traffic_file(name: str) -> dict:
    """The traffic mix's parameters, ``traffic/<name>.json``."""
    return load_json(BENCH_DIR / "traffic" / f"{name}.json")


def traffic_generator(kind: str):
    """The general generator a mix names by its ``kind``."""
    return importlib.import_module(f"bench.traffic.{kind}")


def cell_file(name: str) -> dict:
    """The cell's own load, ``cells/<name>.json`` (such as the rate an
    open loop offers, found for this cell on the chip); empty when the
    cell has no file."""
    path = BENCH_DIR / "cells" / f"{name}.json"
    return load_json(path) if path.is_file() else {}


def missing_load(name: str, generator) -> List[str]:
    """Keys the generator reads from the cell's file that it lacks."""
    load = cell_file(name)
    return [k for k in getattr(generator, "CELL_KEYS", ()) if k not in load]


def metric_reader(name: str):
    """``read(run) -> float | None`` of one per-layer metric."""
    path = BENCH_DIR / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def reports(metric: dict, cell: str, e2e_of_cell: List[str]) -> bool:
    """Whether ``cell`` reports ``metric``: its ``workloads`` list when
    given, else every cell that reports the metric it moves (or, for an
    end-to-end metric without the key, every cell)."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    moves = metric.get("moves")
    return moves is None or moves in e2e_of_cell


def end_to_end_of(bench: dict, cell: str) -> List[dict]:
    return [m for m in bench["end_to_end"] if reports(m, cell, [])]


def per_layer_of(bench: dict, cell: str) -> List[dict]:
    e2e = [m["name"] for m in end_to_end_of(bench, cell)]
    return [m for m in bench["per_layer"] if reports(m, cell, e2e)]


def problems(bench: dict, root: pathlib.Path = ROOT) -> List[str]:
    """Everything in ``bench`` that does not resolve or breaks the naming
    rules; empty when the file is sound."""
    out: List[str] = []

    def name_ok(n, what):
        if not isinstance(n, str) or not NAME_RE.match(n):
            out.append(f"{what}: bad name {n!r}")

    def line_ok(s, what):
        if not isinstance(s, str) or not 1 <= len(s) <= 200 \
                or "\n" in s or "\t" in s:
            out.append(f"{what}: not one line of 1-200 characters")

    seen = set()
    for c in bench["configs"]:
        name_ok(c["name"], "config")
        line_ok(c["source"], f"config {c['name']} source")
        line_ok(c["why"], f"config {c['name']} why")
        for k in c["reduced"]:
            name_ok(k, f"config {c['name']} reduced")
        path = root / c["file"]
        if not path.is_file():
            out.append(f"config {c['name']}: no file {c['file']}")
            continue
        if path.parent != root / BENCH_DIR.name / "configs" \
                or path.stem != c["name"]:
            out.append(f"config {c['name']}: file is not "
                       f"configs/{c['name']}.json")
        out += reference_problems(load_json(path))
    cells = []
    for w in bench["workloads"]:
        name_ok(w["name"], "workload")
        name_ok(w["traffic"], f"workload {w['name']} traffic")
        line_ok(w["why"], f"workload {w['name']} why")
        if w["chips"] not in (1, 4):
            out.append(f"workload {w['name']}: chips {w['chips']}")
        pair = (w["config"], w["traffic"])
        if pair in seen:
            out.append(f"workload {w['name']}: pair {pair} repeats")
        seen.add(pair)
        try:
            config_entry(bench, w["config"])
        except KeyError as e:
            out.append(str(e))
        tpath = BENCH_DIR / "traffic" / f"{w['traffic']}.json"
        if not tpath.is_file():
            out.append(f"workload {w['name']}: no traffic file {tpath.name}")
        else:
            kind = load_json(tpath)["kind"]
            if not (BENCH_DIR / "traffic" / f"{kind}.py").is_file():
                out.append(f"traffic {w['traffic']}: no generator {kind}.py")
            else:
                lack = missing_load(w["name"], traffic_generator(kind))
                if lack:
                    out.append(f"workload {w['name']}: cells/{w['name']}"
                               f".json lacks {lack}")
        cells.append(w["name"])
    names = set()
    e2e_names = set()
    for m in bench["end_to_end"]:
        e2e_names.add(m["name"])
        if m["source"] not in SOURCES_E2E:
            out.append(f"{m['name']}: end-to-end source {m['source']}")
        if not 0 < m["bound"] <= 0.25:
            out.append(f"{m['name']}: bound {m['bound']}")
    for m in bench["end_to_end"] + bench["per_layer"]:
        name_ok(m["name"], "metric")
        if m["name"] in names:
            out.append(f"metric {m['name']} repeats")
        names.add(m["name"])
        if not UNIT_RE.match(m["unit"]):
            out.append(f"{m['name']}: bad unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            out.append(f"{m['name']}: better {m['better']!r}")
        if m["source"] not in SOURCES:
            out.append(f"{m['name']}: source {m['source']!r}")
        for c in m.get("workloads", []):
            if c not in cells:
                out.append(f"{m['name']}: unknown workload {c}")
    if "setup_s" not in e2e_names:
        out.append("no setup_s")
    for m in bench["per_layer"]:
        line_ok(m["layer"], f"{m['name']} layer")
        if m["moves"] not in e2e_names:
            out.append(f"{m['name']}: moves unknown {m['moves']}")
        if not (BENCH_DIR / "metrics" / f"{m['name']}.py").is_file():
            out.append(f"{m['name']}: no reader metrics/{m['name']}.py")
    for cell in cells:
        e2e = [m["name"] for m in end_to_end_of(bench, cell)]
        if "setup_s" not in e2e or len(e2e) < 2:
            out.append(f"{cell}: needs setup_s and one more end-to-end "
                       f"metric, has {e2e}")
        pl = per_layer_of(bench, cell)
        if not pl:
            out.append(f"{cell}: no per-layer metric")
        for m in pl:
            if m["moves"] not in e2e:
                out.append(f"{cell}: {m['name']} moves {m['moves']}, "
                           "which the cell does not report")
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        if c["name"] not in used:
            out.append(f"config {c['name']} used by no cell")
    return out


def resolve(bench: dict, cell: str, root: pathlib.Path = ROOT
            ) -> Dict[str, object]:
    """Everything one run of ``cell`` needs, found by name."""
    w = workload(bench, cell)
    traffic = traffic_file(w["traffic"])
    gen = traffic_generator(traffic["kind"])
    lack = missing_load(cell, gen)
    if lack:
        raise KeyError(f"{cell}: bench/cells/{cell}.json lacks {lack}, "
                       f"which {traffic['kind']} traffic needs")
    return {
        "workload": w,
        "config": config_file(bench, w["config"], root),
        "traffic": traffic,
        "generator": gen,
        "load": cell_file(cell),
        "end_to_end": end_to_end_of(bench, cell),
        "per_layer": per_layer_of(bench, cell),
    }


def find_metric(bench: dict, name: str) -> Optional[dict]:
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] == name:
            return m
    return None
