"""Finding the benchmark's pieces by name, and checking them.

``BENCHMARK.json`` names every cell, configuration and metric. Each
configuration is ``bench/configs/<name>.json``, each traffic mix
``bench/traffic/<name>.json`` and each per-layer metric a reader
``bench/metrics/<name>.py``; a net is ``bench/models/<model>.py``, named
by its configuration's ``"model"`` key. The harness loads them by those
names, so a cell, a configuration, a net or a metric is added by adding
files and entries, never by editing code.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
from typing import Dict, List

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
CONFIG_KEYS = {"name", "source", "file", "reduced", "why"}
CELL_KEYS = {"name", "config", "traffic", "chips", "why"}
E2E_KEYS = {"name", "unit", "better", "bound", "source"}
LAYER_KEYS = {"name", "unit", "better", "source", "layer", "moves"}
READER_KEYS = ("LAYER", "MOVES", "read")
# what a net's module gives the harness (bench/models/cgan28.py)
MODEL_KEYS = ("IMAGE", "NUM_CLASSES", "ema_features", "param_template",
              "init_params", "make_layers", "make_step", "layer_flops",
              "step_flops_per_sample", "build_trainer")


class SpecError(ValueError):
    pass


def _line(text, what, limit=200):
    if not isinstance(text, str) or not 1 <= len(text) <= limit \
            or "\n" in text or "\t" in text:
        raise SpecError(f"{what}: 1 to {limit} characters on one line")


def _name(n, what):
    if not isinstance(n, str) or not NAME.match(n):
        raise SpecError(f"{what} {n!r} is not a valid name")


def load(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def config_path(name: str, root: str = ROOT) -> str:
    return os.path.join(root, "bench", "configs", f"{name}.json")


def traffic_path(name: str, root: str = ROOT) -> str:
    return os.path.join(root, "bench", "traffic", f"{name}.json")


def reader_path(name: str, root: str = ROOT) -> str:
    return os.path.join(root, "bench", "metrics", f"{name}.py")


def model_path(name: str, root: str = ROOT) -> str:
    return os.path.join(root, "bench", "models", f"{name}.py")


def read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _load_file(module: str, path: str):
    spec = importlib.util.spec_from_file_location(
        re.sub(r"\W", "_", module), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(name: str, root: str = ROOT):
    """The per-layer metric's reader module, loaded from its file."""
    return _load_file("bench_metric_" + name, reader_path(name, root))


def load_model(name: str, root: str = ROOT):
    """The net's module, loaded from its file."""
    return _load_file("bench_model_" + name, model_path(name, root))


def _check_model(config: str, cfg: dict, root: str) -> None:
    if "model" not in cfg:
        raise SpecError(f"config {config}: no model")
    model = cfg["model"]
    _name(model, f"config {config}: model")
    if not os.path.exists(model_path(model, root)):
        raise SpecError(f"config {config}: model {model} has no module "
                        f"bench/models/{model}.py")
    mod = load_model(model, root)
    gone = [k for k in MODEL_KEYS if not hasattr(mod, k)]
    if gone:
        raise SpecError(f"config {config}: model {model} lacks "
                        + ", ".join(gone))


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SpecError(f"no workload {name!r} in BENCHMARK.json")


def metrics_for(bench: dict, cell_name: str, kind: str) -> List[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics a cell reports."""
    return [m for m in bench[kind]
            if cell_name in m.get("workloads", [cell_name])]


def validate(bench: dict, root: str = ROOT) -> None:
    """Check BENCHMARK.json and every file it names against the schema;
    raise SpecError on the first fault."""
    if set(bench) != TOP_KEYS:
        raise SpecError(f"top-level keys {sorted(bench)}")
    if len(json.dumps(bench)) > 64 * 1024:
        raise SpecError("BENCHMARK.json over 64 KiB")
    paths = bench["paths"]
    if not 1 <= len(paths) <= 16:
        raise SpecError("1 to 16 paths")
    for p in paths:
        if not PATH.match(p) or p.startswith("/") or ".." in p.split("/"):
            raise SpecError(f"bad path {p!r}")
    cmd = bench["command"]
    if not 1 <= len(cmd) <= 32:
        raise SpecError("command: 1 to 32 words")
    for word in cmd:
        _line(word, "command word")
        if word.startswith("/") or ".." in word.split("/"):
            raise SpecError(f"command word {word!r} leaves the checkout")
        if os.path.exists(os.path.join(root, word)) and not any(
                word == p or word.startswith(p.rstrip("/") + "/")
                for p in paths):
            raise SpecError(f"command names {word!r} outside paths")
    rs = bench["run_seconds"]
    if not isinstance(rs, int) or not 1 <= rs <= 51:
        raise SpecError("run_seconds: a whole number from 1 to 51")

    configs = bench["configs"]
    if not 1 <= len(configs) <= 24:
        raise SpecError("1 to 24 configs")
    files = set()
    for c in configs:
        if set(c) != CONFIG_KEYS:
            raise SpecError(f"config keys {sorted(c)}")
        _name(c["name"], "config")
        _line(c["source"], "config source")
        _line(c["why"], "config why")
        if c["file"] != os.path.relpath(config_path(c["name"], root), root):
            raise SpecError(f"config {c['name']}: file must be "
                            f"bench/configs/{c['name']}.json")
        if c["file"] in files:
            raise SpecError(f"file {c['file']} used twice")
        files.add(c["file"])
        if not os.path.exists(os.path.join(root, c["file"])):
            raise SpecError(f"missing {c['file']}")
        if len(c["reduced"]) > 16:
            raise SpecError("reduced: at most 16 keys")
        for k in c["reduced"]:
            _name(k, "reduced key")
        cfg = read_json(os.path.join(root, c["file"]))
        if cfg.get("source") != c["source"] or cfg.get("reduced") != c["reduced"]:
            raise SpecError(f"config {c['name']}: source and reduced must "
                            "match its file")
        _check_model(c["name"], cfg, root)

    cells = bench["workloads"]
    if not 1 <= len(cells) <= 24:
        raise SpecError("1 to 24 workloads")
    config_names = {c["name"] for c in configs}
    pairs = set()
    for w in cells:
        if set(w) != CELL_KEYS:
            raise SpecError(f"workload keys {sorted(w)}")
        for k in ("name", "config", "traffic"):
            _name(w[k], f"workload {k}")
        _line(w["why"], "workload why")
        if w["config"] not in config_names:
            raise SpecError(f"workload {w['name']}: unknown config")
        if not os.path.exists(traffic_path(w["traffic"], root)):
            raise SpecError(f"workload {w['name']}: no traffic file "
                            f"bench/traffic/{w['traffic']}.json")
        if w["chips"] not in (1, 4):
            raise SpecError("chips: 1 or 4")
        if (w["config"], w["traffic"]) in pairs:
            raise SpecError(f"pair {w['config']}/{w['traffic']} twice")
        pairs.add((w["config"], w["traffic"]))
    four = sum(w["chips"] == 4 for w in cells)
    if four > max(1, len(cells) // 2):
        raise SpecError("too many four-chip cells")
    if {c["name"] for c in configs} - {w["config"] for w in cells}:
        raise SpecError("a config no cell uses")

    cell_names = {w["name"] for w in cells}
    e2e, per = bench["end_to_end"], bench["per_layer"]
    if not 1 <= len(e2e) <= 16 or not 1 <= len(per) <= 128:
        raise SpecError("1 to 16 end-to-end and 1 to 128 per-layer metrics")
    if "setup_s" not in {m["name"] for m in e2e}:
        raise SpecError("no setup_s")
    seen = set()
    for m in e2e + per:
        keys = E2E_KEYS if m in e2e else LAYER_KEYS
        if set(m) - {"workloads"} != keys:
            raise SpecError(f"metric {m.get('name')} keys {sorted(m)}")
        _name(m["name"], "metric")
        if m["name"] in seen:
            raise SpecError(f"metric {m['name']} twice")
        seen.add(m["name"])
        if not UNIT.match(m["unit"]):
            raise SpecError(f"unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            raise SpecError("better: lower or higher")
        if m["source"] not in SOURCES:
            raise SpecError(f"source {m['source']!r}")
        for c in m.get("workloads", []):
            if c not in cell_names:
                raise SpecError(f"metric {m['name']}: unknown cell {c}")
    for m in e2e:
        if m["source"] not in ("host_clock", "device_trace"):
            raise SpecError(f"end-to-end {m['name']}: source")
        b = m["bound"]
        if not isinstance(b, (int, float)) or not 0.01 <= b <= 0.25:
            raise SpecError(f"end-to-end {m['name']}: bound")
    e2e_names = {m["name"] for m in e2e}
    layers: Dict[str, str] = {}
    for m in per:
        _line(m["layer"], "layer")
        if m["moves"] not in e2e_names:
            raise SpecError(f"{m['name']} moves an unknown metric")
        reader = load_reader(m["name"], root)
        for k in READER_KEYS:
            if not hasattr(reader, k):
                raise SpecError(f"reader {m['name']} lacks {k}")
        if (reader.LAYER, reader.MOVES) != (m["layer"], m["moves"]):
            raise SpecError(f"reader {m['name']}: layer and moves must "
                            "match BENCHMARK.json")
        for c in m.get("workloads", cell_names):
            if m["moves"] not in {x["name"] for x in metrics_for(
                    bench, c, "end_to_end")}:
                raise SpecError(f"{m['name']}: cell {c} does not report "
                                f"{m['moves']}")
    for w in cells:
        if len(metrics_for(bench, w["name"], "end_to_end")) < 2 \
                or not metrics_for(bench, w["name"], "per_layer"):
            raise SpecError(f"cell {w['name']} reports too few metrics")
