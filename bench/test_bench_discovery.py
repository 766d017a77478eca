"""BENCHMARK.json and the files it names: found by name, checked
against the schema, extended by adding files only."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import spec

BENCH = spec.load()


def test_benchmark_json_is_valid():
    spec.validate(BENCH)


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_each_cell_finds_its_files(cell):
    w = spec.cell(BENCH, cell)
    cfg = spec.read_json(spec.config_path(w["config"]))
    traffic = spec.read_json(spec.traffic_path(w["traffic"]))
    assert cfg["num_clients"] > 0 and traffic["federate_every"] >= 1
    names = {m["name"] for m in spec.metrics_for(BENCH, cell, "end_to_end")}
    assert {"setup_s", "train_samples_per_s"} <= names
    for m in spec.metrics_for(BENCH, cell, "per_layer"):
        assert callable(spec.load_reader(m["name"]).read)


def test_names_and_units_use_allowed_characters():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in BENCH[group]:
            assert spec.NAME.match(entry["name"])
            if "unit" in entry:
                assert spec.UNIT.match(entry["unit"])


def _copy(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(spec.BENCH_DIR, root / "bench",
                    ignore=shutil.ignore_patterns(".cache", ".trace",
                                                  "__pycache__"))
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), root)
    return root


def test_adding_files_adds_a_cell(tmp_path):
    root = _copy(tmp_path)
    (root / "bench" / "traffic" / "e2.json").write_text(json.dumps(
        dict(spec.read_json(spec.traffic_path("e5")), federate_every=2)))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "paper100.e2", "config": "cgan28-paper100",
                               "traffic": "e2", "chips": 1, "why": "test"})
    for m in bench["per_layer"]:
        m["workloads"].append("paper100.e2")
    spec.validate(bench, str(root))
    assert spec.traffic_path("e2", str(root)).startswith(str(root))


@pytest.mark.parametrize("fault", ["bad-name", "unit", "bound", "reader"])
def test_schema_faults_are_refused(fault):
    bench = json.loads(json.dumps(BENCH))
    if fault == "bad-name":
        bench["workloads"][0]["name"] = "paper 100"
    elif fault == "unit":
        bench["end_to_end"][0]["unit"] = "samples per second"
    elif fault == "bound":
        bench["end_to_end"][0]["bound"] = 0.5
    else:
        bench["per_layer"][0]["layer"] = "another layer"
    with pytest.raises(spec.SpecError):
        spec.validate(bench)


def _run(cwd, env_extra):
    env = dict(os.environ, **env_extra)
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "paper100.e5",
         "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    out = _run(spec.ROOT, {"JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "not a TPU" in out.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    out = _run(_copy(tmp_path), {"JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert out.stdout.strip() == ""
