"""A net is found by the configuration's ``"model"`` key: a second net
is added by adding files and entries only, the harness calls the net's
module for everything specific to the net, a net without its module
is refused, and the population at the cGAN's shape is the one the
benchmark has always built."""
import hashlib
import json
import os

import pytest

from bench import population, run, spec
from bench.models import cgan28
from bench.test_bench_check import _run, _tiny
from bench.test_bench_discovery import _copy

# cgan28 under another name, counting the harness's calls
COPY = '''"""cgan28 under another name, counting the harness's calls."""
import collections

from bench.models import cgan28

CALLS = collections.Counter()
IMAGE, NUM_CLASSES = cgan28.IMAGE, cgan28.NUM_CLASSES


def _counted(name):
    fn = getattr(cgan28, name)

    def call(*a, **k):
        CALLS[name] += 1
        return fn(*a, **k)
    return call


for _name in ("ema_features", "param_template", "init_params", "make_layers",
              "make_step", "layer_flops", "step_flops_per_sample",
              "build_trainer"):
    globals()[_name] = _counted(_name)
'''

# sha256 of the population's domains, image and label bytes, pinned from
# the generator before it took the image shape as an argument
DIGESTS = {(100, 600): "16df554a39b8ca783d3a5ce9f1b0737a"
                       "eef47cdf2982b8d016e3c0b603f7f94c",
           (8, 16): "8412cce7bc606f4c7ad45198c5f0804b"
                    "58729624bb5041120aaf28851914cd84"}
CACHE_TAGS = {(100, 600): "2686840dcb00f3ef", (8, 16): "14267a648af5db24"}


def _files(root):
    out = {}
    for d, _, names in os.walk(root):
        for n in names:
            with open(os.path.join(d, n), "rb") as f:
                out[os.path.relpath(os.path.join(d, n), root)] = \
                    hashlib.sha256(f.read()).hexdigest()
    return out


def _add_net(root, model="cgan28_copy", source=COPY, config="cgan28-copy"):
    """Add a net's module, a configuration naming it, a traffic file and
    a cell; return the extended BENCHMARK.json."""
    if source is not None:
        (root / "bench" / "models" / f"{model}.py").write_text(source)
    cfg = spec.read_json(spec.config_path("cgan28-paper100"))
    if model is None:
        del cfg["model"]
    else:
        cfg["model"] = model
    (root / "bench" / "configs" / f"{config}.json").write_text(
        json.dumps(cfg))
    (root / "bench" / "traffic" / "e3.json").write_text(json.dumps(
        dict(spec.read_json(spec.traffic_path("e5")), federate_every=3)))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": config, "source": cfg["source"],
                             "file": f"bench/configs/{config}.json",
                             "reduced": cfg["reduced"], "why": "test"})
    bench["workloads"].append({"name": "copy.e3", "config": config,
                               "traffic": "e3", "chips": 1, "why": "test"})
    for m in bench["per_layer"]:
        m["workloads"].append("copy.e3")
    return bench


def test_adding_a_net_adds_files_only(tmp_path):
    root = _copy(tmp_path)
    before = _files(root)
    bench = _add_net(root)
    spec.validate(bench, str(root))
    after = _files(root)
    assert {k: after[k] for k in before} == before
    assert sorted(set(after) - set(before)) == [
        "bench/configs/cgan28-copy.json", "bench/models/cgan28_copy.py",
        "bench/traffic/e3.json"]
    mod = spec.load_model("cgan28_copy", str(root))
    assert all(hasattr(mod, k) for k in spec.MODEL_KEYS)


@pytest.mark.parametrize("model,source,names", [
    (None, None, ["cgan28-copy", "no model"]),
    ("nonet", None, ["cgan28-copy", "nonet", "bench/models/nonet.py"]),
    ("cgan28_copy", COPY.replace('"build_trainer"', ""),
     ["cgan28-copy", "cgan28_copy", "build_trainer"])],
    ids=["no-model-key", "no-module", "no-build_trainer"])
def test_a_net_without_its_module_is_refused(tmp_path, model, source, names):
    root = _copy(tmp_path)
    bench = _add_net(root, model=model, source=source)
    with pytest.raises(spec.SpecError) as err:
        spec.validate(bench, str(root))
    for name in names:
        assert name in str(err.value)


def test_harness_calls_the_nets_module(tmp_path, monkeypatch):
    want = _run()["checked"]
    root = _copy(tmp_path)
    (root / "bench" / "models" / "cgan28_copy.py").write_text(COPY)
    copy = spec.load_model("cgan28_copy", str(root))
    load = spec.load_model
    monkeypatch.setattr(spec, "load_model", lambda name, root=spec.ROOT:
                        copy if name == "cgan28_copy" else load(name, root))
    cfg, traffic = _tiny()
    cfg["model"] = "cgan28_copy"
    args = run.parse(["--workload", "tiny", "--seed", "3000000001",
                      "--seconds", "1"])
    cell = {"name": "tiny", "config": "tiny", "traffic": "e1", "chips": 1}
    got = run.run(args, require_chip=False, cell=cell, cfg=cfg,
                  traffic=traffic)["checked"]
    for name in ("init_params", "make_step", "ema_features",
                 "step_flops_per_sample", "build_trainer"):
        assert copy.CALLS[name] > 0, name
    assert got == want


@pytest.mark.parametrize("num_clients,base_size", sorted(DIGESTS))
def test_population_at_the_cgan_shape_is_unchanged(tmp_path, monkeypatch,
                                                   num_clients, base_size):
    monkeypatch.setattr(population, "CACHE_DIR", str(tmp_path))
    traffic = dict(spec.read_json(spec.traffic_path("e5")),
                   base_size=base_size)
    shape = (cgan28.IMAGE, cgan28.NUM_CLASSES)
    path = population.cache_path(traffic, num_clients, *shape)
    assert os.path.basename(path) == \
        f"population-{CACHE_TAGS[num_clients, base_size]}.npz"
    h = hashlib.sha256()
    for dom, imgs, labs in population.population(traffic, num_clients,
                                                 *shape):
        h.update(dom.encode())
        h.update(imgs.dtype.str.encode())
        h.update(str(imgs.shape).encode())
        h.update(imgs.tobytes())
        h.update(labs.dtype.str.encode())
        h.update(labs.tobytes())
    assert h.hexdigest() == DIGESTS[num_clients, base_size]
