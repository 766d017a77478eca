"""The trainer's tracing layer (core/spans.py): the device scopes reach
the HLO ``op_name`` metadata of every program they name, the host spans
land nested in a profiler trace, and the program-cache counters count
one build per miss."""
import glob
import os
import re

import jax
import pytest

from repro.core import spans
from repro.core.genetic import CutSearcher, GAConfig
from repro.core.huscf import HuSCFConfig, HuSCFTrainer
from repro.core.latency import PAPER_DEVICES, Cut
from repro.data import build_scenario

N_CLIENTS = 2


def _op_names(lowered) -> set:
    return set(re.findall(r'op_name="([^"]*)"',
                          lowered.as_text(dialect="hlo", debug_info=True)))


def _host_spans(logdir):
    """[(line, name, start_ns, end_ns)] of every ``huscf.`` host event."""
    path, = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(spans.PREFIX):
                    out.append((line.name, ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns))
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Two clients on one profile with one warm-up round: an epoch, the
    warm-up round and a clustered round build every program, then the
    same epoch and a clustered round run again under the profiler. The
    counters are read around each part."""
    clients = build_scenario("2dom_iid", num_clients=N_CLIENTS, base_size=16,
                             seed=0)
    cfg = HuSCFConfig(batch=2, steps_per_epoch=1, federate_every=10 ** 6,
                      seed=0, warmup_fed_rounds=1)
    tr = HuSCFTrainer(clients, [PAPER_DEVICES[0]] * N_CLIENTS,
                      cuts=[Cut(1, 3, 1, 3)] * N_CLIENTS, config=cfg)
    before = spans.counters()
    tr.train_steps(1)
    tr.federate()
    tr.federate()
    jax.block_until_ready(tr.state)
    first = spans.counters()
    logdir = str(tmp_path_factory.mktemp("trace"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with jax.profiler.trace(logdir, profiler_options=options):
        tr.train_steps(1)
        diag = tr.federate()
        jax.block_until_ready(tr.state)
    return {"trainer": tr, "diag": diag, "spans": _host_spans(logdir),
            "before": before, "first": first, "repeat": spans.counters()}


def _delta(after, before):
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


def test_epoch_program_carries_split_scopes(traced):
    tr = traced["trainer"]
    names = _op_names(tr._epoch_fns[1].lower(
        tr.state, tr._dataset, tr._train_key, tr._mid_ema, tr._ema_init))
    groups = [g.name for g in tr.groups]
    for scope in (spans.SCOPE_HEAD, spans.SCOPE_TAIL):
        for g in groups:
            for net in ("G", "D"):
                # forward ops, and backward ops inside transpose(jvp(..))
                assert any(f"jvp({net})/{scope}/{g}/" in n
                           and "transpose" not in n for n in names), \
                    (net, scope, g)
                assert any(f"transpose(jvp({net}))/{scope}/{g}/" in n
                           for n in names), (net, scope, g)
    for net in ("G", "D"):
        assert any(f"({net})/{spans.SCOPE_TRUNK}/layer" in n for n in names)


def test_cluster_program_named_and_scoped(traced):
    tr = traced["trainer"]
    fn = tr._get_cluster_fn()
    assert fn.__name__ == "cluster_weight"
    lowered = fn.lower(tr._mid_ema, tr._sizes_dev, tr._cluster_key)
    assert "HloModule jit_cluster_weight" in lowered.as_text(dialect="hlo")
    names = _op_names(lowered)
    for scope in (spans.SCOPE_CLUSTER, spans.SCOPE_KLD):
        assert any(n.startswith(f"jit(cluster_weight)/{scope}/")
                   for n in names), scope


def test_aggregation_programs_scoped(traced):
    tr, diag = traced["trainer"], traced["diag"]
    lowered = 0
    for (net, *_), plan in tr._fed_plans.items():
        template = {g.name: tr.state[net]["client"][g.name]
                    for g in tr.groups}
        for key, fn in plan._agg_fns.items():
            if key[0] != "device":
                continue
            names = _op_names(fn.lower(template, diag["weights"],
                                       diag["labels"]))
            assert any(f"/{spans.SCOPE_AGGREGATE}/" in n for n in names)
            lowered += 1
    assert lowered == 2                      # one program per net


def test_ga_program_scoped():
    devices = [PAPER_DEVICES[i % 3] for i in range(6)]
    searcher = CutSearcher(devices, batch=8,
                           config=GAConfig(population_size=16, generations=2,
                                           seed=0))
    args = (jax.random.PRNGKey(0), searcher.tables)
    if searcher._counts is not None:
        args += (searcher._counts,)
    names = _op_names(searcher._search.lower(*args))
    assert any(f"/{spans.SCOPE_GA}/" in n for n in names)


def test_host_spans_nested_in_profiler_trace(traced):
    events = traced["spans"]
    names = [n for _, n, _, _ in events]
    for name in ("train_steps", "train_steps.dispatch",
                 "train_steps.readback", "federate", "federate.cluster",
                 "federate.aggregate"):
        assert names.count(spans.PREFIX + name) == 1, name
    # every dotted span lies inside a span of its parent's name, on the
    # same thread
    for line, name, s, e in events:
        parent = name.rsplit(".", 1)[0]
        if parent == "huscf":
            continue
        assert any(pl == line and pn == parent and ps <= s and e <= pe
                   for pl, pn, ps, pe in events), name


def test_counters_count_one_build_per_miss(traced):
    first = _delta(traced["first"], traced["before"])
    assert first == {"programs_built.epoch": 1, "programs_built.agg": 4,
                     "programs_built.cluster": 1, "rounds.fedavg": 1,
                     "rounds.clustered": 1, "agg.leafwise": 4}
    # the traced epoch and clustered round build nothing
    assert _delta(traced["repeat"], traced["first"]) == {
        "rounds.clustered": 1, "agg.leafwise": 2}


@pytest.mark.parametrize("chunk_size", [None, 2])
def test_aggregation_path_counters(chunk_size):
    """A dense round counts ``agg.leafwise`` once per net (G and D) and a
    chunk-streamed round ``agg.chunked`` instead."""
    import numpy as np
    from repro.core.federation import federate_client_params
    from test_federation_fused import N_LAYERS, build_population
    groups, params = build_population(4, 2)
    before = spans.counters()
    federate_client_params(groups, params, np.ones(4), np.arange(4) % 2,
                           n_layers=N_LAYERS, chunk_size=chunk_size)
    got = _delta(spans.counters(), before)
    got.pop("programs_built.agg", None)
    got.pop("programs_built.agg_chunked", None)
    assert got == ({"agg.leafwise": 2} if chunk_size is None
                   else {"agg.chunked": 2})


def test_counters_are_copies():
    spans.count("test.spans", 2)
    got = spans.counters()
    got["test.spans"] = 0
    assert spans.counters()["test.spans"] >= 2
