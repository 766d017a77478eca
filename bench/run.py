#!/usr/bin/env python3
"""Split-GAN training benchmark: one run of one cell on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything specific to the configuration's net comes from its module,
``bench/models/<model>.py`` (``spec.load_model``). Set-up builds the
cell's population at the net's image shape (cached under
``bench/.cache``), builds the trainer through the net's module, swaps
in the benchmark's weights for ``--seed``, and warms every program the
window runs: one epoch through ``train_steps``, the FedAvg warm-up
rounds and one clustered ``federate()``. The window then loops
``train_epoch()`` in whole federation cycles (``federate_every``
epochs, the last ending in a clustered round) until ``--seconds`` have
passed. ``--trace 1`` runs the same window under the profiler and
reports the per-layer metrics instead of the end-to-end ones.

After the window: the peak device memory is read, the trainer is
freed, and the reference (the net's layers and step over
``bench/reference.py``) recomputes the set-up's first epoch and
clustered round from the same seed; the numbers compared are printed
beside their limits (``bench/compare.py``).

Without a TPU, or on a chip whose kind has no peaks in
``bench/trace_reduce.py``, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)
if sys.path and os.path.abspath(sys.path[0]) == _HERE:
    sys.path.pop(0)
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

import numpy as np  # noqa: E402

from bench import adapter, compare, population, reference  # noqa: E402
from bench import spec  # noqa: E402
from bench import trace_reduce  # noqa: E402

SPAN = "bench."
SEED_MOD = 2 ** 31 - 16            # the trainer derives keys from seed + 4
SAMPLED_ROWS = 2                   # clients per group compared after a round


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Counter:
    """Compilations (backend compiles, cache loads included) and traces."""

    def __init__(self):
        self.compiles = 0
        self.traces = 0

    def __call__(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
        elif event == "/jax/core/compile/jaxpr_trace_duration":
            self.traces += 1


class GcClock:
    """Seconds the interpreter's collector ran, and its longest pause."""

    def __init__(self):
        self.total, self.longest, self.count, self._t = 0.0, 0.0, 0, None

    def __call__(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            d = time.perf_counter() - self._t
            self.total += d
            self.longest = max(self.longest, d)
            self.count += 1


class Spans:
    """Host spans around the calls into each layer: kept in memory, and
    written into the profiler's trace as annotations."""

    def __init__(self, jax, traced: bool):
        self.jax = jax
        self.traced = traced
        self.spans = []

    def wrap(self, name, fn, block=None):
        def call(*a, **k):
            t = time.perf_counter()
            with self.jax.profiler.TraceAnnotation(SPAN + name):
                out = fn(*a, **k)
                if block is not None and self.traced:
                    self.jax.block_until_ready(block())
            self.spans.append((name, t, time.perf_counter()))
            return out
        return call


def check_device(jax, chips: int) -> dict:
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    log(f"[device] platform={info['platform']} kind={info['kind']} "
        f"count={info['count']}")
    if info["platform"] != "tpu":
        raise SystemExit(f"platform {info['platform']!r} is not a TPU; "
                         "nothing was run")
    if len(devs) < chips:
        raise SystemExit(f"{len(devs)} chips, the cell needs {chips}")
    trace_reduce.peaks(info["kind"])
    return info


def clients_of(model, cfg, traffic):
    """The traffic's population at the net's image shape and classes."""
    return population.population(traffic, cfg["num_clients"], model.IMAGE,
                                 model.NUM_CLASSES)


def steps_per_epoch(clients, batch: int) -> int:
    """The trainer's epoch: the median client's size in whole batches."""
    return max(1, int(np.median(population.sizes(clients))) // batch)


def _structure(jax, tree):
    return (jax.tree_util.tree_structure(tree),
            [x.shape for x in jax.tree_util.tree_leaves(tree)])


def sampled_rows(groups, seed31):
    rng = np.random.default_rng([seed31, 11])
    return {g.name: sorted(rng.choice(g.size, min(SAMPLED_ROWS, g.size),
                                      replace=False).tolist())
            for g in groups}


def take_rows(jax, client, rows):
    """{net: {group: {layer: tree}}} -> the sampled rows, on the host."""
    return {net: {gname: jax.tree_util.tree_map(
        lambda x: np.asarray(x[np.asarray(rows[gname])]), layers)
        for gname, layers in per.items()}
        for net, per in client.items()}


def agg_layers(jax, groups, client):
    """{net: {layer: (owners, width)}} from the trainer's client params."""
    out = {}
    for net in ("G", "D"):
        per = {}
        for g in groups:
            for l, tree in client[net][g.name].items():
                w = sum(int(np.prod(x.shape[1:]))
                        for x in jax.tree_util.tree_leaves(tree))
                n, _ = per.get(l, (0, w))
                per[l] = (n + g.size, w)
        out[net] = per
    return out


def run(args, require_chip: bool = True, cell=None, cfg=None,
        traffic=None) -> dict:
    """One run. ``cell``, ``cfg`` and ``traffic`` default to what
    BENCHMARK.json names; tests pass small ones and ``require_chip``
    False."""
    bench = spec.load()
    cell = cell or spec.cell(bench, args.workload)
    cfg = cfg or spec.read_json(spec.config_path(cell["config"]))
    traffic = traffic or spec.read_json(spec.traffic_path(cell["traffic"]))

    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    # every program, however quick to compile, is served from the
    # cache after the first run in a checkout
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    device = (check_device(jax, cell["chips"]) if require_chip else
              {"platform": jax.devices()[0].platform,
               "kind": jax.devices()[0].device_kind,
               "count": len(jax.devices())})
    counter = Counter()
    jax.monitoring.register_event_duration_secs_listener(counter)

    seed31 = args.seed % SEED_MOD
    excluded = 0.0                  # seconds of set-up spent on the check
    phases = [("start", time.perf_counter() - T0)]

    def phase(name):
        phases.append((name, time.perf_counter() - T0))

    model = spec.load_model(cfg["model"])
    clients = clients_of(model, cfg, traffic)
    phase("population")
    groups = reference.groups_of(cfg)
    tr = model.build_trainer(cfg, traffic, clients, seed31)
    phase("trainer")
    if [(g.name, tuple(g.client_ids)) for g in tr.groups] != \
            [(g.name, g.client_ids) for g in groups]:
        raise RuntimeError("the trainer's profile groups differ from the "
                           "configuration's")

    # the benchmark's weights for this seed replace the trainer's own
    adapter.check(tr)
    layout = adapter.layout(tr)
    adapter.drop_weights(tr)
    gc.collect()
    w0 = model.init_params(cfg, groups, seed31)
    for net in ("G", "D"):
        if _structure(jax, w0[net]) != layout[net]:
            raise RuntimeError(f"net {net}: the trainer's parameter layout "
                               "differs from the reference's")
    adapter.set_weights(tr, w0)
    del w0
    n_steps = steps_per_epoch(clients, cfg["batch"])
    phase("weights")

    # epoch 1, recorded step by step: the first check
    metrics = adapter.first_epoch(tr, n_steps)
    phase("epoch")
    t = time.perf_counter()
    start = model.init_params(cfg, groups, seed31)
    now = adapter.trained(tr)
    delta, first, rms = compare.leaf_norms(
        now["params"], {net: start[net] for net in ("G", "D")},
        now["mu"], now["nu"])
    prog_train = {"losses": np.stack([np.asarray(metrics["loss_d"]),
                                      np.asarray(metrics["loss_g"])], 1),
                  "delta": np.asarray(delta), "mu": np.asarray(first),
                  "rms": np.asarray(rms), "names": compare.leaf_names(start)}
    del start, now, delta, first, rms
    excluded += time.perf_counter() - t

    for _ in range(cfg["warmup_fed_rounds"]):
        tr.federate()
    jax.block_until_ready(tr.state)
    phase("fedavg")

    # the checked clustered round, on the benchmark's activations and
    # weights; it compiles the cluster chain and the clustered round
    t = time.perf_counter()
    acts = reference.planted_activations(
        cfg["num_clients"], model.ema_features(cfg), seed31)
    adapter.plant_round(tr, acts, model.init_params(cfg, groups, seed31))
    excluded += time.perf_counter() - t
    diag = tr.federate()
    jax.block_until_ready(tr.state)
    phase("clustered")
    if diag["mode"] != "clustered":
        raise RuntimeError(f"round {diag['round']} ran {diag['mode']!r}")
    t = time.perf_counter()
    rows = sampled_rows(groups, seed31)
    client_now = adapter.client_params(tr)
    prog_round = {"k": int(diag["k"]), "labels": np.asarray(diag["labels"]),
                  "weights": np.asarray(diag["weights"]),
                  "rows": take_rows(jax, client_now, rows)}
    layers = agg_layers(jax, groups, client_now)
    del client_now
    excluded += time.perf_counter() - t
    jax.block_until_ready(tr.state)

    # -------------------------------------------------------------- window
    spans = Spans(jax, traced=bool(args.trace))
    rounds = []
    federate = spans.wrap("federate", tr.federate, block=lambda: tr.state)

    def federate_recorded(*a, **k):
        d = federate(*a, **k)
        rounds.append((d["mode"], d.get("k")))
        return d

    adapter.wrap(tr, spans.wrap("epoch", tr.train_steps), federate_recorded)
    logdir = os.path.join(_HERE, ".trace", cell["name"])
    if args.trace:
        shutil.rmtree(logdir, ignore_errors=True)
        jax.profiler.start_trace(logdir)
    setup_s = time.perf_counter() - T0 - excluded
    log("[setup] " + " ".join(f"{n}={t:.3f}" for n, t in phases)
        + f" (seconds since start; check readings {excluded:.3f} s)")
    compiles0, traces0 = counter.compiles, counter.traces
    # set-up leaves a large heap behind; collect it now and keep it out
    # of the collector's view, so that no full collection of set-up
    # garbage lands inside the window
    gc.collect()
    gc.freeze()
    gc_clock = GcClock()
    gc.callbacks.append(gc_clock)
    epochs, losses = 0, []
    t_start = time.perf_counter()
    with jax.profiler.TraceAnnotation(SPAN + "window"):
        while True:
            for _ in range(traffic["federate_every"]):
                losses.append(tr.train_epoch())
                epochs += 1
            if time.perf_counter() - t_start >= args.seconds:
                break
        jax.block_until_ready(tr.state)
    t_end = time.perf_counter()
    gc.callbacks.remove(gc_clock)
    gc.unfreeze()
    if args.trace:
        jax.profiler.stop_trace()
    window_s = t_end - t_start
    in_window = (counter.compiles - compiles0, counter.traces - traces0)
    longest = {n: max((e - b for m, b, e in spans.spans if m == n), default=0)
               for n in ("epoch", "federate")}
    log(f"[window] seconds={window_s} epochs={epochs} steps_per_epoch="
        f"{n_steps} compilations={in_window[0]} traces={in_window[1]} "
        f"longest_epoch_s={longest['epoch']} longest_federate_s="
        f"{longest['federate']} gc_s={gc_clock.total} gc_longest_s="
        f"{gc_clock.longest} gc_collections={gc_clock.count}")
    for i, (mode, k) in enumerate(rounds):
        log(f"[round] {i} mode={mode} k={None if k is None else int(k)}")
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    log(f"[memory] peak_bytes_in_use={peak}")

    samples = epochs * n_steps * cfg["num_clients"] * cfg["batch"]
    # what the per-layer readers read besides the trace
    data = {"device": device, "steps": epochs * n_steps, "samples": samples,
            "rounds": rounds, "agg_layers": layers,
            "flops_per_sample": model.step_flops_per_sample(cfg)}
    adapter.free(tr)
    del tr
    gc.collect()

    trace_info = None
    if args.trace:
        trace_info = reduce_trace(logdir)
        shutil.rmtree(logdir, ignore_errors=True)

    # ------------------------------------------------------------- check
    numbers = {"window_rounds_not_clustered":
               float(sum(m != "clustered" for m, _ in rounds)),
               "window_nonfinite_losses":
               float(sum(not np.isfinite(v) for m in losses
                         for v in m.values())),
               "window_compilations": float(in_window[0])}
    t_check = time.perf_counter()
    ref_train = reference_train(jax, cfg, groups, clients, seed31, n_steps)
    numbers.update(compare.train_numbers(prog_train, ref_train))
    moved = compare.moved(ref_train["mu"])
    for key in ("mu", "rms", "delta"):
        for name, p, r in compare.worst(prog_train[key], ref_train[key],
                                        prog_train["names"],
                                        moved if key == "delta" else None):
            log(f"[leaf] {key} {name} program={p!r} reference={r!r}")
    for k, v in numbers.items():
        if k not in cfg["limits"]:
            log(f"[reading] {k}={v!r} (not compared)")
    numbers.update(compare.round_numbers(prog_round, reference_round(
        jax, cfg, groups, clients, seed31, rows)))
    log(f"[timing] setup_s={setup_s} excluded_s={excluded} window_s="
        f"{window_s} reference_s={time.perf_counter() - t_check}")
    correct, rows_out = compare.verdict(numbers, cfg["limits"])
    for r in rows_out:
        log(f"[check] {r['name']}={r['value']!r} limit={r['limit']!r}")

    result = {"correct": correct, "attempted": epochs * n_steps,
              "failed": int(numbers["window_nonfinite_losses"]),
              "device": dict(device, memory_peak_bytes=peak)}
    if args.trace:
        result["metrics"] = per_layer(bench, cell, data, trace_info)
        result["device"].update(busy_s=trace_info["busy_s"],
                                window_s=trace_info["window_s"])
        result["breakdown"] = trace_info["breakdown"]
    else:
        result["metrics"] = {
            "train_samples_per_s": {"value": samples / window_s,
                                    "unit": "samples/s"},
            "train_peak_hbm_gb": {"value": (peak or 0) / 1e9, "unit": "GB"},
            "setup_s": {"value": setup_s, "unit": "s"}}
    result["numbers"] = numbers
    result["train"] = {"program": prog_train, "reference": ref_train}
    result["checked"] = {r["name"]: [r["value"], r["limit"]] for r in rows_out}
    return result


def reference_train(jax, cfg, groups, clients, seed31, n_steps, nx=None,
                    keep=None):
    """The reference's epoch: its readings, as the program's. ``keep``
    as in ``reference.sample``."""
    model = spec.load_model(cfg["model"])
    step = model.make_step(cfg, groups, nx or reference.Numerics(), keep)
    params = model.init_params(cfg, groups, seed31)
    data = reference.stage_data(clients, groups)
    state, losses = reference.train(step, params, data,
                                    jax.random.PRNGKey(seed31 + 1), n_steps)
    del params
    start = model.init_params(cfg, groups, seed31)
    delta, first, rms = compare.leaf_norms(
        {net: state[net] for net in ("G", "D")},
        {net: start[net] for net in ("G", "D")},
        {"G": state["opt_g"]["mu"], "D": state["opt_d"]["mu"]},
        {"G": state["opt_g"]["nu"], "D": state["opt_d"]["nu"]})
    return {"losses": losses, "delta": np.asarray(delta),
            "mu": np.asarray(first), "rms": np.asarray(rms)}


def reference_round(jax, cfg, groups, clients, seed31, rows,
                    precision=reference.HIGHEST, fedavg=False):
    """The reference's checked round. ``precision`` below HIGHEST gives
    the control; ``fedavg`` replaces the Eq.-15 weights by dataset
    shares, a fault."""
    model = spec.load_model(cfg["model"])
    acts = reference.planted_activations(
        cfg["num_clients"], model.ema_features(cfg), seed31)
    labels, k = reference.cluster(acts)
    sizes = population.sizes(clients)
    weights = (reference.fedavg_weights(sizes, labels) if fedavg else
               reference.federation_weights(acts, sizes, labels, cfg["beta"],
                                            precision))
    params = model.init_params(cfg, groups, seed31)
    client = {net: params[net]["client"] for net in ("G", "D")}
    agg = reference.aggregate_rows(groups, client, weights, labels, rows,
                                   precision)
    return {"k": k, "labels": labels, "weights": weights,
            "rows": jax.tree_util.tree_map(np.asarray, agg),
            "groups": groups, "client": client, "index": rows}


def reduce_trace(logdir) -> dict:
    """Device busy time, the device events, the host spans and the
    breakdown of the traced window."""
    tr = trace_reduce.load(logdir, SPAN)
    lo, hi = tr.window(SPAN + "window")
    events = tr.events()
    chips = max(1, len(tr.device))
    busy = sum(trace_reduce.busy_seconds([(s, e) for _, s, e in evs], lo, hi)
               for evs in tr.device.values()) / chips
    union = trace_reduce.busy_union(
        [(s, e) for _, s, e in tr.device[sorted(tr.device)[0]]]) \
        if tr.device else []
    spans = [(n[len(SPAN):], s, e) for n, s, e in tr.spans
             if n != SPAN + "window"]
    gaps = trace_reduce.idle_gaps(union, spans, lo, hi)
    return {"events": events, "busy_s": busy, "window_s": hi - lo,
            "spans": spans,
            "breakdown": {"device_ops": [list(x) for x in
                                         trace_reduce.op_totals(events)],
                          "idle_gaps": [list(x) for x in
                                        trace_reduce.gap_totals(gaps)]}}


def per_layer(bench, cell, data, trace_info) -> dict:
    """Each per-layer metric of the cell from its reader; a reader that
    finds nothing to read leaves its metric out."""
    out = {}
    for m in spec.metrics_for(bench, cell["name"], "per_layer"):
        value = spec.load_reader(m["name"]).read(data, trace_info)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def parse(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def main(argv=None) -> int:
    result = run(parse(argv))
    result.pop("numbers")
    result.pop("train")
    checked = result.pop("checked")
    result["checked"] = checked            # the compared numbers come last
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
