#!/usr/bin/env python3
"""Chip smoke test: drive HuSCF-GAN's main paths once on a TPU and check
what comes out.

    python chip_smoke.py               # one chip: train, serve, lm
    python chip_smoke.py --four-chip   # client-axis-sharded path only

One process holds the chip for every phase. Phases, in order:

* device — print platform, device kind and count; exit non-zero with
  no result line unless the platform is ``tpu`` (no CPU fallback).
* train — the paper's population (100 clients round-robin over the 7
  device profiles, ``2dom_noniid``), full-width cGAN, batch 32, Pallas
  aggregation, a federation round every epoch and a GA re-cut every
  round. The warm-up FedAvg rounds run through ``train_epoch``; the
  first round past them must be clustered. Then, on the trained client
  params and a seeded two-domain EMA, the device cluster+weight chain
  must match the numpy oracle (labels and k equal, weights within
  1e-4) and the Pallas aggregation must match the jnp one (5e-4).
* serve — ``SplitGanEngine`` on both ``SERVE_MIXES`` against the
  per-client monolithic forward.
* lm — the split-LM decode tail on the compiled ``mem_attention`` /
  ``flash_decode`` kernels against the dense oracle, and greedy
  generation against the teacher-forced argmax.

``--four-chip`` runs only the sharded path: a training step on a
trainer whose ``fed_mesh`` spans 4 chips, its dense clustered round,
and a chunk-streamed round, each compared with the same round on a
one-chip mesh — at the paper's 100/7 population and at 112 clients
(16 per profile). It prints which path each round actually took; a
fall-back to one device is reported, never passed over.

Each phase prints one line with its result, compile seconds, steady
seconds and ``peak_bytes_in_use`` (set-up information, not metrics).
A failed check raises and the script exits non-zero. The last line of
a passing run is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import kld as kld_mod  # noqa: E402
from repro.core.clustering import (cluster_activations,  # noqa: E402
                                   k_selection_bound)
from repro.core.federation import federate_client_params_device  # noqa: E402
from repro.core.huscf import HuSCFConfig, HuSCFTrainer  # noqa: E402
from repro.core.latency import PAPER_DEVICES  # noqa: E402
from repro.data import build_scenario  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_federation_mesh  # noqa: E402
from repro.launch.serve_split import (SERVE_MIXES, ServeRequest,  # noqa: E402
                                      SplitGanEngine, SplitLMConfig,
                                      build_mix, init_gan_serving_state,
                                      init_split_lm, lm_reference_logits,
                                      monolithic_forward,
                                      split_lm_decode_logits,
                                      split_lm_generate)
from repro.models.gan import DISC_MIDDLE_FEATURES, NUM_CLASSES, Z_DIM  # noqa: E402
from repro.sharding.policy import client_axes  # noqa: E402

NETS = ("G", "D")


class SmokeFailure(AssertionError):
    pass


def _check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _peak_bytes():
    stats = jax.devices()[0].memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def _report(phase: str, compile_s: float, steady_s: float, detail: str):
    print(f"[{phase}] ok compile_s={compile_s:.3f} steady_s={steady_s:.6f} "
          f"peak_bytes_in_use={_peak_bytes()} {detail}", flush=True)


def _timed(fn, *args):
    t = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t


def _max_diff(a, b) -> float:
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    _check(ta == tb, f"tree structures differ: {ta} vs {tb}")
    return max(float(jnp.max(jnp.abs(x.astype(jnp.float32)
                                     - y.astype(jnp.float32))))
               for x, y in zip(la, lb))


def _all_finite(tree) -> bool:
    return all(bool(np.isfinite(np.asarray(x)).all())
               for x in jax.tree_util.tree_leaves(tree))


def _client_params(tr: HuSCFTrainer):
    """{group: {net: tree}} — a device copy, so that a donating round
    cannot invalidate it."""
    return {g.name: {net: jax.tree_util.tree_map(
        jnp.copy, tr.state[net]["client"][g.name]) for net in NETS}
        for g in tr.groups}


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def check_platform(devices=None) -> dict:
    devs = jax.devices() if devices is None else devices
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    print(f"[device] platform={info['platform']} kind={info['kind']} "
          f"count={info['count']}", flush=True)
    if info["platform"] != "tpu":
        raise SystemExit(f"chip_smoke: platform is {info['platform']!r}, "
                         "not 'tpu'; nothing was run")
    return info


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def _paper_trainer(num_clients: int, batch: int, steps: int, base_size: int,
                   mesh=None, **cfg) -> HuSCFTrainer:
    clients = build_scenario("2dom_noniid", num_clients=num_clients,
                             base_size=base_size, seed=0)
    devices = [PAPER_DEVICES[i % len(PAPER_DEVICES)]
               for i in range(num_clients)]
    config = HuSCFConfig(batch=batch, use_kernel=True,
                         steps_per_epoch=steps, **cfg)
    return HuSCFTrainer(clients, devices, config=config, fed_mesh=mesh)


def _domain_blobs(tr: HuSCFTrainer, seed: int = 7) -> np.ndarray:
    """A seeded [K, F] EMA with one tight blob per data domain: both
    k-means implementations reach the same partition from any seeding
    only on separated data, so the fused-vs-oracle check runs here."""
    rng = np.random.default_rng(seed)
    domains = sorted({c.domain for c in tr.clients})
    side = np.array([-5.0 if c.domain == domains[0] else 5.0
                     for c in tr.clients])
    noise = rng.normal(0, 0.3, (len(tr.clients), DISC_MIDDLE_FEATURES))
    return (side[:, None] + noise).astype(np.float32)


def phase_train(num_clients: int = 100, batch: int = 32, steps: int = 2,
                base_size: int = 600) -> dict:
    t = time.perf_counter()
    tr = _paper_trainer(num_clients, batch, steps, base_size,
                        federate_every=1, reoptimize_every=1)
    init_s = time.perf_counter() - t
    cfg = tr.cfg
    epoch_s, losses = [], []
    for _ in range(cfg.warmup_fed_rounds):
        t = time.perf_counter()
        losses.append(tr.train_epoch())
        jax.block_until_ready(tr.state)
        epoch_s.append(time.perf_counter() - t)
    t = time.perf_counter()
    losses.append(tr.train_steps(steps))
    diag = tr.federate()
    jax.block_until_ready(tr.state)
    epoch_s.append(time.perf_counter() - t)
    _check(diag["mode"] == "clustered",
           f"round {diag['round']} past the warm-up ran {diag['mode']!r}")
    _check(all(np.isfinite(v) for m in losses for v in m.values()),
           f"non-finite losses: {losses}")
    _check(_all_finite(tr.state), "non-finite trainer state after the "
           "clustered round")

    # fused stage 3+4 vs the numpy oracle on one seeded EMA
    ema = _domain_blobs(tr)
    sizes = np.asarray(tr.sizes, np.float64)
    fn = tr._get_cluster_fn()       # the jitted chain federate() runs
    args = (jnp.asarray(ema), jnp.asarray(sizes, jnp.float32),
            jax.random.PRNGKey(cfg.seed + 7))
    (labels, k_sel, _, weights, _), cluster_s = _timed(fn, *args)
    if jax.default_backend() == "tpu":
        _check(_has_kernel(fn.lower(*args).compile()),
               "kmeans_assign did not compile to a TPU kernel")
    oracle = cluster_activations(ema, seed=cfg.seed)
    w_oracle, _ = kld_mod.activation_weights(ema, sizes, oracle.labels,
                                             cfg.beta)
    _check(int(k_sel) == oracle.k, f"k {int(k_sel)} != oracle {oracle.k}")
    _check(np.array_equal(np.asarray(labels), oracle.labels),
           "cluster labels differ from the numpy oracle")
    w_err = float(np.max(np.abs(np.asarray(weights, np.float64)
                                - w_oracle)))
    _check(w_err <= 1e-4, f"weights differ from the oracle by {w_err}")

    # Pallas aggregation vs jnp on the trained client params
    params = _client_params(tr)
    bound = k_selection_bound(len(tr.clients))
    agg = {use_kernel: federate_client_params_device(
        tr.groups, params, weights, labels, bound, use_kernel=use_kernel)
        for use_kernel in (True, False)}
    p_err = _max_diff(agg[True], agg[False])
    _check(p_err <= 5e-4, f"kernel vs jnp aggregation differ by {p_err}")
    _report("train", epoch_s[0], min(epoch_s[1:]),
            f"clients={len(tr.clients)} groups={len(tr.groups)} "
            f"scan_unroll={tr._epoch_unroll(steps)} "
            f"init_s={init_s:.3f} epoch_s={[round(s, 3) for s in epoch_s]} "
            f"loss_d={losses[-1]['loss_d']:.4f} "
            f"loss_g={losses[-1]['loss_g']:.4f} "
            f"round_k={int(diag['k'])} recut={diag.get('recut')} "
            f"oracle: k={oracle.k} weight_err={w_err:.2e} "
            f"cluster_s={cluster_s:.3f} kernel_vs_jnp_param_err={p_err:.2e}")
    return {"k": oracle.k, "weight_err": w_err, "param_err": p_err}


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def phase_serve(n_requests: int = 16, seed: int = 0) -> dict:
    errs = {}
    for mix in sorted(SERVE_MIXES):
        groups = build_mix(mix)
        client, server = init_gan_serving_state(jax.random.PRNGKey(seed),
                                                groups)
        engine = SplitGanEngine(groups, client, server)
        rng = np.random.default_rng(seed)
        n_clients = sum(g.size for g in groups)
        reqs = [ServeRequest(int(rng.integers(0, n_clients)),
                             rng.normal(0, 1, Z_DIM).astype(np.float32),
                             int(rng.integers(0, NUM_CLASSES)))
                for _ in range(n_requests)]
        # the engine and its oracle both in f32: TPU's default f32
        # matmul/conv is one bf16 pass
        with jax.default_matmul_precision("highest"):
            _, compile_s = _timed(engine.serve, reqs)
            imgs, steady_s = _timed(engine.serve, reqs)
            want = np.stack([monolithic_forward(groups, client, server, r)
                             for r in reqs])
        _check(imgs.shape == (n_requests, 28, 28, 1),
               f"{mix}: images {imgs.shape}")
        err = float(np.max(np.abs(imgs - want)))
        _check(err <= 1e-4, f"{mix}: engine vs monolithic forward differ "
               f"by {err}")
        errs[mix] = err
        _report(f"serve:{mix}", compile_s, steady_s,
                f"requests={n_requests} cuts={len(groups)} max_err={err:.2e}")
    return errs


# ---------------------------------------------------------------------------
# lm
# ---------------------------------------------------------------------------

def phase_lm(batch: int = 3, seq: int = 40, prompt: int = 24,
             gen: int = 8, seed: int = 3) -> dict:
    cfg = SplitLMConfig(s_max=96)
    params = init_split_lm(jax.random.PRNGKey(seed), cfg)
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (batch, seq)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        t = time.perf_counter()
        decode = jax.jit(lambda p, x: split_lm_decode_logits(
            cfg, p, x, prompt)).lower(params, toks).compile()
        compile_s = time.perf_counter() - t
        if jax.default_backend() == "tpu":
            _check(_has_kernel(decode),
                   "split-LM tail did not compile to TPU kernels")
        jax.block_until_ready(decode(params, toks))
        eng, steady_s = _timed(decode, params, toks)
        want = jax.jit(lambda p, x: lm_reference_logits(cfg, p, x))(
            params, toks)[:, prompt - 1:seq - 1]
        err = float(jnp.max(jnp.abs(eng - want)))
        _check(bool(jnp.all(jnp.abs(eng - want)
                            <= 2e-4 + 2e-4 * jnp.abs(want))),
               f"split-LM logits differ from the dense oracle by {err}")
        head = toks[:, :prompt]
        out = np.asarray(jax.jit(lambda p, x: split_lm_generate(
            cfg, p, x, gen))(params, head))
        full = jnp.concatenate([head, jnp.asarray(out)], axis=1)
        forced = np.asarray(jax.jit(lambda p, x: split_lm_decode_logits(
            cfg, p, x, prompt))(params, full))
    _check(np.array_equal(out, np.argmax(forced, -1)),
           "greedy generation differs from the teacher-forced argmax")
    _report("lm", compile_s, steady_s,
            f"batch={batch} seq={seq} prompt={prompt} gen={gen} "
            f"max_logit_err={err:.2e} greedy==teacher_forced")
    return {"err": err}


# ---------------------------------------------------------------------------
# four chips: client-axis-sharded step + rounds
# ---------------------------------------------------------------------------

def _path(plans, n_dev: int) -> str:
    axes = {str(p.shard_axes) for p in plans.values()}
    if axes == {"None"}:
        return "one device (FELL BACK)" if n_dev > 1 else "one device"
    return f"sharded over {'/'.join(sorted(axes))} ({n_dev} devices)"


def phase_four_chip(n_dev: int = 4, populations=(100, 112), batch: int = 32,
                    chunk: int = 8, base_size: int = 600) -> dict:
    mesh, mesh1 = make_federation_mesh(n_dev), make_federation_mesh(1)
    result = {}
    for K in populations:
        t = time.perf_counter()
        tr = _paper_trainer(K, batch, 1, base_size, mesh=mesh,
                            warmup_fed_rounds=0)
        sizes = [g.size for g in tr.groups]
        sharded_rows = sum(client_axes(mesh, s) is not None for s in sizes)
        tr.train_steps(1)
        jax.block_until_ready(tr.state)
        first_s = time.perf_counter() - t
        t = time.perf_counter()
        losses = tr.train_steps(1)
        jax.block_until_ready(tr.state)
        step_s = time.perf_counter() - t
        _check(all(np.isfinite(v) for v in losses.values()),
               f"K={K}: non-finite step losses {losses}")
        pre = _client_params(tr)
        t = time.perf_counter()
        diag = tr.federate()            # the trainer's dense round
        jax.block_until_ready(tr.state)
        round_s = time.perf_counter() - t
        dense4 = {g.name: {net: tr.state[net]["client"][g.name]
                           for net in NETS} for g in tr.groups}
        w, lab = diag["weights"], diag["labels"]
        bound = k_selection_bound(K)
        runs, plans = {}, {}
        for name, m, c in (("dense@1", mesh1, None),
                           (f"chunked({chunk})@{n_dev}", mesh, chunk),
                           (f"chunked({chunk})@1", mesh1, chunk)):
            plans[name] = {}
            runs[name] = federate_client_params_device(
                tr.groups, pre, w, lab, bound, use_kernel=True, mesh=m,
                chunk_size=c, plan_cache=plans[name])
        errs = {
            "dense": _max_diff(dense4, runs["dense@1"]),
            "chunked": _max_diff(runs[f"chunked({chunk})@{n_dev}"],
                                 runs[f"chunked({chunk})@1"]),
            "chunked_vs_dense": _max_diff(runs[f"chunked({chunk})@{n_dev}"],
                                          runs["dense@1"])}
        for what, e in errs.items():
            _check(e <= 5e-4, f"K={K}: {what} rounds differ by {e}")
        paths = {
            "train step": (f"dataset rows sharded for {sharded_rows}/"
                           f"{len(sizes)} groups, the rest replicated"),
            f"dense round @{n_dev}": _path(tr._fed_plans, n_dev),
            f"chunked round @{n_dev}": _path(
                plans[f"chunked({chunk})@{n_dev}"], n_dev)}
        for what, p in paths.items():
            print(f"[four-chip] K={K} group_sizes={sizes} {what}: {p}",
                  flush=True)
        _report(f"four-chip:K={K}", first_s, step_s,
                f"round_s={round_s:.3f} k={int(diag['k'])} "
                + " ".join(f"{k}_err={v:.2e}" for k, v in errs.items()))
        result[K] = {"paths": paths, "errs": errs}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the client-axis-sharded path on 4 chips")
    args = ap.parse_args(argv)
    info = check_platform()
    cache = enable_compile_cache()
    entries = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    print(f"[cache] dir={cache} entries_at_start={entries}", flush=True)
    if args.four_chip:
        _check(info["count"] >= 4, f"--four-chip needs 4 chips, found "
               f"{info['count']}")
        phase_four_chip()
    else:
        phase_train()
        phase_serve()
        phase_lm()
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
