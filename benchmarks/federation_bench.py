"""Federation-round macro-benchmark: fused single-dispatch path vs the
legacy per-(layer, cluster, leaf) loop, plus the client-axis-sharded
round at 1/2/4/8 host devices.

32 clients x the paper cGAN (~3M params across G+D client segments),
heterogeneous cuts (4 profile groups), 3 clusters — the server-side
hot spot of every federation round (Eq. 16). Reports warm wall-clock
per round; ``bench/federation_round`` carries the headline
fused-vs-legacy comparison for the perf trajectory.

Sharded section: on the CPU backend the forced host-device count is
fixed at backend init, so each device count runs in its own subprocess
(``python -m benchmarks.federation_bench --sharded-worker N`` under
``XLA_FLAGS=--xla_force_host_platform_device_count=N``) and reports
its warm round time back on stdout; the shards share one physical
socket, so those numbers track dispatch/collective overhead of the
shard_map path, not multi-chip scaling. On an accelerator the section
runs in process over the real devices (counts up to the visible
device count), and every row names the platform it ran on.

Chunked section (DESIGN.md §Chunk-streamed aggregation): a synthetic
population-scale round — 2 profile groups over a 2-layer net with
n_cols ~ 8192 — at 1k and 8k clients, streamed in chunks of 256. The
headline is the memory claim, not wall clock: the dense round reads
every owned client row and writes as many anew (`dense_buffer_bytes`),
which grows with the client count, while the chunk stream's working
set (`chunked_buffer_bytes`) is O(chunk + clusters). Against a 128 MB
working-set envelope the 8k dense rows do not fit, so their wall clock
is skipped and only the chunked round reports; at 1k both run and the
dense round is the wall-clock baseline. ``tiny=True``
(ci_smoke) keeps a 256-client / d=512 / chunk-64 variant of just this
section.
"""
from __future__ import annotations

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.kernel_bench import _bench
from repro.core.federation import federate_client_params
from repro.core.latency import Cut, PAPER_DEVICES
from repro.core.splitting import (client_owned_layers, group_by_profile,
                                  layer_pair)
from repro.models.gan import DISC_LAYER_DEFS, GEN_LAYER_DEFS

N_CLIENTS = 32
N_CLUSTERS = 3
N_LAYERS = {"G": 5, "D": 5}
_CUTS = (Cut(1, 3, 1, 3), Cut(2, 4, 2, 4), Cut(1, 4, 2, 3), Cut(2, 3, 1, 4))


def _build_population():
    devices = [PAPER_DEVICES[i % len(_CUTS)] for i in range(N_CLIENTS)]
    cuts = [_CUTS[i % len(_CUTS)] for i in range(N_CLIENTS)]
    groups = group_by_profile(devices, cuts)
    key = jax.random.PRNGKey(0)
    params = {}
    for net, defs in (("G", GEN_LAYER_DEFS), ("D", DISC_LAYER_DEFS)):
        for g in groups:
            params.setdefault(g.name, {}).setdefault(net, {})
            for l in client_owned_layers(layer_pair(g.cut, net), 5):
                key, sub = jax.random.split(key)
                keys = jax.random.split(sub, g.size)
                params[g.name][net][str(l)] = jax.vmap(
                    lambda kk, l=l: defs[l][0](kk, jnp.float32))(keys)
    # model size (one full G+D copy) for the scale label
    key = jax.random.PRNGKey(1)
    n_params = sum(
        x.size
        for defs in (GEN_LAYER_DEFS, DISC_LAYER_DEFS) for init, _ in defs
        for x in jax.tree_util.tree_leaves(init(key, jnp.float32)))
    return groups, params, n_params


def _round_inputs():
    """One source of truth for the benchmark round's inputs — the
    sharded worker subprocess must aggregate byte-identical weights/
    labels/population or its rows stop being comparable to fused_*."""
    groups, params, n_params = _build_population()
    rng = np.random.default_rng(0)
    weights = rng.random(N_CLIENTS)
    labels = np.arange(N_CLIENTS) % N_CLUSTERS
    return groups, params, n_params, weights, labels


def run(report, tiny=False):
    if tiny:
        _run_chunked(report, tiny=True)
        return
    _run_dense_vs_legacy(report)
    _run_chunked(report, tiny=False)


def _run_dense_vs_legacy(report):
    groups, params, n_params, weights, labels = _round_inputs()
    plans = {}

    def round_with(**kw):
        return federate_client_params(groups, params, weights, labels,
                                      n_layers=N_LAYERS, plan_cache=plans,
                                      **kw)

    us_fused = _bench(round_with, iters=3)
    us_kernel = _bench(lambda: round_with(use_kernel=True), iters=3)
    us_legacy = _bench(lambda: round_with(fused=False), iters=1)

    scale = f"{N_CLIENTS}c_{n_params/1e6:.1f}Mp"
    report(f"federation/fused_jnp_{scale}", us_fused, "1 jit/net")
    mode = "interpret" if jax.default_backend() == "cpu" else "compiled"
    report(f"federation/fused_kernel_{scale}", us_kernel,
           f"1 pallas_call/net ({mode})")
    report(f"federation/legacy_loop_{scale}", us_legacy,
           "per-(layer,cluster,leaf) dispatches")
    best = min(us_fused, us_kernel)
    report("bench/federation_round", best,
           f"legacy={us_legacy:.0f}us speedup={us_legacy / best:.2f}x")

    # --- sharded round. On the CPU backend: 1/2/4/8 forced host
    # devices, one subprocess per count (the device-count flag binds at
    # backend init). On an accelerator: in process, over the real
    # devices — forced-CPU children would measure the host instead.
    cpu = jax.default_backend() == "cpu"
    counts = (SHARDED_DEVICE_COUNTS if cpu else
              [n for n in SHARDED_DEVICE_COUNTS if n <= jax.device_count()])
    for n in counts:
        us = _run_sharded_worker(n) if cpu else _sharded_round_us(n)
        derived = ("single-device fallback (mesh of 1)" if n == 1 else
                   f"shard_map+psum, {N_CLIENTS // n} client rows/shard")
        platform = "forced-cpu" if cpu else jax.devices()[0].platform
        report(f"federation/sharded_round_{n}dev_{scale}", us,
               f"{derived}; {platform}")


# ---------------------------------------------------------------------------
# chunk-streamed population-scale section
# ---------------------------------------------------------------------------

# (n_clients, chunk, f32-per-layer): 2 layers -> n_cols = 2 * d_layer
CHUNK_SCALES = ((1024, 256, 4096), (8192, 256, 4096))
CHUNK_SCALES_TINY = ((256, 64, 256),)
MEM_ENVELOPE_BYTES = 128 * 2 ** 20


def _chunk_population(n_clients, d_layer, seed=0):
    """Synthetic 2-group population over a 2-layer net: cut (1,2) owns
    layer 0 only, cut (2,2) owns both — heterogeneous ownership with
    the smallest possible layer count, so the buffers are all client
    rows, not model depth."""
    half = n_clients // 2
    devices = ([PAPER_DEVICES[0]] * half
               + [PAPER_DEVICES[1]] * (n_clients - half))
    cuts = ([Cut(1, 2, 1, 2)] * half
            + [Cut(2, 2, 2, 2)] * (n_clients - half))
    groups = group_by_profile(devices, cuts)
    rng = np.random.default_rng(seed)
    params = {}
    for g in groups:
        owned = client_owned_layers((g.cut.g_h, g.cut.g_t), 2)
        params[g.name] = {"G": {
            str(l): {"w": jnp.asarray(rng.standard_normal(
                (g.size, d_layer), dtype=np.float32))}
            for l in owned}}
    weights = rng.random(n_clients)
    labels = np.arange(n_clients) % N_CLUSTERS
    return groups, params, weights, labels


def _run_chunked(report, tiny):
    from repro.core.federation import get_federation_plan
    for n_clients, chunk, d_layer in (CHUNK_SCALES_TINY if tiny
                                      else CHUNK_SCALES):
        groups, params, weights, labels = _chunk_population(n_clients,
                                                            d_layer)
        tmpl = {g.name: params[g.name]["G"] for g in groups}
        cache = {}
        plan = get_federation_plan(groups, "G", 2, tmpl, plan_cache=cache,
                                   chunk_size=chunk)
        dense_b = plan.dense_buffer_bytes()
        work_b = plan.chunked_buffer_bytes(N_CLUSTERS)
        mem = (f"workset={work_b / 2**20:.2f}MB "
               f"dense={dense_b / 2**20:.1f}MB "
               f"ratio={dense_b / work_b:.0f}x")

        def fed(**kw):
            return federate_client_params(groups, params, weights, labels,
                                          n_layers={"G": 2},
                                          plan_cache=cache, **kw)

        us_chunk = _bench(lambda: fed(chunk_size=chunk), iters=2)
        scale = f"{n_clients}c_chunk{chunk}_d{2 * d_layer}"
        if dense_b <= MEM_ENVELOPE_BYTES:
            us_dense = _bench(lambda: fed(), iters=2)
            report(f"federation/chunked_round_{scale}", us_chunk,
                   f"{mem}; dense round {us_dense:.0f}us "
                   f"({us_chunk / us_dense:.2f}x)")
            report(f"federation/dense_round_{n_clients}c_d{2 * d_layer}",
                   us_dense, mem)
        else:
            report(f"federation/chunked_round_{scale}", us_chunk,
                   f"{mem}; dense buffer exceeds the "
                   f"{MEM_ENVELOPE_BYTES / 2**20:.0f}MB envelope -> "
                   "dense wall clock skipped")


# ---------------------------------------------------------------------------
# client-axis-sharded section
# ---------------------------------------------------------------------------

SHARDED_DEVICE_COUNTS = (1, 2, 4, 8)


def _run_sharded_worker(n_devices: int) -> float:
    from repro.launch.mesh import forced_device_env
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = forced_device_env(n_devices, [os.path.join(root, "src")])
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.federation_bench",
         "--sharded-worker", str(n_devices)],
        env=env, cwd=root, capture_output=True, text=True, timeout=1800)
    if proc.returncode != 0:
        raise RuntimeError(
            f"sharded worker ({n_devices} dev) failed:\n{proc.stdout}\n"
            f"{proc.stderr}")
    for line in reversed(proc.stdout.splitlines()):
        if line.startswith("SHARDED_US="):
            return float(line.split("=", 1)[1])
    raise RuntimeError(f"sharded worker emitted no SHARDED_US line:\n"
                       f"{proc.stdout}")


def _sharded_round_us(n_devices: int) -> float:
    """Warm round time on a federation mesh of the first ``n_devices``
    visible devices."""
    from repro.launch.mesh import make_federation_mesh
    groups, params, _, weights, labels = _round_inputs()
    mesh = make_federation_mesh(n_devices)
    plans = {}
    return _bench(lambda: federate_client_params(
        groups, params, weights, labels, n_layers=N_LAYERS,
        plan_cache=plans, mesh=mesh), iters=3)


def _sharded_worker_main(n_devices: int) -> None:
    assert jax.device_count() == n_devices, \
        f"worker saw {jax.device_count()} devices, wanted {n_devices}"
    print(f"SHARDED_US={_sharded_round_us(n_devices)}", flush=True)


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--sharded-worker", type=int, default=None,
                    metavar="N_DEVICES")
    args = ap.parse_args()
    if args.sharded_worker is not None:
        _sharded_worker_main(args.sharded_worker)
    else:
        run(lambda name, v, d="": print(f"{name},{v:.3f},{d}"))
