"""Stage-3/4 clustered-round macro-benchmark: host-numpy path vs the
device-resident jitted path (DESIGN.md §Device-resident clustering).

One "cluster round" is everything between the trained step and the
aggregated params: middle-activation EMA -> k-means + silhouette
k-selection -> Eq. 13-15 KLD weighting -> Eq. 16 clustered aggregation.
The host path reads the [K, F] EMA back, clusters/weights in numpy,
builds the block-diagonal weight matrix on the host and re-dispatches;
the fused path runs the same chain as two dispatches (one jitted
cluster+weight call, one jitted in-jit-weight-matrix aggregation per
net) with labels/weights never leaving the device.

Population: 3 activation domains at the paper's F=6272 EMA width,
heterogeneous cuts (4 profile groups). Client segments use small dense
layers rather than the full cGAN so the 128-client round fits the CI
container — the federation_bench section already carries the
full-model aggregation numbers; this section isolates the stage-3/4
host hop. ``bench/cluster_round`` reports the headline fused-vs-numpy
speedup at the largest client count run (128, or 32 under ``tiny``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.kernel_bench import _bench
from repro.core import kld as kld_mod
from repro.core.clustering import (cluster_activations,
                                   cluster_activations_jax,
                                   k_selection_bound)
from repro.core.federation import (federate_client_params,
                                   federate_client_params_device)
from repro.core.latency import Cut, PAPER_DEVICES
from repro.core.splitting import (client_owned_layers, group_by_profile,
                                  layer_pair)

EMA_FEATURES = 6272                    # the GAN's D-middle 7x7x128 width
N_LAYERS = {"G": 5}
LAYER_SHAPE = (64, 64)                 # small dense per-layer segments
BETA = 150.0
_CUTS = (Cut(1, 3, 1, 3), Cut(2, 4, 2, 4), Cut(1, 4, 2, 3), Cut(2, 3, 1, 4))


def _build_population(n_clients: int, seed: int = 0):
    devices = [PAPER_DEVICES[i % len(_CUTS)] for i in range(n_clients)]
    cuts = [_CUTS[i % len(_CUTS)] for i in range(n_clients)]
    groups = group_by_profile(devices, cuts)
    key = jax.random.PRNGKey(seed)
    params = {}
    for g in groups:
        params[g.name] = {"G": {}}
        for l in client_owned_layers(layer_pair(g.cut, "G"), 5):
            key, sub = jax.random.split(key)
            params[g.name]["G"][str(l)] = {
                "w": jax.random.normal(sub, (g.size,) + LAYER_SHAPE,
                                       jnp.float32)}
    # 3 separated activation domains + per-client sizes
    rng = np.random.default_rng(seed)
    per = -(-n_clients // 3)
    acts = np.vstack([rng.normal(0, 0.3, (per, EMA_FEATURES)) + off
                      for off in (-6, 0, 6)])[:n_clients]
    acts_dev = jnp.asarray(acts, jnp.float32)
    sizes = rng.integers(50, 700, n_clients)
    return groups, params, acts_dev, sizes


def _run_scale(report, n_clients: int):
    groups, params, acts_dev, sizes = _build_population(n_clients)
    sizes_dev = jnp.asarray(sizes, jnp.float32)
    bound = k_selection_bound(n_clients)
    key = jax.random.PRNGKey(1)
    plans_host, plans_dev, plans_ker = {}, {}, {}

    def host_round():
        # EMA readback + numpy stage 3/4 + host-built weight matrix
        acts = np.asarray(acts_dev)
        cl = cluster_activations(acts, seed=0)
        w, _ = kld_mod.activation_weights(acts, sizes, cl.labels, BETA)
        return federate_client_params(groups, params, w, cl.labels,
                                      n_layers=N_LAYERS,
                                      plan_cache=plans_host)

    @jax.jit
    def _cluster_weight(acts, sizes, key):
        labels, k_sel, sil = cluster_activations_jax(acts, key)
        w, klds = kld_mod.activation_weights_jax(acts, sizes, labels,
                                                 bound, BETA)
        return labels, w

    def device_round(use_kernel=False, plans=None):
        labels, w = _cluster_weight(acts_dev, sizes_dev, key)
        return federate_client_params_device(
            groups, params, w, labels, bound, n_layers=N_LAYERS,
            use_kernel=use_kernel, plan_cache=plans)

    us_host = _bench(host_round, iters=3)
    us_dev = _bench(lambda: device_round(plans=plans_dev), iters=3)
    us_ker = _bench(lambda: device_round(use_kernel=True, plans=plans_ker),
                    iters=3)

    scale = f"{n_clients}c"
    report(f"cluster/host_numpy_{scale}", us_host,
           "EMA readback + numpy kmeans/silhouette/KLD + host W")
    report(f"cluster/fused_jit_{scale}", us_dev, "2 dispatches, in-jit W")
    mode = "interpret" if jax.default_backend() == "cpu" else "compiled"
    report(f"cluster/fused_kernel_{scale}", us_ker,
           f"pallas kmeans_assign + clustered_agg ({mode})")
    return us_host, min(us_dev, us_ker)


def run(report, tiny: bool = False):
    scales = (32,) if tiny else (32, 128)
    us_host = us_fused = None
    for n in scales:
        us_host, us_fused = _run_scale(report, n)
    report("bench/cluster_round", us_fused,
           f"{scales[-1]}c host={us_host:.0f}us "
           f"speedup={us_host / us_fused:.2f}x")


if __name__ == "__main__":
    run(lambda name, v, d="": print(f"{name},{v:.3f},{d}"))
