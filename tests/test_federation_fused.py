"""Fused single-dispatch federation round vs the legacy quadruple-loop
oracle: equivalence over heterogeneous cuts and >=3 clusters, the
paper population's seven groups (host weights, device weights, FedAvg
and cohort rounds, with and without the Pallas kernel), the
zero-weight-sum fallback, fedavg as the degenerate single-cluster
case, and plan caching."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import federation as fed
from repro.core.federation import (FederationPlan, federate_client_params,
                                   federate_client_params_device,
                                   fedavg_uniform, get_federation_plan)
from repro.core.latency import Cut, PAPER_DEVICES
from repro.core.splitting import (client_owned_layers, group_by_profile,
                                  layer_pair)
from repro.models.gan import DISC_LAYER_DEFS, GEN_LAYER_DEFS

N_LAYERS = {"G": 5, "D": 5}
# heterogeneous cuts -> 4 profile groups with distinct owned-layer sets
HET_CUTS = (Cut(1, 3, 1, 3), Cut(2, 4, 2, 4), Cut(1, 4, 2, 3),
            Cut(2, 3, 1, 4))
# the benchmark population's cuts (bench/configs/cgan28-paper100.json):
# device1 owns no G layer 1 or 3 and no D layer 1 or 3, device2 no D
# layer 1
PAPER_CUTS = (Cut(1, 4, 1, 4), Cut(2, 3, 1, 3)) + (Cut(2, 3, 2, 3),) * 5


def build_population(n_clients, n_profiles, seed=0, cuts_cycle=HET_CUTS):
    devices = [PAPER_DEVICES[i % n_profiles] for i in range(n_clients)]
    cuts = [cuts_cycle[i % n_profiles] for i in range(n_clients)]
    groups = group_by_profile(devices, cuts)
    key = jax.random.PRNGKey(seed)
    params = {}
    for net, defs in (("G", GEN_LAYER_DEFS), ("D", DISC_LAYER_DEFS)):
        for g in groups:
            params.setdefault(g.name, {}).setdefault(net, {})
            for l in client_owned_layers(layer_pair(g.cut, net), 5):
                key, sub = jax.random.split(key)
                params[g.name][net][str(l)] = jax.vmap(
                    lambda kk, l=l: defs[l][0](kk, jnp.float32))(
                        jax.random.split(sub, g.size))
    return groups, params


def paper_template_shapes(net, n_clients=100, sharding=None):
    """(groups, template) of the benchmark population at its real size,
    as shapes only: every group's stacked client leaves of ``net`` as
    ``jax.ShapeDtypeStruct`` (for lowering and compiling a round
    without allocating it)."""
    devices = [PAPER_DEVICES[i % 7] for i in range(n_clients)]
    groups = group_by_profile(devices, [PAPER_CUTS[i % 7]
                                        for i in range(n_clients)])
    defs = GEN_LAYER_DEFS if net == "G" else DISC_LAYER_DEFS
    template = {}
    for g in groups:
        template[g.name] = {}
        for l in client_owned_layers(layer_pair(g.cut, net), 5):
            tree = jax.eval_shape(lambda k, l=l: defs[l][0](k, jnp.float32),
                                  jax.random.PRNGKey(0))
            template[g.name][str(l)] = jax.tree_util.tree_map(
                lambda x, n=g.size: jax.ShapeDtypeStruct(
                    (n,) + x.shape, x.dtype, sharding=sharding), tree)
    return groups, template


def assert_trees_close(got, want, atol=1e-5):
    gl, gt = jax.tree_util.tree_flatten(got)
    wl, wt = jax.tree_util.tree_flatten(want)
    assert gt == wt
    for g, w in zip(gl, wl):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32), atol=atol)


@pytest.fixture(scope="module")
def population():
    return build_population(n_clients=9, n_profiles=3)


@pytest.mark.parametrize("use_kernel", [False, True])
def test_fused_matches_legacy_heterogeneous(population, use_kernel):
    groups, params = population
    rng = np.random.default_rng(1)
    K = sum(g.size for g in groups)
    labels = np.arange(K) % 3          # >= 3 clusters
    weights = rng.random(K)
    want = federate_client_params(groups, params, weights, labels,
                                  n_layers=N_LAYERS, fused=False)
    got = federate_client_params(groups, params, weights, labels,
                                 n_layers=N_LAYERS, use_kernel=use_kernel)
    assert_trees_close(got, want)


def test_fused_zero_weight_sum_fallback(population):
    """A cluster whose Eq.-15 weights sum to zero falls back to the
    uniform average — identically on both paths."""
    groups, params = population
    K = sum(g.size for g in groups)
    labels = np.arange(K) % 3
    weights = np.random.default_rng(2).random(K)
    weights[labels == 1] = 0.0
    want = federate_client_params(groups, params, weights, labels,
                                  n_layers=N_LAYERS, fused=False)
    got = federate_client_params(groups, params, weights, labels,
                                 n_layers=N_LAYERS)
    assert_trees_close(got, want)


def test_fedavg_uniform_is_single_cluster_case(population):
    groups, params = population
    K = sum(g.size for g in groups)
    sizes = np.random.default_rng(3).integers(10, 100, K)
    want = fedavg_uniform(groups, params, sizes, n_layers=N_LAYERS,
                          fused=False)
    got = fedavg_uniform(groups, params, sizes, n_layers=N_LAYERS)
    assert_trees_close(got, want)
    # degenerate = federate with one global cluster + size weights
    via_federate = federate_client_params(
        groups, params, sizes / sizes.sum(), np.zeros(K, np.int64),
        n_layers=N_LAYERS)
    assert_trees_close(got, via_federate, atol=0)


def test_aggregate_preserves_copies_within_cluster(population):
    """After a round every member of a (layer, cluster) block holds the
    same aggregated copy."""
    groups, params = population
    K = sum(g.size for g in groups)
    labels = np.arange(K) % 2
    weights = np.ones(K)
    out = federate_client_params(groups, params, weights, labels,
                                 n_layers={"G": 5})
    cid_of = {g.name: g.client_ids for g in groups}
    seen = {}
    for g in groups:
        for l, tree in out[g.name]["G"].items():
            leaves = jax.tree_util.tree_leaves(tree)
            for pos, cid in enumerate(cid_of[g.name]):
                key = (l, labels[cid])
                sig = np.asarray(leaves[0][pos]).ravel()[:8].copy()
                if key in seen:
                    np.testing.assert_allclose(sig, seen[key], atol=1e-6)
                else:
                    seen[key] = sig


def test_plan_cache_reuse_and_layout(population):
    groups, params = population
    cache = {}
    tmpl = {g.name: params[g.name]["G"] for g in groups}
    p1 = get_federation_plan(groups, "G", 5, tmpl, plan_cache=cache)
    p2 = get_federation_plan(groups, "G", 5, tmpl, plan_cache=cache)
    assert p1 is p2 and len(cache) == 1
    assert p1.n_rows == sum(g.size for g in groups)
    # every (group, layer) ownership gets exactly one entry
    n_entries = sum(
        len(client_owned_layers(layer_pair(g.cut, "G"), 5)) for g in groups)
    assert len(p1.entries) == n_entries
    assert p1.n_copies == sum(g.size * len(client_owned_layers(
        layer_pair(g.cut, "G"), 5)) for g in groups)
    # leafwise layout: each ownable layer lists exactly its owner
    # groups, whose rows partition the layer's owner rows, and the
    # round reads only owned rows (no zero columns)
    for (l, rows, _), (l2, owners) in zip(p1._layer_rows,
                                          p1._layer_entries):
        assert l == l2 and owners
        assert {e.gname for e in owners} == {
            g.name for g in groups
            if l in client_owned_layers(layer_pair(g.cut, "G"), 5)}
        assert np.array_equal(np.concatenate(
            [np.arange(e.row0, e.row1) for e in owners]), rows)
    assert p1.dense_buffer_bytes() == 4 * sum(
        x.size for g in groups for x in jax.tree_util.tree_leaves(
            params[g.name]["G"]))


def test_weight_segments_block_structure(population):
    """A rows are normalized over each (layer, cluster) owner block and
    zero elsewhere; seg_ids only reference real segments."""
    groups, params = population
    K = sum(g.size for g in groups)
    labels = np.arange(K) % 3
    weights = np.random.default_rng(4).random(K)
    tmpl = {g.name: params[g.name]["G"] for g in groups}
    plan = FederationPlan(groups, "G", 5, tmpl)
    A, seg_ids = plan.weight_segments(weights, labels)
    assert A.shape[0] % fed._SEGMENT_PAD == 0
    n_real = int(seg_ids.max()) + 1
    np.testing.assert_allclose(A[:n_real].sum(1), 1.0, atol=1e-6)
    assert np.all(A[n_real:] == 0)
    assert A.shape[1] == plan.n_rows and len(seg_ids) == plan.n_copies


# --------------------------------------------------------------------------
# the paper population's seven groups: leafwise round vs the legacy loop
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def paper_population():
    """Two clients on each of the seven paper device profiles, with the
    benchmark's cuts and the real GAN layer shapes."""
    groups, params = build_population(14, 7, seed=5, cuts_cycle=PAPER_CUTS)
    assert len(groups) == 7
    rng = np.random.default_rng(8)
    return groups, params, rng.random(14), np.arange(14) % 3


def _legacy(groups, params, weights, labels):
    return federate_client_params(groups, params, weights, labels,
                                  n_layers=N_LAYERS, fused=False)


@pytest.mark.parametrize("use_kernel", [False, True])
def test_paper_groups_host_weights_round(paper_population, use_kernel):
    groups, params, weights, labels = paper_population
    got = federate_client_params(groups, params, weights, labels,
                                 n_layers=N_LAYERS, use_kernel=use_kernel)
    assert_trees_close(got, _legacy(groups, params, weights, labels))


@pytest.mark.parametrize("use_kernel", [False, True])
def test_paper_groups_device_weights_round(paper_population, use_kernel):
    """The in-jit weight matrix at the static cluster bound (more
    clusters than are used), the path the trainer's rounds take."""
    groups, params, weights, labels = paper_population
    w = weights.copy()
    for c in np.unique(labels):
        w[labels == c] /= w[labels == c].sum()
    got = federate_client_params_device(
        groups, params, jnp.asarray(w, jnp.float32),
        jnp.asarray(labels, jnp.int32), num_clusters=6, n_layers=N_LAYERS,
        use_kernel=use_kernel)
    assert_trees_close(got, _legacy(groups, params, weights, labels))


@pytest.mark.parametrize("use_kernel", [False, True])
def test_paper_groups_fedavg_round(paper_population, use_kernel):
    groups, params, _, _ = paper_population
    sizes = np.random.default_rng(9).integers(10, 100, 14)
    want = fedavg_uniform(groups, params, sizes, n_layers=N_LAYERS,
                          fused=False)
    got = fedavg_uniform(groups, params, sizes, n_layers=N_LAYERS,
                         use_kernel=use_kernel)
    assert_trees_close(got, want)


@pytest.mark.parametrize("use_kernel", [False, True])
def test_paper_groups_cohort_round(paper_population, use_kernel):
    """A sampled cohort: participants get the legacy loop's aggregate
    over the cohort's weights, the others keep their params bit for
    bit."""
    groups, params, weights, labels = paper_population
    mask = np.zeros(14, bool)
    mask[[0, 2, 3, 6, 7, 9, 12]] = True
    w = np.where(mask, weights, 0.0)
    got = federate_client_params(groups, params, w, labels,
                                 n_layers=N_LAYERS, use_kernel=use_kernel,
                                 cohort_mask=mask)
    want = _legacy(groups, params, w, labels)
    for g in groups:
        keep = ~mask[np.asarray(g.client_ids)]
        for net in N_LAYERS:
            for l, tree in params[g.name][net].items():
                for old, new, ref in zip(
                        jax.tree_util.tree_leaves(tree),
                        jax.tree_util.tree_leaves(got[g.name][net][l]),
                        jax.tree_util.tree_leaves(want[g.name][net][l])):
                    old, new = np.asarray(old), np.asarray(new)
                    assert np.array_equal(new[keep], old[keep])
                    np.testing.assert_allclose(new[~keep],
                                               np.asarray(ref)[~keep],
                                               atol=1e-5)
