"""Chunk-streamed clustered aggregation vs the dense fused path and
the legacy numpy-loop oracle (DESIGN.md §Chunk-streamed aggregation).

The chunked path never holds every client row at once, as the dense
round does: a ``lax.scan`` over fixed-size client chunks accumulates
per-segment weighted partial sums and weight masses, and one normalize
at the end divides them out. Summation is therefore *re-associated*
relative to the dense round, so equivalence is tolerance-bounded
(f32 accumulator, observed max-abs ~1e-7 on GAN-sized layers), not
bit-exact — except where a case is engineered to take the identical
compute path, which is asserted byte-identical.

Matrix covered here:
  * chunk sizes 1, small, = K, > K and non-divisible tails, with and
    without the Pallas ``clustered_agg`` kernel, host and device entry
    points;
  * hypothesis property twin over arbitrary (n_clients, chunk_size)
    when hypothesis is installed (bare env: the deterministic sweep
    above is the same assertion on a pinned grid);
  * cohort rounds: full-participation mask is byte-identical to no
    mask, device-dense vs chunked agree at the paper's beta=150 (both
    f32 — the host f64 oracle is only comparable at moderate beta, see
    the f32-underflow note in DESIGN.md), non-members come back
    bit-identical to their pre-round params;
  * a compiled trainer round with ``agg_chunk`` + ``cohort_size`` runs
    under ``jax.transfer_guard('disallow_explicit')`` — streaming adds
    zero host<->device syncs;
  * plan-cache keying on (chunk_size, cohort_size);
  * multihost twin: the chunked scan composes with the client-axis
    ``shard_map`` at 2/4/8 forced CPU devices, and a group size not
    divisible by the mesh falls back (``_chunk_axes is None``) to the
    unsharded stream byte-identically.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import kld as kldm
from repro.core.federation import (federate_client_params,
                                   federate_client_params_device,
                                   fedavg_uniform, get_federation_plan)
from repro.core.registry import ClientRegistry
from test_federation_fused import (N_LAYERS, assert_trees_close,
                                   build_population)

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                       # bare env: deterministic grid only
    given = None

MODULE = "test_federation_chunked"
K = 9                                     # module population size
N_CLUSTERS = 3


@pytest.fixture(scope="module")
def population():
    groups, params = build_population(n_clients=K, n_profiles=3)
    rng = np.random.default_rng(11)
    weights = rng.random(K)
    labels = np.arange(K) % N_CLUSTERS
    return groups, params, weights, labels


@pytest.fixture(scope="module")
def dense_and_legacy(population):
    groups, params, weights, labels = population
    legacy = federate_client_params(groups, params, weights, labels,
                                    n_layers=N_LAYERS, fused=False)
    dense = federate_client_params(groups, params, weights, labels,
                                   n_layers=N_LAYERS)
    return legacy, dense


# --------------------------------------------------------------------------
# chunked == dense fused == legacy oracle
# --------------------------------------------------------------------------

@pytest.mark.parametrize("chunk", [1, 2, 4, K, K + 5])
def test_chunked_matches_dense_and_legacy(population, dense_and_legacy,
                                          chunk):
    """Every chunk size — including 1 (pure streaming), a non-divisible
    tail (4 over per-group counts of 3), = K and > K (single padded
    chunk) — reproduces both oracles to f32-reassociation tolerance."""
    groups, params, weights, labels = population
    legacy, dense = dense_and_legacy
    got = federate_client_params(groups, params, weights, labels,
                                 n_layers=N_LAYERS, chunk_size=chunk)
    assert_trees_close(got, dense, atol=1e-5)
    assert_trees_close(got, legacy, atol=1e-5)


def test_chunked_kernel_matches_dense(population, dense_and_legacy):
    """The Pallas clustered_agg kernel per chunk agrees with the jnp
    matmul chunk body and with the dense round."""
    groups, params, weights, labels = population
    _, dense = dense_and_legacy
    got = federate_client_params(groups, params, weights, labels,
                                 n_layers=N_LAYERS, chunk_size=3,
                                 use_kernel=True)
    assert_trees_close(got, dense, atol=1e-5)


def test_chunked_device_entry_point(population, dense_and_legacy):
    """federate_client_params_device(chunk_size=) — device weights and
    labels in, no host numpy — matches the dense device round."""
    groups, params, weights, labels = population
    w = jnp.asarray(weights, jnp.float32)
    l = jnp.asarray(labels, jnp.int32)
    dense = federate_client_params_device(groups, params, w, l, N_CLUSTERS,
                                          n_layers=N_LAYERS)
    got = federate_client_params_device(groups, params, w, l, N_CLUSTERS,
                                        n_layers=N_LAYERS, chunk_size=2)
    assert_trees_close(got, dense, atol=1e-5)


def test_chunked_zero_weight_cluster_fallback(population):
    """A cluster whose weights all vanish goes uniform over its
    (participating) members — the same fallback, chunked and dense."""
    groups, params, _, labels = population
    weights = np.random.default_rng(5).random(K)
    weights[labels == 1] = 0.0
    dense = federate_client_params(groups, params, weights, labels,
                                   n_layers=N_LAYERS)
    got = federate_client_params(groups, params, weights, labels,
                                 n_layers=N_LAYERS, chunk_size=2)
    assert_trees_close(got, dense, atol=1e-5)


def test_fedavg_rides_the_chunked_plan(population):
    """Degenerate FedAvg (one cluster, size weights) streams through
    the same scan."""
    groups, params, _, _ = population
    sizes = np.random.default_rng(6).integers(10, 100, K)
    want = fedavg_uniform(groups, params, sizes, n_layers=N_LAYERS)
    got = fedavg_uniform(groups, params, sizes, n_layers=N_LAYERS,
                         chunk_size=4)
    assert_trees_close(got, want, atol=1e-5)


def test_chunked_requires_chunked_plan(population):
    groups, params, weights, labels = population
    tmpl = {g.name: params[g.name]["G"] for g in groups}
    plan = get_federation_plan(groups, "G", 5, tmpl)     # no chunk_size
    with pytest.raises(ValueError, match="chunk_size"):
        plan.aggregate_chunked(tmpl, jnp.asarray(weights, jnp.float32),
                               jnp.asarray(labels, jnp.int32), N_CLUSTERS)


def test_buffer_bytes_are_population_independent(population):
    """The acceptance claim in O() form: the dense round's client rows
    (read once, written once) grow with the client count, the chunk
    working set doesn't."""
    groups, params, _, _ = population
    tmpl = {g.name: params[g.name]["G"] for g in groups}
    plan = get_federation_plan(groups, "G", 5, tmpl, chunk_size=2)
    big_groups, big_params = build_population(n_clients=3 * K, n_profiles=3)
    big_tmpl = {g.name: big_params[g.name]["G"] for g in big_groups}
    big = get_federation_plan(big_groups, "G", 5, big_tmpl, chunk_size=2)
    assert big.dense_buffer_bytes() == 3 * plan.dense_buffer_bytes()
    assert (big.chunked_buffer_bytes(N_CLUSTERS)
            == plan.chunked_buffer_bytes(N_CLUSTERS))
    # the workset (dominated by acc [S, D]) wins once clients outnumber
    # segments — at 27 clients vs S=16 it already does; at 9 it needn't
    assert big.chunked_buffer_bytes(N_CLUSTERS) < big.dense_buffer_bytes()


# --------------------------------------------------------------------------
# hypothesis property twin (skipped in the bare env)
# --------------------------------------------------------------------------

def _assert_chunked_equals_dense(seed, n_clients, chunk):
    groups, params = build_population(n_clients, n_profiles=3, seed=seed)
    rng = np.random.default_rng(seed + 1)
    weights = rng.random(n_clients)
    labels = rng.integers(0, N_CLUSTERS, n_clients)
    dense = federate_client_params(groups, params, weights, labels,
                                   n_layers={"G": 5})
    got = federate_client_params(groups, params, weights, labels,
                                 n_layers={"G": 5}, chunk_size=chunk)
    assert_trees_close(got, dense, atol=1e-5)


if given is not None:
    @given(seed=st.integers(0, 2 ** 31 - 1), n_clients=st.integers(3, 12),
           chunk=st.integers(1, 16))
    @settings(max_examples=10, deadline=None)
    def test_chunked_equals_dense_property(seed, n_clients, chunk):
        """Arbitrary (n_clients, chunk_size) — chunk > K, chunk = 1 and
        non-divisible tails all arise from the search space."""
        _assert_chunked_equals_dense(seed, n_clients, chunk)
else:
    @pytest.mark.skip(reason="property tests need hypothesis (bare env); "
                             "the deterministic sweep above pins the grid")
    def test_chunked_equals_dense_property():
        pass


# --------------------------------------------------------------------------
# cohort rounds
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def cohort_case(population):
    groups, params, _, labels = population
    reg = ClientRegistry(sizes=np.random.default_rng(8).integers(20, 200, K))
    ids = reg.sample_cohort(jax.random.PRNGKey(0), 5)
    mask = reg.cohort_mask(ids)
    klds = np.random.default_rng(9).random(K) * 2.0
    return groups, params, labels, reg, np.asarray(mask), klds


def test_full_cohort_mask_is_identity(population):
    """An all-ones mask takes the identical compute path as no mask
    (participation = 1 everywhere) — byte-identical output."""
    groups, params, weights, labels = population
    base = federate_client_params(groups, params, weights, labels,
                                  n_layers=N_LAYERS, chunk_size=3)
    got = federate_client_params(groups, params, weights, labels,
                                 n_layers=N_LAYERS, chunk_size=3,
                                 cohort_mask=np.ones(K, bool))
    assert_trees_close(got, base, atol=0)


def test_cohort_chunked_matches_dense_device_at_paper_beta(cohort_case):
    """beta=150 cohort weights (log-space, f32) through the chunked
    stream vs the dense device round — the two f32 paths, which share
    the participation-aware uniform fallback, agree tightly even where
    the weights graze the f32 underflow cliff."""
    groups, params, labels, reg, mask, klds = cohort_case
    w = kldm.cohort_federation_weights_jax(
        jnp.asarray(klds, jnp.float32), jnp.asarray(reg.sizes, jnp.float32),
        jnp.asarray(labels, jnp.int32), jnp.asarray(mask), N_CLUSTERS,
        beta=150.0)
    l = jnp.asarray(labels, jnp.int32)
    m = jnp.asarray(mask)
    dense = federate_client_params_device(groups, params, w, l, N_CLUSTERS,
                                          n_layers=N_LAYERS, cohort_mask=m)
    got = federate_client_params_device(groups, params, w, l, N_CLUSTERS,
                                        n_layers=N_LAYERS, chunk_size=2,
                                        cohort_mask=m, cohort_size=5)
    assert_trees_close(got, dense, atol=1e-5)


def test_cohort_chunked_matches_host_oracle_moderate_beta(cohort_case):
    """Host f64 oracle (cohort_federation_weights + per-segment
    renormalize) vs the chunked stream at beta=5 — moderate beta keeps
    every cohort weight representable in f32, where the two paths are
    the same formula (at beta=150 the host f64 renormalize can recover
    weights that underflow to 0 in f32; see DESIGN.md)."""
    groups, params, labels, reg, mask, klds = cohort_case
    w = kldm.cohort_federation_weights(klds, reg.sizes, labels, mask,
                                       beta=5.0)
    host = federate_client_params(groups, params, w, labels,
                                  n_layers=N_LAYERS, cohort_mask=mask)
    got = federate_client_params(groups, params, w, labels,
                                 n_layers=N_LAYERS, chunk_size=3,
                                 cohort_mask=mask)
    assert_trees_close(got, host, atol=1e-5)


def test_cohort_non_members_bit_identical(cohort_case):
    """Non-members neither contribute nor receive: their returned
    params are the exact input arrays, all paths."""
    groups, params, labels, reg, mask, klds = cohort_case
    w = kldm.cohort_federation_weights(klds, reg.sizes, labels, mask,
                                       beta=5.0)
    wj = jnp.asarray(w, jnp.float32)
    lj = jnp.asarray(labels, jnp.int32)
    outs = [
        federate_client_params(groups, params, w, labels, n_layers=N_LAYERS,
                               cohort_mask=mask),
        federate_client_params(groups, params, w, labels, n_layers=N_LAYERS,
                               chunk_size=3, cohort_mask=mask),
        federate_client_params_device(groups, params, wj, lj, N_CLUSTERS,
                                      n_layers=N_LAYERS,
                                      cohort_mask=jnp.asarray(mask)),
        federate_client_params_device(groups, params, wj, lj, N_CLUSTERS,
                                      n_layers=N_LAYERS, chunk_size=2,
                                      cohort_mask=jnp.asarray(mask),
                                      cohort_size=int(mask.sum())),
    ]
    touched = 0
    for g in groups:
        for pos, cid in enumerate(g.client_ids):
            if mask[cid]:
                continue
            touched += 1
            for net in ("G", "D"):
                for l, tree in params[g.name][net].items():
                    want = jax.tree_util.tree_leaves(tree)
                    for out in outs:
                        got = jax.tree_util.tree_leaves(out[g.name][net][l])
                        for a, b in zip(got, want):
                            assert np.array_equal(np.asarray(a[pos]),
                                                  np.asarray(b[pos]))
    assert touched == K - int(mask.sum()) > 0


# --------------------------------------------------------------------------
# plan cache keys on (chunk_size, cohort_size)
# --------------------------------------------------------------------------

def test_plan_cache_keys_on_chunk_and_cohort(population):
    groups, params, _, _ = population
    tmpl = {g.name: params[g.name]["G"] for g in groups}
    cache = {}
    base = get_federation_plan(groups, "G", 5, tmpl, plan_cache=cache)
    c2 = get_federation_plan(groups, "G", 5, tmpl, plan_cache=cache,
                             chunk_size=2)
    c4 = get_federation_plan(groups, "G", 5, tmpl, plan_cache=cache,
                             chunk_size=4)
    c2s = get_federation_plan(groups, "G", 5, tmpl, plan_cache=cache,
                              chunk_size=2, cohort_size=5)
    assert len(cache) == 4
    assert len({id(base), id(c2), id(c4), id(c2s)}) == 4
    # re-requesting each key hits the cached plan
    assert get_federation_plan(groups, "G", 5, tmpl, plan_cache=cache,
                               chunk_size=2) is c2
    assert get_federation_plan(groups, "G", 5, tmpl, plan_cache=cache,
                               chunk_size=2, cohort_size=5) is c2s
    assert len(cache) == 4
    assert base.chunk_size is None and c2.chunk_size == 2
    assert c2s.cohort_size == 5


# --------------------------------------------------------------------------
# trainer round: cohort + chunked, zero host<->device syncs
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def cohort_trainer():
    from repro.core import HuSCFConfig, HuSCFTrainer, PAPER_DEVICES
    from repro.core.latency import Cut
    from repro.data import build_scenario
    from test_cluster_fused import _ema_blobs
    clients = build_scenario("2dom_iid", num_clients=8, base_size=16, seed=0)
    devices = [PAPER_DEVICES[i % 2] for i in range(8)]
    cuts = [Cut(1, 3, 1, 3) if i % 2 == 0 else Cut(2, 4, 2, 4)
            for i in range(8)]
    cfg = HuSCFConfig(batch=2, steps_per_epoch=2, federate_every=10 ** 6,
                      seed=0, warmup_fed_rounds=0, fused_cluster=True,
                      cohort_size=5, agg_chunk=3)
    tr = HuSCFTrainer(clients, devices, cuts=cuts, config=cfg)
    tr.train_steps(1)
    tr._mid_ema = jnp.asarray(_ema_blobs(8))
    before = jax.tree_util.tree_map(
        np.asarray, {net: tr.state[net]["client"] for net in ("G", "D")})
    diag = tr.federate()                   # compiles the cohort round
    return tr, before, diag


def test_trainer_cohort_chunked_round(cohort_trainer):
    """The wired round clusters, reports its sampled cohort, and leaves
    every non-member's client params bit-identical."""
    tr, before, diag = cohort_trainer
    assert diag["mode"] == "clustered"
    cohort = np.asarray(diag["cohort"])
    assert cohort.shape == (5,) and len(np.unique(cohort)) == 5
    member = np.zeros(8, bool)
    member[cohort] = True
    for g in tr.groups:
        for pos, cid in enumerate(g.client_ids):
            if member[cid]:
                continue
            for net in ("G", "D"):
                got = jax.tree_util.tree_leaves(
                    tr.state[net]["client"][g.name])
                want = jax.tree_util.tree_leaves(before[net][g.name])
                for a, b in zip(got, want):
                    np.testing.assert_array_equal(np.asarray(a[pos]), b[pos])


def test_trainer_cohort_chunked_zero_host_transfers(cohort_trainer):
    """The acceptance property: with the cohort+chunked round compiled,
    sampling, clustering, weighting and the chunk-streamed aggregation
    all run under jax.transfer_guard('disallow_explicit')."""
    tr, _, _ = cohort_trainer
    tr.train_steps(1)
    with jax.transfer_guard("disallow_explicit"):
        diag = tr.federate()
    assert diag["mode"] == "clustered"


# --------------------------------------------------------------------------
# multihost twin: chunk stream x client-axis shard_map
# --------------------------------------------------------------------------

def _check_chunked_sharded():
    """16 clients / 4 profile groups (4 per group): meshes of 2/4
    divide every group, so the chunk stream shards; results match the
    unsharded chunked round and the dense fused oracle. An 8-device
    mesh does not divide the per-group count of 4, so the plan falls
    back (``_chunk_axes is None``) to the unsharded stream
    byte-identically."""
    import jax
    import numpy as np
    from repro.core.federation import (federate_client_params,
                                       get_federation_plan)
    from repro.launch.mesh import make_federation_mesh
    from test_federation_fused import (N_LAYERS, assert_trees_close,
                                       build_population)
    assert jax.device_count() >= 8
    groups, params = build_population(n_clients=16, n_profiles=4, seed=2)
    rng = np.random.default_rng(3)
    weights, labels = rng.random(16), np.arange(16) % 3
    tmpl = {g.name: params[g.name]["G"] for g in groups}

    def fed(**kw):
        return federate_client_params(groups, params, weights, labels,
                                      n_layers=N_LAYERS, chunk_size=2, **kw)

    dense = federate_client_params(groups, params, weights, labels,
                                   n_layers=N_LAYERS)
    unsharded = fed()
    assert_trees_close(unsharded, dense, atol=1e-5)
    for nd in (2, 4):
        mesh = make_federation_mesh(nd)
        plan = get_federation_plan(groups, "G", 5, tmpl, mesh=mesh,
                                   chunk_size=2)
        assert plan._chunk_axes == "data", f"{nd}-device mesh must shard"
        assert_trees_close(fed(mesh=mesh), unsharded, atol=1e-5)
        assert_trees_close(fed(mesh=mesh), dense, atol=1e-5)
    # kernel body under the sharded stream
    assert_trees_close(fed(mesh=make_federation_mesh(4), use_kernel=True),
                       dense, atol=1e-5)
    # 8 devices don't divide the per-group count of 4 -> unsharded
    # fallback, byte-identical to the plain chunk stream
    mesh8 = make_federation_mesh(8)
    plan8 = get_federation_plan(groups, "G", 5, tmpl, mesh=mesh8,
                                chunk_size=2)
    assert plan8._chunk_axes is None
    got8 = fed(mesh=mesh8)
    gl = jax.tree_util.tree_leaves(got8)
    ul = jax.tree_util.tree_leaves(unsharded)
    for g, u in zip(gl, ul):
        assert np.array_equal(np.asarray(g), np.asarray(u))


def _check_chunked_cohort_sharded():
    """Cohort round through the sharded chunk stream: per-group cids
    shard with the clients; non-members stay bit-identical."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import kld as kldm
    from repro.core.federation import federate_client_params_device
    from repro.core.registry import ClientRegistry
    from repro.launch.mesh import make_federation_mesh
    from test_federation_fused import (N_LAYERS, assert_trees_close,
                                       build_population)
    assert jax.device_count() >= 8
    groups, params = build_population(n_clients=16, n_profiles=4, seed=4)
    labels = np.arange(16) % 3
    reg = ClientRegistry(sizes=np.random.default_rng(5).integers(20, 200, 16))
    mask = np.asarray(reg.cohort_mask(
        reg.sample_cohort(jax.random.PRNGKey(1), 10)))
    w = kldm.cohort_federation_weights(
        np.random.default_rng(6).random(16), reg.sizes, labels, mask,
        beta=5.0)

    def fed(**kw):
        return federate_client_params_device(
            groups, params, jnp.asarray(w, jnp.float32),
            jnp.asarray(labels, jnp.int32), 3, n_layers=N_LAYERS,
            chunk_size=2, cohort_mask=jnp.asarray(mask), cohort_size=10,
            **kw)

    unsharded = fed()
    sharded = fed(mesh=make_federation_mesh(4))
    assert_trees_close(sharded, unsharded, atol=1e-5)
    for g in groups:
        for pos, cid in enumerate(g.client_ids):
            if mask[cid]:
                continue
            for l, tree in params[g.name]["G"].items():
                want = jax.tree_util.tree_leaves(tree)
                got = jax.tree_util.tree_leaves(sharded[g.name]["G"][l])
                for a, b in zip(got, want):
                    assert np.array_equal(np.asarray(a[pos]),
                                          np.asarray(b[pos]))


# --------------------------------------------------------------------------
# bucket-padded program sharing (satellite: compile-cache stability)
# --------------------------------------------------------------------------

def test_chunked_program_shared_across_bucket_sizes():
    """Two populations whose per-group sizes differ but land in the
    same power-of-two buckets (3 -> pad 4 vs true 4) execute the SAME
    compiled chunked round — the layout is keyed on buckets, actual
    counts arrive as traced scalars."""
    from repro.core.federation import _chunked_fn_cache_stats
    g9, p9 = build_population(n_clients=9, n_profiles=3, seed=1)
    g12, p12 = build_population(n_clients=12, n_profiles=3, seed=2)
    assert [g.name for g in g9] == [g.name for g in g12]
    rng = np.random.default_rng(3)

    def fed(groups, params, k):
        return federate_client_params(groups, params, rng.random(k),
                                      np.arange(k) % N_CLUSTERS,
                                      n_layers=N_LAYERS, chunk_size=2)
    a = fed(g9, p9, 9)
    after_first = _chunked_fn_cache_stats()
    b = fed(g12, p12, 12)
    after_second = _chunked_fn_cache_stats()
    assert after_second == after_first        # no new program, no retrace
    # and the padded round still computes the right thing
    dense = federate_client_params(g12, p12, rng.random(12),
                                   np.arange(12) % N_CLUSTERS,
                                   n_layers=N_LAYERS)
    assert set(b) == set(dense)
    del a


def test_trainer_chunked_cache_stable_across_churn():
    """The regression the bucket padding exists for: a churn rebuild
    flushes the trainer's FederationPlans, but as long as the regrouped
    sizes stay within their buckets the rebuilt plan replays the SAME
    compiled chunked round — no recompile per joined/left client."""
    from repro.core.federation import _chunked_fn_cache_stats
    from repro.core.genetic import GAConfig
    from repro.core.huscf import HuSCFConfig, HuSCFTrainer
    from repro.core.latency import PAPER_DEVICES
    from test_recut import mk_clients
    cfg = HuSCFConfig(batch=8, federate_every=1, seed=0, steps_per_epoch=1,
                      warmup_fed_rounds=0, agg_chunk=2)
    # two profiles -> a 256-point gene space the 128-individual GA
    # certainly solves identically before and after churn (test_recut's
    # tie-stability argument), so only group SIZES change.
    ga = GAConfig(population_size=128, generations=12, seed=0,
                  early_stop_patience=6)
    clients = mk_clients(6)
    devices = [PAPER_DEVICES[i % 2] for i in range(6)]
    tr = HuSCFTrainer(clients, devices, config=cfg, ga_config=ga)
    tr.train_steps(1)
    tr.federate()
    cuts_before = [c.as_tuple() for c in tr.cuts]
    sizes_before = sorted(g.size for g in tr.groups)
    stats = _chunked_fn_cache_stats()
    # join one client on an incumbent profile: 3 -> 4 stays in bucket 4
    joiner = mk_clients(1, seed=9, id0=6)[0]
    tr.apply_churn(join=[(joiner, PAPER_DEVICES[0])])
    assert [c.as_tuple() for c in tr.cuts][:6] == cuts_before
    assert sorted(g.size for g in tr.groups) != sizes_before
    tr.train_steps(1)
    tr.federate()
    assert _chunked_fn_cache_stats() == stats


def test_chunked_sharded_multihost(multihost):
    multihost(MODULE, "_check_chunked_sharded")


def test_chunked_cohort_sharded_multihost(multihost):
    multihost(MODULE, "_check_chunked_cohort_sharded")
