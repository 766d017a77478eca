"""Client-axis-sharded federation rounds vs the fused single-device
path and the legacy loop, on forced multi-device CPU.

Every multi-device case is a plain ``_check_*`` function dispatched
through the ``multihost`` fixture (see tests/conftest.py): in the
ordinary 1-device suite each check runs in a subprocess that forces 8
host CPU devices before jax import; under scripts/ci_smoke.sh's second
pytest invocation (flag already set) they run inline. Checks build
meshes of 2/4/8 devices out of the forced 8 via
``make_federation_mesh``.

Matrix: heterogeneous cuts (4 profile groups), >= 3 clusters, client
counts both divisible (16) and non-divisible (10) by the mesh — the
latter exercising ``sharding.policy.client_axes``'s sanitize fallback
to the unsharded path — the paper population's seven groups (group
sizes 3 and 2: some split over the mesh, some kept whole on every
shard) on the host-weights, device-weights, FedAvg and cohort rounds,
plus plan-cache keying on (mesh identity,
chunk_size, cohort_size) and the ``mesh=None`` default staying
byte-identical. The chunk-streamed round's own sharded matrix lives
in tests/test_federation_chunked.py.
"""
import numpy as np
import pytest

MODULE = "test_federation_sharded"
N_CLIENTS = 16          # divisible by every mesh size {2, 4, 8}
N_PROFILES = 4          # heterogeneous cuts -> 4 distinct owned-layer sets
N_CLUSTERS = 3


def _population(n_clients, seed=0):
    from test_federation_fused import build_population
    groups, params = build_population(n_clients, N_PROFILES, seed=seed)
    K = sum(g.size for g in groups)
    rng = np.random.default_rng(seed + 1)
    return groups, params, rng.random(K), np.arange(K) % N_CLUSTERS


def _assert_trees_equal(got, want, atol=0.0):
    import jax
    gl, gt = jax.tree_util.tree_flatten(got)
    wl, wt = jax.tree_util.tree_flatten(want)
    assert gt == wt
    for g, w in zip(gl, wl):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        if atol == 0.0:
            assert np.array_equal(g, w), "expected byte-identical trees"
        else:
            np.testing.assert_allclose(g, w, atol=atol, rtol=0)


# --------------------------------------------------------------------------
# multi-device check bodies (run under >= 8 forced CPU devices)
# --------------------------------------------------------------------------

def _check_equivalence_matrix():
    """sharded(2/4/8 dev) == fused (<= 1e-6 max-abs) == legacy; the
    Pallas kernel per-shard path agrees; 1-device mesh is the fallback
    (byte-identical to fused); fedavg rides the same plan."""
    import jax
    from repro.core.federation import federate_client_params, fedavg_uniform
    from repro.launch.mesh import make_federation_mesh
    from test_federation_fused import N_LAYERS
    assert jax.device_count() >= 8
    groups, params, weights, labels = _population(N_CLIENTS)

    def fed(**kw):
        return federate_client_params(groups, params, weights, labels,
                                      n_layers=N_LAYERS, **kw)

    legacy = fed(fused=False)
    fused = fed()
    _assert_trees_equal(fused, legacy, atol=1e-5)

    for nd in (2, 4, 8):
        mesh = make_federation_mesh(nd)
        _assert_trees_equal(fed(mesh=mesh), fused, atol=1e-6)
    mesh8 = make_federation_mesh(8)
    _assert_trees_equal(fed(mesh=mesh8, use_kernel=True), fused, atol=1e-6)
    # 1-device mesh: sanitize drops the size-1 axis -> unsharded path
    _assert_trees_equal(fed(mesh=make_federation_mesh(1)), fused, atol=0.0)
    # degenerate FedAvg through the same sharded plan
    sizes = np.random.default_rng(7).integers(10, 100,
                                              sum(g.size for g in groups))
    want = fedavg_uniform(groups, params, sizes, n_layers=N_LAYERS)
    got = fedavg_uniform(groups, params, sizes, n_layers=N_LAYERS,
                         mesh=mesh8)
    _assert_trees_equal(got, want, atol=1e-6)


def _check_non_divisible_fallback():
    """10 clients: a 2-device mesh shards (10 % 2 == 0); 4/8-device
    meshes hit sanitize's divisibility fallback — plan reports no
    client axes and the result is byte-identical to the fused path."""
    import jax
    from repro.core.federation import (federate_client_params,
                                       get_federation_plan)
    from repro.launch.mesh import make_federation_mesh
    from test_federation_fused import N_LAYERS
    assert jax.device_count() >= 8
    groups, params, weights, labels = _population(10, seed=3)
    tmpl = {g.name: params[g.name]["G"] for g in groups}

    def fed(**kw):
        return federate_client_params(groups, params, weights, labels,
                                      n_layers=N_LAYERS, **kw)

    fused = fed()
    m2 = make_federation_mesh(2)
    assert get_federation_plan(groups, "G", 5, tmpl,
                               mesh=m2)._client_axes == "data"
    _assert_trees_equal(fed(mesh=m2), fused, atol=1e-6)
    for nd in (4, 8):
        mesh = make_federation_mesh(nd)
        plan = get_federation_plan(groups, "G", 5, tmpl, mesh=mesh)
        assert plan._client_axes is None, \
            f"{nd}-device mesh must fall back for 10 clients"
        _assert_trees_equal(fed(mesh=mesh), fused, atol=0.0)


def _check_plan_cache_mesh_identity():
    """Plans are cached per (mesh identity, chunk_size, cohort_size):
    distinct meshes (and None) get distinct plans; an equal mesh (same
    devices + axis names, rebuilt) reuses the cached one; the chunked
    and cohort variants of the same mesh key separately (their scan /
    recv-select bake different programs)."""
    import jax
    from repro.core.federation import get_federation_plan
    from repro.launch.mesh import make_federation_mesh
    assert jax.device_count() >= 8
    groups, params, _, _ = _population(N_CLIENTS)
    tmpl = {g.name: params[g.name]["G"] for g in groups}
    cache = {}
    p_none = get_federation_plan(groups, "G", 5, tmpl, plan_cache=cache)
    p2 = get_federation_plan(groups, "G", 5, tmpl, plan_cache=cache,
                             mesh=make_federation_mesh(2))
    p4 = get_federation_plan(groups, "G", 5, tmpl, plan_cache=cache,
                             mesh=make_federation_mesh(4))
    assert len(cache) == 3
    assert len({id(p_none), id(p2), id(p4)}) == 3
    # Mesh hashes by device assignment + axis names -> rebuilding an
    # equal mesh hits the same plan.
    p2b = get_federation_plan(groups, "G", 5, tmpl, plan_cache=cache,
                              mesh=make_federation_mesh(2))
    assert p2b is p2 and len(cache) == 3
    assert p_none._client_axes is None and p2._client_axes == "data"
    # (chunk_size, cohort_size) join the key on the same mesh
    p2c = get_federation_plan(groups, "G", 5, tmpl, plan_cache=cache,
                              mesh=make_federation_mesh(2), chunk_size=2)
    p2cs = get_federation_plan(groups, "G", 5, tmpl, plan_cache=cache,
                               mesh=make_federation_mesh(2), chunk_size=2,
                               cohort_size=8)
    assert len(cache) == 5 and p2c is not p2 and p2cs is not p2c
    assert p2c._chunk_axes == "data"      # 4 per group, divisible by 2
    assert get_federation_plan(groups, "G", 5, tmpl, plan_cache=cache,
                               mesh=make_federation_mesh(2),
                               chunk_size=2, cohort_size=8) is p2cs
    assert len(cache) == 5


def _check_trainer_sharded_rounds():
    """HuSCFTrainer wiring: a trainer with fed_mesh set runs its FedAvg
    warmup round and its clustered round through the sharded path and
    lands within 1e-6 of an identically-seeded unsharded twin."""
    import jax
    from repro.core import HuSCFConfig, HuSCFTrainer, PAPER_DEVICES
    from repro.core.latency import Cut
    from repro.data import build_scenario
    from repro.launch.mesh import make_federation_mesh
    assert jax.device_count() >= 8
    clients = build_scenario("2dom_iid", num_clients=8, base_size=24, seed=0)
    devices = [PAPER_DEVICES[i % 3] for i in range(8)]
    cuts = [Cut(1, 3, 1, 3) if i % 2 == 0 else Cut(2, 4, 2, 4)
            for i in range(8)]
    cfg = HuSCFConfig(batch=4, steps_per_epoch=1, federate_every=1,
                      warmup_fed_rounds=1, seed=0)

    def make(mesh):
        tr = HuSCFTrainer(clients, devices, cuts=cuts, config=cfg,
                          fed_mesh=mesh)
        tr.train_steps(1)
        return tr

    tr_mesh = make(make_federation_mesh(4))     # 8 clients % 4 == 0
    tr_none = make(None)
    for expected_mode in ("fedavg", "clustered"):
        assert tr_mesh.federate()["mode"] == expected_mode
        assert tr_none.federate()["mode"] == expected_mode
        for net in ("G", "D"):
            _assert_trees_equal(tr_mesh.state[net]["client"],
                                tr_none.state[net]["client"], atol=1e-6)


def _check_paper_groups():
    """The paper's seven groups (16 clients: groups of 3 and 2, real GAN
    shapes, the benchmark's cuts) on 1/2/4/8-device meshes, with and
    without the Pallas kernel, against the legacy loop, for the
    host-weights, device-weights, FedAvg and cohort rounds: groups the
    mesh divides split over it, the rest stay whole on every shard.
    (Both kernel settings on every mesh size are left out for time;
    each runs on two mesh sizes.)"""
    import jax
    import jax.numpy as jnp
    from repro.core.federation import (federate_client_params,
                                       federate_client_params_device,
                                       fedavg_uniform, get_federation_plan)
    from repro.launch.mesh import make_federation_mesh
    from test_federation_fused import N_LAYERS, PAPER_CUTS, build_population
    assert jax.device_count() >= 8
    groups, params = build_population(16, 7, seed=2, cuts_cycle=PAPER_CUTS)
    assert sorted(g.size for g in groups) == [2] * 5 + [3] * 2
    rng = np.random.default_rng(4)
    weights, labels = rng.random(16), np.arange(16) % N_CLUSTERS
    mask = rng.random(16) < 0.6
    sizes = rng.integers(10, 100, 16)
    cohort_w = np.where(mask, weights, 0.0)
    device_w = weights / np.bincount(labels, weights)[labels]
    rounds = {
        "host": lambda **kw: federate_client_params(
            groups, params, weights, labels, n_layers=N_LAYERS, **kw),
        "device": lambda **kw: federate_client_params_device(
            groups, params, jnp.asarray(device_w, jnp.float32),
            jnp.asarray(labels, jnp.int32), num_clusters=6,
            n_layers=N_LAYERS, **kw),
        "fedavg": lambda **kw: fedavg_uniform(
            groups, params, sizes, n_layers=N_LAYERS, **kw),
        # the unsharded cohort round is checked against the legacy loop
        # in test_federation_fused.py
        "cohort": lambda **kw: federate_client_params(
            groups, params, cohort_w, labels, n_layers=N_LAYERS,
            cohort_mask=mask, **kw)}
    legacy = federate_client_params(groups, params, weights, labels,
                                    n_layers=N_LAYERS, fused=False)
    wants = {"host": legacy, "device": legacy,
             "fedavg": fedavg_uniform(groups, params, sizes,
                                      n_layers=N_LAYERS, fused=False),
             "cohort": rounds["cohort"]()}
    tmpl = {g.name: params[g.name]["G"] for g in groups}
    # the kernel alternates with the mesh size: the 2-device mesh splits
    # the groups of 2 and keeps the groups of 3 whole, the 4- and
    # 8-device meshes keep every group whole
    for nd, use_kernel in ((1, False), (2, True), (4, False), (8, True)):
        mesh = make_federation_mesh(nd)
        plan = get_federation_plan(groups, "G", 5, tmpl, mesh=mesh)
        assert (plan._client_axes is None) == (nd == 1)
        for path, run in rounds.items():
            _assert_trees_equal(run(mesh=mesh, use_kernel=use_kernel),
                                wants[path], atol=1e-5)


# --------------------------------------------------------------------------
# pytest wrappers
# --------------------------------------------------------------------------

def test_sharded_paper_groups(multihost):
    multihost(MODULE, "_check_paper_groups")


def test_sharded_equivalence_matrix(multihost):
    multihost(MODULE, "_check_equivalence_matrix")


def test_sharded_non_divisible_fallback(multihost):
    multihost(MODULE, "_check_non_divisible_fallback")


def test_plan_cache_keys_on_mesh_identity(multihost):
    multihost(MODULE, "_check_plan_cache_mesh_identity")


def test_trainer_sharded_rounds(multihost):
    multihost(MODULE, "_check_trainer_sharded_rounds")


def test_mesh_none_default_byte_identical():
    """The mesh=None default (and a trivial 1-device mesh) must leave
    today's single-device path untouched — runs inline on any device
    count, no multihost needed."""
    from repro.core.federation import (federate_client_params,
                                       get_federation_plan)
    from repro.launch.mesh import make_federation_mesh
    from test_federation_fused import N_LAYERS
    groups, params, weights, labels = _population(6)

    def fed(**kw):
        return federate_client_params(groups, params, weights, labels,
                                      n_layers=N_LAYERS, **kw)

    base = fed()
    _assert_trees_equal(fed(mesh=None), base, atol=0.0)
    m1 = make_federation_mesh(1)
    plan = get_federation_plan(groups, "G", 5,
                               {g.name: params[g.name]["G"] for g in groups},
                               mesh=m1)
    assert plan._client_axes is None
    _assert_trees_equal(fed(mesh=m1), base, atol=0.0)
