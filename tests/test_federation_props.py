"""Property tests (hypothesis) for ``FederationPlan``: the identity
round (every client its own cluster) returns random pytrees under
random cuts unchanged, and ``weight_segments`` rows are normalized
within each (layer, cluster) block — including the zero-weight-sum
fallback.

Cases are derived deterministically from hypothesis-drawn integers
(seed + structure knobs) so each example is reproducible from its
shrunk values; the plan machinery is cut-agnostic, so cuts range over
the general ``0 <= h <= t <= n_layers`` contract, not just the
paper-valid middle-on-server cuts.
"""
import numpy as np
import pytest

pytest.importorskip("hypothesis",
                    reason="property tests need hypothesis (bare env)")
from hypothesis import assume, given, settings, strategies as st

import jax
import jax.numpy as jnp

from repro.core import federation as fed
from repro.core.federation import FederationPlan
from repro.core.latency import Cut, PAPER_DEVICES
from repro.core.splitting import ProfileGroup, client_owned_layers


def _random_case(seed: int, n_layers: int, n_groups: int):
    """Deterministic random population: per-layer leaf pytrees (1-3
    leaves, rank 1-2, dims 1-4 — shared across groups, as the plan
    requires), per-group random cuts and sizes, and the stacked f32
    template. Returns (groups, template)."""
    rng = np.random.default_rng(seed)
    layer_shapes = {
        l: [tuple(rng.integers(1, 5, rng.integers(1, 3)))
            for _ in range(rng.integers(1, 4))]
        for l in range(n_layers)}
    groups = []
    cid = 0
    for gi in range(n_groups):
        h = int(rng.integers(0, n_layers + 1))
        t = int(rng.integers(h, n_layers + 1))
        size = int(rng.integers(1, 4))
        ids = list(range(cid, cid + size))
        cid += size
        groups.append(ProfileGroup(f"g{gi}|{h}-{t}", PAPER_DEVICES[0],
                                   Cut(h, t, h, t), ids))
    template = {
        g.name: {
            str(l): {f"w{i}": rng.standard_normal(
                        (g.size,) + shp).astype(np.float32)
                     for i, shp in enumerate(layer_shapes[l])}
            for l in client_owned_layers((g.cut.g_h, g.cut.g_t), n_layers)}
        for g in groups}
    return groups, template


@given(seed=st.integers(0, 2**31 - 1), n_layers=st.integers(2, 4),
       n_groups=st.integers(1, 3))
@settings(max_examples=15, deadline=None)
def test_flatten_unflatten_roundtrip_is_identity(seed, n_layers, n_groups):
    """The identity round: with every client in a cluster of its own at
    weight 1, the leafwise round (host and device weight paths) returns
    every client's params bit for bit — each leaf is contracted and
    gathered back in its own layout, and no other group's or layer's
    rows leak in."""
    groups, template = _random_case(seed, n_layers, n_groups)
    assume(any(template[g.name] for g in groups))   # someone owns a layer
    plan = FederationPlan(groups, "G", n_layers, template)
    K = plan.n_rows
    labels = np.arange(K)
    A, seg_ids = plan.weight_segments(np.ones(K), labels)
    host = plan.aggregate(template, A, seg_ids)
    device = plan.aggregate_device(template, jnp.ones(K, jnp.float32),
                                   jnp.asarray(labels, jnp.int32), K)
    for out in (host, device):
        for g in groups:
            for l, tree in template[g.name].items():
                got = jax.tree_util.tree_leaves(out[g.name][l])
                want = jax.tree_util.tree_leaves(tree)
                assert len(got) == len(want)
                for a, b in zip(got, want):
                    assert a.dtype == b.dtype and a.shape == b.shape
                    assert np.array_equal(np.asarray(a), b)


@given(seed=st.integers(0, 2**31 - 1), n_layers=st.integers(2, 4),
       n_groups=st.integers(1, 3), n_clusters=st.integers(1, 4),
       zero_cluster=st.booleans())
@settings(max_examples=15, deadline=None)
def test_weight_segments_rows_normalized(seed, n_layers, n_groups,
                                         n_clusters, zero_cluster):
    """Every real A row (one per (layer, cluster) block) sums to 1 with
    non-negative entries — also when a whole cluster's Eq.-15 weights
    are zero (uniform fallback) — and the _SEGMENT_PAD rows are zero."""
    groups, template = _random_case(seed, n_layers, n_groups)
    assume(any(template[g.name] for g in groups))
    plan = FederationPlan(groups, "G", n_layers, template)
    rng = np.random.default_rng(seed + 1)
    labels = rng.integers(0, n_clusters, plan.n_rows)
    weights = rng.random(plan.n_rows)
    if zero_cluster:
        weights[labels == labels[0]] = 0.0
    A, seg_ids = plan.weight_segments(weights, labels)
    assert A.shape == (A.shape[0], plan.n_rows)
    assert A.shape[0] % fed._SEGMENT_PAD == 0
    assert seg_ids.shape == (plan.n_copies,)
    n_real = int(seg_ids.max()) + 1 if plan.n_copies else 0
    if n_real:
        np.testing.assert_allclose(A[:n_real].sum(axis=1), 1.0, atol=1e-6)
        assert np.all(A >= 0)
    assert np.all(A[n_real:] == 0)
