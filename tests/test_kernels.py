"""Per-kernel correctness: shape/dtype sweeps, interpret-mode Pallas vs
the pure-jnp oracles in repro.kernels.ref."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.weighted_agg import (clustered_agg, kernel_fits, leaf_view,
                                        weighted_agg_flat)
from repro.kernels.kmeans_assign import kmeans_assign
from repro.kernels.flash_decode import flash_decode
from repro.kernels.mem_attention import mem_attention


@pytest.mark.parametrize("K", [1, 3, 16])
@pytest.mark.parametrize("D", [128, 8192, 10_001])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_weighted_agg_sweep(K, D, dtype):
    key = jax.random.PRNGKey(K * 1000 + D)
    x = jax.random.normal(key, (K, D), dtype)
    w = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(1), (K,)))
    got = weighted_agg_flat(x, w, interpret=True)
    want = ref.weighted_agg_ref(x, w)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


def test_weighted_agg_nd_tree():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 3, 7, 5))
    w = jnp.asarray([0.1, 0.2, 0.3, 0.4])
    got = ops.weighted_agg(x, w)
    want = ref.weighted_agg_ref(x, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


@pytest.mark.parametrize("S", [1, 4, 15])
@pytest.mark.parametrize("K", [1, 3, 32])
@pytest.mark.parametrize("D", [128, 8192, 10_001])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_clustered_agg_sweep(S, K, D, dtype):
    """Rank-1 leaves (view ``[K, D]``), one owner group."""
    key = jax.random.PRNGKey(S * 100 + K * 10 + D)
    x = jax.random.normal(key, (K, D), dtype)
    w = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(2), (S, K)),
                       axis=1)
    got = clustered_agg([w], [x], interpret=True)
    want = ref.clustered_agg_ref(w, x)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("block_tiles", [1, 2, None])
def test_clustered_agg_block_tiles(block_tiles):
    """A streamed grid of (8 * block_tiles, 128) blocks, ragged at both
    edges (compiled-mode tiling), and the whole-view interpret block
    agree with the oracle, over two owner groups."""
    C, M, L = 6, 3 * 8 + 5, 2 * 128 + 77
    xs = [jax.random.normal(jax.random.PRNGKey(r), (r, M, L))
          for r in (5, 3)]
    ws = [jax.random.normal(jax.random.PRNGKey(10 + r), (C, r))
          for r in (5, 3)]
    block = None if block_tiles is None else (8 * block_tiles, 128)
    got = clustered_agg(ws, xs, block=block, interpret=True)
    want = (ref.clustered_agg_ref(ws[0], xs[0])
            + ref.clustered_agg_ref(ws[1], xs[1]))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_clustered_agg_nd_op():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 3, 7, 5))
    w = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(1), (2, 4)))
    got = ops.clustered_agg(w, x)
    want = ref.clustered_agg_ref(w, x)
    assert got.shape == (2, 3, 7, 5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_weighted_agg_is_single_segment_case():
    x = jax.random.normal(jax.random.PRNGKey(0), (5, 1000))
    w = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(1), (5,)))
    single = weighted_agg_flat(x, w, interpret=True)
    multi = clustered_agg([w.reshape(1, -1)], [x], interpret=True)[0]
    np.testing.assert_allclose(np.asarray(single), np.asarray(multi),
                               atol=1e-6)


# owner counts split into profile groups the way the paper population
# splits them (100 = 7 groups, 85 = 6, one group below that)
_OWNER_GROUPS = {1: (1,), 14: (14,), 15: (15,),
                 85: (15, 14, 14, 14, 14, 14),
                 100: (15, 15, 14, 14, 14, 14, 14)}
_MIDDLE = {2: (), 3: (16,), 4: (3, 8), 5: (2, 3, 8)}


def _leaf_case(rank, minor, owners, C, seed):
    shape = _MIDDLE[rank] + (minor,)
    key = jax.random.PRNGKey(seed)
    xs, ws = [], []
    for r in _OWNER_GROUPS[owners]:
        kx, kw, key = jax.random.split(key, 3)
        xs.append(jax.random.normal(kx, (r,) + shape, jnp.float32))
        ws.append(jax.random.uniform(kw, (C, r), jnp.float32))
    return ws, xs


def _leaf_ref(ws, xs):
    return sum(jnp.einsum("cr,r...->c...", w, x,
                          precision=jax.lax.Precision.HIGHEST)
               for w, x in zip(ws, xs))


@pytest.mark.parametrize("owners", sorted(_OWNER_GROUPS))
@pytest.mark.parametrize("minor", [1, 64, 128, 256])
@pytest.mark.parametrize("rank", [2, 3, 4, 5])
def test_clustered_agg_leaf_views(rank, minor, owners):
    """Stacked leaves of rank 2-5 with minor dims 1/64/128/256, owned by
    1-100 clients over up to 7 groups, through ``ops.clustered_agg``'s
    leaf views (C cycles over 1-8 across the grid)."""
    C = 1 + (rank * 7 + minor + owners) % 8
    ws, xs = _leaf_case(rank, minor, owners, C, seed=rank * 1000 + minor)
    got = ops.clustered_agg(ws, xs)
    assert got.shape == (C,) + xs[0].shape[1:]
    np.testing.assert_allclose(np.asarray(got), np.asarray(_leaf_ref(ws, xs)),
                               atol=2e-5, rtol=1e-5)


@pytest.mark.parametrize("C", list(range(1, 9)))
def test_clustered_agg_cluster_counts(C):
    """Every cluster count 1-8 on a paper-shaped view (85 owners in six
    groups, ``[k, 4, 4, 8, 128]``), streamed in compiled-size blocks."""
    key = jax.random.PRNGKey(C)
    ws, xs = [], []
    for r in _OWNER_GROUPS[85]:
        kx, kw, key = jax.random.split(key, 3)
        xs.append(jax.random.normal(kx, (r, 4, 4, 8, 128), jnp.float32))
        ws.append(jax.random.uniform(kw, (C, r), jnp.float32))
    views = [x.reshape(leaf_view(x.shape)) for x in xs]
    got = clustered_agg(ws, views, block=(64, 128), interpret=True)
    want = _leaf_ref(ws, views)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-5)


def test_kernel_fits_by_leaf_shape():
    """The compiled kernel takes whole lane tiles in a bitcast view; the
    paper GAN's small and ragged leaves stay in XLA."""
    assert kernel_fits((110, 12544))          # G layer 0 dense weight
    assert kernel_fits((12544,))              # its bias
    assert kernel_fits((4, 4, 256, 128))      # G layer 1 conv weight
    assert kernel_fits((4, 4, 128, 256))      # D layer 3 conv weight
    assert not kernel_fits((256,))            # BatchNorm vectors
    assert not kernel_fits((3, 3, 64, 1))     # G layer 4 (576 weights)
    assert not kernel_fits((4096, 1))         # D layer 4
    assert not kernel_fits((4, 4, 128, 64))   # half a lane tile
    assert not kernel_fits((4, 4, 2, 128))    # view not a bitcast
    assert leaf_view((7, 4, 4, 256, 128)) == (7, 4096, 128)
    assert leaf_view((7, 12544)) == (7, 12544)
    assert leaf_view((7,)) == (7, 1)


def _f32_array_sizes(text):
    """Element counts of every f32 array type in a lowered program."""
    return [int(np.prod([int(d) for d in dims.split("x")]))
            for dims in re.findall(r"tensor<((?:\d+x)+)f32>", text)
            for dims in [dims.rstrip("x")]]


@pytest.mark.parametrize("use_kernel", [False, True])
@pytest.mark.parametrize("net", ["G", "D"])
def test_paper100_round_holds_no_flat_buffer(net, use_kernel):
    """The aggregation program for the benchmark's 100 clients in seven
    groups holds no array larger than twice the largest single stacked
    leaf of one group (the flat ``[K, D]`` buffer was 10x that)."""
    from repro.core.federation import FederationPlan
    from test_federation_fused import paper_template_shapes
    groups, template = paper_template_shapes(net)
    plan = FederationPlan(groups, net, 5, template)
    fn = plan._make_agg_device_fn(6, use_kernel, donate=False)
    text = fn.lower(template, jax.ShapeDtypeStruct((100,), jnp.float32),
                    jax.ShapeDtypeStruct((100,), jnp.int32)).as_text()
    largest = max(x.size for x in jax.tree_util.tree_leaves(template))
    sizes = _f32_array_sizes(text)
    assert largest in sizes
    assert max(sizes) <= 2 * largest


@pytest.mark.parametrize("N", [1, 100, 257])
@pytest.mark.parametrize("M", [2, 6])
@pytest.mark.parametrize("D", [32, 300])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kmeans_assign_sweep(N, M, D, dtype):
    """Oracle sweep mirroring the clustered_agg shape/dtype sweeps: N
    crossing the 128-row tile (1 / sub-tile / non-multiple), D far from
    any lane multiple, both f32 and bf16 inputs (centers scaled x3 so
    assignments are decisive under bf16 rounding)."""
    key = jax.random.PRNGKey(N + M + D)
    x = jax.random.normal(key, (N, D), dtype)
    c = jax.random.normal(jax.random.PRNGKey(1), (M, D), dtype) * 3
    got = kmeans_assign(x, c, interpret=True)
    want = ref.kmeans_assign_ref(x, c)
    assert got.dtype == jnp.int32 and got.shape == (N,)
    assert bool(jnp.all(got == want))


def test_kmeans_assign_single_center():
    """M=1 degenerates to the constant assignment."""
    x = jax.random.normal(jax.random.PRNGKey(0), (70, 48))
    c = jax.random.normal(jax.random.PRNGKey(1), (1, 48))
    got = kmeans_assign(x, c, interpret=True)
    assert bool(jnp.all(got == 0))
    assert bool(jnp.all(got == ref.kmeans_assign_ref(x, c)))


def test_kmeans_assign_exact_ties_pick_lowest_index():
    """Duplicated center rows produce exact distance ties; argmin must
    resolve to the first occurrence, identically in kernel and oracle
    (the kernel drops the ||x||^2 term — ties must survive that)."""
    c_base = jax.random.normal(jax.random.PRNGKey(2), (3, 64)) * 2
    c = jnp.concatenate([c_base, c_base[::-1]], axis=0)   # rows 0..2 == 5..3
    x = c_base + 0.01 * jax.random.normal(jax.random.PRNGKey(3), (3, 64))
    got = kmeans_assign(x, c, interpret=True)
    want = ref.kmeans_assign_ref(x, c)
    assert bool(jnp.all(got == want))
    assert bool(jnp.all(got == jnp.arange(3)))   # first of each dup pair


def test_kmeans_assign_jitted_op():
    """The jitted public wrapper (ops.kmeans_assign) matches the oracle
    — the path the clustering stage actually calls."""
    x = jax.random.normal(jax.random.PRNGKey(4), (200, 96))
    c = jax.random.normal(jax.random.PRNGKey(5), (5, 96)) * 3
    got = ops.kmeans_assign(x, c)
    want = ref.kmeans_assign_ref(x, c)
    assert bool(jnp.all(got == want))


@pytest.mark.parametrize("B,H,KV,hd", [(1, 4, 4, 16), (2, 8, 2, 32),
                                       (3, 6, 1, 64)])
@pytest.mark.parametrize("S,blk", [(64, 64), (100, 32), (1000, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_sweep(B, H, KV, hd, S, blk, dtype):
    keys = jax.random.split(jax.random.PRNGKey(B * S), 3)
    q = jax.random.normal(keys[0], (B, H, hd), dtype)
    k = jax.random.normal(keys[1], (B, S, KV, hd), dtype)
    v = jax.random.normal(keys[2], (B, S, KV, hd), dtype)
    clen = jnp.asarray(S - 7, jnp.int32)
    got = flash_decode(q, k, v, clen, block_s=blk, interpret=True)
    want = ref.flash_decode_ref(q, k, v, clen)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


def test_flash_decode_empty_prefix_masking():
    """Tokens past cache_len must not contribute."""
    key = jax.random.PRNGKey(0)
    B, H, KV, hd, S = 1, 2, 2, 8, 32
    q = jax.random.normal(key, (B, H, hd))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, KV, hd))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, KV, hd))
    clen = jnp.asarray(5, jnp.int32)
    got = flash_decode(q, k, v, clen, block_s=8, interpret=True)
    # corrupting the masked region must not change the result
    k2 = k.at[:, 5:].set(99.0)
    v2 = v.at[:, 5:].set(-99.0)
    got2 = flash_decode(q, k2, v2, clen, block_s=8, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(got2), atol=1e-6)


@pytest.mark.parametrize("B,H,KV,hd", [(1, 4, 4, 16), (2, 4, 2, 16),
                                       (3, 6, 3, 32)])
@pytest.mark.parametrize("S,bq,bk", [(37, 16, 16), (128, 128, 128),
                                     (300, 128, 64)])
@pytest.mark.parametrize("causal", [True, False])
def test_mem_attention_sweep(B, H, KV, hd, S, bq, bk, causal):
    keys = jax.random.split(jax.random.PRNGKey(B * S + causal), 3)
    q = jax.random.normal(keys[0], (B, S, H, hd))
    k = jax.random.normal(keys[1], (B, S, KV, hd))
    v = jax.random.normal(keys[2], (B, S, KV, hd))
    lens = jnp.asarray([S - i * 3 for i in range(B)], jnp.int32)
    got = mem_attention(q, k, v, lens, causal=causal, block_q=bq,
                        block_k=bk, interpret=True)
    want = ref.mem_attention_ref(q, k, v, lens, causal=causal)
    # rows past lens see an all-masked score row in both implementations
    # (normalization garbage); only valid rows are contractual.
    mask = (np.arange(S)[None, :] < np.asarray(lens)[:, None]
            )[:, :, None, None]
    np.testing.assert_allclose(np.where(mask, np.asarray(got), 0.0),
                               np.where(mask, np.asarray(want), 0.0),
                               atol=2e-5, rtol=2e-5)


def test_mem_attention_length_masking():
    """KV past lens must not contribute to valid query rows."""
    B, S, H, KV, hd = 2, 48, 4, 2, 16
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(keys[0], (B, S, H, hd))
    k = jax.random.normal(keys[1], (B, S, KV, hd))
    v = jax.random.normal(keys[2], (B, S, KV, hd))
    lens = jnp.asarray([30, 17], jnp.int32)
    got = mem_attention(q, k, v, lens, block_q=16, block_k=16,
                        interpret=True)
    k2 = jnp.where((jnp.arange(S) >= 17)[None, :, None, None], 55.0, k)
    v2 = jnp.where((jnp.arange(S) >= 17)[None, :, None, None], -55.0, v)
    got2 = mem_attention(q, k2, v2, lens, block_q=16, block_k=16,
                         interpret=True)
    np.testing.assert_allclose(np.asarray(got[1, :17]),
                               np.asarray(got2[1, :17]), atol=1e-6)


def test_mem_attention_decode_consistency():
    """Causal prefill row t == flash_decode with a t+1-token cache (the
    two serving kernels agree on their overlap)."""
    B, S, H, KV, hd = 2, 24, 4, 2, 16
    keys = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(keys[0], (B, S, H, hd))
    k = jax.random.normal(keys[1], (B, S, KV, hd))
    v = jax.random.normal(keys[2], (B, S, KV, hd))
    full = mem_attention(q, k, v, jnp.asarray(S, jnp.int32),
                         block_q=8, block_k=8, interpret=True)
    for t in (0, 7, S - 1):
        dec = flash_decode(q[:, t], k, v, jnp.asarray(t + 1, jnp.int32),
                           block_s=8, interpret=True)
        np.testing.assert_allclose(np.asarray(full[:, t]), np.asarray(dec),
                                   atol=2e-5, rtol=2e-5)


def test_mem_attention_jitted_op():
    B, S, H, KV, hd = 1, 40, 4, 4, 8
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(keys[0], (B, S, H, hd))
    k = jax.random.normal(keys[1], (B, S, KV, hd))
    v = jax.random.normal(keys[2], (B, S, KV, hd))
    got = ops.mem_attention(q, k, v, jnp.asarray(S, jnp.int32))
    want = ref.mem_attention_ref(q, k, v, jnp.asarray(S, jnp.int32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
