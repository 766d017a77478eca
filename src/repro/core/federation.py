"""Clustered, KLD-weighted, layer-wise federated aggregation — Eq. (16).

Client-side segments are aggregated *within clusters*; because cuts are
heterogeneous, aggregation is **layer-wise over the layer's owners**:
for model layer l and cluster C, every client k in C that holds l
(in its head or tail) contributes its copy with weight
s_k / sum_{owners(l) in C} s_j, and all owners receive the aggregate.
Server-side segments are single shared copies trained on the combined
stream (see DESIGN.md §7 for the interpretation of the paper's global
Eq. 16 on shared parameters).

Fused round (DESIGN.md §Fused federation): a cached ``FederationPlan``
packs every profile group's stacked client segments into one
contiguous ``theta [K, D]`` f32 buffer per net (one row per client
copy, one column run per ownable layer, zero-filled where a cut does
not own the layer), builds the block-diagonal Eq.-15/16 weight matrix
on the host — one block per (layer, cluster), one row per receiving
client copy, factored exactly as ``W = B @ A`` with ``A [S, K]`` the
per-segment reduce rows and ``B`` the one-hot broadcast — and runs
flatten -> A @ theta -> broadcast-gather -> unflatten as a single
jitted computation, one Pallas ``clustered_agg`` call per net when
``use_kernel=True``. Treedefs, leaf shapes, and layer/row offsets are
cached on the plan so repeat rounds do zero host-side tree walking.
The original quadruple loop (net x layer x cluster x member) is kept
as the correctness oracle behind ``fused=False``.

Sharded round (DESIGN.md §Sharded federation): with ``mesh=`` given,
``theta``'s client (row) axis shards over the mesh's ('pod', 'data')
axes — the same "rows" placement as every population-batch tensor —
and the ``A @ theta`` cluster reduction runs as a ``shard_map``-ed
local partial-sum (the Pallas ``clustered_agg`` kernel on each
shard's row block) followed by a ``psum`` over the client axis, so
every host ends the collective holding the replicated ``[S, D]``
cluster means and ``_unflatten`` stays local. When the client count
is not divisible by the mesh (``sharding.policy.client_axes``'s
sanitize fallback) or the mesh has one device, the plan silently
uses the single-device path; ``mesh=None`` (the default) is that
path byte-for-byte.

Device-resident round (DESIGN.md §Device-resident clustering): with
stage 3+4 running on device (``clustering.cluster_activations_jax`` +
``kld.activation_weights_jax``), ``federate_client_params_device``
consumes the resulting *device* labels/weights arrays and assembles
the block-diagonal weight matrix in-jit
(``FederationPlan.device_weight_segments``): one segment row per
(layer, cluster-id < k bound), so the segment count is fixed by the
static ``k_selection_bound`` and never retraces as the selected k
moves round to round.

Chunk-streamed round (DESIGN.md §Chunk-streamed aggregation): with
``chunk_size=`` the dense ``[K, D]`` buffer is never materialized —
``aggregate_chunked`` ``lax.scan``s each profile group's stacked rows
in fixed-size chunks, contracting one ``A_c [S, c] @ theta_c [c, D]``
tile per chunk (the Pallas ``clustered_agg`` kernel when
``use_kernel=True``) into a running per-segment ``(acc [S, D],
mass [S])`` accumulator, and normalizes once at the end:
``agg = acc / mass``. Round working set is O(chunk + clusters),
independent of the client count; the re-associated summation makes
equivalence with the dense paths tolerance-bounded, not bit-exact.
With ``cohort_size``/``cohort_mask`` only the sampled cohort's
(pre-renormalized, ``kld.cohort_federation_weights_jax``) weights are
non-zero and non-members get their original params back via a
recv-select in ``_unflatten``. Sharding composes: each shard streams
its local row block of every group's leaf stacks (requires per-group
divisibility — ``sharding.policy.group_client_axes``) and one psum
merges the partial (acc, mass).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import spans
from repro.core.splitting import (ProfileGroup, bucket_size,
                                  client_owned_layers, layer_pair)
from repro.sharding.policy import client_axes, group_client_axes

# Segment-count padding: round the number of (layer, cluster) blocks up
# so A's leading dim takes few distinct values (bounds jit retraces as
# the silhouette-selected k changes round to round) and stays
# sublane-aligned for the kernel.
_SEGMENT_PAD = 8
_HIGHEST = jax.lax.Precision.HIGHEST


def weighted_average_stacked(stacked: Any, weights: jnp.ndarray,
                             use_kernel: bool = False) -> Any:
    """Weighted sum over the leading client axis of every leaf.
    `weights` must already be normalized over that axis."""
    if use_kernel:
        from repro.kernels import ops as kops
        return jax.tree_util.tree_map(
            lambda x: kops.weighted_agg(x, weights), stacked)
    w = weights.astype(jnp.float32)
    return jax.tree_util.tree_map(
        lambda x: jnp.einsum("k,k...->...", w, x.astype(jnp.float32),
                             precision=_HIGHEST).astype(x.dtype), stacked)


def _reduce_rows(A: jnp.ndarray, theta: jnp.ndarray,
                 use_kernel: bool) -> jnp.ndarray:
    """``A [S, k] @ theta [k, D]`` — the Pallas ``clustered_agg`` body
    behind ``use_kernel``, else one jnp matmul. Both run at HIGHEST
    precision: TPU's default f32 matmul is a single bf16 pass, which
    would round every averaged parameter to bf16."""
    if use_kernel:
        from repro.kernels import ops as kops
        return kops.clustered_agg(A, theta)
    return jnp.matmul(A, theta, precision=_HIGHEST)


# ---------------------------------------------------------------------------
# fused single-dispatch federation round
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class _LeafSpec:
    shape: Tuple[int, ...]      # per-client shape (no leading K axis)
    size: int
    dtype: Any


@dataclasses.dataclass(frozen=True)
class _SegmentEntry:
    """One (group, layer) tile of the flat buffer: the group's rows x
    the layer's column run."""
    layer: int
    gname: str
    row0: int
    row1: int
    col0: int
    width: int                  # flat per-copy param count of the layer
    sid0: int                   # slice into the per-copy segment-id vec
    sid1: int
    treedef: Any
    leaves: Tuple[_LeafSpec, ...]


# ---------------------------------------------------------------------------
# bucket-padded chunk stream: one compiled program per *bucket* layout
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class _ChunkedLayout:
    """Structural signature of a bucket-padded chunked round: everything
    the traced program bakes in, with every group/population size
    rounded up to its power-of-two bucket (`splitting.bucket_size`).
    Two plans with the same layout — e.g. before and after a churn
    event that stays within the buckets — share one compiled program
    (module-level ``_CHUNKED_FNS``); actual sizes enter the trace as
    runtime validity masks, not shapes. Group names appear because they
    are pytree dict keys of the params argument (a renamed group is a
    different jit-visible structure)."""
    groups: Tuple[Tuple[str, int, Tuple[int, ...]], ...]
    # (gname, bucket_size, owned layers in entries order)
    layers: Tuple[Tuple[int, int, int, Any, Tuple[_LeafSpec, ...]], ...]
    # (layer, col0, width, treedef, leaf specs), ascending layer
    n_cols: int
    S: int                      # padded segment count
    C: int                      # static cluster bound
    chunk: int
    use_kernel: bool
    with_cohort: bool


def _jit_round(fn: Callable, donate: bool, cache: str) -> Callable:
    """One aggregation program: ``fn`` jitted with its ops under the
    ``round.aggregate`` scope, counted as a build of ``cache``."""
    spans.count("programs_built." + cache)
    return jax.jit(jax.named_scope(spans.SCOPE_AGGREGATE)(fn),
                   donate_argnums=(0,) if donate else ())


_CHUNKED_FNS: Dict[Tuple[_ChunkedLayout, bool], Callable] = {}


def _chunked_fn_cache_stats() -> Dict[str, int]:
    """Test hook: number of shared chunked programs built and their
    summed jit-trace counts (cache stability across churn asserts on
    this)."""
    return {"programs": spans.counters().get("programs_built.agg_chunked",
                                             0),
            "traces": sum(f._cache_size() for f in _CHUNKED_FNS.values())}


def _accumulate_chunks_padded(layout: _ChunkedLayout, net_params,
                              cids_by_group, kg_by_group, w_all, lab_all,
                              part_all, zero_seg):
    """Bucket-padded twin of ``FederationPlan._accumulate_chunks``: the
    scan trip count is ``ceil(bucket / chunk)`` (static per *bucket*,
    not per population) and the actual group size ``kg`` arrives as a
    traced scalar feeding the validity mask. Padded rows carry zero
    weight, so every chunk's ``A_c`` columns for them are zero and the
    accumulator matches the unpadded stream bit-for-bit (0.0
    contributions either way)."""
    C, S, c = layout.C, layout.S, layout.chunk
    layer_ids = [l for l, _, _, _, _ in layout.layers]
    Lpos = len(layer_ids)
    acc = jnp.zeros((S, layout.n_cols), jnp.float32)
    mass = jnp.zeros(S, jnp.float32)
    for gname, Bg, owned_t in layout.groups:
        if Bg == 0:
            continue
        owned = set(owned_t)
        cids_g = cids_by_group[gname]            # [Bg] padded
        kg = kg_by_group[gname]                  # traced actual size

        def body(carry, i, gname=gname, owned=owned, Bg=Bg, kg=kg,
                 cids_g=cids_g):
            acc, mass = carry
            idx = i * c + jnp.arange(c)
            # rows past the actual size (padding and the tail-chunk
            # overhang alike) get zero weight; the gather clamps to the
            # static bucket bound.
            valid = (idx < kg).astype(jnp.float32)
            idxc = jnp.minimum(idx, Bg - 1)
            cid_c = cids_g[idxc]
            lab_c = lab_all[cid_c]
            w_c = w_all[cid_c]
            fb_c = part_all[cid_c]
            onehot = jax.nn.one_hot(lab_c, C, dtype=jnp.float32)
            parts = []
            for l, _, wdt, _, _ in layout.layers:
                if l in owned:
                    leaves = jax.tree_util.tree_leaves(
                        net_params[gname][str(l)])
                    parts.append(jnp.concatenate(
                        [jnp.take(x, idxc, axis=0).reshape(c, -1)
                         .astype(jnp.float32) for x in leaves],
                        axis=1))
                else:
                    parts.append(jnp.zeros((c, wdt), jnp.float32))
            theta_c = jnp.concatenate(parts, axis=1)         # [c, D]
            ablocks = []
            for li, l in enumerate(layer_ids):
                if l in owned:
                    w_eff = jnp.where(zero_seg[li * C + lab_c],
                                      fb_c, w_c) * valid
                    ablocks.append(onehot.T * w_eff[None, :])
                else:
                    ablocks.append(jnp.zeros((C, c), jnp.float32))
            if S > Lpos * C:
                ablocks.append(jnp.zeros((S - Lpos * C, c), jnp.float32))
            A_c = jnp.concatenate(ablocks, axis=0)           # [S, c]
            part = _reduce_rows(A_c, theta_c, layout.use_kernel)
            return (acc + part.astype(jnp.float32),
                    mass + A_c.sum(1)), None

        (acc, mass), _ = jax.lax.scan(body, (acc, mass),
                                      jnp.arange(-(-Bg // c)))
    return acc, mass


def _unflatten_padded(layout: _ChunkedLayout, agg, seg_ids,
                      originals=None, recv=None):
    """Bucket-row twin of ``FederationPlan._unflatten``: leaves come
    back with bucket-sized leading axes (the caller slices ``[:Kg]``
    outside the jit). Padded copies gather garbage segment rows —
    harmless, they are sliced off."""
    linfo = {l: (c0, w, td, specs) for l, c0, w, td, specs in layout.layers}
    out: Dict[str, Dict[str, Any]] = {}
    sid = 0
    for gname, Bg, owned_t in layout.groups:
        for l in owned_t:
            c0, width, treedef, specs = linfo[l]
            s0, s1 = sid, sid + Bg
            sid += Bg
            block = jnp.take(agg[:, c0:c0 + width], seg_ids[s0:s1], axis=0)
            mask = None if recv is None else recv[s0:s1]
            orig_leaves = (None if originals is None else
                           jax.tree_util.tree_leaves(
                               originals[gname][str(l)]))
            leaves, off = [], 0
            for i, s in enumerate(specs):
                leaf = (block[:, off:off + s.size]
                        .reshape((Bg,) + s.shape).astype(s.dtype))
                if mask is not None:
                    m = mask.reshape((Bg,) + (1,) * len(s.shape))
                    leaf = jnp.where(m, leaf, orig_leaves[i])
                leaves.append(leaf)
                off += s.size
            out.setdefault(gname, {})[str(l)] = \
                jax.tree_util.tree_unflatten(treedef, leaves)
    return out


def _make_chunked_padded_fn(layout: _ChunkedLayout, donate: bool) -> Callable:
    """The shared bucket-padded chunked round. All per-population data
    — padded params, padded cids, actual sizes, padded copy maps,
    padded weights/labels — arrives as traced operands, so the program
    closes over nothing plan-specific and any plan with this layout
    dispatches the same compiled computation."""
    C, S = layout.C, layout.S

    def run(net_params, cids, kg, copy_lpos, copy_cid, copy_valid,
            w_all, lab_all, cohort_mask=None):
        w_all = w_all.astype(jnp.float32)
        lab_all = lab_all.astype(jnp.int32)
        part = (cohort_mask.astype(jnp.float32) if layout.with_cohort
                else jnp.ones_like(w_all))
        vf = copy_valid.astype(jnp.float32)
        seg_of_copy = copy_lpos * C + lab_all[copy_cid]
        # padded copies point at client 0 — mask them out of the
        # segment masses so the uniform-fallback detection sees only
        # real members.
        raw = jax.ops.segment_sum(w_all[copy_cid] * vf, seg_of_copy,
                                  num_segments=S)
        cnt = jax.ops.segment_sum(part[copy_cid] * vf, seg_of_copy,
                                  num_segments=S)
        zero_seg = (raw <= 0) & (cnt > 0)
        acc, mass = _accumulate_chunks_padded(
            layout, net_params, cids, kg, w_all, lab_all, part, zero_seg)
        agg = acc / jnp.maximum(mass, 1e-20)[:, None]
        seg_ids = seg_of_copy.astype(jnp.int32)
        if layout.with_cohort:
            recv = cohort_mask.astype(bool)[copy_cid]
            return _unflatten_padded(layout, agg, seg_ids,
                                     originals=net_params, recv=recv)
        return _unflatten_padded(layout, agg, seg_ids)

    if layout.with_cohort:
        def fn(net_params, cids, kg, copy_lpos, copy_cid, copy_valid,
               w_all, lab_all, cohort_mask):
            return run(net_params, cids, kg, copy_lpos, copy_cid,
                       copy_valid, w_all, lab_all, cohort_mask)
    else:
        def fn(net_params, cids, kg, copy_lpos, copy_cid, copy_valid,
               w_all, lab_all):
            return run(net_params, cids, kg, copy_lpos, copy_cid,
                       copy_valid, w_all, lab_all)
    return _jit_round(fn, donate, "agg_chunked")


def _pad_rows(x: jnp.ndarray, b: int) -> jnp.ndarray:
    """Zero-pad the leading axis to ``b`` rows (device-side op — safe
    under transfer_guard)."""
    n = x.shape[0]
    if n == b:
        return x
    return jnp.concatenate(
        [x, jnp.zeros((b - n,) + x.shape[1:], x.dtype)], axis=0)


class FederationPlan:
    """Host-side flattening/aggregation plan for one (net, topology).

    Flat layout: ``theta [K, D]`` — one row per client copy (groups in
    canonical order), one contiguous column run per client-ownable
    layer (zero-filled where a client's cut does not own the layer).
    The Eq.-16 round is then ``W @ theta`` with the block-diagonal
    per-(layer, cluster) weight matrix, factored exactly as
    ``W = B @ A``: ``A [S, K]`` holds one normalized reduce row per
    segment and the one-hot ``B`` broadcasts each segment's aggregate
    back to every receiving copy (a gather on the [S, D] output,
    restricted to that layer's columns — non-member columns of an
    ``A`` row are never read).

    Built once from a template of the client params; repeat rounds
    reuse the cached treedefs/shapes/offsets and the jitted aggregate
    functions (retraced only when the segment count changes).

    ``mesh``: client-axis sharding for the round. The ``[K, D]``
    buffer's rows shard over the mesh's ('pod', 'data') axes and the
    reduction becomes a shard_map partial-sum + psum; falls back to
    the single-device path when K is not divisible by the mesh (or
    the mesh is trivial). Plans are cached per mesh identity — see
    ``get_federation_plan``.

    ``chunk_size``: enables ``aggregate_chunked`` — the round streams
    each group's stacked rows in chunks of this many clients instead
    of building the dense ``[K, D]`` buffer (O(chunk + clusters)
    memory). ``cohort_size``: declared per-round participant count
    (part of the plan cache key so cohort and full-participation
    rounds never share a jitted program; the actual cohort arrives per
    call as ``cohort_mask``).
    """

    def __init__(self, groups: Sequence[ProfileGroup], net: str,
                 n_layers: int, template: Dict[str, Dict[str, Any]],
                 mesh: Optional[Mesh] = None,
                 chunk_size: Optional[int] = None,
                 cohort_size: Optional[int] = None):
        self.net = net
        self.n_layers = n_layers
        self.mesh = mesh
        self.chunk_size = None if chunk_size is None else int(chunk_size)
        self.cohort_size = None if cohort_size is None else int(cohort_size)
        # rows: one per client copy, groups in canonical order
        self._group_rows: Dict[str, Tuple[int, int]] = {}
        self.row_cids: List[int] = []
        row = 0
        for g in groups:
            self._group_rows[g.name] = (row, row + g.size)
            self.row_cids.extend(g.client_ids)
            row += g.size
        self.n_rows = row

        owned: Dict[str, List[int]] = {
            g.name: client_owned_layers(layer_pair(g.cut, net), n_layers)
            for g in groups}
        layers = sorted({l for ls in owned.values() for l in ls})

        # columns: contiguous run per client-ownable layer; leaf specs
        # must agree across groups (same layer definition).
        self._col_runs: Dict[int, Tuple[int, int]] = {}
        col = 0
        layer_specs: Dict[int, Tuple] = {}
        for l in layers:
            for g in groups:
                if l not in owned[g.name]:
                    continue
                leaves, treedef = jax.tree_util.tree_flatten(
                    template[g.name][str(l)])
                specs = tuple(_LeafSpec(
                    tuple(x.shape[1:]),
                    int(np.prod(x.shape[1:], dtype=np.int64)),
                    x.dtype) for x in leaves)
                if l not in layer_specs:
                    layer_specs[l] = (treedef, specs)
                elif layer_specs[l][1] != specs:
                    raise ValueError(
                        f"layer {l} leaf layout differs across groups "
                        f"(group {g.name})")
            width = sum(s.size for s in layer_specs[l][1])
            self._col_runs[l] = (col, width)
            col += width
        self.n_cols = col

        # entries: (group, layer) tiles + the per-copy segment-id slice
        self.entries: List[_SegmentEntry] = []
        sid = 0
        for g in groups:
            r0, r1 = self._group_rows[g.name]
            for l in owned[g.name]:
                c0, w = self._col_runs[l]
                treedef, specs = layer_specs[l]
                self.entries.append(_SegmentEntry(
                    l, g.name, r0, r1, c0, w, sid, sid + g.size,
                    treedef, specs))
                sid += g.size
        self.n_copies = sid          # receiving (layer, client copy) pairs

        # per-layer owner rows for the weight blocks
        self._layer_rows: List[Tuple[int, np.ndarray, np.ndarray]] = []
        cids_arr = np.asarray(self.row_cids, np.int64)
        for l in layers:
            rows = np.concatenate([
                np.arange(*self._group_rows[g.name]) for g in groups
                if l in owned[g.name]])
            self._layer_rows.append((l, rows, cids_arr[rows]))
        # static per-copy indices for the in-jit weight-matrix build
        # (device_weight_segments): seg_id(copy) = layer_pos * C + label
        layer_pos = {l: i for i, (l, _, _) in enumerate(self._layer_rows)}
        self._copy_layer_pos = np.zeros(max(self.n_copies, 1), np.int32)
        self._copy_cid = np.zeros(max(self.n_copies, 1), np.int32)
        for e in self.entries:
            self._copy_layer_pos[e.sid0:e.sid1] = layer_pos[e.layer]
            self._copy_cid[e.sid0:e.sid1] = cids_arr[e.row0:e.row1]
        self._owned = owned
        self._groups_order = [g.name for g in groups]
        self._agg_fns: Dict[Tuple, Callable] = {}
        # client-axis placement: the divisibility-aware sanitize drops
        # the axes (-> None -> single-device path) when K % mesh != 0
        # or the mesh axes multiply to 1.
        self._client_axes = (None if mesh is None or self.n_rows == 0
                             else client_axes(mesh, self.n_rows))
        # chunk-streamed sharding splits each group's stacked leaves on
        # their leading axis, so it needs *per-group* divisibility — a
        # stricter condition than the dense buffer's total-row check.
        group_sizes = [r1 - r0 for r0, r1 in self._group_rows.values()
                       if r1 > r0]
        self._chunk_axes = (None if mesh is None or self.chunk_size is None
                            or not group_sizes
                            else group_client_axes(mesh, group_sizes))

    @property
    def shard_axes(self):
        """Mesh axes this plan's round reduces over — the chunk
        stream's when built with ``chunk_size`` — or None when the
        round runs on one device (no mesh, or the divisibility check
        fell back)."""
        return (self._chunk_axes if self.chunk_size is not None
                else self._client_axes)

    # -- host-side weight matrix (Eq. 15/16 block diagonal) ----------------
    def weight_segments(self, weights: np.ndarray, cluster_labels: np.ndarray
                        ) -> Tuple[np.ndarray, np.ndarray]:
        """Returns (A [S, K], seg_ids [n_copies]).

        ``A`` rows are the normalized per-(layer, cluster) reduce
        weights over that layer's owner rows (zero elsewhere);
        ``seg_ids`` maps every receiving (layer, client copy) pair —
        in ``entries`` order — to its segment row, i.e. the one-hot
        broadcast factor ``B`` of the block-diagonal ``W = B @ A``.
        S is padded to a multiple of _SEGMENT_PAD with zero rows
        (bounds retraces; padded segments are never gathered)."""
        rows_a: List[np.ndarray] = []
        seg_of: Dict[Tuple[int, int], int] = {}
        for l, rows, cids in self._layer_rows:
            for c in np.unique(cluster_labels[cids]):
                sel = cluster_labels[cids] == c
                w = np.asarray(weights, np.float64)[cids[sel]]
                if w.sum() <= 0:
                    w = np.ones_like(w)
                w = w / w.sum()
                a = np.zeros(self.n_rows, np.float32)
                a[rows[sel]] = w.astype(np.float32)
                seg_of[(l, int(c))] = len(rows_a)
                rows_a.append(a)
        seg_ids = np.zeros(self.n_copies, np.int32)
        for e in self.entries:
            row_cids = self.row_cids[e.row0:e.row1]
            seg_ids[e.sid0:e.sid1] = [
                seg_of[(e.layer, int(cluster_labels[cid]))]
                for cid in row_cids]
        S = max(_SEGMENT_PAD,
                -(-len(rows_a) // _SEGMENT_PAD) * _SEGMENT_PAD)
        A = np.zeros((S, self.n_rows), np.float32)
        if rows_a:
            A[:len(rows_a)] = np.stack(rows_a)
        return A, seg_ids

    # -- device-side weight matrix (traced twin, in-jit) -------------------
    def device_weight_segments(self, weights: jnp.ndarray,
                               labels: jnp.ndarray, num_clusters: int,
                               participation: Optional[jnp.ndarray] = None
                               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Traced twin of ``weight_segments``: assemble (A [S, K],
        seg_ids [n_copies]) from *device* per-client weights/labels so
        the whole round stays in one jit (DESIGN.md §Device-resident
        clustering).

        Unlike the host path, which enumerates only the clusters
        actually present, every (layer, cluster-id < num_clusters)
        pair gets a segment row — ``num_clusters`` is the static
        ``k_selection_bound``, so S is fixed and the round never
        retraces as the silhouette-selected k moves. Rows of empty
        segments are zero and never gathered (their seg_id is never
        produced); a present segment whose member weights sum to zero
        falls back to uniform over its members, like the host path.
        ``participation`` ([K] 0/1, default all-ones) restricts that
        fallback to the round's cohort — a segment whose cohort
        weight-mass underflows goes uniform over its *participating*
        members, and a segment with no participants gets a zero row
        (its non-member copies are recv-select-restored)."""
        C = int(num_clusters)
        n_seg = len(self._layer_rows) * C
        S = max(_SEGMENT_PAD, -(-n_seg // _SEGMENT_PAD) * _SEGMENT_PAD)
        A = jnp.zeros((S, self.n_rows), jnp.float32)
        w = weights.astype(jnp.float32)
        part = (jnp.ones_like(w) if participation is None
                else participation.astype(jnp.float32))
        for li, (l, rows, cids) in enumerate(self._layer_rows):
            lab = labels[cids]                                     # [R]
            onehot = jax.nn.one_hot(lab, C, dtype=jnp.float32)     # [R, C]
            raw = onehot * w[cids][:, None]
            denom = raw.sum(0)                                     # [C]
            mem = onehot * part[cids][:, None]
            cnt = mem.sum(0)
            blk = jnp.where(denom > 0,
                            raw / jnp.where(denom > 0, denom, 1.0),
                            mem / jnp.maximum(cnt, 1.0))           # [R, C]
            A = A.at[li * C:(li + 1) * C, rows].set(blk.T)
        seg_ids = (jnp.asarray(self._copy_layer_pos[:self.n_copies]) * C
                   + labels[jnp.asarray(self._copy_cid[:self.n_copies])]
                   ).astype(jnp.int32)
        return A, seg_ids

    # -- device-side flatten / unflatten (inside jit) ----------------------
    def _flatten(self, net_params: Dict[str, Dict[str, Any]]) -> jnp.ndarray:
        bufs = []
        for gname in self._groups_order:
            r0, r1 = self._group_rows[gname]
            k = r1 - r0
            parts, col = [], 0
            for l, (c0, w) in sorted(self._col_runs.items()):
                assert c0 == col
                if l in self._owned[gname]:
                    leaves = jax.tree_util.tree_leaves(net_params[gname][str(l)])
                    parts.append(jnp.concatenate(
                        [x.reshape(k, -1).astype(jnp.float32)
                         for x in leaves], axis=1))
                else:
                    parts.append(jnp.zeros((k, w), jnp.float32))
                col += w
            bufs.append(jnp.concatenate(parts, axis=1))
        return jnp.concatenate(bufs, axis=0)

    def _unflatten(self, agg: jnp.ndarray, seg_ids: jnp.ndarray,
                   originals: Optional[Dict[str, Dict[str, Any]]] = None,
                   recv: Optional[jnp.ndarray] = None
                   ) -> Dict[str, Dict[str, Any]]:
        """``recv`` ([n_copies] bool, with ``originals`` = the
        pre-round net_params): cohort recv-select — copies whose
        client did not participate this round keep their original
        leaves instead of gathering a segment aggregate they took no
        part in (which may be garbage when their whole (layer,
        cluster ∩ cohort) is empty)."""
        out: Dict[str, Dict[str, Any]] = {}
        for e in self.entries:
            block = jnp.take(agg[:, e.col0:e.col0 + e.width],
                             seg_ids[e.sid0:e.sid1], axis=0)
            mask = None if recv is None else recv[e.sid0:e.sid1]
            orig_leaves = (None if originals is None else
                           jax.tree_util.tree_leaves(
                               originals[e.gname][str(e.layer)]))
            leaves, off = [], 0
            for i, s in enumerate(e.leaves):
                leaf = (block[:, off:off + s.size]
                        .reshape((e.row1 - e.row0,) + s.shape)
                        .astype(s.dtype))
                if mask is not None:
                    m = mask.reshape((e.row1 - e.row0,)
                                     + (1,) * len(s.shape))
                    leaf = jnp.where(m, leaf, orig_leaves[i])
                leaves.append(leaf)
                off += s.size
            out.setdefault(e.gname, {})[str(e.layer)] = \
                jax.tree_util.tree_unflatten(e.treedef, leaves)
        return out

    # -- the jitted round --------------------------------------------------
    def _reduce_fn(self, use_kernel: bool) -> Callable:
        """(A [S, K], theta [K, D]) -> replicated agg [S, D] f32."""
        if self._client_axes is None:
            # single-device / fallback path: one full-K contraction.
            return lambda A, theta: _reduce_rows(A, theta, use_kernel)

        # Sharded path: theta rows and A columns split over the client
        # axis; each shard contracts its local row block (the Pallas
        # kernel runs per-shard) into a partial [S, D], and one psum
        # over the client axis leaves the full cluster means replicated
        # on every host — S*D is tiny next to K*D, and _unflatten's
        # seg_ids gather needs every segment row locally, so a
        # psum_scatter would only defer the same all-gather (DESIGN.md
        # §Sharded federation).
        axes = self._client_axes
        axis_names = (axes,) if isinstance(axes, str) else tuple(axes)

        def local_partial(a_blk, theta_blk):
            part = _reduce_rows(a_blk, theta_blk, use_kernel)
            return jax.lax.psum(part.astype(jnp.float32), axis_names)

        # check_vma=False: pallas_call has no shard_map replication
        # rule; the out_spec below is still fully replicated (psum).
        return jax.shard_map(local_partial, mesh=self.mesh,
                             in_specs=(P(None, axes), P(axes, None)),
                             out_specs=P(None, None), check_vma=False)

    def _make_agg_fn(self, use_kernel: bool, donate: bool,
                     with_cohort: bool = False) -> Callable:
        reduce = self._reduce_fn(use_kernel)
        theta_sharding = (None if self._client_axes is None else
                          NamedSharding(self.mesh, P(self._client_axes, None)))

        def core(net_params, A, seg_ids):
            theta = self._flatten(net_params)
            if theta_sharding is not None:
                theta = jax.lax.with_sharding_constraint(theta, theta_sharding)
            return reduce(A, theta)

        if with_cohort:
            def fn(net_params, A, seg_ids, recv):
                agg = core(net_params, A, seg_ids)
                return self._unflatten(agg, seg_ids,
                                       originals=net_params, recv=recv)
        else:
            def fn(net_params, A, seg_ids):
                return self._unflatten(core(net_params, A, seg_ids), seg_ids)
        return _jit_round(fn, donate, "agg")

    def aggregate(self, net_params: Dict[str, Dict[str, Any]],
                  A: np.ndarray, seg_ids: np.ndarray,
                  use_kernel: bool = False,
                  donate: bool = False,
                  cohort_mask: Optional[np.ndarray] = None
                  ) -> Dict[str, Dict[str, Any]]:
        key = (use_kernel, donate, cohort_mask is not None)
        if key not in self._agg_fns:
            self._agg_fns[key] = self._make_agg_fn(
                use_kernel, donate, cohort_mask is not None)
        args = [net_params, jnp.asarray(A, jnp.float32),
                jnp.asarray(seg_ids, jnp.int32)]
        if cohort_mask is not None:
            recv = np.asarray(cohort_mask, bool)[
                self._copy_cid[:self.n_copies]]
            args.append(jnp.asarray(recv))
        return self._agg_fns[key](*args)

    def _make_agg_device_fn(self, num_clusters: int, use_kernel: bool,
                            donate: bool, with_cohort: bool = False
                            ) -> Callable:
        reduce = self._reduce_fn(use_kernel)
        theta_sharding = (None if self._client_axes is None else
                          NamedSharding(self.mesh, P(self._client_axes, None)))
        copy_cid = jnp.asarray(self._copy_cid[:self.n_copies])

        def core(net_params, weights, labels, participation=None):
            A, seg_ids = self.device_weight_segments(
                weights, labels, num_clusters, participation=participation)
            theta = self._flatten(net_params)
            if theta_sharding is not None:
                theta = jax.lax.with_sharding_constraint(theta, theta_sharding)
            return reduce(A, theta), seg_ids

        if with_cohort:
            def fn(net_params, weights, labels, cohort_mask):
                agg, seg_ids = core(net_params, weights, labels,
                                    cohort_mask.astype(jnp.float32))
                recv = cohort_mask.astype(bool)[copy_cid]
                return self._unflatten(agg, seg_ids,
                                       originals=net_params, recv=recv)
        else:
            def fn(net_params, weights, labels):
                agg, seg_ids = core(net_params, weights, labels)
                return self._unflatten(agg, seg_ids)
        return _jit_round(fn, donate, "agg")

    def aggregate_device(self, net_params: Dict[str, Dict[str, Any]],
                         weights: jnp.ndarray, labels: jnp.ndarray,
                         num_clusters: int, use_kernel: bool = False,
                         donate: bool = False,
                         cohort_mask: Optional[jnp.ndarray] = None
                         ) -> Dict[str, Dict[str, Any]]:
        """Device-resident round: weights/labels are per-client device
        arrays (label ids < the static ``num_clusters`` bound); the
        Eq.-15/16 weight matrix is assembled in-jit — no host numpy
        between the inputs and the aggregated params. weights/labels
        are never donated (the caller reuses them across nets).
        ``cohort_mask`` ([K] bool, weights pre-renormalized over the
        cohort — ``kld.cohort_federation_weights_jax``): non-members
        keep their original params via the recv-select."""
        key = ("device", int(num_clusters), use_kernel, donate,
               cohort_mask is not None)
        if key not in self._agg_fns:
            self._agg_fns[key] = self._make_agg_device_fn(
                int(num_clusters), use_kernel, donate,
                cohort_mask is not None)
        if cohort_mask is not None:
            return self._agg_fns[key](net_params, weights, labels,
                                      cohort_mask)
        return self._agg_fns[key](net_params, weights, labels)

    # -- chunk-streamed round (DESIGN.md §Chunk-streamed aggregation) ------
    def _accumulate_chunks(self, net_params, cids_by_group, w_all, lab_all,
                           part_all, zero_seg, num_clusters: int, chunk: int,
                           use_kernel: bool):
        """Stream every group's stacked rows in fixed-size chunks,
        contracting one ``A_c [S, c] @ theta_c [c, D]`` tile per chunk
        into the scan-carried ``(acc [S, D], mass [S])`` accumulator
        (XLA donates the carry in place). ``zero_seg`` [S] marks
        segments whose raw weight mass is zero but have participating
        members — their members switch to their ``part_all`` value
        (1.0 for participants, 0 outside the cohort), reproducing the
        dense paths' uniform-over-participants fallback without
        knowing the total mid-stream. Runs on the *local* row block
        under shard_map (leaf leading dims and cids are shard-local
        there); returns unnormalized (acc, mass)."""
        C = int(num_clusters)
        Lpos = len(self._layer_rows)
        S = zero_seg.shape[0]
        c = int(chunk)
        sorted_runs = sorted(self._col_runs.items())
        acc = jnp.zeros((S, self.n_cols), jnp.float32)
        mass = jnp.zeros(S, jnp.float32)
        for gname in self._groups_order:
            cids_g = cids_by_group[gname]
            Kg = int(cids_g.shape[0])
            if Kg == 0:
                continue
            owned = self._owned[gname]

            def body(carry, i, gname=gname, cids_g=cids_g, Kg=Kg,
                     owned=owned):
                acc, mass = carry
                idx = i * c + jnp.arange(c)
                # JAX clamps out-of-range dynamic indices, which would
                # double-count the last row on the tail chunk — clamp
                # explicitly and zero the weights of the overhang.
                valid = (idx < Kg).astype(jnp.float32)
                idxc = jnp.minimum(idx, Kg - 1)
                cid_c = cids_g[idxc]
                lab_c = lab_all[cid_c]
                w_c = w_all[cid_c]
                fb_c = part_all[cid_c]
                onehot = jax.nn.one_hot(lab_c, C, dtype=jnp.float32)
                parts = []
                for l, (c0, wdt) in sorted_runs:
                    if l in owned:
                        leaves = jax.tree_util.tree_leaves(
                            net_params[gname][str(l)])
                        parts.append(jnp.concatenate(
                            [jnp.take(x, idxc, axis=0).reshape(c, -1)
                             .astype(jnp.float32) for x in leaves],
                            axis=1))
                    else:
                        parts.append(jnp.zeros((c, wdt), jnp.float32))
                theta_c = jnp.concatenate(parts, axis=1)         # [c, D]
                ablocks = []
                for li, (l, _, _) in enumerate(self._layer_rows):
                    if l in owned:
                        w_eff = jnp.where(zero_seg[li * C + lab_c],
                                          fb_c, w_c) * valid
                        ablocks.append(onehot.T * w_eff[None, :])
                    else:
                        ablocks.append(jnp.zeros((C, c), jnp.float32))
                if S > Lpos * C:
                    ablocks.append(jnp.zeros((S - Lpos * C, c),
                                             jnp.float32))
                A_c = jnp.concatenate(ablocks, axis=0)           # [S, c]
                part = _reduce_rows(A_c, theta_c, use_kernel)
                return (acc + part.astype(jnp.float32),
                        mass + A_c.sum(1)), None

            (acc, mass), _ = jax.lax.scan(body, (acc, mass),
                                          jnp.arange(-(-Kg // c)))
        return acc, mass

    def _make_agg_chunked_fn(self, num_clusters: int, use_kernel: bool,
                             donate: bool, with_cohort: bool) -> Callable:
        C = int(num_clusters)
        chunk = int(self.chunk_size)
        n_seg = len(self._layer_rows) * C
        S = max(_SEGMENT_PAD, -(-n_seg // _SEGMENT_PAD) * _SEGMENT_PAD)
        n_cop = self.n_copies
        copy_lpos = jnp.asarray(self._copy_layer_pos[:n_cop])
        copy_cid = jnp.asarray(self._copy_cid[:n_cop])
        cids_np = {g: np.asarray(self.row_cids[r0:r1], np.int32)
                   for g, (r0, r1) in self._group_rows.items()}
        axes = self._chunk_axes
        axis_names = (() if axes is None else
                      ((axes,) if isinstance(axes, str) else tuple(axes)))

        def run(net_params, w_all, lab_all, cohort_mask=None):
            w_all = w_all.astype(jnp.float32)
            lab_all = lab_all.astype(jnp.int32)
            # participation vector: 1.0 for clients in the round. The
            # uniform fallback for a segment whose weight mass
            # underflows goes uniform over *participants* only, and a
            # (layer, cluster) with an empty cohort keeps mass 0 (its
            # copies are recv-select-restored) — matching
            # device_weight_segments' participation semantics.
            part = (cohort_mask.astype(jnp.float32) if with_cohort
                    else jnp.ones_like(w_all))
            seg_of_copy = copy_lpos * C + lab_all[copy_cid]
            raw = jax.ops.segment_sum(w_all[copy_cid], seg_of_copy,
                                      num_segments=S)
            cnt = jax.ops.segment_sum(part[copy_cid], seg_of_copy,
                                      num_segments=S)
            zero_seg = (raw <= 0) & (cnt > 0)
            cids = {g: jnp.asarray(v) for g, v in cids_np.items()}
            if axes is None:
                acc, mass = self._accumulate_chunks(
                    net_params, cids, w_all, lab_all, part, zero_seg,
                    C, chunk, use_kernel)
            else:
                # Sharded stream: each shard holds a row block of every
                # group's leaf stack (and the matching cids slice),
                # scans its local chunks, and one psum merges the tiny
                # (acc, mass) — same collective shape as the dense
                # sharded reduction. check_vma=False: pallas_call has
                # no shard_map replication rule.
                def local(net_p, cids_l, w, lab, pt, zs):
                    a, m = self._accumulate_chunks(
                        net_p, cids_l, w, lab, pt, zs, C, chunk,
                        use_kernel)
                    return (jax.lax.psum(a, axis_names),
                            jax.lax.psum(m, axis_names))
                p_specs = jax.tree_util.tree_map(
                    lambda x: P(axes, *([None] * (x.ndim - 1))),
                    net_params)
                c_specs = {g: P(axes) for g in cids}
                acc, mass = jax.shard_map(
                    local, mesh=self.mesh,
                    in_specs=(p_specs, c_specs, P(None), P(None),
                              P(None), P(None)),
                    out_specs=(P(None, None), P(None)),
                    check_vma=False)(net_params, cids, w_all, lab_all,
                                     part, zero_seg)
            agg = acc / jnp.maximum(mass, 1e-20)[:, None]
            seg_ids = seg_of_copy.astype(jnp.int32)
            if with_cohort:
                recv = cohort_mask.astype(bool)[copy_cid]
                return self._unflatten(agg, seg_ids,
                                       originals=net_params, recv=recv)
            return self._unflatten(agg, seg_ids)

        if with_cohort:
            def fn(net_params, weights, labels, cohort_mask):
                return run(net_params, weights, labels, cohort_mask)
        else:
            def fn(net_params, weights, labels):
                return run(net_params, weights, labels)
        return _jit_round(fn, donate, "agg")

    def _chunked_layout(self, num_clusters: int, use_kernel: bool,
                        with_cohort: bool) -> _ChunkedLayout:
        """Bucket-padded structural signature of this plan's chunked
        round (see ``_ChunkedLayout``)."""
        C = int(num_clusters)
        n_seg = len(self._layer_rows) * C
        S = max(_SEGMENT_PAD, -(-n_seg // _SEGMENT_PAD) * _SEGMENT_PAD)
        by_layer: Dict[int, Tuple] = {}
        for e in self.entries:
            by_layer.setdefault(e.layer, (e.col0, e.width, e.treedef,
                                          e.leaves))
        layers = tuple((l,) + by_layer[l] for l in sorted(by_layer))
        groups = tuple((g, bucket_size(r1 - r0), tuple(self._owned[g]))
                       for g, (r0, r1) in self._group_rows.items())
        return _ChunkedLayout(groups, layers, self.n_cols, S, C,
                              int(self.chunk_size), use_kernel,
                              with_cohort)

    def _chunked_operands(self):
        """Per-plan device operands of the shared chunked program:
        bucket-padded group cids, traced actual sizes, and the
        bucket-strided copy->(layer_pos, cid, valid) maps. Built once
        per plan and cached as device arrays so repeat rounds do zero
        host->device transfers (transfer_guard-safe after warm-up)."""
        ops = getattr(self, "_chunk_ops", None)
        if ops is not None:
            return ops
        layer_pos = {l: i for i, (l, _, _) in enumerate(self._layer_rows)}
        cids_arr = np.asarray(self.row_cids, np.int64)
        cids_pad: Dict[str, jnp.ndarray] = {}
        kg: Dict[str, jnp.ndarray] = {}
        lpos_l, cid_l, valid_l = [], [], []
        for g, (r0, r1) in self._group_rows.items():
            Kg = r1 - r0
            Bg = bucket_size(Kg)
            c = np.zeros(Bg, np.int32)
            c[:Kg] = cids_arr[r0:r1]
            cids_pad[g] = jnp.asarray(c)
            kg[g] = jnp.asarray(Kg, jnp.int32)
            for l in self._owned[g]:
                lpos_l.append(np.full(Bg, layer_pos[l], np.int32))
                cc = np.zeros(Bg, np.int32)
                cc[:Kg] = cids_arr[r0:r1]
                cid_l.append(cc)
                vv = np.zeros(Bg, bool)
                vv[:Kg] = True
                valid_l.append(vv)

        def cat(xs, dtype):
            return jnp.asarray(np.concatenate(xs) if xs
                               else np.zeros(0, dtype))
        self._chunk_ops = (cids_pad, kg, cat(lpos_l, np.int32),
                           cat(cid_l, np.int32), cat(valid_l, bool))
        return self._chunk_ops

    def aggregate_chunked(self, net_params: Dict[str, Dict[str, Any]],
                          weights: jnp.ndarray, labels: jnp.ndarray,
                          num_clusters: int, use_kernel: bool = False,
                          donate: bool = False,
                          cohort_mask: Optional[jnp.ndarray] = None
                          ) -> Dict[str, Dict[str, Any]]:
        """Chunk-streamed round (requires the plan to be built with
        ``chunk_size=``): same signature semantics as
        ``aggregate_device`` but the dense ``[K, D]`` buffer is never
        built — partial sums + weight masses accumulate over a
        ``lax.scan`` of client chunks and a single normalize at the
        end divides them out. Equivalence with the dense paths is
        tolerance-bounded (re-associated f32 summation), not
        bit-exact.

        Unsharded rounds run the *shared* bucket-padded program
        (module-level ``_CHUNKED_FNS``, one per ``_ChunkedLayout``):
        group sizes pad to power-of-two buckets, scan trip counts are
        per-bucket, and actual sizes arrive as traced validity masks —
        so a churned population whose per-group counts stay within the
        buckets reuses the compiled round instead of retracing.
        Numerically identical to the unpadded stream (padded rows carry
        zero weight). The sharded stream (``_chunk_axes``) keeps its
        per-plan program: shard_map bakes the mesh and per-shard row
        blocks into the closure, and padding would break the per-group
        divisibility contract."""
        if self.chunk_size is None:
            raise ValueError("plan was built without chunk_size; pass "
                             "chunk_size= to get_federation_plan")
        if self._chunk_axes is not None:
            key = ("chunked", int(num_clusters), use_kernel, donate,
                   cohort_mask is not None)
            if key not in self._agg_fns:
                self._agg_fns[key] = self._make_agg_chunked_fn(
                    int(num_clusters), use_kernel, donate,
                    cohort_mask is not None)
            if cohort_mask is not None:
                return self._agg_fns[key](net_params, weights, labels,
                                          cohort_mask)
            return self._agg_fns[key](net_params, weights, labels)

        layout = self._chunked_layout(int(num_clusters), use_kernel,
                                      cohort_mask is not None)
        fkey = (layout, donate)
        fn = _CHUNKED_FNS.get(fkey)
        if fn is None:
            fn = _CHUNKED_FNS[fkey] = _make_chunked_padded_fn(layout,
                                                              donate)
        cids_pad, kg, lpos, cid, valid = self._chunked_operands()
        KB = bucket_size(int(weights.shape[0]))
        params_pad: Dict[str, Dict[str, Any]] = {}
        for gname, Bg, _ in layout.groups:
            params_pad[gname] = {
                l: jax.tree_util.tree_map(
                    lambda x: _pad_rows(jnp.asarray(x), Bg), tree)
                for l, tree in net_params[gname].items()}
        args = [params_pad, cids_pad, kg, lpos, cid, valid,
                _pad_rows(jnp.asarray(weights), KB),
                _pad_rows(jnp.asarray(labels), KB)]
        if cohort_mask is not None:
            args.append(_pad_rows(jnp.asarray(cohort_mask), KB))
        out_pad = fn(*args)
        out: Dict[str, Dict[str, Any]] = {}
        for gname, Bg, _ in layout.groups:
            Kg = self._group_rows[gname][1] - self._group_rows[gname][0]
            if Bg == Kg:
                out[gname] = out_pad[gname]
            else:
                out[gname] = {
                    l: jax.tree_util.tree_map(lambda x: x[:Kg], tree)
                    for l, tree in out_pad[gname].items()}
        return out

    # -- memory envelopes --------------------------------------------------
    def dense_buffer_bytes(self) -> int:
        """f32 bytes of the dense ``theta [K, D]`` flat client buffer
        the non-chunked paths materialize (the O(clients) term the
        chunk stream eliminates)."""
        return 4 * self.n_rows * self.n_cols

    def chunked_buffer_bytes(self, num_clusters: int) -> int:
        """f32 bytes of the chunk stream's working set: one
        ``theta_c [c, D]`` + ``A_c [S, c]`` tile plus the carried
        ``(acc [S, D], mass [S])`` — O(chunk + clusters), independent
        of the client count."""
        if self.chunk_size is None:
            raise ValueError("plan was built without chunk_size")
        n_seg = len(self._layer_rows) * int(num_clusters)
        S = max(_SEGMENT_PAD, -(-n_seg // _SEGMENT_PAD) * _SEGMENT_PAD)
        c = int(self.chunk_size)
        return 4 * (c * self.n_cols + S * c + S * self.n_cols + S)


_PLAN_CACHE: Dict[Tuple, FederationPlan] = {}


def _plan_key(groups: Sequence[ProfileGroup], net: str, n_layers: int,
              template: Dict[str, Dict[str, Any]],
              mesh: Optional[Mesh] = None,
              chunk_size: Optional[int] = None,
              cohort_size: Optional[int] = None) -> Tuple:
    # The leaf-layout fingerprint guards the shared cache against two
    # same-topology populations with differently-shaped layer params
    # (walking ~100 aval objects per round is noise next to the round).
    # Mesh identity is part of the key: a plan bakes its shard_map /
    # sharding constraints to one mesh, so the same topology on a
    # different mesh (or none) must get its own plan (jax.sharding.Mesh
    # hashes by device assignment + axis names). (chunk_size,
    # cohort_size) join it for the same reason: the chunked scan and
    # the cohort recv-select are baked into the plan's jitted programs,
    # so a dense full-participation round must never reuse a chunked /
    # cohort plan (or vice versa).
    layout = tuple(
        (g.name, tuple(
            (l, tuple((tuple(x.shape), str(x.dtype)) for x in
                      jax.tree_util.tree_leaves(tree)))
            for l, tree in sorted(template[g.name].items())))
        for g in groups)
    return (net, n_layers, tuple(
        (g.name, g.cut.as_tuple(), tuple(g.client_ids)) for g in groups),
        layout, mesh, chunk_size, cohort_size)


def get_federation_plan(groups: Sequence[ProfileGroup], net: str,
                        n_layers: int,
                        template: Dict[str, Dict[str, Any]],
                        plan_cache: Optional[Dict] = None,
                        mesh: Optional[Mesh] = None,
                        chunk_size: Optional[int] = None,
                        cohort_size: Optional[int] = None) -> FederationPlan:
    cache = _PLAN_CACHE if plan_cache is None else plan_cache
    key = _plan_key(groups, net, n_layers, template, mesh,
                    chunk_size=chunk_size, cohort_size=cohort_size)
    if key not in cache:
        cache[key] = FederationPlan(groups, net, n_layers, template,
                                    mesh=mesh, chunk_size=chunk_size,
                                    cohort_size=cohort_size)
    return cache[key]


def _default_n_layers() -> Dict[str, int]:
    """Per-net layer counts derived from the model depth (lazy import:
    federation must stay importable without the models package in the
    graph at module load). A hardcoded {net: 5} here would silently
    mis-plan the flat buffer if the layer defs ever grow."""
    from repro.models.gan import DISC_LAYER_DEFS, GEN_LAYER_DEFS
    return {"G": len(GEN_LAYER_DEFS), "D": len(DISC_LAYER_DEFS)}


def donate_default() -> bool:
    """Whether a caller that *owns* its buffers (replaces every
    reference after the round, like the trainer) should donate them.
    CPU XLA ignores donation (with a warning per call) — only donate
    where the runtime can actually alias the buffers."""
    return jax.default_backend() in ("tpu", "gpu")


def federate_client_params(groups: Sequence[ProfileGroup],
                           client_params: Dict[str, Dict[str, Dict[str, Any]]],
                           weights: np.ndarray,
                           cluster_labels: np.ndarray,
                           n_layers: Dict[str, int] = None,
                           use_kernel: bool = False,
                           fused: bool = True,
                           plan_cache: Optional[Dict] = None,
                           donate: Optional[bool] = None,
                           mesh: Optional[Mesh] = None,
                           chunk_size: Optional[int] = None,
                           cohort_mask: Optional[np.ndarray] = None
                           ) -> Dict[str, Dict[str, Dict[str, Any]]]:
    """Aggregate client-held layers cluster-wise.

    client_params: {group.name: {net: {str(layer): stacked pytree}}}
    weights: Eq.-15 intra-cluster weights, indexed by global client id.
    cluster_labels: cluster id per global client id.
    fused=True runs the single-dispatch flat-buffer path (one jitted
    call per net; Pallas kernel when use_kernel); fused=False runs the
    legacy per-(layer, cluster, leaf) loop (correctness oracle —
    full-participation dense rounds only).
    donate=True aliases the input buffers into the jitted round —
    only safe when the caller drops every reference to client_params
    afterwards (the trainer does; pass ``donate_default()``). The
    default never donates, so repeated calls on the same params are
    always valid.
    mesh=Mesh(...) shards the flat client buffer's rows over the
    mesh's ('pod', 'data') axes and reduces via shard_map partial-sums
    + psum (see FederationPlan); ``None`` keeps today's single-device
    path unchanged. Non-divisible client counts fall back silently.
    chunk_size=c streams the round in c-client chunks instead of
    building the dense [K, D] buffer (tolerance-bounded equivalence —
    see FederationPlan.aggregate_chunked). cohort_mask ([n_clients]
    bool) runs a sampled-cohort round: ``weights`` must already be
    renormalized over the cohort (``kld.cohort_federation_weights``,
    zero outside it) and non-members keep their original params.
    Returns a new client_params with aggregated copies broadcast back.
    """
    n_layers = n_layers or _default_n_layers()
    if not fused:
        if chunk_size is not None or cohort_mask is not None:
            raise ValueError("the legacy loop is a full-participation "
                             "dense oracle: chunk_size/cohort_mask "
                             "require fused=True")
        return _federate_client_params_legacy(
            groups, client_params, weights, cluster_labels,
            n_layers=n_layers, use_kernel=use_kernel)
    if donate is None:
        donate = False
    weights = np.asarray(weights)
    cluster_labels = np.asarray(cluster_labels)
    cohort_size = (None if cohort_mask is None
                   else int(np.asarray(cohort_mask, bool).sum()))
    out = {gname: dict(nets) for gname, nets in client_params.items()}
    for net, n_lay in n_layers.items():
        template = {g.name: client_params[g.name][net] for g in groups}
        plan = get_federation_plan(groups, net, n_lay, template,
                                   plan_cache=plan_cache, mesh=mesh,
                                   chunk_size=chunk_size,
                                   cohort_size=cohort_size)
        if plan.n_rows == 0:
            continue
        if chunk_size is not None:
            new_net = plan.aggregate_chunked(
                template, jnp.asarray(weights, jnp.float32),
                jnp.asarray(cluster_labels, jnp.int32),
                num_clusters=int(cluster_labels.max()) + 1,
                use_kernel=use_kernel, donate=donate,
                cohort_mask=None if cohort_mask is None
                else jnp.asarray(np.asarray(cohort_mask, bool)))
        else:
            A, seg_ids = plan.weight_segments(weights, cluster_labels)
            new_net = plan.aggregate(template, A, seg_ids,
                                     use_kernel=use_kernel, donate=donate,
                                     cohort_mask=cohort_mask)
        for g in groups:
            if g.name in new_net:
                out[g.name][net] = new_net[g.name]
    return out


def federate_client_params_device(
        groups: Sequence[ProfileGroup],
        client_params: Dict[str, Dict[str, Dict[str, Any]]],
        weights: jnp.ndarray,
        cluster_labels: jnp.ndarray,
        num_clusters: int,
        n_layers: Dict[str, int] = None,
        use_kernel: bool = False,
        plan_cache: Optional[Dict] = None,
        donate: Optional[bool] = None,
        mesh: Optional[Mesh] = None,
        chunk_size: Optional[int] = None,
        cohort_mask: Optional[jnp.ndarray] = None,
        cohort_size: Optional[int] = None
        ) -> Dict[str, Dict[str, Dict[str, Any]]]:
    """Device-resident twin of ``federate_client_params``: weights and
    cluster_labels are *device* arrays (e.g. straight out of the jitted
    stage-3/4 ``cluster_activations_jax``/``activation_weights_jax``
    chain) and the A matrix + seg_ids are assembled in-jit, so the
    round performs zero host<->device transfers of activations, labels,
    or weights. ``num_clusters`` is the static label-id bound
    (``clustering.k_selection_bound``) that fixes the segment count.
    chunk_size streams the round (``aggregate_chunked``); cohort_mask
    ([K] bool device array, weights pre-renormalized over the cohort)
    runs a sampled-cohort round — pass the static ``cohort_size``
    alongside so the plan cache separates cohort programs (the mask
    itself never leaves the device)."""
    n_layers = n_layers or _default_n_layers()
    donate = bool(donate)
    out = {gname: dict(nets) for gname, nets in client_params.items()}
    for net, n_lay in n_layers.items():
        template = {g.name: client_params[g.name][net] for g in groups}
        plan = get_federation_plan(groups, net, n_lay, template,
                                   plan_cache=plan_cache, mesh=mesh,
                                   chunk_size=chunk_size,
                                   cohort_size=cohort_size)
        if plan.n_rows == 0:
            continue
        if chunk_size is not None:
            new_net = plan.aggregate_chunked(
                template, weights, cluster_labels, num_clusters,
                use_kernel=use_kernel, donate=donate,
                cohort_mask=cohort_mask)
        else:
            new_net = plan.aggregate_device(
                template, weights, cluster_labels, num_clusters,
                use_kernel=use_kernel, donate=donate,
                cohort_mask=cohort_mask)
        for g in groups:
            if g.name in new_net:
                out[g.name][net] = new_net[g.name]
    return out


def _federate_client_params_legacy(
        groups: Sequence[ProfileGroup],
        client_params: Dict[str, Dict[str, Dict[str, Any]]],
        weights: np.ndarray,
        cluster_labels: np.ndarray,
        n_layers: Dict[str, int],
        use_kernel: bool = False
        ) -> Dict[str, Dict[str, Dict[str, Any]]]:
    """Reference quadruple loop: net x layer x cluster x member, one
    gather/stack/reduce/scatter dispatch chain per combination."""
    out = jax.tree_util.tree_map(lambda x: x, client_params)  # shallow copy

    for net, n_lay in n_layers.items():
        for layer in range(n_lay):
            # owners: (group, position-in-group, global client id)
            owners: List = []
            for g in groups:
                if layer in client_owned_layers(layer_pair(g.cut, net), n_lay):
                    for pos, cid in enumerate(g.client_ids):
                        owners.append((g, pos, cid))
            if not owners:
                continue
            # aggregate per cluster over owners
            for c in np.unique(cluster_labels[[cid for _, _, cid in owners]]):
                members = [(g, pos, cid) for g, pos, cid in owners
                           if cluster_labels[cid] == c]
                w = np.array([weights[cid] for _, _, cid in members])
                if w.sum() <= 0:
                    w = np.ones_like(w)
                w = w / w.sum()
                # gather copies -> stacked [M, ...]
                copies = [jax.tree_util.tree_map(lambda x: x[pos],
                                                 client_params[g.name][net][str(layer)])
                          for g, pos, _ in members]
                stacked = jax.tree_util.tree_map(
                    lambda *xs: jnp.stack(xs), *copies)
                agg = weighted_average_stacked(stacked, jnp.asarray(w),
                                               use_kernel=use_kernel)
                # scatter aggregate back to every member
                for g, pos, _ in members:
                    cur = out[g.name][net][str(layer)]
                    out[g.name][net][str(layer)] = jax.tree_util.tree_map(
                        lambda full, a: full.at[pos].set(a.astype(full.dtype)),
                        cur, agg)
    return out


def fedavg_uniform(groups: Sequence[ProfileGroup],
                   client_params: Dict[str, Dict[str, Dict[str, Any]]],
                   sizes: np.ndarray,
                   n_layers: Dict[str, int] = None,
                   use_kernel: bool = False,
                   fused: bool = True,
                   plan_cache: Optional[Dict] = None,
                   donate: Optional[bool] = None,
                   mesh: Optional[Mesh] = None,
                   chunk_size: Optional[int] = None,
                   cohort_mask: Optional[np.ndarray] = None
                   ) -> Dict[str, Dict[str, Dict[str, Any]]]:
    """Vanilla FedAvg (first two federation rounds, paper §4.5): the
    degenerate single-cluster case of the fused path — one global
    cluster, weights proportional to dataset size. With cohort_mask,
    sizes renormalize over the cohort and non-members sit the round
    out (same recv-select as the clustered cohort round)."""
    sizes = np.asarray(sizes, np.float64)
    if cohort_mask is not None:
        sized = sizes * np.asarray(cohort_mask, bool)
        weights = sized / sized.sum()
    else:
        weights = sizes / sizes.sum()
    labels = np.zeros(len(sizes), np.int64)
    return federate_client_params(groups, client_params, weights, labels,
                                  n_layers=n_layers, use_kernel=use_kernel,
                                  fused=fused, plan_cache=plan_cache,
                                  donate=donate, mesh=mesh,
                                  chunk_size=chunk_size,
                                  cohort_mask=cohort_mask)
