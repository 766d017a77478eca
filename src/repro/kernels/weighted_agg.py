"""Pallas TPU kernel: KLD-weighted clustered aggregation of one stacked
client leaf, in the layout the leaf is stored in.

``clustered_agg`` computes, for one parameter leaf held stacked over its
owners' leading client axis, split across the profile groups that own it,

    agg[c, ...] = sum_g sum_r W_g[c, r] * x_g[r, ...]

``W_g [C, R_g]`` holds group ``g``'s columns of the (layer, cluster)
weight block — one row per cluster, normalized over the cluster's
members — and ``x_g [R_g, ...]`` is the group's stacked leaf. The groups
are never concatenated: each group's rows stream through the kernel
once and their partial contractions add up in VMEM, so a federation
round reads every client row once and writes ``C`` aggregate rows
(repro.core.federation broadcasts them back with a gather along the
client axis).

Layout: a leaf ``[R, *shape]`` is viewed as ``[R, L]`` (rank-1 leaves)
or ``[R, M, L]`` (``M`` the product of the leading dims of ``shape``,
``L`` its minor dim; ``leaf_view``). On the TPU's tiled layout that
view is a bitcast whenever ``shape[-2]`` is a multiple of the sublane
tile, so no relayout of the client state happens around the kernel.
The grid streams ``(R_g, tm, tl)`` blocks (``(R_g, tl)`` for rank-1
leaves) of every group at once, about ``BLOCK_ELEMS`` elements of each
client row per step; each step is one ``[C, R_g] x [R_g, tm * tl]``
contraction per group on the MXU at HIGHEST precision (averaged
parameters must keep f32 accuracy; the extra MXU passes hide under the
HBM stream). Arithmetic intensity is ``2 C`` FLOPs per 4 bytes read, so
the kernel is HBM-bandwidth-bound.

``kernel_fits`` says from a leaf's shape alone whether the compiled
kernel takes it: the minor dim must fill whole 128-lane tiles and the
view must be a bitcast; smaller or ragged leaves (biases, BatchNorm
vectors, lane dims of 1 or 64) contract in plain XLA inside the same
program.

Sharded rounds (repro.core.federation, ``mesh=``) invoke the same
kernel per shard inside a ``shard_map`` over the client axis; the
cross-device ``psum`` of the small ``[C, ...]`` aggregates happens
outside the kernel. (shard_map needs ``check_vma=False`` around
pallas_call; the caller handles that.)

In interpret mode (the CPU oracle path) the emulator pays a
full-operand copy per grid step, so the wrapper runs the whole leaf in
a single step unless ``block`` is given — same kernel body, same math.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 128
SUBLANE = 8
# elements of one client row a grid step streams (32 KiB f32): with 100
# client rows double-buffered that is ~6.5 MiB of VMEM
BLOCK_ELEMS = 8192


def leaf_view(shape: Tuple[int, ...]) -> Tuple[int, ...]:
    """The kernel's view of a stacked leaf ``[R, *s]``: ``[R, L]`` for
    rank-1 ``s``, ``[R, prod(s[:-1]), s[-1]]`` otherwise (``[R, 1]``
    for a scalar per client)."""
    R, s = shape[0], tuple(shape[1:])
    if len(s) == 0:
        return (R, 1)
    if len(s) == 1:
        return (R, s[0])
    m = 1
    for d in s[:-1]:
        m *= d
    return (R, m, s[-1])


def kernel_fits(leaf_shape: Tuple[int, ...]) -> bool:
    """Whether the compiled kernel takes a per-client leaf shape: at
    least one (8, 128) tile of data, a minor dim that fills whole lane
    tiles, and a view that is a bitcast of the tiled layout."""
    s = tuple(leaf_shape)
    if not s or s[-1] % LANE:
        return False
    size = 1
    for d in s:
        size *= d
    if size < SUBLANE * LANE:
        return False
    return len(s) <= 2 or s[-2] % SUBLANE == 0


def _blocks(view: Tuple[int, ...]) -> Tuple[int, ...]:
    """Per-client block of a view for the compiled grid: ``(tl,)`` or
    ``(tm, tl)``, about ``BLOCK_ELEMS`` elements, lane-tile aligned (a
    dim smaller than its tile is taken whole)."""
    if len(view) == 2:
        L = view[1]
        return (L if L <= BLOCK_ELEMS else BLOCK_ELEMS,)
    M, L = view[1], view[2]
    tl = L if L <= BLOCK_ELEMS // SUBLANE else BLOCK_ELEMS // SUBLANE
    tm = max(SUBLANE, (BLOCK_ELEMS // tl) // SUBLANE * SUBLANE)
    return (M if M <= tm else tm, tl)


def _clustered_agg_kernel(*refs):
    """Blocks: G weight refs ``[C, R_g]``, G leaf refs ``[R_g, tl]`` or
    ``[R_g, tm, tl]``, then the output ``[C, tl]`` / ``[C, tm, tl]``.
    One ``[C, R_g] x [R_g, tm * tl]`` matmul per group per grid step,
    summed in f32."""
    o_ref = refs[-1]
    G = (len(refs) - 1) // 2
    acc = None
    for w_ref, x_ref in zip(refs[:G], refs[G:2 * G]):
        x = x_ref[...].astype(jnp.float32)
        part = jax.lax.dot_general(
            w_ref[...].astype(jnp.float32), x.reshape(x.shape[0], -1),
            (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        acc = part if acc is None else acc + part
    o_ref[...] = acc.reshape(o_ref.shape)


def clustered_agg(weights: Sequence[jnp.ndarray],
                  views: Sequence[jnp.ndarray], *,
                  block: Optional[Tuple[int, ...]] = None,
                  interpret: bool = True) -> jnp.ndarray:
    """``sum_g weights[g] [C, R_g] @ views[g] [R_g, (M,) L]`` -> ``[C,
    (M,) L]`` f32, one ``pallas_call`` over every group's rows.

    ``views`` are leaf views (``leaf_view``) sharing their trailing
    shape. ``block`` overrides the per-client block (``(tl,)`` or
    ``(tm, tl)``); by default the compiled kernel streams ``_blocks``
    and interpret mode takes the whole view in one step."""
    ws = [jnp.asarray(w, jnp.float32) for w in weights]
    xs = list(views)
    tail = tuple(xs[0].shape[1:])
    C = ws[0].shape[0]
    if block is None:
        block = tail if interpret else _blocks(xs[0].shape)
    block = tuple(block)
    grid = tuple(pl.cdiv(n, b) for n, b in zip(tail, block))
    if len(block) == 1:
        idx = lambda i: (0, i)
    else:
        idx = lambda i, j: (0, i, j)
    w_specs = [pl.BlockSpec(w.shape, lambda *g: (0, 0)) for w in ws]
    x_specs = [pl.BlockSpec((x.shape[0],) + block, idx) for x in xs]
    return pl.pallas_call(
        _clustered_agg_kernel,
        grid=grid,
        in_specs=w_specs + x_specs,
        out_specs=pl.BlockSpec((C,) + block, idx),
        out_shape=jax.ShapeDtypeStruct((C,) + tail, jnp.float32),
        interpret=interpret,
    )(*ws, *xs)


def weighted_agg_flat(stacked_flat: jnp.ndarray, weights: jnp.ndarray, *,
                      interpret: bool = True) -> jnp.ndarray:
    """stacked_flat [K, D] -> [D]: the degenerate single-cluster,
    single-group case of ``clustered_agg``."""
    out = clustered_agg([weights.reshape(1, -1)], [stacked_flat],
                        interpret=interpret)
    return out[0].astype(stacked_flat.dtype)
