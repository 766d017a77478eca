"""Pallas TPU kernels: KLD-weighted federated parameter aggregation.

Two entry points share one kernel body:

``weighted_agg_flat``   — the original single-output reduction
    out[d] = sum_k w[k] * theta[k, d] over a flat parameter vector.

``clustered_agg_flat``  — the multi-output clustered generalization
    agg[s, d] = sum_k W[s, k] * theta[k, d]
    i.e. ``W @ theta`` with the (small) weight matrix resident in VMEM
    and the parameter axis streamed in (SUBLANE, LANE) = (8, 1024)
    tiles.  One row of ``W`` per (layer, cluster) aggregation segment:
    this computes *every* cluster aggregate of a federation round (Eq.
    16) in a single ``pallas_call`` per network instead of one dispatch
    per (layer, cluster, leaf).  The block-diagonal "one row per
    receiving client copy" broadcast matrix factors exactly as
    ``W_full = B @ W`` with ``B`` one-hot; the cheap ``B`` gather is
    applied outside the kernel (see repro.core.federation), so the
    kernel only streams theta once and writes S aggregate rows rather
    than M >> S broadcast rows.

TPU mapping: the flat parameter axis is tiled into (8, 1024)-shaped
VMEM blocks (sublane x lane aligned); the weight matrix stays resident
per block so each block is one [S, K] x [K, 8*1024] contraction on the
MXU/VPU — arithmetic intensity is low (streaming reduction), so the
kernel is HBM-bandwidth-bound and the tiling keeps aligned 2D tiles
streaming through VMEM exactly once. The contraction runs at HIGHEST
precision: averaged parameters must keep f32 accuracy, and the extra
MXU passes hide under the HBM stream.

Sharded rounds (repro.core.federation, ``mesh=``) invoke the same
kernel *per shard* inside a ``shard_map`` over the client axis: each
device's block sees only its local ``[K/n, D]`` row slice of theta
(and the matching ``[S, K/n]`` column slice of ``W``), computes the
local partial aggregate, and the cross-device ``psum`` happens outside
the kernel — the kernel body is oblivious to the mesh, K is simply
smaller.  (shard_map needs ``check_vma=False`` around pallas_call;
the caller handles that.)

``block_tiles`` groups several (8, 1024) tiles into one grid step.  On
real TPU keep the default of 1 (a [K, 8, 1024] block per step fits
VMEM); in interpret mode (the CPU oracle path) the emulator pays a
full-operand copy per grid step, so the wrapper coalesces the whole
parameter axis into a single step — same kernel body, same math.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 1024        # lane-dim tile (multiple of 128)
SUBLANE = 8        # sublane tile


def _clustered_agg_kernel(w_ref, x_ref, o_ref):
    """Blocks: w_ref [S, K]; x_ref [K, T, SUBLANE, LANE]; o_ref
    [S, T, SUBLANE, LANE]. One [S, K] x [K, T*SUBLANE*LANE] matmul
    per grid step (T = block_tiles)."""
    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    K = x.shape[0]
    agg = jax.lax.dot_general(w, x.reshape(K, -1),
                              (((1,), (0,)), ((), ())),
                              precision=jax.lax.Precision.HIGHEST,
                              preferred_element_type=jnp.float32)
    o_ref[...] = agg.reshape((w.shape[0],) + x.shape[1:])


def clustered_agg_flat(weights: jnp.ndarray, stacked_flat: jnp.ndarray, *,
                       block_tiles: int | None = None,
                       interpret: bool = True) -> jnp.ndarray:
    """Multi-output clustered aggregation: weights [S, K] @
    stacked_flat [K, D] -> [S, D] f32; D padded to SUBLANE*LANE tiles.

    Each weight row is one aggregation segment (a (layer, cluster)
    block of the federation round), already normalized over its
    members and zero elsewhere.
    """
    K, D = stacked_flat.shape
    S = weights.shape[0]
    tile = SUBLANE * LANE
    n_tiles = max(1, -(-D // tile))
    if block_tiles is None:
        # interpret mode pays a full-operand copy per grid step — run
        # the whole parameter axis in one step; compiled TPU streams
        # tile by tile.
        block_tiles = n_tiles if interpret else 1
    steps = -(-n_tiles // block_tiles)
    D_pad = steps * block_tiles * tile
    x = jnp.pad(stacked_flat, ((0, 0), (0, D_pad - D)))
    x = x.reshape(K, steps * block_tiles, SUBLANE, LANE)
    w = weights.astype(jnp.float32)

    out = pl.pallas_call(
        _clustered_agg_kernel,
        grid=(steps,),
        in_specs=[
            pl.BlockSpec((S, K), lambda i: (0, 0)),
            pl.BlockSpec((K, block_tiles, SUBLANE, LANE),
                         lambda i: (0, i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((S, block_tiles, SUBLANE, LANE),
                               lambda i: (0, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((S, steps * block_tiles, SUBLANE,
                                        LANE), jnp.float32),
        interpret=interpret,
    )(w, x)
    return out.reshape(S, D_pad)[:, :D]


def weighted_agg_flat(stacked_flat: jnp.ndarray, weights: jnp.ndarray, *,
                      interpret: bool = True) -> jnp.ndarray:
    """stacked_flat [K, D] -> [D]: the degenerate single-segment case
    of ``clustered_agg_flat`` (S=1, all clients in one cluster)."""
    out = clustered_agg_flat(weights.reshape(1, -1), stacked_flat,
                             block_tiles=None if interpret else 1,
                             interpret=interpret)
    return out[0].astype(stacked_flat.dtype)
