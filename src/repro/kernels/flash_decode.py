"""Pallas TPU kernel: flash decode — one query token vs a long KV cache.

The serving hot spot for decode_32k / long_500k: out = softmax(q.K^T).V
with S up to 524288. HBM-bandwidth-bound (the whole cache streams once
per token), so the kernel's job is a single pass over S with an online
softmax, never materializing the [S] score vector in HBM.

TPU mapping: grid over (batch, S blocks), the cache length in SMEM as a
scalar-prefetch operand; each step loads a
[BLOCK_S, KV*hd] cache tile into VMEM, computes q.k on the MXU, and
maintains running (max, denom, acc) f32 accumulators in VMEM scratch.
GQA handled by grouping H = KV * G query heads per kv head. The final
grid step normalizes. Masking via the logical cache length (ring
caches pass min(length, S)).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_S = 512
# VMEM budget for one [block_s, KV, hd] cache tile: k and v are
# double-buffered, so four tiles plus the f32 score/prob temporaries
# must fit the scoped VMEM of a v5e core (16 MiB).
TILE_BYTES = 512 * 1024
NEG_INF = -1e30
_HIGHEST = jax.lax.Precision.HIGHEST


def _flash_decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                         acc_ref, m_ref, d_ref, *, block_s: int,
                         n_blocks: int):
    """Grid (B, n_blocks); one batch row x one cache block per step.

    q_ref [1, KV, G, hd]; k_ref/v_ref [1, block_s, KV, hd];
    o_ref [1, KV, G, hd]; scratch: acc [KV, G, hd], m/d [KV, G, 128].
    """
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        d_ref[...] = jnp.zeros_like(d_ref)

    q = q_ref[0].astype(jnp.float32)                     # [KV, G, hd]
    k = k_ref[0].astype(jnp.float32)                     # [S_blk, KV, hd]
    v = v_ref[0].astype(jnp.float32)
    hd = q.shape[-1]
    s = jnp.einsum("kgh,skh->kgs", q, k,
                   precision=_HIGHEST) / math.sqrt(hd)    # [KV, G, S_blk]
    pos = j * block_s + jnp.arange(block_s)
    valid = pos < len_ref[b]
    s = jnp.where(valid[None, None, :], s, NEG_INF)

    m_prev = m_ref[:, :, 0]                               # [KV, G]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[..., None])                     # [KV, G, S_blk]
    p = jnp.where(valid[None, None, :], p, 0.0)
    acc_ref[...] = acc_ref[...] * corr[..., None] + \
        jnp.einsum("kgs,skh->kgh", p, v, precision=_HIGHEST)
    d_ref[:, :, 0] = d_ref[:, :, 0] * corr + jnp.sum(p, axis=-1)
    m_ref[:, :, 0] = m_new

    @pl.when(j == n_blocks - 1)
    def _finalize():
        denom = jnp.maximum(d_ref[:, :, 0], 1e-30)[..., None]
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_decode(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                 cache_len: jnp.ndarray, *, block_s: int = BLOCK_S,
                 interpret: bool = True) -> jnp.ndarray:
    """q [B, H, hd]; k/v [B, S, KV, hd]; cache_len scalar -> [B, H, hd].

    ``block_s`` is capped so one cache tile stays within TILE_BYTES
    (a multiple of 8 rows), then at S."""
    B, H, hd = q.shape
    S, KV = k.shape[1], k.shape[2]
    G = H // KV
    fit = TILE_BYTES // (KV * hd * k.dtype.itemsize)
    block_s = min(block_s, max(8, fit // 8 * 8), S)
    S_pad = -(-S // block_s) * block_s
    kp = jnp.pad(k, ((0, 0), (0, S_pad - S), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, S_pad - S), (0, 0), (0, 0)))
    qh = q.reshape(B, KV, G, hd)
    n_blocks = S_pad // block_s
    lens = jnp.broadcast_to(jnp.minimum(cache_len, S).astype(jnp.int32),
                            (B,))

    out = pl.pallas_call(
        functools.partial(_flash_decode_kernel, block_s=block_s,
                          n_blocks=n_blocks),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, n_blocks),
            in_specs=[
                pl.BlockSpec((1, KV, G, hd), lambda b, j, lens: (b, 0, 0, 0)),
                pl.BlockSpec((1, block_s, KV, hd),
                             lambda b, j, lens: (b, j, 0, 0)),
                pl.BlockSpec((1, block_s, KV, hd),
                             lambda b, j, lens: (b, j, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, KV, G, hd),
                                   lambda b, j, lens: (b, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((KV, G, hd), jnp.float32),
                pltpu.VMEM((KV, G, 128), jnp.float32),
                pltpu.VMEM((KV, G, 128), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        interpret=interpret,
    )(lens, qh, kp, vp)
    return out.reshape(B, H, hd)
