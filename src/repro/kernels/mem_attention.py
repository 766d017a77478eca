"""Pallas TPU kernel: memory-efficient (flash-style) prefill attention.

The split-serving engine's server-segment hot spot: a full [S, S]
attention over the prompt during the U-shaped LM prefill. A naive
softmax(q.K^T).V materializes the [H, S, S] score tensor; at serving
prompt lengths that is the peak-memory term. This kernel streams KV in
blocks with an online softmax — running (max, denom, acc) accumulators
in VMEM scratch, never more than one [block_q, block_k] score tile live
— the same recurrence as `flash_decode` extended from one query token
to a query block.

TPU mapping: the wrapper transposes to head-major [B, H, S, hd]; grid
(B, H, q blocks, k blocks), k innermost so the
scratch accumulators carry across a q block's KV sweep. Each step loads
a [block_q, hd] query tile and a [block_k, hd] KV tile into VMEM,
computes the tile's scores on the MXU, rescales the accumulator by
exp(m_prev - m_new) and folds the tile in; the last k block normalizes.
Causal masking (and the per-row valid-length mask for bucket-padded
cohorts) works off absolute positions, so out-of-diagonal tiles simply
contribute all-masked scores; the per-row lengths ride in SMEM as a
scalar-prefetch operand. GQA: kv head = query head // group size,
resolved in the BlockSpec index map.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_Q = 128
BLOCK_K = 128
NEG_INF = -1e30
_HIGHEST = jax.lax.Precision.HIGHEST


def _mem_attention_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                          acc_ref, m_ref, d_ref, *, block_q: int,
                          block_k: int, n_k: int, causal: bool):
    """One (batch, head, q block) x one k block per step.

    len_ref [B] int32 in SMEM (scalar prefetch); q_ref/o_ref
    [1, 1, block_q, hd]; k_ref/v_ref [1, 1, block_k, hd]; scratch: acc
    [block_q, hd], m/d [block_q, 128] (every lane carries the running
    max / denom of its row).
    """
    b = pl.program_id(0)
    qi = pl.program_id(2)
    kj = pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        d_ref[...] = jnp.zeros_like(d_ref)

    q = q_ref[0, 0].astype(jnp.float32)                  # [bq, hd]
    k = k_ref[0, 0].astype(jnp.float32)                  # [bk, hd]
    v = v_ref[0, 0].astype(jnp.float32)
    hd = q.shape[-1]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            precision=_HIGHEST,
                            preferred_element_type=jnp.float32)
    s = s / math.sqrt(hd)                                # [bq, bk]

    qpos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    kpos = kj * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = kpos < len_ref[b]
    if causal:
        mask = mask & (kpos <= qpos)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                                  # [bq, 128]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.where(mask, jnp.exp(s - m_new[:, :1]), 0.0)
    pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                             precision=_HIGHEST,
                             preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * corr[:, :1] + pv
    d_ref[...] = d_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    m_ref[...] = m_new

    @pl.when(kj == n_k - 1)
    def _finalize():
        denom = jnp.maximum(d_ref[:, :1], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def mem_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                  lens: jnp.ndarray, *, causal: bool = True,
                  block_q: int = BLOCK_Q, block_k: int = BLOCK_K,
                  interpret: bool = True) -> jnp.ndarray:
    """q [B, S, H, hd]; k/v [B, S, KV, hd] (H a multiple of KV — GQA);
    lens [B] or scalar valid prompt lengths -> [B, S, H, hd].

    Rows past ``lens`` (bucket padding in the serving cohort) see an
    all-masked score row and produce zeros-after-normalization garbage;
    callers slice or mask them — the engine pads per cut bucket and
    discards the tail.

    The kernel runs head-major (``[B, H, S, hd]``): a TPU block's last
    two dims must tile (8, 128) or span the array, which a one-head
    block of the sequence-major layout cannot do.
    """
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    S_q = -(-S // block_q) * block_q
    S_k = -(-S // block_k) * block_k
    qt = jnp.pad(q, ((0, 0), (0, S_q - S), (0, 0), (0, 0))
                 ).transpose(0, 2, 1, 3)
    kt = jnp.pad(k, ((0, 0), (0, S_k - S), (0, 0), (0, 0))
                 ).transpose(0, 2, 1, 3)
    vt = jnp.pad(v, ((0, 0), (0, S_k - S), (0, 0), (0, 0))
                 ).transpose(0, 2, 1, 3)
    n_q = S_q // block_q
    n_k = S_k // block_k
    lens_b = jnp.broadcast_to(jnp.minimum(lens, S).astype(jnp.int32), (B,))

    out = pl.pallas_call(
        functools.partial(_mem_attention_kernel, block_q=block_q,
                          block_k=block_k, n_k=n_k, causal=causal),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H, n_q, n_k),
            in_specs=[
                pl.BlockSpec((1, 1, block_q, hd),
                             lambda b, h, i, j, lens: (b, h, i, 0)),
                pl.BlockSpec((1, 1, block_k, hd),
                             lambda b, h, i, j, lens: (b, h // G, j, 0)),
                pl.BlockSpec((1, 1, block_k, hd),
                             lambda b, h, i, j, lens: (b, h // G, j, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, block_q, hd),
                                   lambda b, h, i, j, lens: (b, h, i, 0)),
            scratch_shapes=[
                pltpu.VMEM((block_q, hd), jnp.float32),
                pltpu.VMEM((block_q, 128), jnp.float32),
                pltpu.VMEM((block_q, 128), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, H, S_q, hd), q.dtype),
        interpret=interpret,
    )(lens_b, qt, kt, vt)
    return out.transpose(0, 2, 1, 3)[:, :S]
