"""Jit'd public wrappers for the Pallas kernels.

The backend picks the kernel mode when a wrapper is traced: on the CPU
backend (the test suite, ``JAX_PLATFORMS=cpu PYTHONPATH=src pytest``)
the kernel body runs in Pallas interpret mode; on TPU every kernel is
compiled through Mosaic (``python chip_smoke.py`` drives them there).
There is no switch and no fallback to the ``ref`` twins. All wrappers
are shape-polymorphic at the JAX level and validated against
repro.kernels.ref oracles in tests/test_kernels.py; tests/
test_tpu_compile.py compiles them for a described v5e chip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import flash_decode as _fd
from repro.kernels import kmeans_assign as _km
from repro.kernels import mem_attention as _ma
from repro.kernels import weighted_agg as _wa


def _interpret() -> bool:
    """Interpret mode exactly when tracing for the CPU backend; read at
    trace time, never at import."""
    return jax.default_backend() == "cpu"


@functools.partial(jax.jit, static_argnames=())
def weighted_agg(stacked: jnp.ndarray, weights: jnp.ndarray) -> jnp.ndarray:
    """out = sum_k w[k] * stacked[k, ...] (any trailing shape)."""
    K = stacked.shape[0]
    flat = stacked.reshape(K, -1)
    out = _wa.weighted_agg_flat(flat, weights, interpret=_interpret())
    return out.reshape(stacked.shape[1:])


@jax.jit
def clustered_agg(weights, stacked) -> jnp.ndarray:
    """Clustered aggregation of one stacked leaf, split over the groups
    that own it: ``out[c] = sum_g sum_k weights[g][c, k] *
    stacked[g][k, ...]`` in f32 (any trailing shape, shared by the
    groups). ``weights``/``stacked`` are one array each or matching
    sequences (one entry per group; the groups are never concatenated).
    The kernel runs on each leaf's ``leaf_view``, a reshape that keeps
    the two minor dims.

    NOTE: the clustered family takes weights FIRST (matmul order,
    ``W @ theta``), unlike the legacy ``weighted_agg(stacked, w)`` —
    a transposed call fails on shape unless S == K."""
    if not isinstance(stacked, (list, tuple)):
        weights, stacked = [weights], [stacked]
    tail = stacked[0].shape[1:]
    views = [x.reshape(_wa.leaf_view(x.shape)) for x in stacked]
    out = _wa.clustered_agg(weights, views, interpret=_interpret())
    return out.reshape((out.shape[0],) + tail)


@jax.jit
def kmeans_assign(x: jnp.ndarray, centers: jnp.ndarray) -> jnp.ndarray:
    return _km.kmeans_assign(x, centers, interpret=_interpret())


@jax.jit
def flash_decode(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                 cache_len: jnp.ndarray) -> jnp.ndarray:
    return _fd.flash_decode(q, k, v, cache_len, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("causal",))
def mem_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                  lens: jnp.ndarray, causal: bool = True) -> jnp.ndarray:
    """Memory-efficient prefill attention: q [B, S, H, hd],
    k/v [B, S, KV, hd], lens [B] -> [B, S, H, hd] without ever
    materializing the [S, S] score tensor (the split-serving engine's
    server-segment prefill block)."""
    return _ma.mem_attention(q, k, v, lens, causal=causal,
                             interpret=_interpret())
