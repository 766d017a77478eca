"""Kernel micro-benchmarks: Pallas kernels vs the pure-jnp oracle.

The backend picks the kernel mode: on the CPU backend (tests, this
container) the Pallas bodies run interpreted, so the numbers
characterize the interpreter, not a kernel; on TPU the same command
times the compiled kernels. Reported as name,us_per_call,derived-GB/s.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref


def _bench(fn, *args, iters: int = 5) -> float:
    jax.block_until_ready(fn(*args))   # warmup/compile, one call
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters * 1e6  # us


def run(report):
    key = jax.random.PRNGKey(0)
    # weighted_agg: 16 clients x 3M params (the GAN federation round)
    K, D = 16, 3_000_000
    x = jax.random.normal(key, (K, D), jnp.float32)
    w = jax.nn.softmax(jax.random.normal(key, (K,)))
    us = _bench(ops.weighted_agg, x, w)
    gbps = K * D * 4 / (us / 1e6) / 1e9
    report("kernel/weighted_agg_16x3M", us, f"{gbps:.1f}GB/s")
    us = _bench(jax.jit(ref.weighted_agg_ref), x, w)
    report("kernel/weighted_agg_ref", us, "oracle")

    # clustered multi-output aggregation: 16 (layer, cluster) segments
    # over the same 16 x 3M stacked buffer (the fused federation round)
    S = 16
    seg_w = jax.nn.softmax(jax.random.normal(key, (S, K)), axis=1)
    us = _bench(ops.clustered_agg, seg_w, x)
    gbps = (K + S) * D * 4 / (us / 1e6) / 1e9
    report("kernel/clustered_agg_16seg_16x3M", us, f"{gbps:.1f}GB/s")
    us = _bench(jax.jit(ref.clustered_agg_ref), seg_w, x)
    report("kernel/clustered_agg_ref", us, "oracle")

    # kmeans assign: 256 clients x 6272-dim activations, 4 centers
    x = jax.random.normal(key, (256, 6272))
    c = jax.random.normal(key, (4, 6272))
    report("kernel/kmeans_assign_256x6272", _bench(ops.kmeans_assign, x, c),
           "")
    report("kernel/kmeans_assign_ref",
           _bench(jax.jit(ref.kmeans_assign_ref), x, c), "oracle")

    # flash decode: B=4, H=32 (kv 8), 4k cache (interpret mode on CPU
    # is the oracle-path timing; use 32k+ on real TPU)
    B, H, KV, hd, S = 4, 32, 8, 128, 4096
    q = jax.random.normal(key, (B, H, hd), jnp.bfloat16)
    k = jax.random.normal(key, (B, S, KV, hd), jnp.bfloat16)
    v = jax.random.normal(key, (B, S, KV, hd), jnp.bfloat16)
    clen = jnp.asarray(S, jnp.int32)
    us = _bench(ops.flash_decode, q, k, v, clen, iters=2)
    stream_gb = 2 * B * S * KV * hd * 2 / 1e9
    report("kernel/flash_decode_4k", us,
           f"streams {stream_gb:.2f}GB/call")
    report("kernel/flash_decode_ref",
           _bench(jax.jit(ref.flash_decode_ref), q, k, v, clen, iters=2),
           "oracle")
