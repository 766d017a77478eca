"""Pallas TPU kernel: k-means nearest-center assignment.

labels[n] = argmin_m ||x[n] - c[m]||^2 — the inner step of the paper's
activation clustering (Eq. 12), dominated by the [N, D] x [D, M]
distance matmul.

TPU mapping: N is tiled into 128-row VMEM blocks (MXU-aligned); centers
[M, D] stay fully resident (M = #domains is tiny, D = activation dim up
to ~8k fits VMEM). The ||x||^2 term is constant under argmin and
dropped, so each block is one matmul on the MXU plus a VPU argmin:
    d2[n, m] ~ -2 x.c^T + ||c||^2.
The matmul runs at HIGHEST precision: at D ~ 6k a one-pass bf16
product moves near-tied distances enough to flip labels.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ROW_TILE = 128


def _kmeans_kernel(x_ref, c_ref, o_ref):
    """x_ref [ROWS, D]; c_ref [M, D]; o_ref [ROWS, 1] int32."""
    x = x_ref[...].astype(jnp.float32)
    c = c_ref[...].astype(jnp.float32)
    xc = jax.lax.dot_general(x, c, (((1,), (1,)), ((), ())),
                             precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32)
    scores = -2.0 * xc + jnp.sum(c * c, axis=-1)[None, :]
    o_ref[:, 0] = jnp.argmin(scores, axis=1).astype(jnp.int32)


def kmeans_assign(x: jnp.ndarray, centers: jnp.ndarray, *,
                  interpret: bool = True) -> jnp.ndarray:
    """x [N, D], centers [M, D] -> labels [N] int32."""
    N, D = x.shape
    M = centers.shape[0]
    N_pad = -(-N // ROW_TILE) * ROW_TILE
    xp = jnp.pad(x, ((0, N_pad - N), (0, 0)))
    out = pl.pallas_call(
        _kmeans_kernel,
        grid=(N_pad // ROW_TILE,),
        in_specs=[
            pl.BlockSpec((ROW_TILE, D), lambda i: (i, 0)),
            pl.BlockSpec((M, D), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((ROW_TILE, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N_pad, 1), jnp.int32),
        interpret=interpret,
    )(xp, centers)
    return out[:N, 0]
