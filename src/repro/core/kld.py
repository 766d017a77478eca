"""Activation-based KLD scoring — paper §4.5, Eq. (13)-(15).

P_k   = softmax(mean middle-layer discriminator activation of client k)
P_j,k = leave-one-out mean of P over client k's cluster
KLD_k = KL(P_k || P_j,k)
s_k   = n_k exp(-beta KLD_k) / sum_{j in cluster} n_j exp(-beta KLD_j)

Eq. (15) is computed in **log-space** (softmax of ``log n_k − beta
KLD_k`` within each cluster): the literal ``n_k exp(-beta KLD_k)``
underflows to all-zero at the paper's beta=150 for moderate KLDs,
which silently discarded the sizes and fell back to *uniform* weights.
The log-space form is exact where the literal form doesn't underflow
and stays size-weighted in the degenerate limit.

Also provides the label-distribution-based variant (FeGAN-style,
paper §6.3 comparison) which shares the same weighting equation but
feeds label histograms instead of activations, jit-compatible JAX
twins (``*_jax``) of the Eq. 13-15 chain for the device-resident
clustered round (DESIGN.md §Device-resident clustering), and the
cohort-renormalized variants (``cohort_federation_weights[_jax]``)
used when only a sampled cohort of the registered population
participates in a round (core/registry.py, DESIGN.md §Chunk-streamed
aggregation).
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def softmax_np(x: np.ndarray, axis: int = -1) -> np.ndarray:
    x = x - x.max(axis=axis, keepdims=True)
    e = np.exp(x)
    return e / e.sum(axis=axis, keepdims=True)


def kl_divergence(p: np.ndarray, q: np.ndarray, eps: float = 1e-12) -> float:
    """Eq. (2)."""
    p = np.clip(p, eps, None)
    q = np.clip(q, eps, None)
    return float(np.sum(p * np.log(p / q)))


def activation_distributions(acts: np.ndarray) -> np.ndarray:
    """Eq. (13): P_k = softmax(alpha_k)."""
    return softmax_np(acts.astype(np.float64), axis=-1)


def cluster_klds(P: np.ndarray, labels: np.ndarray) -> np.ndarray:
    """Eq. (14) leave-one-out cluster mean + Eq. (2) KLD per client."""
    K = P.shape[0]
    klds = np.zeros(K)
    for k in range(K):
        same = np.flatnonzero(labels == labels[k])
        others = same[same != k]
        if others.size == 0:
            klds[k] = 0.0
            continue
        P_j = P[others].sum(0) / others.size
        klds[k] = kl_divergence(P[k], P_j)
    return klds


def _logits(klds: np.ndarray, sizes: np.ndarray, beta: float) -> np.ndarray:
    """log n_k − beta KLD_k, the log of Eq. 15's unnormalized s_k."""
    return (np.log(np.maximum(sizes.astype(np.float64), 1e-300))
            - beta * np.asarray(klds, np.float64))


def _softmax_masked(logits: np.ndarray, mask: np.ndarray) -> np.ndarray:
    l = logits[mask]
    e = np.exp(l - l.max())
    return e / e.sum()


def federation_weights(klds: np.ndarray, sizes: np.ndarray,
                       labels: np.ndarray, beta: float = 150.0) -> np.ndarray:
    """Eq. (15): within-cluster normalized s_k. Returns [K] weights that
    sum to 1 *within each cluster*.

    Computed as a log-space softmax of ``log n_k − beta KLD_k`` per
    cluster: ``n_k exp(-beta KLD_k)`` underflows to all-zero at
    beta=150 for KLDs past ~5, and the old ``denom > 0`` fallback then
    silently dropped the sizes and went uniform."""
    logits = _logits(klds, sizes, beta)
    out = np.zeros_like(logits)
    for c in np.unique(labels):
        mask = labels == c
        out[mask] = _softmax_masked(logits, mask)
    return out


def global_weights(klds: np.ndarray, sizes: np.ndarray,
                   beta: float = 150.0) -> np.ndarray:
    """Eq. (15) applied globally (server-side segments, paper §4.5 end).
    Log-space for the same underflow reason as federation_weights."""
    logits = _logits(klds, sizes, beta)
    return _softmax_masked(logits, np.ones(len(logits), bool))


def cohort_federation_weights(klds: np.ndarray, sizes: np.ndarray,
                              labels: np.ndarray, cohort: np.ndarray,
                              beta: float = 150.0) -> np.ndarray:
    """Eq. (15) renormalized over a sampled *cohort*: within each
    cluster the softmax runs over the cohort members only, so the
    participating clients' weights sum to 1 per (cluster ∩ cohort)
    and every non-member gets exactly 0 (it contributes nothing to —
    and receives nothing from — the round; see core/registry.py).

    Same log-space form as ``federation_weights`` (softmax of
    ``log n_k − beta KLD_k``), so beta=150 cannot underflow the sizes
    away; a singleton cohort member in a cluster degenerates to
    weight 1.0. ``cohort``: [K] bool participation mask."""
    logits = _logits(klds, sizes, beta)
    cohort = np.asarray(cohort, bool)
    out = np.zeros_like(logits)
    for c in np.unique(labels[cohort]):
        mask = (labels == c) & cohort
        out[mask] = _softmax_masked(logits, mask)
    return out


def activation_weights(acts: np.ndarray, sizes: np.ndarray,
                       labels: np.ndarray, beta: float = 150.0
                       ) -> Tuple[np.ndarray, np.ndarray]:
    """End-to-end Eq. 13-15: returns (intra-cluster weights, klds)."""
    P = activation_distributions(acts)
    klds = cluster_klds(P, labels)
    return federation_weights(klds, sizes, labels, beta), klds


def label_weights(label_hists: np.ndarray, sizes: np.ndarray,
                  labels: np.ndarray, beta: float = 150.0
                  ) -> Tuple[np.ndarray, np.ndarray]:
    """FeGAN-style label-distribution KLD (privacy-leaking baseline,
    paper §6.3). label_hists: [K, num_classes] counts."""
    P = label_hists.astype(np.float64)
    P = P / np.clip(P.sum(-1, keepdims=True), 1e-12, None)
    klds = cluster_klds(P, labels)
    return federation_weights(klds, sizes, labels, beta), klds


# ---------------------------------------------------------------------------
# JAX twins (device-resident stage 4 — DESIGN.md §Device-resident clustering)
# ---------------------------------------------------------------------------

# One-hot segment sums run at HIGHEST precision: TPU's default f32
# matmul is one bf16 pass, and beta = 150 amplifies the rounding of a
# leave-one-out mean into the Eq.-15 weight logits.
_HIGHEST = jax.lax.Precision.HIGHEST

def cluster_klds_jax(P: jnp.ndarray, labels: jnp.ndarray,
                     num_clusters: int, eps: float = 1e-12) -> jnp.ndarray:
    """Traced twin of cluster_klds: Eq. (14) leave-one-out cluster mean
    + Eq. (2) KLD per client. ``num_clusters`` is the static label-id
    bound; singleton clusters score 0 like the numpy path."""
    onehot = jax.nn.one_hot(labels, num_clusters, dtype=P.dtype)   # [K, C]
    counts = onehot.sum(0)                                         # [C]
    csum = jnp.matmul(onehot.T, P, precision=_HIGHEST)             # [C, F]
    own = counts[labels]                                           # [K]
    loo = (csum[labels] - P) / jnp.maximum(own - 1.0, 1.0)[:, None]
    p = jnp.clip(P, eps, None)
    q = jnp.clip(loo, eps, None)
    kld = jnp.sum(p * (jnp.log(p) - jnp.log(q)), axis=-1)
    return jnp.where(own > 1, kld, 0.0)


def federation_weights_jax(klds: jnp.ndarray, sizes: jnp.ndarray,
                           labels: jnp.ndarray, num_clusters: int,
                           beta: float = 150.0) -> jnp.ndarray:
    """Traced twin of federation_weights: within-cluster log-space
    softmax of ``log n_k − beta KLD_k`` via one-hot segment reductions
    (no host loop over cluster ids)."""
    onehot = jax.nn.one_hot(labels, num_clusters, dtype=jnp.float32)
    logits = (jnp.log(jnp.maximum(sizes.astype(jnp.float32), 1e-30))
              - beta * klds.astype(jnp.float32))
    seg_max = jnp.where(onehot > 0, logits[:, None], -jnp.inf).max(0)  # [C]
    e = jnp.exp(logits - seg_max[labels])
    denom = jnp.matmul(onehot.T, e, precision=_HIGHEST)                # [C]
    return e / denom[labels]


def cohort_federation_weights_jax(klds: jnp.ndarray, sizes: jnp.ndarray,
                                  labels: jnp.ndarray,
                                  cohort_mask: jnp.ndarray,
                                  num_clusters: int,
                                  beta: float = 150.0) -> jnp.ndarray:
    """Traced twin of ``cohort_federation_weights``: within-cluster
    log-space softmax restricted to the cohort, via masked one-hot
    segment reductions. Non-members (and members of clusters with an
    empty cohort) come out exactly 0; the seg-max shift is guarded so
    an empty (cluster ∩ cohort) never produces a NaN."""
    m = cohort_mask.astype(bool)
    onehot = (jax.nn.one_hot(labels, num_clusters, dtype=jnp.float32)
              * m[:, None].astype(jnp.float32))                    # [K, C]
    logits = (jnp.log(jnp.maximum(sizes.astype(jnp.float32), 1e-30))
              - beta * klds.astype(jnp.float32))
    masked = jnp.where(onehot > 0, logits[:, None], -jnp.inf)
    seg_max = masked.max(0)                                        # [C]
    seg_max_safe = jnp.where(jnp.isfinite(seg_max), seg_max, 0.0)
    shifted = jnp.where(m, logits - seg_max_safe[labels], -jnp.inf)
    e = jnp.exp(shifted)                                           # [K]
    denom = jnp.matmul(onehot.T, e, precision=_HIGHEST)            # [C]
    d = denom[labels]
    return jnp.where(m & (d > 0), e / jnp.where(d > 0, d, 1.0), 0.0)


def activation_weights_jax(acts: jnp.ndarray, sizes: jnp.ndarray,
                           labels: jnp.ndarray, num_clusters: int,
                           beta: float = 150.0,
                           cohort_mask: jnp.ndarray = None
                           ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """End-to-end Eq. 13-15 on device: returns (intra-cluster weights,
    klds) as device arrays. f32 (the numpy oracle runs f64 — agreement
    is to fp tolerance, amplified by beta in the weights).

    ``cohort_mask`` (optional [K] bool) renormalizes the Eq.-15
    weights over the sampled cohort instead of the whole cluster; the
    KLDs themselves stay full-cluster (Eq. 14's leave-one-out mean is
    over the cluster the server clustered, participation only gates
    who synchronizes this round — DESIGN.md §Chunk-streamed
    aggregation)."""
    P = jax.nn.softmax(acts.astype(jnp.float32), axis=-1)
    klds = cluster_klds_jax(P, labels, num_clusters)
    if cohort_mask is not None:
        w = cohort_federation_weights_jax(klds, sizes, labels, cohort_mask,
                                          num_clusters, beta)
    else:
        w = federation_weights_jax(klds, sizes, labels, num_clusters, beta)
    return w, klds
