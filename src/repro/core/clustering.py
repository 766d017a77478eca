"""K-means clustering of discriminator mid-layer activations — paper §4.5.

Two implementations of the same stage-3 procedure:

* the numpy reference (runs on the 'server'; K = #clients is small):
  k-means++ seeding, Lloyd iterations; the number of clusters is
  selected by silhouette score over k in [2, k_max], falling back to
  k=1 when the best silhouette is weak (single-domain populations);
* a jit-compatible JAX twin (``cluster_activations_jax``) used by the
  device-resident clustered round (DESIGN.md §Device-resident
  clustering): the Lloyd loop is a ``lax.scan``, k-means++ seeding
  draws from a ``jax.random`` key, and the assignment step can run the
  Pallas ``kmeans_assign`` kernel behind ``use_kernel``. Every
  candidate k in [2, upper] is unrolled at trace time (``upper`` is
  the static ``k_selection_bound``), so shapes are fixed and the
  function traces once per population size.

Both paths canonicalize labels to first-occurrence order so their
cluster ids are directly comparable (k-means labels are otherwise
arbitrary up to permutation).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def kmeans_pp_init(x: np.ndarray, k: int, rng: np.random.Generator) -> np.ndarray:
    n = x.shape[0]
    centers = [x[rng.integers(n)]]
    for _ in range(1, k):
        d2 = np.min(((x[:, None, :] - np.array(centers)[None]) ** 2).sum(-1), 1)
        total = d2.sum()
        if total <= 1e-12:
            centers.append(x[rng.integers(n)])
            continue
        probs = d2 / total
        centers.append(x[rng.choice(n, p=probs)])
    return np.array(centers)


def kmeans(x: np.ndarray, k: int, *, iters: int = 50, seed: int = 0
           ) -> Tuple[np.ndarray, np.ndarray, float]:
    """Returns (labels [N], centers [k, D], inertia)."""
    rng = np.random.default_rng(seed)
    if k <= 1:
        center = x.mean(0, keepdims=True)
        inertia = float(((x - center) ** 2).sum())
        return np.zeros(x.shape[0], np.int32), center, inertia
    centers = kmeans_pp_init(x, k, rng)
    labels = np.zeros(x.shape[0], np.int32)
    for _ in range(iters):
        d2 = ((x[:, None, :] - centers[None]) ** 2).sum(-1)
        new_labels = d2.argmin(1).astype(np.int32)
        if np.array_equal(new_labels, labels) and _ > 0:
            break
        labels = new_labels
        empties = []
        for c in range(k):
            mask = labels == c
            if mask.any():
                centers[c] = x[mask].mean(0)
            else:
                empties.append(c)
        if empties:
            # Re-seed empty clusters at farthest points, measured
            # against the *updated* non-empty centers, excluding points
            # already chosen this pass — the stale pre-update d2 put
            # every empty cluster on the same farthest point, leaving
            # duplicate centers forever.
            valid = [c for c in range(k) if c not in empties]
            d2u = ((x[:, None, :] - centers[valid][None]) ** 2
                   ).sum(-1).min(1)
            for c in empties:
                i = int(d2u.argmax())
                centers[c] = x[i]
                d2u[i] = -np.inf
    inertia = float(((x - centers[labels]) ** 2).sum())
    return labels, centers, inertia


def silhouette(x: np.ndarray, labels: np.ndarray) -> float:
    """Mean silhouette coefficient (euclidean).

    Singleton clusters score s_i = 0 (the standard convention): the
    old a=0 ⇒ s_i=1 treatment handed every lone point a perfect score,
    biasing silhouette k-selection toward fragmenting clusters."""
    n = x.shape[0]
    uniq = np.unique(labels)
    if uniq.size < 2 or n < 3:
        return -1.0
    d = np.sqrt(np.maximum(((x[:, None, :] - x[None]) ** 2).sum(-1), 0.0))
    s = np.zeros(n)
    for i in range(n):
        same = labels == labels[i]
        same[i] = False
        if not same.any():          # singleton cluster
            continue
        a = d[i][same].mean()
        bs = [d[i][labels == c].mean() for c in uniq if c != labels[i]]
        b = min(bs)
        s[i] = 0.0 if max(a, b) == 0 else (b - a) / max(a, b)
    return float(s.mean())


def canonicalize_labels(labels: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Relabel clusters to first-occurrence order. Returns
    (canonical labels, old->new id map over [0, labels.max()])."""
    labels = np.asarray(labels)
    uniq, first = np.unique(labels, return_index=True)
    order = np.argsort(first)            # uniq[order] = appearance order
    remap = np.zeros(int(uniq.max()) + 1, labels.dtype)
    remap[uniq[order]] = np.arange(order.size, dtype=labels.dtype)
    return remap[labels], remap


def k_selection_bound(n_clients: int, k: Optional[int] = None,
                      k_max: int = 6) -> int:
    """Static upper bound on cluster ids out of cluster_activations /
    cluster_activations_jax — the silhouette-selection candidate cap
    (or the forced k). The device round sizes its in-jit weight matrix
    by this bound so the segment count never retraces."""
    if k is not None:
        return max(1, int(k))
    return min(k_max, max(2, n_clients // 2))


@dataclasses.dataclass
class ClusterResult:
    labels: np.ndarray
    centers: np.ndarray
    k: int
    silhouette: float


def cluster_activations(acts: np.ndarray, *, k: Optional[int] = None,
                        k_max: int = 6, seed: int = 0,
                        min_silhouette: float = 0.15) -> ClusterResult:
    """Cluster client activation vectors [K_clients, D].

    If `k` is given, use it (the paper assumes domain count detection);
    otherwise pick k by silhouette, accepting k=1 when separation is weak.
    """
    # standardize (activation scales vary across training)
    mu, sd = acts.mean(0), acts.std(0) + 1e-8
    z = (acts - mu) / sd

    def _canonical(labels, centers):
        new_labels, remap = canonicalize_labels(labels)
        # move the center rows of appearing clusters to their new ids;
        # rows of empty clusters land past them and are never referenced
        new = centers.copy()
        for old in np.unique(labels):
            new[remap[old]] = centers[old]
        return new_labels, new

    if k is not None:
        labels, centers, _ = kmeans(z, k, seed=seed)
        labels, centers = _canonical(labels, centers)
        return ClusterResult(labels, centers, k, silhouette(z, labels))
    best: Optional[ClusterResult] = None
    upper = k_selection_bound(acts.shape[0], k_max=k_max)
    for kk in range(2, upper + 1):
        labels, centers, _ = kmeans(z, kk, seed=seed)
        labels, centers = _canonical(labels, centers)
        sil = silhouette(z, labels)
        if best is None or sil > best.silhouette:
            best = ClusterResult(labels, centers, kk, sil)
    if best is None or best.silhouette < min_silhouette:
        labels, centers, _ = kmeans(z, 1, seed=seed)
        return ClusterResult(labels, centers, 1, 0.0)
    return best


# ---------------------------------------------------------------------------
# JAX twins (device-resident stage 3 — DESIGN.md §Device-resident clustering)
# ---------------------------------------------------------------------------

def canonicalize_labels_jax(labels: jnp.ndarray, num_clusters: int
                            ) -> jnp.ndarray:
    """Traced twin of canonicalize_labels: relabel to first-occurrence
    order. ``num_clusters`` is the static id bound."""
    n = labels.shape[0]
    first = jnp.full(num_clusters, n, jnp.int32)
    first = first.at[labels].min(jnp.arange(n, dtype=jnp.int32))
    # appearance rank; absent clusters (first == n) sort last, stably
    rank = jnp.argsort(jnp.argsort(first))
    return rank[labels].astype(labels.dtype)


# Distance and centre matmuls run at HIGHEST precision: TPU's default
# f32 matmul is one bf16 pass, which at F = 6272 moves near-tied
# distances enough to flip labels and the selected k.
_HIGHEST = jax.lax.Precision.HIGHEST


def _sq_dists(x: jnp.ndarray, centers: jnp.ndarray) -> jnp.ndarray:
    """[N, M] squared euclidean distances, clipped at 0."""
    xc = jnp.matmul(x, centers.T, precision=_HIGHEST)
    d2 = (jnp.sum(x * x, -1)[:, None]
          - 2.0 * xc + jnp.sum(centers * centers, -1)[None, :])
    return jnp.maximum(d2, 0.0)


def _assign(x: jnp.ndarray, centers: jnp.ndarray,
            use_kernel: bool) -> jnp.ndarray:
    """argmin_m ||x - c_m||^2 — Pallas kmeans_assign behind use_kernel
    (the ||x||^2 term is constant under argmin either way)."""
    if use_kernel:
        from repro.kernels import ops as kops
        return kops.kmeans_assign(x, centers)
    scores = (-2.0 * jnp.matmul(x, centers.T, precision=_HIGHEST)
              + jnp.sum(centers * centers, -1)[None, :])
    return jnp.argmin(scores, axis=1).astype(jnp.int32)


def _kmeans_pp_init_jax(x: jnp.ndarray, k: int, key: jnp.ndarray
                        ) -> jnp.ndarray:
    """k-means++ seeding from a jax PRNG key. Unfilled center slots sit
    at +inf so distance minima only ever see chosen centers."""
    n = x.shape[0]
    key, k0 = jax.random.split(key)
    centers = jnp.full((k,) + x.shape[1:], jnp.inf, x.dtype)
    centers = centers.at[0].set(x[jax.random.randint(k0, (), 0, n)])

    def body(j, carry):
        centers, key = carry
        key, kc = jax.random.split(key)
        d2 = ((x[:, None, :] - centers[None]) ** 2).sum(-1).min(1)
        total = d2.sum()
        # degenerate (all points on chosen centers): uniform draw,
        # matching kmeans_pp_init's total <= 1e-12 fallback
        logits = jnp.where(total > 1e-12,
                           jnp.log(jnp.maximum(d2, 1e-30)),
                           jnp.zeros_like(d2))
        idx = jax.random.categorical(kc, logits)
        return centers.at[j].set(x[idx]), key

    centers, _ = jax.lax.fori_loop(1, k, body, (centers, key))
    return centers


def kmeans_jax(x: jnp.ndarray, k: int, key: jnp.ndarray, *,
               iters: int = 50, use_kernel: bool = False
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Jitted Lloyd loop: returns (labels [N] int32, centers [k, D]).

    ``k``/``iters``/``use_kernel`` are static; the iteration is a
    ``lax.while_loop`` with the numpy loop's convergence test (labels
    stable after the first update) as the in-graph exit condition —
    fixed-trip-count scanning burned ~iters/actual-iters more wall
    than the host path, which early-breaks. The assignment step
    optionally runs the Pallas ``kmeans_assign`` kernel, and empty
    clusters re-seed at distinct farthest points measured against the
    updated centers (the same semantics as the fixed numpy
    ``kmeans``)."""
    n = x.shape[0]
    if k <= 1:
        return (jnp.zeros(n, jnp.int32), jnp.mean(x, 0, keepdims=True))
    centers0 = _kmeans_pp_init_jax(x, k, key)

    def cond(carry):
        _, _, it, done = carry
        return (~done) & (it < iters)

    def body(carry):
        centers, labels, it, _ = carry
        new_labels = _assign(x, centers, use_kernel)
        done = (it > 0) & jnp.all(new_labels == labels)
        onehot = jax.nn.one_hot(new_labels, k, dtype=x.dtype)    # [N, k]
        counts = onehot.sum(0)                                   # [k]
        sums = jnp.matmul(onehot.T, x, precision=_HIGHEST)      # [k, D]
        new = jnp.where(counts[:, None] > 0,
                        sums / jnp.maximum(counts, 1.0)[:, None], centers)
        # empty-cluster re-seed: farthest points from the *updated*
        # non-empty centers, one distinct point per empty cluster
        d2c = _sq_dists(x, new)
        d2u = jnp.where(counts[None, :] > 0, d2c, jnp.inf).min(1)
        taken = jnp.zeros(n, bool)
        for c in range(k):                       # static unroll, k small
            empty = counts[c] == 0
            idx = jnp.argmax(jnp.where(taken, -jnp.inf, d2u))
            new = new.at[c].set(jnp.where(empty, x[idx], new[c]))
            taken = taken.at[idx].set(taken[idx] | empty)
        # a converged step keeps the previous centers (the numpy loop
        # breaks before its update; the update would be idempotent)
        new = jnp.where(done, centers, new)
        return new, new_labels, it + 1, done

    centers, _, _, _ = jax.lax.while_loop(
        cond, body, (centers0, jnp.zeros(n, jnp.int32),
                     jnp.zeros((), jnp.int32), jnp.zeros((), bool)))
    return _assign(x, centers, use_kernel), centers


def silhouette_jax(x: jnp.ndarray, labels: jnp.ndarray,
                   num_clusters: int) -> jnp.ndarray:
    """Traced twin of ``silhouette`` (singleton clusters score 0);
    ``num_clusters`` is the static id bound. Returns a f32 scalar,
    -1.0 when fewer than two clusters appear or n < 3."""
    n = x.shape[0]
    # centring shrinks the norms whose difference the Gram expansion
    # takes; the self-distance is exactly 0, not a rounding residue
    xc = x - x.mean(0)
    d2 = jnp.where(jnp.eye(n, dtype=bool), 0.0, _sq_dists(xc, xc))
    d = jnp.sqrt(d2)
    onehot = jax.nn.one_hot(labels, num_clusters, dtype=x.dtype)  # [N, C]
    counts = onehot.sum(0)                                        # [C]
    sums = jnp.matmul(d, onehot, precision=_HIGHEST)              # [N, C]
    own = counts[labels]                                          # [N]
    a = sums[jnp.arange(n), labels] / jnp.maximum(own - 1.0, 1.0)
    mean_c = jnp.where(counts[None, :] > 0,
                       sums / jnp.maximum(counts, 1.0)[None, :], jnp.inf)
    mean_c = jnp.where(onehot > 0, jnp.inf, mean_c)   # mask own cluster
    b = mean_c.min(1)
    denom = jnp.maximum(a, b)
    s = jnp.where((own <= 1) | (denom <= 0), 0.0, (b - a) / denom)
    valid = ((counts > 0).sum() >= 2) & (n >= 3)
    return jnp.where(valid, s.mean(), -1.0).astype(jnp.float32)


def cluster_activations_jax(acts: jnp.ndarray, key: jnp.ndarray, *,
                            k: Optional[int] = None, k_max: int = 6,
                            min_silhouette: float = 0.15,
                            iters: int = 50, use_kernel: bool = False
                            ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Device twin of ``cluster_activations``: returns device arrays
    (labels [K] int32, selected k (int32 scalar), silhouette (f32
    scalar)) without leaving the device. Candidate k values unroll at
    trace time up to the static ``k_selection_bound``, so label ids
    stay below that bound and the function traces once per population
    size."""
    K = acts.shape[0]
    mu = acts.mean(0)
    sd = acts.std(0) + 1e-8
    z = ((acts - mu) / sd).astype(jnp.float32)
    if k is not None:
        if k <= 1:
            return (jnp.zeros(K, jnp.int32), jnp.asarray(1, jnp.int32),
                    jnp.asarray(0.0, jnp.float32))
        labels, _ = kmeans_jax(z, k, key, iters=iters, use_kernel=use_kernel)
        labels = canonicalize_labels_jax(labels, k)
        return (labels, jnp.asarray(k, jnp.int32),
                silhouette_jax(z, labels, k))
    upper = k_selection_bound(K, k_max=k_max)
    keys = jax.random.split(key, upper - 1)
    cand_labels, cand_sils = [], []
    for i, kk in enumerate(range(2, upper + 1)):
        labels, _ = kmeans_jax(z, kk, keys[i], iters=iters,
                               use_kernel=use_kernel)
        labels = canonicalize_labels_jax(labels, kk)
        cand_labels.append(labels)
        cand_sils.append(silhouette_jax(z, labels, kk))
    sils = jnp.stack(cand_sils)
    best = jnp.argmax(sils)                      # first max, like the numpy >
    sil = sils[best]
    labels = jnp.stack(cand_labels)[best]
    ok = sil >= min_silhouette
    return (jnp.where(ok, labels, 0).astype(jnp.int32),
            jnp.where(ok, best + 2, 1).astype(jnp.int32),
            jnp.where(ok, sil, 0.0).astype(jnp.float32))
