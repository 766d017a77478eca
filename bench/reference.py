"""Plain jax.numpy reference of what the timed path computes, for any
split G/D net.

Imports nothing of the program. A net's own layers, weights and
training step are in its module, ``bench/models/<model>.py``; they run
on what this module gives, which follows the published semantics of
arXiv:2507.12979:

* the U-shaped split: layer ``l`` of client ``c`` runs on the client's
  own weights where its cut owns ``l`` (head ``l < h``, tail ``l >= t``),
  and otherwise on the server's single copy, applied once to the
  concatenation of every client that delegates ``l``, so that the
  server's BatchNorm statistics span all of them;
* Adam, and BatchNorm running statistics put back into the parameters;
* batches drawn on the device from the training key, as the trainer
  states it: per step ``key, sub = split(key)``, per profile group ``i``
  ``split(fold_in(sub, i), 3)`` for the rows, ``z`` and the fake labels;
* stage 3: k-means on per-feature standardised middle activations,
  ``k`` by best silhouette over 2..6, ``k = 1`` under 0.15, labels in
  first-occurrence order; stage 4: Eq. 13-15 weights (softmax of the
  activations, leave-one-out cluster mean, ``softmax(log n - beta KLD)``
  within the cluster); Eq. 16: every owner of a layer receives the
  weighted mean of the owners of that layer in its cluster.

``Numerics`` and ``precision`` select the control: ``bfloat16`` compute
for the step, ``Precision.HIGH`` (three bfloat16 passes) for the round.
XLA:CPU computes float32 products exactly at any precision, so the CPU
tests pass ``BF16_3X``, the three passes written out (``_matmul``).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST
BF16_3X = "bf16_3x"
ADAM_B2 = 0.999
ADAM_EPS = 1e-8
BLOB_SPACING = 100.0                # planted blobs' offset step (planted_activations)


# ---------------------------------------------------------------------------
# population layout
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Group:
    """Clients that share a device profile and therefore a cut."""
    name: str
    cut: Tuple[int, int, int, int]      # (G head end, G tail start, D ...)
    client_ids: Tuple[int, ...]

    @property
    def size(self) -> int:
        return len(self.client_ids)

    def owns(self, net: str, layer: int) -> bool:
        h, t = self.cut[:2] if net == "G" else self.cut[2:]
        return layer < h or layer >= t


def device_list(cfg: dict) -> List[str]:
    cyc = cfg["device_cycle"]
    return [cyc[i % len(cyc)] for i in range(cfg["num_clients"])]


def groups_of(cfg: dict) -> List[Group]:
    """Groups keyed by (profile, cut), in the order of ``str`` of that
    key: the order the trainer stages its data in."""
    table: Dict[Tuple, List[int]] = {}
    for cid, dev in enumerate(device_list(cfg)):
        table.setdefault((dev, tuple(cfg["cuts"][dev])), []).append(cid)
    return [Group(f"{dev}|{cut}", cut, tuple(ids))
            for (dev, cut), ids in sorted(table.items(),
                                          key=lambda kv: str(kv[0]))]


@dataclasses.dataclass(frozen=True)
class Numerics:
    """The precision a net's reference layers compute in."""
    dtype: object = jnp.float32
    precision: object = HIGHEST


# ---------------------------------------------------------------------------
# the split forward pass
# ---------------------------------------------------------------------------

def _flat(x):
    """[K, b, ...] -> [K*b, ...] for an array or a tuple of arrays."""
    if isinstance(x, tuple):
        return tuple(_flat(v) for v in x)
    return x.reshape((-1,) + x.shape[2:])


def split_forward(layers, net: str, groups: Sequence[Group], params,
                  inputs: Dict[str, tuple]):
    """inputs: {group: tuple of [K_g, b, ...]}. Returns (outputs
    {group: [K_g, b, ...]}, BatchNorm statistics in the parameter
    layout)."""
    x = dict(inputs)
    stats = {"client": {g.name: {} for g in groups}, "server": {}}
    for l, apply in enumerate(layers):
        for g in groups:
            if g.owns(net, l):
                y, st = jax.vmap(apply)(params["client"][g.name][str(l)],
                                        x[g.name])
                x[g.name] = y
                if st is not None:
                    stats["client"][g.name][str(l)] = st
        delegated = [g for g in groups if not g.owns(net, l)]
        if delegated:
            parts = [_flat(x[g.name]) for g in delegated]
            if isinstance(parts[0], tuple):
                cat = tuple(jnp.concatenate(p, 0) for p in zip(*parts))
            else:
                cat = jnp.concatenate(parts, 0)
            y, st = apply(params["server"][str(l)], cat)
            if st is not None:
                stats["server"][str(l)] = st
            off = 0
            for g in delegated:
                b = inputs[g.name][0].shape[1]
                n = g.size * b
                x[g.name] = y[off:off + n].reshape((g.size, b) + y.shape[1:])
                off += n
    return x, stats


def with_stats(params, stats):
    """Parameters with their BatchNorm running statistics replaced."""
    def put(node, st):
        node = dict(node)
        for k, v in st.items():
            node[k] = {**node[k], "bn": {**node[k]["bn"], **v}}
        return node
    return {"client": {gn: put(params["client"][gn], st)
                       for gn, st in stats["client"].items()},
            "server": put(params["server"], stats["server"])}


# ---------------------------------------------------------------------------
# Adam, batches and the epoch
# ---------------------------------------------------------------------------

def adam_init(params):
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    return {"t": jnp.zeros((), jnp.int32), "mu": zeros,
            "nu": jax.tree_util.tree_map(jnp.zeros_like, params)}


def adam_update(state, grads, params, lr, b1):
    t = state["t"] + 1
    grads = jax.tree_util.tree_map(lambda g: g.astype(jnp.float32), grads)
    mu = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g,
                                state["mu"], grads)
    nu = jax.tree_util.tree_map(lambda v, g: ADAM_B2 * v + (1 - ADAM_B2) * g * g,
                                state["nu"], grads)
    c1 = 1 - b1 ** t.astype(jnp.float32)
    c2 = 1 - ADAM_B2 ** t.astype(jnp.float32)
    new = jax.tree_util.tree_map(
        lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + ADAM_EPS),
        params, mu, nu)
    return {"t": t, "mu": mu, "nu": nu}, new


def sample(data, key, groups: Sequence[Group], batch: int, z_dim: int,
           n_classes: int, keep: int = None):
    """One batch per client. ``data``: {group: (images [K, n_max, ...],
    labels [K, n_max], counts [K])}. ``keep`` < batch drops the rest of
    every client's batch (the fault that leaves half of it out)."""
    out = {"real": {}, "fake": {}}
    take = jax.vmap(lambda rows, ix: rows[ix])
    for i, g in enumerate(groups):
        k_idx, k_z, k_y = jax.random.split(jax.random.fold_in(key, i), 3)
        imgs, labs, counts = data[g.name]
        idx = jax.random.randint(k_idx, (g.size, batch), 0, counts[:, None])
        z = jax.random.normal(k_z, (g.size, batch, z_dim), jnp.float32)
        fy = jax.random.randint(k_y, (g.size, batch), 0, n_classes, jnp.int32)
        sl = slice(0, keep)
        out["real"][g.name] = (take(imgs, idx)[:, sl], take(labs, idx)[:, sl])
        out["fake"][g.name] = (z[:, sl], fy[:, sl])
    return out


def train(step, params, data, key, n_steps: int):
    """``n_steps`` of a net's ``step`` (its module's ``make_step``) from
    ``params``. Returns (state, losses [n, 2])."""
    state = {"G": params["G"], "D": params["D"],
             "opt_g": adam_init(params["G"]), "opt_d": adam_init(params["D"])}
    losses = []
    for _ in range(n_steps):
        state, key, loss = step(state, data, key)
        losses.append(loss)
    return state, np.asarray(jnp.stack(losses), np.float64)


def stage_data(clients, groups: Sequence[Group]):
    """{group: (images [K, n_max, H, W, C], labels [K, n_max], counts)}
    on the device, rows past a client's count zero."""
    out = {}
    for g in groups:
        rows = [clients[c] for c in g.client_ids]
        n_max = max(len(r[2]) for r in rows)
        imgs = np.zeros((g.size, n_max) + rows[0][1].shape[1:], np.float32)
        labs = np.zeros((g.size, n_max), np.int32)
        for i, (_, im, lb) in enumerate(rows):
            imgs[i, :len(lb)] = im
            labs[i, :len(lb)] = lb
        out[g.name] = (jnp.asarray(imgs), jnp.asarray(labs),
                       jnp.asarray([len(r[2]) for r in rows], jnp.int32))
    return out


# ---------------------------------------------------------------------------
# stage 3 and 4: clustering, Eq. 13-15 weights, Eq. 16 aggregation
# ---------------------------------------------------------------------------

def _canonical(labels: np.ndarray) -> np.ndarray:
    order = {}
    return np.array([order.setdefault(int(c), len(order)) for c in labels],
                    np.int32)


def _lloyd(z: np.ndarray, k: int, iters: int = 100) -> np.ndarray:
    """Farthest-point seeding from the first row, then Lloyd."""
    centers = [z[0]]
    for _ in range(1, k):
        d2 = np.min([((z - c) ** 2).sum(1) for c in centers], 0)
        centers.append(z[int(d2.argmax())])
    centers = np.array(centers)
    labels = None
    for _ in range(iters):
        d2 = ((z[:, None, :] - centers[None]) ** 2).sum(-1)
        new = d2.argmin(1)
        if labels is not None and np.array_equal(new, labels):
            break
        labels = new
        for c in range(k):
            if (labels == c).any():
                centers[c] = z[labels == c].mean(0)
    return labels


def _silhouette(d: np.ndarray, labels: np.ndarray) -> float:
    """Mean silhouette over the pairwise distances ``d``; a point alone
    in its cluster scores 0."""
    s = np.zeros(len(d))
    ks = np.unique(labels)
    for i in range(len(d)):
        own = labels == labels[i]
        if own.sum() <= 1:
            continue
        a = d[i, own].sum() / (own.sum() - 1)
        b = min(d[i, labels == c].mean() for c in ks if c != labels[i])
        s[i] = (b - a) / max(a, b)
    return float(s.mean())


def cluster(acts: np.ndarray, k_max: int = 6, min_silhouette: float = 0.15):
    """Stage 3 in float64 on the host: (labels, k)."""
    x = acts.astype(np.float64)
    z = (x - x.mean(0)) / (x.std(0) + 1e-8)
    upper = min(k_max, max(2, len(z) // 2))
    sq = (z * z).sum(1)
    d = np.sqrt(np.maximum(sq[:, None] + sq[None] - 2 * z @ z.T, 0))
    np.fill_diagonal(d, 0.0)
    best = None
    for k in range(2, upper + 1):
        labels = _canonical(_lloyd(z, k))
        sil = _silhouette(d, labels)
        if best is None or sil > best[0]:
            best = (sil, k, labels)
    if best[0] < min_silhouette:
        return np.zeros(len(z), np.int32), 1
    return best[2], best[1]


def _matmul(a, b, precision):
    """``a @ b`` in float32 at ``precision``; ``BF16_3X`` is three
    bfloat16 products of the operands' high and low halves."""
    if precision != BF16_3X:
        return jnp.matmul(a, b, precision=precision)

    def halves(x):
        hi = x.astype(jnp.bfloat16)
        return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)

    (ah, al), (bh, bl) = halves(a), halves(b)
    dot = lambda x, y: jnp.matmul(x, y, preferred_element_type=jnp.float32)
    return dot(ah, bh) + (dot(ah, bl) + dot(al, bh))


def federation_weights(acts, sizes, labels, beta: float, precision):
    """Eq. 13-15 in float32 at ``precision``: [K] weights summing to 1
    within each cluster."""
    P = jax.nn.softmax(jnp.asarray(acts, jnp.float32), axis=-1)
    lab = np.asarray(labels)
    C = int(lab.max()) + 1
    onehot = jnp.asarray(np.eye(C, dtype=np.float32)[lab])         # [K, C]
    csum = _matmul(onehot.T, P, precision)                          # [C, F]
    own = np.bincount(lab, minlength=C)[lab].astype(np.float32)
    loo = (csum[lab] - P) / jnp.maximum(jnp.asarray(own) - 1.0, 1.0)[:, None]
    p = jnp.clip(P, 1e-12, None)
    q = jnp.clip(loo, 1e-12, None)
    kld = jnp.sum(p * (jnp.log(p) - jnp.log(q)), -1)
    kld = jnp.where(jnp.asarray(own) > 1, kld, 0.0)
    logit = jnp.log(jnp.asarray(sizes, jnp.float32)) - beta * kld
    w = np.zeros(len(lab))
    logit = np.asarray(logit, np.float64)
    for c in range(C):
        m = lab == c
        e = np.exp(logit[m] - logit[m].max())
        w[m] = e / e.sum()
    return w


def fedavg_weights(sizes, labels) -> np.ndarray:
    """Dataset shares within each cluster, Eq. 15 without its KLD term."""
    lab = np.asarray(labels)
    n = np.asarray(sizes, np.float64)
    return n / np.bincount(lab, weights=n)[lab]


def aggregate_rows(groups: Sequence[Group], client_params, weights, labels,
                   rows: Dict[str, List[int]], precision):
    """Eq. 16 for the sampled client rows: {net: {group: {layer: tree
    with leading axis len(rows[group])}}}. ``client_params``:
    {net: {group: {layer: tree [K_g, ...]}}}."""
    lab = np.asarray(labels)
    out = {}
    for net, per_group in client_params.items():
        out[net] = {}
        for g in groups:
            out[net][g.name] = {}
            for layer in per_group[g.name]:
                owners = [h for h in groups if layer in per_group[h.name]]
                cids = np.concatenate([h.client_ids for h in owners])
                trees = [per_group[h.name][layer] for h in owners]
                picks = []
                for r in rows[g.name]:
                    c = lab[g.client_ids[r]]
                    a = np.where(lab[cids] == c, np.asarray(weights)[cids], 0.0)
                    picks.append(a / a.sum())
                A = jnp.asarray(np.stack(picks), jnp.float32)       # [R, K_l]

                def agg(*leaves):
                    x = jnp.concatenate(leaves, 0)
                    return _matmul(A, x.reshape(x.shape[0], -1), precision
                                   ).reshape((A.shape[0],) + x.shape[1:])
                out[net][g.name][layer] = jax.tree_util.tree_map(agg, *trees)
    return out


def _flat_rows(tree) -> np.ndarray:
    """A layer's leaves [R, ...] as one float64 row per client [R, W]."""
    return np.concatenate([np.asarray(x, np.float64).reshape(x.shape[0], -1)
                           for x in jax.tree_util.tree_leaves(tree)], 1)


def out_of_span(groups: Sequence[Group], client_params, labels,
                rows: Dict[str, List[int]], got, want) -> float:
    """The part of ``got - want`` (sampled aggregates, as
    ``aggregate_rows`` returns them) that no weighted sum of the
    cluster's owners of the layer can give, over ``|want|``: the
    largest over sampled rows and layers, in float64 on the host.

    Eq. 16 makes every aggregate a combination of its cluster's owner
    rows. An error in the Eq.-15 weights stays inside their span; the
    rounding of the aggregation's products and sums, and rows taken
    from outside the cluster, do not."""
    lab = np.asarray(labels)
    worst = 0.0
    for net, per_group in client_params.items():
        for layer in sorted({l for g in groups for l in per_group[g.name]}):
            owners = [h for h in groups if layer in per_group[h.name]]
            sampled = [g for g in owners if rows.get(g.name)]
            if not sampled:
                continue
            cids = np.concatenate([h.client_ids for h in owners])
            x = np.concatenate([_flat_rows(per_group[h.name][layer])
                                for h in owners], 0)                # [K_l, W]
            basis = {}
            for g in sampled:
                d = (_flat_rows(got[net][g.name][layer])
                     - _flat_rows(want[net][g.name][layer]))
                b = np.linalg.norm(_flat_rows(want[net][g.name][layer]), axis=1)
                for i, r in enumerate(rows[g.name]):
                    c = int(lab[g.client_ids[r]])
                    if c not in basis:
                        xc = x[lab[cids] == c]
                        basis[c] = (xc, xc @ xc.T)
                    xc, gram = basis[c]
                    coef = np.linalg.lstsq(gram, xc @ d[i], rcond=None)[0]
                    share = np.linalg.norm(d[i] - coef @ xc) / max(b[i], 1e-30)
                    worst = max(worst, float(share) if np.isfinite(share)
                                else np.inf)
    return worst


def planted_activations(num_clients: int, features: int, seed: int
                        ) -> np.ndarray:
    """The benchmark's middle-activation EMA [num_clients, features] for
    the checked round (``features``: the net's ``ema_features``): two
    to four well separated blobs (their count and members drawn from
    the seed), so that every correct stage 3 finds the same partition.

    Each blob also carries a constant offset over all its features,
    ``BLOB_SPACING`` apart: softmax, and so Eq. 13-15, cannot see it,
    while stage 3's per-feature standardisation is dominated by it.
    Without it, features where two random centres nearly coincide are
    noise after standardisation, and one k-means++ seeding (the
    program's stage 3) puts two centres into one blob on about 5% of
    seeds; with it, the seeding lands in every blob on every seed."""
    rng = np.random.default_rng([seed, 7])
    n_blobs = 2 + seed % 3
    members = rng.permutation(np.arange(num_clients) % n_blobs)
    centers = rng.normal(0.0, 1.0, (n_blobs, features)) * 3.0
    noise = rng.normal(0.0, 0.3, (num_clients, features))
    offset = rng.permutation(n_blobs) * BLOB_SPACING
    return (centers[members] + noise
            + offset[members][:, None]).astype(np.float32)
