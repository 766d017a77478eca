"""The benchmark's own copy of the client population generator.

A frozen copy of the synthetic domains and of the ``2dom_noniid``
partition of ``repro.data`` (``data/synthetic.py``, ``data/partition.py``)
at the settings a traffic file names, so that a later change to the
program's data code cannot change the yardstick.
``bench/test_bench_population.py`` checks that the copy equals
``repro.data.build_scenario`` at a small size.

The population is a pure function of the traffic's ``scenario``,
``num_clients``, ``base_size`` and ``partition_seed``; ``--seed`` never
changes it. Built once per checkout and cached under ``bench/.cache/``
(git-ignored), keyed by those settings.
"""
from __future__ import annotations

import hashlib
import json
import os
import zlib
from typing import List, Sequence, Tuple

import numpy as np

NUM_CLASSES = 10
IMG = 28
CACHE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), ".cache")


def _grid():
    ax = np.linspace(-1.0, 1.0, IMG, dtype=np.float32)
    return np.meshgrid(ax, ax, indexing="ij")


def _gratings(cls, rng):
    yy, xx = _grid()
    n = cls.shape[0]
    theta = cls * (np.pi / NUM_CLASSES) + rng.normal(0, 0.05, n)
    freq = 4.0 + (cls % 3) + rng.normal(0, 0.1, n)
    phase = rng.uniform(0, 2 * np.pi, n)
    t = theta[:, None, None]
    proj = np.cos(t) * xx[None] + np.sin(t) * yy[None]
    return np.sin(freq[:, None, None] * np.pi * proj + phase[:, None, None])


def _blobs(cls, rng):
    yy, xx = _grid()
    n = cls.shape[0]
    img = np.full((n, IMG, IMG), -1.0, np.float32)
    for i in range(n):
        k = int(cls[i]) % 5 + 1
        r = 0.25 + 0.5 * ((int(cls[i]) // 5) + 1) / 3.0
        ang0 = rng.uniform(0, 2 * np.pi)
        for j in range(k):
            a = ang0 + 2 * np.pi * j / k
            cx, cy = r * np.cos(a), r * np.sin(a)
            cx += rng.normal(0, 0.03)
            cy += rng.normal(0, 0.03)
            d2 = (xx - cx) ** 2 + (yy - cy) ** 2
            img[i] += 2.0 * np.exp(-d2 / 0.02)
    return np.clip(img, -1.0, 1.0)


_FAMILIES = {"gratings": _gratings, "blobs": _blobs}


def _make_dataset(domain: str, n: int, seed: int, noise: float = 0.12):
    rng = np.random.default_rng(
        seed + zlib.crc32(domain.encode()) % (2 ** 16))
    labels = rng.integers(0, NUM_CLASSES, n).astype(np.int32)
    imgs = _FAMILIES[domain](labels, rng).astype(np.float32)
    imgs = imgs + rng.normal(0, noise, imgs.shape).astype(np.float32)
    return np.clip(imgs, -1.0, 1.0)[..., None], labels


def _exclusion_plan(num_clients: int, plan, seed: int) -> List[List[int]]:
    rng = np.random.default_rng(seed)
    exclusions: List[List[int]] = [[] for _ in range(num_clients)]
    order = rng.permutation(num_clients)
    idx = 0
    for count, n_excl in plan:
        for _ in range(count):
            if idx >= num_clients:
                break
            exclusions[order[idx]] = list(
                rng.choice(NUM_CLASSES, n_excl, replace=False))
            idx += 1
    return exclusions


def _domain_clients(domain, client_ids, sizes, exclusions, seed):
    out = []
    for i, cid in enumerate(client_ids):
        raw_n = int(sizes[i] * (1.0 + 0.25 * len(exclusions[i]) + 0.2)) + 8
        imgs, labs = _make_dataset(domain, raw_n, seed * 10007 + cid)
        if exclusions[i]:
            keep = ~np.isin(labs, np.asarray(exclusions[i], labs.dtype))
            imgs, labs = imgs[keep], labs[keep]
        out.append((domain, imgs[:sizes[i]], labs[:sizes[i]]))
    return out


def two_domain_noniid(num_clients: int, base_size: int, seed: int
                      ) -> List[Tuple[str, np.ndarray, np.ndarray]]:
    """``2dom_noniid``: half the clients on gratings, half on blobs;
    per domain 20/5/5 (per 100) clients lose 2/3/4 labels; sizes drawn
    from {base, 2/3 base}. Returns [(domain, images, labels)]."""
    rng = np.random.default_rng(seed + 99)
    half = num_clients // 2

    def scale(x):
        return max(1, int(round(x * num_clients / 100)))

    out = []
    for g, dom in ((0, "gratings"), (1, "blobs")):
        ids = list(range(g * half, g * half + half))
        plan = [(scale(20), 2), (scale(5), 3), (scale(5), 4)]
        pool = [base_size, int(base_size * 2 / 3)]
        excl = _exclusion_plan(half, plan, seed + g)
        sizes = [int(rng.choice(pool)) for _ in range(half)]
        out += _domain_clients(dom, ids, sizes, excl, seed + g)
    return out


SCENARIOS = {"2dom_noniid": two_domain_noniid}


def population(traffic: dict, num_clients: int
               ) -> List[Tuple[str, np.ndarray, np.ndarray]]:
    """The traffic's population of ``num_clients`` clients, from the
    cache or built and cached."""
    key = {k: traffic[k] for k in ("scenario", "base_size",
                                   "partition_seed")}
    key["num_clients"] = int(num_clients)
    tag = hashlib.sha1(json.dumps(key, sort_keys=True).encode()).hexdigest()
    path = os.path.join(CACHE_DIR, f"population-{tag[:16]}.npz")
    if os.path.exists(path):
        with np.load(path) as z:
            domains = [str(d) for d in z["domains"]]
            counts = z["counts"]
            ends = np.cumsum(counts)
            starts = ends - counts
            images, labels = z["images"], z["labels"]
        return [(d, images[s:e], labels[s:e])
                for d, s, e in zip(domains, starts, ends)]
    clients = SCENARIOS[key["scenario"]](key["num_clients"],
                                         key["base_size"],
                                         key["partition_seed"])
    os.makedirs(CACHE_DIR, exist_ok=True)
    tmp = path + ".tmp.npz"
    np.savez(tmp, domains=np.array([c[0] for c in clients]),
             counts=np.array([len(c[2]) for c in clients]),
             images=np.concatenate([c[1] for c in clients]),
             labels=np.concatenate([c[2] for c in clients]))
    os.replace(tmp, path)
    return clients


def sizes(clients: Sequence) -> np.ndarray:
    return np.array([len(c[2]) for c in clients], np.int64)
