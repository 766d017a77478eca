"""The benchmark's own copy of the client population generator.

A frozen copy of the synthetic domains and of the ``2dom_noniid``
partition of ``repro.data`` (``data/synthetic.py``, ``data/partition.py``)
at the settings a traffic file names, so that a later change to the
program's data code cannot change the yardstick.
``bench/test_bench_population.py`` checks that the copy equals
``repro.data.build_scenario`` at a small size.

The population is a pure function of the traffic's ``scenario``,
``num_clients``, ``base_size`` and ``partition_seed``, and of the net's
image shape (H, W, C) and class count (``IMAGE``, ``NUM_CLASSES`` of
``bench/models/<model>.py``); ``--seed`` never changes it. Built once
per checkout and cached under ``bench/.cache/`` (git-ignored), keyed by
those settings.

``repro.data`` renders 28x28x1 images of 10 classes only, and at that
shape and class count the copy equals it. At any other shape or class
count the generator is the benchmark's own: each family is drawn on an
H x W grid over [-1, 1], the same grey image on every channel, each
channel with noise of its own; gratings turn by ``pi / classes`` a
class, and blobs take ``class % 5 + 1`` spots on one of two rings.
"""
from __future__ import annotations

import hashlib
import json
import os
import zlib
from typing import List, Sequence, Tuple

import numpy as np

CACHE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), ".cache")
# the shape and class count the cache was first keyed at, without them
# in the key: kept so that no checkout rebuilds its cached population
UNKEYED = ((28, 28, 1), 10)


def _grid(hw):
    return np.meshgrid(np.linspace(-1.0, 1.0, hw[0], dtype=np.float32),
                       np.linspace(-1.0, 1.0, hw[1], dtype=np.float32),
                       indexing="ij")


def _gratings(cls, rng, hw, num_classes):
    yy, xx = _grid(hw)
    n = cls.shape[0]
    theta = cls * (np.pi / num_classes) + rng.normal(0, 0.05, n)
    freq = 4.0 + (cls % 3) + rng.normal(0, 0.1, n)
    phase = rng.uniform(0, 2 * np.pi, n)
    t = theta[:, None, None]
    proj = np.cos(t) * xx[None] + np.sin(t) * yy[None]
    return np.sin(freq[:, None, None] * np.pi * proj + phase[:, None, None])


def _blobs(cls, rng, hw, num_classes):
    yy, xx = _grid(hw)
    n = cls.shape[0]
    img = np.full((n,) + tuple(hw), -1.0, np.float32)
    for i in range(n):
        k = int(cls[i]) % 5 + 1
        r = 0.25 + 0.5 * ((int(cls[i]) // 5) % 2 + 1) / 3.0
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


def _make_dataset(domain: str, n: int, seed: int, image, num_classes: int,
                  noise: float = 0.12):
    rng = np.random.default_rng(
        seed + zlib.crc32(domain.encode()) % (2 ** 16))
    labels = rng.integers(0, num_classes, n).astype(np.int32)
    imgs = _FAMILIES[domain](labels, rng, image[:2], num_classes
                             ).astype(np.float32)
    imgs = np.repeat(imgs[..., None], image[2], -1)
    imgs = imgs + rng.normal(0, noise, imgs.shape).astype(np.float32)
    return np.clip(imgs, -1.0, 1.0), labels


def _exclusion_plan(num_clients: int, plan, seed: int, num_classes: int
                    ) -> List[List[int]]:
    rng = np.random.default_rng(seed)
    exclusions: List[List[int]] = [[] for _ in range(num_clients)]
    order = rng.permutation(num_clients)
    idx = 0
    for count, n_excl in plan:
        for _ in range(count):
            if idx >= num_clients:
                break
            exclusions[order[idx]] = list(
                rng.choice(num_classes, n_excl, replace=False))
            idx += 1
    return exclusions


def _domain_clients(domain, client_ids, sizes, exclusions, seed, image,
                    num_classes):
    out = []
    for i, cid in enumerate(client_ids):
        raw_n = int(sizes[i] * (1.0 + 0.25 * len(exclusions[i]) + 0.2)) + 8
        imgs, labs = _make_dataset(domain, raw_n, seed * 10007 + cid, image,
                                   num_classes)
        if exclusions[i]:
            keep = ~np.isin(labs, np.asarray(exclusions[i], labs.dtype))
            imgs, labs = imgs[keep], labs[keep]
        out.append((domain, imgs[:sizes[i]], labs[:sizes[i]]))
    return out


def two_domain_noniid(num_clients: int, base_size: int, seed: int,
                      image: Tuple[int, int, int], num_classes: int
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
        excl = _exclusion_plan(half, plan, seed + g, num_classes)
        sizes = [int(rng.choice(pool)) for _ in range(half)]
        out += _domain_clients(dom, ids, sizes, excl, seed + g, image,
                               num_classes)
    return out


SCENARIOS = {"2dom_noniid": two_domain_noniid}


def cache_path(traffic: dict, num_clients: int, image, num_classes: int
               ) -> str:
    key = {k: traffic[k] for k in ("scenario", "base_size",
                                   "partition_seed")}
    key["num_clients"] = int(num_clients)
    if (tuple(image), int(num_classes)) != UNKEYED:
        key.update(image=[int(x) for x in image],
                   num_classes=int(num_classes))
    tag = hashlib.sha1(json.dumps(key, sort_keys=True).encode()).hexdigest()
    return os.path.join(CACHE_DIR, f"population-{tag[:16]}.npz")


def population(traffic: dict, num_clients: int, image, num_classes: int
               ) -> List[Tuple[str, np.ndarray, np.ndarray]]:
    """The traffic's population of ``num_clients`` clients with images
    of shape ``image`` (H, W, C) and ``num_classes`` labels, from the
    cache or built and cached."""
    path = cache_path(traffic, num_clients, image, num_classes)
    if os.path.exists(path):
        with np.load(path) as z:
            domains = [str(d) for d in z["domains"]]
            counts = z["counts"]
            ends = np.cumsum(counts)
            starts = ends - counts
            images, labels = z["images"], z["labels"]
        return [(d, images[s:e], labels[s:e])
                for d, s, e in zip(domains, starts, ends)]
    clients = SCENARIOS[traffic["scenario"]](
        int(num_clients), traffic["base_size"], traffic["partition_seed"],
        tuple(image), int(num_classes))
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
