"""The comparison that decides ``correct``.

Readings of the program after the set-up's first epoch (12 steps, one
call of the window's own epoch program) and its first clustered round,
against the reference from the same seed, weights and data. A leaf is
one client's copy of a parameter, or the server's; a leaf's gap is
``| |x| - |x_ref| | / max(|x_ref|, median leaf |x_ref|)``.

* ``train_loss_gap_step1``: the first step's D and G losses,
  ``|loss - ref| / |ref|``, the larger;
* ``train_grad_rms_gap_median``: the gradient as the optimizer holds it
  at the end of the epoch, the root of Adam's second moment, the median
  leaf's gap;
* ``train_delta_gap_median``: the parameters' change over the epoch,
  the median leaf's gap, leaving out leaves whose reference first
  moment is under a thousandth of the median leaf's (conv biases in
  front of BatchNorm, BatchNorm running statistics): Adam moves those
  by round-off alone;
* ``round_k_mismatch``, ``round_label_mismatch``: stage-3 ``k`` and
  the clients whose cluster differs (limit 0);
* ``round_weight_gap``: the largest gap of an Eq.-15 weight;
* ``round_param_gap``: over the sampled clients' aggregated parameters,
  by the worst leaf, ``max |agg - ref| / max |ref|``;
* ``round_agg_residual``: the part of ``agg - ref`` outside the span of
  the cluster's owner rows, over ``|ref|``, by the worst sampled row and
  layer (``reference.out_of_span``): the aggregation's own rounding,
  which the Eq.-15 weights' rounding does not reach;
* ``window_rounds_not_clustered``, ``window_nonfinite_losses``,
  ``window_compilations``: in the window (limit 0).

The later steps' losses, Adam's first moment and the worst leaf of the
second moment and of the change are read too and printed beside them,
not compared: the reference at the configuration's own precision drifts
from the one at HIGHEST as far, in the same leaves (``PERF.md``).
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference

EXCLUDE_BELOW = 1e-3        # of the median leaf's reference first moment


def _rows(x):
    """Per-leaf norms: one per client row of a stacked client leaf."""
    x = x.astype(jnp.float32)
    return jnp.sqrt(jnp.sum(jnp.square(x.reshape(x.shape[0], -1)), 1))


@jax.jit
def leaf_norms(params, start, mu, nu):
    """(|params - start|, |mu|, |sqrt(nu)|) per leaf, flattened in tree
    order. Client leaves give one entry per client."""
    def per(tree_fn, *trees):
        out = []
        for net in ("G", "D"):
            for gname in sorted(trees[0][net]["client"]):
                out += jax.tree_util.tree_leaves(jax.tree_util.tree_map(
                    lambda *x: _rows(tree_fn(*x)),
                    *[t[net]["client"][gname] for t in trees]))
            out += [n[None] for n in jax.tree_util.tree_leaves(
                jax.tree_util.tree_map(
                    lambda *x: jnp.linalg.norm(tree_fn(*x).astype(
                        jnp.float32).reshape(-1)),
                    *[t[net]["server"] for t in trees]))]
        return jnp.concatenate(out)
    delta = per(lambda p, s: p - s, params, start)
    first = per(lambda m: m, mu)
    rms = per(jnp.sqrt, nu)
    return delta, first, rms


def leaf_names(params) -> List[str]:
    """Names of the entries ``leaf_norms`` returns, in its order."""
    out = []
    for net in ("G", "D"):
        client = params[net]["client"]
        for gname in sorted(client):
            for path, x in jax.tree_util.tree_flatten_with_path(
                    client[gname])[0]:
                key = jax.tree_util.keystr(path)
                out += [f"{net}/{gname}#{i}{key}" for i in range(x.shape[0])]
        out += [f"{net}/server{jax.tree_util.keystr(path)}" for path, _ in
                jax.tree_util.tree_flatten_with_path(params[net]["server"])[0]]
    return out


def worst(prog, ref, names, keep=None, top: int = 5) -> List[tuple]:
    """The ``top`` leaves with the widest norm gap: (name, prog, ref)."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    idx = np.arange(len(ref)) if keep is None else np.flatnonzero(keep)
    scale = np.maximum(ref[idx], _median(ref[idx]))
    gap = np.abs(prog[idx] - ref[idx]) / np.where(scale > 0, scale, 1.0)
    order = idx[np.argsort(-gap)[:top]]
    return [(names[i], float(prog[i]), float(ref[i])) for i in order]


def _median(ref: np.ndarray) -> float:
    """The median leaf, over the leaves the reference moves at all
    (BatchNorm running statistics have no gradient)."""
    moved = ref[ref > 0]
    return float(np.median(moved)) if moved.size else 0.0


def leaf_gaps(prog: np.ndarray, ref: np.ndarray, keep=None) -> np.ndarray:
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    if keep is not None:
        prog, ref = prog[keep], ref[keep]
    scale = np.maximum(ref, _median(ref))
    return np.abs(prog - ref) / np.where(scale > 0, scale, 1.0)


def moved(ref_mu) -> np.ndarray:
    """Leaves the reference's gradient moves: first moment at least a
    thousandth of the median leaf's."""
    mu = np.asarray(ref_mu, np.float64)
    return mu >= EXCLUDE_BELOW * _median(mu)


def train_numbers(prog: dict, ref: dict) -> Dict[str, float]:
    """``prog``/``ref``: {"losses": [n, 2], "delta", "mu", "rms": [L]}.
    The compared numbers first, then the unsteady ones beside them."""
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    if lp.shape != lr.shape or not np.isfinite(lp).all():
        step1 = every = float("inf")
    else:
        gap = np.abs(lp - lr) / np.abs(lr)
        step1, every = float(gap[0].max()), float(gap.max())
    keep = moved(ref["mu"])
    rms = leaf_gaps(prog["rms"], ref["rms"])
    mu = leaf_gaps(prog["mu"], ref["mu"])
    delta = leaf_gaps(prog["delta"], ref["delta"], keep)
    return {"train_loss_gap_step1": step1,
            "train_grad_rms_gap_median": float(np.median(rms)),
            "train_delta_gap_median": float(np.median(delta)),
            "train_loss_gap_every_step": every,
            "train_grad_rms_gap_worst": float(rms.max()),
            "train_first_moment_gap_worst": float(mu.max()),
            "train_first_moment_gap_median": float(np.median(mu)),
            "train_delta_gap_worst": float(delta.max())}


def round_numbers(prog: dict, ref: dict) -> Dict[str, float]:
    """``prog``/``ref``: {"k", "labels" [K], "weights" [K],
    "rows": {net: {group: {layer: tree}}} of sampled clients}; ``ref``
    also holds what ``reference.out_of_span`` needs: "groups", "client"
    (the owners' rows) and "index" (the sampled rows)."""
    pl = [np.asarray(x, np.float64) for x in
          jax.tree_util.tree_leaves(prog["rows"])]
    rl = [np.asarray(x, np.float64) for x in
          jax.tree_util.tree_leaves(ref["rows"])]
    param_gap = 0.0
    for a, b in zip(pl, rl):
        scale = np.max(np.abs(b))
        gap = np.max(np.abs(a - b)) / (scale if scale > 0 else 1.0)
        param_gap = max(param_gap, float(gap) if np.isfinite(gap) else np.inf)
    return {
        "round_k_mismatch": float(abs(int(prog["k"]) - int(ref["k"]))),
        "round_label_mismatch": float(np.sum(np.asarray(prog["labels"])
                                             != np.asarray(ref["labels"]))),
        "round_weight_gap": float(np.max(np.abs(
            np.asarray(prog["weights"], np.float64)
            - np.asarray(ref["weights"], np.float64)))),
        "round_param_gap": param_gap,
        "round_agg_residual": reference.out_of_span(
            ref["groups"], ref["client"], ref["labels"], ref["index"],
            prog["rows"], ref["rows"]),
    }


def verdict(numbers: Dict[str, float], limits: Dict[str, float]
            ) -> Tuple[bool, List[dict]]:
    """Every number against its limit; a missing or NaN number fails."""
    rows, ok = [], True
    for name, limit in limits.items():
        v = numbers.get(name, float("nan"))
        passed = bool(np.isfinite(v) and v <= limit)
        ok &= passed
        rows.append({"name": name, "value": v, "limit": limit})
    return ok, rows
