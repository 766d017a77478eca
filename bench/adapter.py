"""Every access the benchmark makes to ``HuSCFTrainer`` beyond its public
calls, in one place.

The trainer has no public way to take given weights, to report the
per-step losses of an epoch, or to run a round on planted activations,
so the harness reaches into these attributes (``PRIVATE``). ``check``
refuses a trainer that lacks one, and ``test_bench_adapter.py`` fails
on the CPU when one is renamed, so a refactor of the trainer cannot
silently change what the benchmark times or checks.
"""
from __future__ import annotations

from typing import Callable, Dict, List

import jax
import jax.numpy as jnp

# attributes of a constructed trainer that the harness reads or replaces
PRIVATE = ("state", "groups", "_dataset", "_epoch_fns", "_build_epoch",
           "_opt_init_g", "_opt_init_d", "_mid_ema", "train_steps",
           "train_epoch", "federate")


def missing(tr) -> List[str]:
    return [name for name in PRIVATE if not hasattr(tr, name)]


def check(tr) -> None:
    gone = missing(tr)
    if gone:
        raise RuntimeError("the trainer no longer has "
                           + ", ".join(gone) + "; bench/adapter.py needs "
                           "updating")


def layout(tr) -> Dict[str, tuple]:
    """{net: (tree structure, leaf shapes)} of the trainer's weights."""
    return {net: (jax.tree_util.tree_structure(tr.state[net]),
                  [x.shape for x in jax.tree_util.tree_leaves(tr.state[net])])
            for net in ("G", "D")}


def drop_weights(tr) -> None:
    tr.state = None


def set_weights(tr, params) -> None:
    """The given weights, with fresh Adam moments, in the trainer's place."""
    tr.state = {"G": params["G"], "D": params["D"],
                "opt_g": tr._opt_init_g(params["G"]),
                "opt_d": tr._opt_init_d(params["D"]),
                "step": jnp.zeros((), jnp.int32)}


def first_epoch(tr, n_steps: int) -> dict:
    """One ``train_steps(n_steps)`` call through the window's own epoch
    program, recording its per-step metrics: {"loss_d": [n], ...}."""
    epoch_fn = tr._build_epoch(n_steps)
    seen = {}

    def recorded(*a):
        out = epoch_fn(*a)
        seen["metrics"] = out[-1]
        return out

    tr._epoch_fns[n_steps] = recorded
    try:
        tr.train_steps(n_steps)
    finally:
        tr._epoch_fns[n_steps] = epoch_fn
    return seen["metrics"]


def trained(tr) -> dict:
    """{"params": {net: tree}, "mu": {net: tree}, "nu": {net: tree}}."""
    return {"params": {net: tr.state[net] for net in ("G", "D")},
            "mu": {"G": tr.state["opt_g"].mu, "D": tr.state["opt_d"].mu},
            "nu": {"G": tr.state["opt_g"].nu, "D": tr.state["opt_d"].nu}}


def plant_round(tr, acts, client_params) -> None:
    """The next round clusters ``acts`` and aggregates ``client_params``
    ({net: {group: {layer: tree}}})."""
    tr._mid_ema = jnp.asarray(acts)
    for net in ("G", "D"):
        tr.state[net]["client"] = client_params[net]["client"]


def client_params(tr) -> dict:
    return {net: tr.state[net]["client"] for net in ("G", "D")}


def wrap(tr, train_steps: Callable, federate: Callable) -> None:
    """Route the trainer's own calls of its layers through the wrappers."""
    tr.train_steps = train_steps
    tr.federate = federate


def free(tr) -> None:
    tr.state = tr._dataset = None
