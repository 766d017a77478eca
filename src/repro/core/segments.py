"""SplitProgram — one compiled representation of a cut configuration
(DESIGN.md §SplitProgram).

The paper's U-shaped split schedule (§4.1/§4.4, Eq. 3-10) used to exist
three separate times in this repo: `huscf.build_net_apply` hand-rolled
the head/server/tail loops for training, `latency_jax` staged the
Eq. 7-8 schedule purely analytically, and `launch/serve.py` never split
at all. This module compiles a cut configuration ONCE into typed
segments — per-group client heads, a sequence of server steps with
explicit join/depart barriers, per-group client tails — and every
consumer executes or analyzes that shared program:

* `make_apply` — the training/eval executor. Bit-exact with the legacy
  `build_net_apply` loops by construction: it replays the identical op
  sequence (vmapped heads in group order, per-server-layer concat over
  the active groups in group order, the same splits / middle capture /
  ghost-BN averaging), just driven by the compiled `ServerStep` table
  instead of re-deriving activity from cuts inline.
* `program_net_latency` / `program_iteration_latency` — the Eq. 7-10
  analytic model evaluated from the program structure (host f64,
  exactly equal to `latency.huscf_iteration_latency`), plus
  `program_forward_latency` for serving (one U-shaped forward pass).
  `join_barrier_scan` is the Eq. 7/8 recurrence as a `lax.scan`,
  shared with `core.latency_jax`.
* the `launch/serve_split.py` engine — executes `make_apply` in eval
  mode over a bucket-padded cohort (`SplitProgram.buckets`, power-of-
  two request counts per cut) so a churning request mix reuses one
  compiled program per bucket signature.

Join barriers live in the *executor/analyzer*, not the model: a layer
`apply(params, x, train)` is a pure local function; which clients'
activations concatenate before it (Eq. 7's join) and which peel off
after it (Eq. 8's depart) is scheduling, decided entirely by the cut
configuration. Baking it into the model would fuse topology into
weights; the program table keeps one model definition serving every
cut mix.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.latency import DeviceProfile, PAPER_SERVER
from repro.core.spans import SCOPE_HEAD, SCOPE_TAIL, SCOPE_TRUNK
from repro.core.splitting import (ProfileGroup, bucket_size, layer_pair,
                                  server_union_span)
from repro.models.gan import (DISC_LAYER_COSTS, DISC_LAYER_DEFS,
                              GEN_LAYER_COSTS, GEN_LAYER_DEFS)
from repro.sharding.policy import maybe_shard

Array = jnp.ndarray

NET_LAYER_DEFS = {"G": GEN_LAYER_DEFS, "D": DISC_LAYER_DEFS}
NET_LAYER_COSTS = {"G": GEN_LAYER_COSTS, "D": DISC_LAYER_COSTS}


# ---------------------------------------------------------------------------
# program structure
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Segment:
    """One typed client-side layer range of the program."""
    kind: str                    # "head" | "tail"
    gname: str                   # owning profile group
    start: int                   # half-open layer range [start, stop)
    stop: int


@dataclasses.dataclass(frozen=True)
class ServerStep:
    """One server layer of the program with its barrier structure.

    ``active``: groups whose span covers this layer, in canonical group
    order — the executor concatenates their activations in exactly this
    order and the latency model weights the layer by their sizes.
    ``joins``: groups whose head ends here (Eq. 7 forward barrier — the
    server cannot start this layer before their uplink lands).
    ``departs``: groups whose server span ends after this layer (Eq. 8
    reverse barrier / forward downlink — their activations peel off to
    the client tail).
    """
    layer: int
    active: Tuple[str, ...]
    joins: Tuple[str, ...]
    departs: Tuple[str, ...]


@dataclasses.dataclass(frozen=True)
class SplitProgram:
    """Compiled cut configuration for one network (G or D).

    Parallel tuples indexed by group position (canonical group order):
    ``group_names``, ``sizes`` (client counts), ``buckets`` (sizes
    rounded up to powers of two — the padded-cohort compile shapes),
    ``cuts`` ((head_end, tail_start) pairs for this net).
    """
    net: str
    n_layers: int
    middle: int
    group_names: Tuple[str, ...]
    sizes: Tuple[int, ...]
    buckets: Tuple[int, ...]
    cuts: Tuple[Tuple[int, int], ...]
    heads: Tuple[Segment, ...]
    steps: Tuple[ServerStep, ...]
    tails: Tuple[Segment, ...]

    def index_of(self, gname: str) -> int:
        return self.group_names.index(gname)

    def cut_of(self, gname: str) -> Tuple[int, int]:
        return self.cuts[self.index_of(gname)]

    def size_of(self, gname: str) -> int:
        return self.sizes[self.index_of(gname)]

    def bucket_of(self, gname: str) -> int:
        return self.buckets[self.index_of(gname)]

    def server_span(self) -> Tuple[int, ...]:
        return tuple(s.layer for s in self.steps)

    def shape_key(self, padded: bool = False) -> Tuple:
        """Hashable compile-shape fingerprint: everything a traced
        executor bakes in. With ``padded=True`` group sizes enter as
        their buckets, so any population whose per-group counts stay
        within the buckets maps to the same key (and may share one
        compiled program)."""
        counts = self.buckets if padded else self.sizes
        return (self.net, self.n_layers,
                tuple(zip(self.group_names, self.cuts, counts)))


def compile_split_program(groups: Sequence[ProfileGroup], net: str,
                          n_layers: Optional[int] = None) -> SplitProgram:
    """Compile the (groups, net) cut configuration into a SplitProgram.

    Pure host-side structure derivation — cheap enough to run per
    rebuild; the expensive artifact is the traced executor, which is
    keyed on `shape_key` by its consumers.
    """
    if n_layers is None:
        n_layers = len(NET_LAYER_DEFS[net])
    names = tuple(g.name for g in groups)
    cuts = tuple(layer_pair(g.cut, net) for g in groups)
    sizes = tuple(g.size for g in groups)
    span = server_union_span(groups, net, n_layers)
    steps = []
    for l in span:
        active = tuple(n for n, (h, t) in zip(names, cuts) if h <= l < t)
        joins = tuple(n for n, (h, _) in zip(names, cuts) if h == l)
        departs = tuple(n for n, (_, t) in zip(names, cuts) if t == l + 1)
        steps.append(ServerStep(l, active, joins, departs))
    return SplitProgram(
        net=net, n_layers=n_layers, middle=n_layers // 2,
        group_names=names, sizes=sizes,
        buckets=tuple(bucket_size(s) for s in sizes), cuts=cuts,
        heads=tuple(Segment("head", n, 0, h)
                    for n, (h, _) in zip(names, cuts)),
        steps=tuple(steps),
        tails=tuple(Segment("tail", n, t, n_layers)
                    for n, (_, t) in zip(names, cuts)))


# ---------------------------------------------------------------------------
# client-side segment passes (shared with the legacy huscf oracle)
# ---------------------------------------------------------------------------

def head_pass(defs, params: Dict[str, Any], x, stop: int, train: bool):
    new = {}
    for l in range(stop):
        x, new[str(l)] = defs[l][1](params[str(l)], x, train)
    return x, new


def tail_pass(defs, params: Dict[str, Any], x, start: int, n: int,
              train: bool):
    new = {}
    for l in range(start, n):
        x, new[str(l)] = defs[l][1](params[str(l)], x, train)
    return x, new


# ---------------------------------------------------------------------------
# the executor
# ---------------------------------------------------------------------------

def make_apply(program: SplitProgram, capture_middle: bool = False,
               concat_groups: bool = True) -> Callable:
    """The U-shaped split executor for one compiled program.

    Returns ``apply(client_params, server_params, inputs, train) ->
    (outputs {gname: [K,b,...]}, new_client, new_server, middles)``
    with ``inputs`` = {gname: tuple of per-client-stacked arrays fed to
    layer 0} — the same contract as `huscf.build_net_apply`, which now
    delegates here.

    concat_groups=True is the paper-faithful schedule (the server
    concatenates all active clients' activations per layer — the Eq. 7
    join — so BatchNorm stats span the population). False is the
    beyond-paper TPU optimization (EXPERIMENTS.md §Perf iteration 5):
    each group flows through the shared server weights separately,
    keeping the client-sharded layout intact at the cost of ghost-BN
    (per-group) statistics.
    """
    defs = NET_LAYER_DEFS[program.net]
    n = program.n_layers
    middle = program.middle

    def apply(client_params, server_params, inputs, train: bool):
        with jax.named_scope(program.net):
            return _apply(client_params, server_params, inputs, train)

    def _apply(client_params, server_params, inputs, train: bool):
        new_client = {name: dict(client_params[name])
                      for name in program.group_names}
        new_server = dict(server_params)
        # --- client heads (vmapped over the group's stacked clients)
        bufs: Dict[str, Array] = {}
        shapes: Dict[str, Tuple[int, int]] = {}
        for seg in program.heads:
            head_fn = functools.partial(head_pass, defs, stop=seg.stop,
                                        train=train)
            with jax.named_scope(SCOPE_HEAD), jax.named_scope(seg.gname):
                acts, upd = jax.vmap(lambda p, *xs: head_fn(p, xs))(
                    client_params[seg.gname], *inputs[seg.gname])
                new_client[seg.gname].update(upd)
                k, b = acts.shape[0], acts.shape[1]
                shapes[seg.gname] = (k, b)
                bufs[seg.gname] = maybe_shard(
                    acts.reshape((k * b,) + acts.shape[2:]), "rows")
        # --- server trunk: one ServerStep per layer, joins/departs
        #     resolved at compile time (paper Fig. 7)
        outs: Dict[str, Array] = {}
        middles: Dict[str, Array] = {}
        for step in program.steps:
            l = step.layer
            with jax.named_scope(SCOPE_TRUNK), jax.named_scope(f"layer{l}"):
                if concat_groups:
                    xs = [bufs[gname] for gname in step.active]
                    sizes = [x.shape[0] for x in xs]
                    x = jnp.concatenate(xs, 0) if len(xs) > 1 else xs[0]
                    x, new_server[str(l)] = defs[l][1](
                        server_params[str(l)], x, train)
                    parts = (jnp.split(x, list(np.cumsum(sizes)[:-1]), 0)
                             if len(xs) > 1 else [x])
                else:
                    # per-group pass through the SAME shared server
                    # weights; BN state updates merge by equal-weight
                    # averaging.
                    parts, bn_updates = [], []
                    for gname in step.active:
                        y, upd = defs[l][1](server_params[str(l)],
                                            bufs[gname], train)
                        parts.append(y)
                        bn_updates.append(upd)
                    new_server[str(l)] = jax.tree_util.tree_map(
                        lambda *xs: sum(xs) / len(xs), *bn_updates)
                for gname, part in zip(step.active, parts):
                    bufs[gname] = maybe_shard(part, "rows")
                    if capture_middle and l == middle:
                        k, b = shapes[gname]
                        mid = part.reshape((k, b) + part.shape[1:])
                        middles[gname] = jnp.mean(
                            mid.reshape(k, b, -1).astype(jnp.float32),
                            axis=1)
                    if gname in step.departs:
                        outs[gname] = bufs[gname]
        # --- client tails (vmapped)
        results: Dict[str, Array] = {}
        for seg in program.tails:
            with jax.named_scope(SCOPE_TAIL), jax.named_scope(seg.gname):
                k, b = shapes[seg.gname]
                x = outs[seg.gname]
                x = x.reshape((k, b) + x.shape[1:])
                tail_fn = functools.partial(tail_pass, defs, start=seg.start,
                                            n=n, train=train)
                y, upd = jax.vmap(tail_fn)(client_params[seg.gname], x)
                new_client[seg.gname].update(upd)
                results[seg.gname] = y
        return results, new_client, new_server, middles

    return apply


# ---------------------------------------------------------------------------
# Eq. 7/8 schedule machinery (shared with latency_jax)
# ---------------------------------------------------------------------------

def join_barrier_scan(terms: Array, barriers: Array,
                      reverse: bool = False) -> Array:
    """Eq. 7/8 cumulative server schedule as a `lax.scan` recurrence:
    ``S[i+1] = max(S[i] + terms[i], barriers[i])`` (forward), swept
    top-down with ``reverse=True`` for the backward Eq. 8. Returns the
    [n] cumulative values in layer order.
    """
    def sched(s, x):
        a, bar = x
        s = jnp.maximum(s + a, bar)
        return s, s

    _, out = jax.lax.scan(sched, jnp.float32(0.0), (terms, barriers),
                          reverse=reverse)
    return out


# ---------------------------------------------------------------------------
# analytic latency evaluated from the program structure (host f64)
# ---------------------------------------------------------------------------

def _seg_flops(costs, start: int, stop: int, backward: bool) -> float:
    key = "flops_bwd" if backward else "flops_fwd"
    return sum(getattr(c, key) for c in costs[start:stop])


def program_net_latency(program: SplitProgram,
                        profiles: Mapping[str, DeviceProfile],
                        server: DeviceProfile = PAPER_SERVER,
                        batch: int = 64,
                        counts: Optional[Mapping[str, float]] = None
                        ) -> Tuple[float, float]:
    """(L_f, L_b) — Eq. 7-9 for one network from the program structure.

    ``profiles`` maps group name -> DeviceProfile. Exactly equal to
    `latency._one_net_latency` over the member-expanded population: all
    members of a group are identical, so the per-layer occupancy
    collapses to size-weighted sums and the barrier/completion maxes
    are unchanged. ``counts`` overrides the per-group multiplicities
    (serving cohorts: number of requests per cut instead of the
    training population size).
    """
    costs = NET_LAYER_COSTS[program.net]
    n = program.n_layers
    b = float(batch)
    names = program.group_names
    mult = {g: float(program.size_of(g)) if counts is None
            else float(counts[g]) for g in names}

    head_f, head_b, tail_f, tail_b = {}, {}, {}, {}
    up_f, up_b, down_f, down_b = {}, {}, {}, {}
    for g, (h, t) in zip(names, program.cuts):
        dev = profiles[g]
        head_f[g] = b * _seg_flops(costs, 0, h, False) / dev.flops_per_s
        head_b[g] = b * _seg_flops(costs, 0, h, True) / dev.flops_per_s
        tail_f[g] = b * _seg_flops(costs, t, n, False) / dev.flops_per_s
        tail_b[g] = b * _seg_flops(costs, t, n, True) / dev.flops_per_s
        up_f[g] = b * costs[h - 1].act_bytes / dev.rate_bytes_per_s
        up_b[g] = b * costs[t - 1].act_bytes / dev.rate_bytes_per_s
        down_f[g] = b * costs[t - 1].act_bytes / server.rate_bytes_per_s
        down_b[g] = b * costs[h - 1].act_bytes / server.rate_bytes_per_s

    srv_f = [b * costs[i].flops_fwd / server.flops_per_s for i in range(n)]
    srv_b = [b * costs[i].flops_bwd / server.flops_per_s for i in range(n)]
    step_of = {s.layer: s for s in program.steps}

    # Eq. 7 forward schedule: joins gate the layer, occupancy scales it
    S_f = [0.0] * (n + 1)
    for i in range(n):
        step = step_of.get(i)
        joins = ([head_f[g] + up_f[g] for g in step.joins]
                 if step is not None else [])
        n_act = (sum(mult[g] for g in step.active)
                 if step is not None else 0.0)
        barrier = max(joins) if joins else 0.0
        S_f[i + 1] = max(S_f[i] + srv_f[i] * n_act, barrier)
    L_f = max(S_f[t] + down_f[g] + tail_f[g]
              for g, (_, t) in zip(names, program.cuts))

    # Eq. 8 backward schedule, top layer down
    S_b = [0.0] * (n + 2)
    for i in range(n - 1, -1, -1):
        step = step_of.get(i)
        joins = ([tail_b[g] + up_b[g] for g in step.departs]
                 if step is not None else [])
        n_act = (sum(mult[g] for g in step.active)
                 if step is not None else 0.0)
        barrier = max(joins) if joins else 0.0
        S_b[i] = max(S_b[i + 1] + srv_b[i] * n_act, barrier)
    L_b = max(S_b[h] + down_b[g] + head_b[g]
              for g, (h, _) in zip(names, program.cuts))
    return L_f, L_b


def program_iteration_latency(prog_g: SplitProgram, prog_d: SplitProgram,
                              profiles: Mapping[str, DeviceProfile],
                              server: DeviceProfile = PAPER_SERVER,
                              batch: int = 64) -> float:
    """Eq. 10 from two compiled programs: L = gf + gb + 3 (df + db)."""
    gf, gb = program_net_latency(prog_g, profiles, server, batch)
    df, db = program_net_latency(prog_d, profiles, server, batch)
    return gf + gb + 3.0 * (df + db)


def program_forward_latency(program: SplitProgram,
                            profiles: Mapping[str, DeviceProfile],
                            server: DeviceProfile = PAPER_SERVER,
                            batch: int = 64,
                            counts: Optional[Mapping[str, float]] = None
                            ) -> float:
    """Serving prediction: one U-shaped forward pass (Eq. 7 + Eq. 9
    completion, no backward). ``counts`` = requests per cut."""
    l_f, _ = program_net_latency(program, profiles, server, batch,
                                 counts=counts)
    return l_f
