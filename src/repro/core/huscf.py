"""HuSCF-GAN trainer — the paper's five-stage procedure (§4.1).

1. GA cut selection from device capabilities (repro.core.genetic).
2. Heterogeneous U-shaped split learning for G and D (§4.4): client
   heads -> server trunk (per-layer concatenation across clients whose
   span covers the layer) -> client tails, for both networks, forward
   and backward (backward comes free via JAX autodiff through the same
   graph).
3. Every E epochs: K-means on mid-layer D activations (real data).
4. Intra-cluster KLD-weighted federation of client segments (Eq. 13-16),
   vanilla FedAvg for the first two rounds.
5. Evaluation hooks (generation for the metric suite).

Simulation semantics: clients grouped by profile (appendix D); each
group's client-side segments are stacked pytrees vmapped over clients.
On a TPU mesh the stacked client axis shards over ('pod','data') and
server segments over 'model' — see repro/launch/dryrun.py.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import kld as kld_mod
from repro.core import spans
from repro.core.clustering import (cluster_activations,
                                   cluster_activations_jax,
                                   k_selection_bound)
from repro.core.federation import (donate_default, federate_client_params,
                                   federate_client_params_device,
                                   fedavg_uniform)
from repro.core.genetic import CutSearcher, GAConfig, optimize_cuts
from repro.core.latency import Cut, DeviceProfile, PAPER_DEVICES, PAPER_SERVER, huscf_iteration_latency
from repro.core.registry import ClientRegistry
from repro.core.segments import (compile_split_program, make_apply,
                                 head_pass as _head_pass,
                                 tail_pass as _tail_pass)
from repro.core.splitting import (ProfileGroup, client_owned_layers,
                                  group_by_profile, layer_pair,
                                  server_union_span)
from repro.data.partition import ClientSpec
from repro.data.pipeline import sample_batch, stage_clients
from repro.sharding.policy import maybe_shard
from repro.models import gan
from repro.models.gan import (DISC_LAYER_DEFS, DISC_MIDDLE,
                              DISC_MIDDLE_FEATURES, GEN_LAYER_DEFS,
                              Z_DIM, d_loss_fn, g_loss_fn)
from repro.optim import adam

Array = jnp.ndarray

_EMA_DECAY = 0.8                     # middle-activation EMA (stage 3 input)

# client-ownable layer counts per net, derived from the model depth so
# a layer-defs change cannot silently mis-plan the federation buffer
_N_LAYERS = {"G": len(GEN_LAYER_DEFS), "D": len(DISC_LAYER_DEFS)}


@dataclasses.dataclass
class HuSCFConfig:
    batch: int = 32
    federate_every: int = 5          # E
    beta: float = 150.0              # KLD weight scale
    lr: float = 2e-4
    adam_b1: float = 0.5
    num_clusters: Optional[int] = None   # None -> silhouette selection
    seed: int = 0
    use_kernel: bool = False         # Pallas weighted_agg for aggregation
    steps_per_epoch: Optional[int] = None
    warmup_fed_rounds: int = 2       # vanilla FedAvg rounds (paper §4.5)
    fused_epoch: bool = True         # scan-fused device-resident epochs;
    #                                  False = per-step loop (oracle)
    fused_cluster: bool = True       # device-resident stage 3+4 (jitted
    #                                  k-means/silhouette/KLD + in-jit
    #                                  weight matrix); False = host
    #                                  numpy path (correctness oracle)
    epoch_unroll: Optional[int] = None
    # scan unroll for the fused epoch. None = backend auto: full unroll
    # on CPU (XLA:CPU only multithreads the entry computation, so a
    # while-loop body runs its convs single-threaded — measured ~2.3x
    # per-step wall on 2 cores), 1 (true scan, O(1) compile) on
    # TPU/GPU where the loop body parallelizes fine.
    cohort_size: Optional[int] = None
    # per-round participant count: each federate() samples this many of
    # the registered clients (core/registry.py) from a dedicated PRNG
    # chain; Eq. 15 weights renormalize over the cohort and everyone
    # else keeps their params. None = full participation (paper
    # default).
    agg_chunk: Optional[int] = None
    # chunk-streamed aggregation: the round scans client chunks of this
    # size instead of materializing the dense [K, D] buffer
    # (federation.FederationPlan.aggregate_chunked, O(chunk + clusters)
    # memory). None = dense fused round.
    reoptimize_every: Optional[int] = None
    # re-run the (fused, device-resident) GA cut search every this many
    # federation rounds against the *current* device profiles; when it
    # finds strictly better cuts the trainer regroups online (profile
    # groups, migrated client/server params, re-staged dataset) and
    # invalidates the FederationPlan cache. 1 = every round (cheap: one
    # cached-program dispatch per search). None = static cuts (paper).
    split_program: bool = True
    # True: forward/backward graphs execute the compiled SplitProgram
    # (core/segments.py) shared with the latency model and the serving
    # engine. False: the legacy hand-rolled per-group loops
    # (build_net_apply_legacy) — kept as the bit-exactness oracle
    # (tests/test_segments.py).


# ---------------------------------------------------------------------------
# functional forward passes over the split topology
# ---------------------------------------------------------------------------

# client-side segment passes (_head_pass/_tail_pass) now live in
# core.segments as head_pass/tail_pass, shared with the serving
# executor; imported above under their old names for the legacy oracle.


def build_net_apply(groups: Sequence[ProfileGroup], net: str,
                    capture_middle: bool = False,
                    concat_groups: bool = True):
    """Returns apply(client_params, server_params, inputs, train) ->
    (outputs {gname: [K,b,...]}, new_client, new_server, middles).

    inputs: {gname: tuple of per-client-stacked arrays fed to layer 0}.

    Compiles the cut configuration into a `core.segments.SplitProgram`
    and returns its executor — the same program structure the analytic
    latency model and the split-serving engine consume. Bit-exact with
    `build_net_apply_legacy` (the pre-SplitProgram loops, kept as the
    oracle behind ``HuSCFConfig.split_program=False``).
    """
    program = compile_split_program(groups, net)
    return make_apply(program, capture_middle=capture_middle,
                      concat_groups=concat_groups)


def build_net_apply_legacy(groups: Sequence[ProfileGroup], net: str,
                           capture_middle: bool = False,
                           concat_groups: bool = True):
    """Pre-SplitProgram implementation: hand-rolled per-group loops that
    re-derive layer activity from the cuts inline. Semantically (and
    bit-) identical to `build_net_apply`; survives as the equivalence
    oracle for tests and for ``HuSCFConfig.split_program=False``.

    concat_groups=True is the paper-faithful schedule (the server
    concatenates all clients' activations per layer, so BatchNorm stats
    span the whole population). False is the beyond-paper TPU
    optimization (EXPERIMENTS.md §Perf iteration 5): each profile group
    flows through the shared server weights separately, which keeps the
    client-sharded layout intact (no realignment all-gathers) at the
    cost of ghost-BatchNorm (per-group) statistics.
    """
    defs = GEN_LAYER_DEFS if net == "G" else DISC_LAYER_DEFS
    n = len(defs)
    middle = n // 2
    span = server_union_span(groups, net, n)

    def apply(client_params, server_params, inputs, train: bool):
        new_client = {g.name: dict(client_params[g.name]) for g in groups}
        new_server = dict(server_params)
        # --- heads (vmapped over clients)
        bufs: Dict[str, Array] = {}
        shapes: Dict[str, Tuple[int, int]] = {}
        for g in groups:
            h, _ = layer_pair(g.cut, net)
            head_fn = functools.partial(_head_pass, defs, stop=h, train=train)
            acts, upd = jax.vmap(lambda p, *xs: head_fn(p, xs))(
                client_params[g.name], *inputs[g.name])
            new_client[g.name].update(upd)
            k, b = acts.shape[0], acts.shape[1]
            shapes[g.name] = (k, b)
            bufs[g.name] = maybe_shard(
                acts.reshape((k * b,) + acts.shape[2:]), "rows")
        # --- server trunk with per-layer join/leave (paper Fig. 7)
        outs: Dict[str, Array] = {}
        middles: Dict[str, Array] = {}
        for l in span:
            active = [g for g in groups
                      if layer_pair(g.cut, net)[0] <= l < layer_pair(g.cut, net)[1]]
            if concat_groups:
                xs = [bufs[g.name] for g in active]
                sizes = [x.shape[0] for x in xs]
                x = jnp.concatenate(xs, 0) if len(xs) > 1 else xs[0]
                x, new_server[str(l)] = defs[l][1](server_params[str(l)], x,
                                                   train)
                parts = (jnp.split(x, list(np.cumsum(sizes)[:-1]), 0)
                         if len(xs) > 1 else [x])
            else:
                # per-group pass through the SAME shared server weights;
                # BN state updates merge by equal-weight averaging.
                parts, bn_updates = [], []
                for g in active:
                    y, upd = defs[l][1](server_params[str(l)],
                                        bufs[g.name], train)
                    parts.append(y)
                    bn_updates.append(upd)
                new_server[str(l)] = jax.tree_util.tree_map(
                    lambda *xs: sum(xs) / len(xs), *bn_updates)
            for g, part in zip(active, parts):
                bufs[g.name] = maybe_shard(part, "rows")
                if capture_middle and l == middle:
                    k, b = shapes[g.name]
                    mid = part.reshape((k, b) + part.shape[1:])
                    middles[g.name] = jnp.mean(
                        mid.reshape(k, b, -1).astype(jnp.float32), axis=1)
                if layer_pair(g.cut, net)[1] == l + 1:
                    outs[g.name] = bufs[g.name]
        # --- tails (vmapped)
        results: Dict[str, Array] = {}
        for g in groups:
            _, t = layer_pair(g.cut, net)
            k, b = shapes[g.name]
            x = outs[g.name]
            x = x.reshape((k, b) + x.shape[1:])
            tail_fn = functools.partial(_tail_pass, defs, start=t, n=n,
                                        train=train)
            y, upd = jax.vmap(tail_fn)(client_params[g.name], x)
            new_client[g.name].update(upd)
            results[g.name] = y
        return results, new_client, new_server, middles

    return apply


def make_epoch_fn(groups: Sequence[ProfileGroup], step_core: Callable,
                  sample: Callable, n_steps: int,
                  unroll: int = 1) -> Callable:
    """The scan-fused device-resident epoch (DESIGN.md §Device-resident
    epochs), shared by the trainer and the production-mesh dry-run so
    the lowered computation cannot drift from the one that trains.

    ``step_core(state, batch) -> (state, metrics, mids)`` with ``mids``
    the per-group ``[K_p, F]`` middle-activation batch means;
    ``sample(dataset, key) -> batch``. Returns
    ``epoch(state, dataset, key, ema, ema_init)`` scanning the carry
    ``(state, rng, mid_ema [K, F], ema_init)`` for ``n_steps``.
    """
    rows = {g.name: jnp.asarray(g.client_ids, jnp.int32) for g in groups}

    def epoch(state, dataset, key, ema, ema_init):
        def body(carry, _):
            state, key, ema, ema_init = carry
            key, ks = jax.random.split(key)
            state, metrics, mids = step_core(state, sample(dataset, ks))
            # middle-activation EMA lives in the carry as one [K, F]
            # array — no per-step device->host sync; it is read back
            # once per epoch for stage-3 clustering.
            for g in groups:
                m = mids[g.name].astype(jnp.float32)
                prev = ema[rows[g.name]]
                ema = ema.at[rows[g.name]].set(
                    jnp.where(ema_init,
                              _EMA_DECAY * prev + (1 - _EMA_DECAY) * m, m))
            return (state, key, ema, jnp.ones((), jnp.bool_)), metrics

        (state, key, ema, ema_init), metrics = jax.lax.scan(
            body, (state, key, ema, ema_init), None, length=n_steps,
            unroll=unroll)
        return state, key, ema, ema_init, metrics

    return epoch


# ---------------------------------------------------------------------------
# trainer
# ---------------------------------------------------------------------------

class HuSCFTrainer:
    """End-to-end HuSCF-GAN over a client population."""

    def __init__(self, clients: Sequence[ClientSpec],
                 devices: Optional[Sequence[DeviceProfile]] = None,
                 cuts: Optional[Sequence[Cut]] = None,
                 config: HuSCFConfig = HuSCFConfig(),
                 server: DeviceProfile = PAPER_SERVER,
                 ga_config: Optional[GAConfig] = None,
                 fed_mesh: Optional[Any] = None):
        # fed_mesh: jax Mesh for client-axis-sharded federation rounds
        # (launch.mesh.make_federation_mesh); None = single-device path.
        # A Mesh is a device-topology object, so it rides the trainer,
        # not the (value-semantics) HuSCFConfig dataclass.
        self.clients = list(clients)
        self.cfg = config
        self.fed_mesh = fed_mesh
        K = len(self.clients)
        if devices is None:
            devices = [PAPER_DEVICES[i % len(PAPER_DEVICES)] for i in range(K)]
        self.devices = list(devices)
        self.server_profile = server

        # Stage 1: GA cut selection
        self._ga_config = ga_config or GAConfig(population_size=200,
                                                generations=30,
                                                seed=config.seed)
        if cuts is None:
            result = optimize_cuts(self.devices, server, batch=config.batch,
                                   config=self._ga_config)
            cuts = result.cuts
            self.ga_latency = result.latency
        else:
            self.ga_latency = huscf_iteration_latency(cuts, self.devices,
                                                      server, config.batch)
        self.cuts = list(cuts)
        self.groups = group_by_profile(self.devices, self.cuts)
        self.sizes = np.array([c.n for c in self.clients], np.int64)

        key = jax.random.PRNGKey(config.seed)
        self.state = self._init_state(key)
        self._rng = np.random.default_rng(config.seed + 1)
        # device-resident data: every group's client rows staged once
        # (padded + valid counts); batches are drawn inside the jitted
        # step from the training PRNG key, so epochs never touch host
        # numpy. With a fed_mesh the rows shard over its client axes
        # and the rest of the training state replicates onto the same
        # device set (one mesh for step + federation).
        self._dataset = stage_clients(self.groups, self.clients,
                                      mesh=fed_mesh)
        self._train_key = jax.random.PRNGKey(config.seed + 1)
        self._mid_ema = jnp.zeros((K, DISC_MIDDLE_FEATURES), jnp.float32)
        self._ema_init = jnp.zeros((), jnp.bool_)
        # device-resident stage 3+4 inputs: dataset sizes staged once,
        # a dedicated cluster PRNG key split per round on device
        self._sizes_dev = jnp.asarray(self.sizes, jnp.float32)
        self._cluster_key = jax.random.PRNGKey(config.seed + 2)
        # population registry + per-round cohort sampling (its own key
        # chain so enabling cohorts never perturbs the cluster stream)
        self.registry = ClientRegistry.from_clients(self.clients)
        if config.cohort_size is not None and not (
                1 <= config.cohort_size <= K):
            raise ValueError(f"cohort_size {config.cohort_size} out of "
                             f"range for {K} registered clients")
        self._cohort_key = jax.random.PRNGKey(config.seed + 3)
        # on-device GA cut re-optimization: its own key chain + a
        # cache of staged searchers (rebuilt only when the device
        # population itself changes)
        self._ga_key = jax.random.PRNGKey(config.seed + 4)
        self._searchers: Dict = {}
        if fed_mesh is not None and fed_mesh.devices.size > 1:
            self.state = jax.tree_util.tree_map(self._put_replicated,
                                                self.state)
            self._train_key = self._put_replicated(self._train_key)
            self._mid_ema = self._put_replicated(self._mid_ema)
            self._ema_init = self._put_replicated(self._ema_init)
            self._sizes_dev = self._put_replicated(self._sizes_dev)
            self._cluster_key = self._put_replicated(self._cluster_key)
            self._cohort_key = self._put_replicated(self._cohort_key)
        # fused-federation plans (treedefs/leaf shapes/layer offsets),
        # built on first round and reused so repeat rounds pay zero
        # host-side tree walking.
        self._fed_plans: Dict = {}
        self._step_core = self._build_step_core()
        self._step_fn = self._build_step()
        self._epoch_fns: Dict[int, Callable] = {}
        self._cluster_fns: Dict[Tuple, Callable] = {}
        self._gen_fn = None
        self.fed_round = 0
        self.epoch = 0
        self._trained = False        # host mirror of _ema_init (no readback)
        self._mid_acc: Dict[int, np.ndarray] = {}
        self.history: List[Dict[str, float]] = []

    # -- initialization ----------------------------------------------------
    def _init_state(self, key) -> Dict[str, Any]:
        kg, kd, kc = jax.random.split(key, 3)
        n_g, n_d = len(GEN_LAYER_DEFS), len(DISC_LAYER_DEFS)
        # server holds the union span of every layer any client delegates
        server_g = {}
        for l in server_union_span(self.groups, "G", n_g):
            kg, sub = jax.random.split(kg)
            server_g[str(l)] = GEN_LAYER_DEFS[l][0](sub, jnp.float32)
        server_d = {}
        for l in server_union_span(self.groups, "D", n_d):
            kd, sub = jax.random.split(kd)
            server_d[str(l)] = DISC_LAYER_DEFS[l][0](sub, jnp.float32)

        client_g, client_d = {}, {}
        for g in self.groups:
            kc, k1, k2 = jax.random.split(kc, 3)
            gh, gt = g.cut.g_h, g.cut.g_t
            dh, dt = g.cut.d_h, g.cut.d_t
            keys_g = jax.random.split(k1, g.size)
            client_g[g.name] = {
                str(l): jax.vmap(lambda kk, l=l: GEN_LAYER_DEFS[l][0](kk, jnp.float32))(keys_g)
                for l in list(range(gh)) + list(range(gt, n_g))}
            keys_d = jax.random.split(k2, g.size)
            client_d[g.name] = {
                str(l): jax.vmap(lambda kk, l=l: DISC_LAYER_DEFS[l][0](kk, jnp.float32))(keys_d)
                for l in list(range(dh)) + list(range(dt, n_d))}

        g_params = {"client": client_g, "server": server_g}
        d_params = {"client": client_d, "server": server_d}
        # init fns kept: an online re-cut rebuilds the Adam moments for
        # the migrated param structure (the param->slot mapping changed)
        self._opt_init_g, self._opt_update_g = adam(self.cfg.lr,
                                                    b1=self.cfg.adam_b1)
        self._opt_init_d, self._opt_update_d = adam(self.cfg.lr,
                                                    b1=self.cfg.adam_b1)
        return {"G": g_params, "D": d_params,
                "opt_g": self._opt_init_g(g_params),
                "opt_d": self._opt_init_d(d_params),
                "step": jnp.zeros((), jnp.int32)}

    def _put_replicated(self, x):
        """Replicate a device value onto the federation mesh (identity
        without one)."""
        if self.fed_mesh is None or self.fed_mesh.devices.size <= 1:
            return x
        from jax.sharding import NamedSharding, PartitionSpec as P
        return jax.device_put(x, NamedSharding(self.fed_mesh, P()))

    # -- one training step (pure body, shared by both epoch paths) ---------
    def _build_step_core(self) -> Callable:
        build = (build_net_apply if self.cfg.split_program
                 else build_net_apply_legacy)
        gen_apply = build(self.groups, "G")
        disc_apply = build(self.groups, "D", capture_middle=True)
        groups = self.groups
        total_clients = sum(g.size for g in groups)
        opt_update_g, opt_update_d = self._opt_update_g, self._opt_update_d

        def mean_client_loss(logits: Dict[str, Array], target: float) -> Array:
            tot = 0.0
            for g in groups:
                per = gan.bce_logits(logits[g.name].reshape(-1), target)
                tot = tot + per * g.size
            return tot / total_clients

        def step(state, batch):
            g_params, d_params = state["G"], state["D"]

            # ---------------- discriminator update
            def d_loss(d_p):
                fake, _, _, _ = gen_apply(g_params["client"],
                                          g_params["server"],
                                          {g.name: (batch["z"][g.name],
                                                    batch["fake_y"][g.name])
                                           for g in groups}, True)
                fake = {k: jax.lax.stop_gradient(v) for k, v in fake.items()}
                lr_, ncr, nsr, mids = disc_apply(
                    d_p["client"], d_p["server"],
                    {g.name: (batch["real_img"][g.name],
                              batch["real_y"][g.name]) for g in groups}, True)
                lf_, _, _, _ = disc_apply(
                    d_p["client"], d_p["server"],
                    {g.name: (fake[g.name], batch["fake_y"][g.name])
                     for g in groups}, True)
                loss = (mean_client_loss(lr_, 1.0)
                        + mean_client_loss(lf_, 0.0))
                return loss, ({"client": ncr, "server": nsr}, mids)

            (loss_d, (d_bn, mids)), grads_d = jax.value_and_grad(
                d_loss, has_aux=True)(d_params)
            new_opt_d, d_new = opt_update_d(state["opt_d"], grads_d, d_params)
            # keep BatchNorm running stats from the real-data pass
            d_new = _merge_bn(d_new, d_bn)

            # ---------------- generator update (vs updated D)
            def g_loss(g_p):
                fake, ncg, nsg, _ = gen_apply(g_p["client"], g_p["server"],
                                              {g.name: (batch["z"][g.name],
                                                        batch["fake_y"][g.name])
                                               for g in groups}, True)
                logits, _, _, _ = disc_apply(
                    d_new["client"], d_new["server"],
                    {g.name: (fake[g.name], batch["fake_y"][g.name])
                     for g in groups}, True)
                loss = mean_client_loss(logits, 1.0)
                return loss, {"client": ncg, "server": nsg}

            (loss_g, g_bn), grads_g = jax.value_and_grad(
                g_loss, has_aux=True)(g_params)
            new_opt_g, g_new = opt_update_g(state["opt_g"], grads_g, g_params)
            g_new = _merge_bn(g_new, g_bn)

            new_state = {"G": g_new, "D": d_new, "opt_g": new_opt_g,
                         "opt_d": new_opt_d, "step": state["step"] + 1}
            metrics = {"loss_d": loss_d, "loss_g": loss_g}
            return new_state, metrics, mids

        return step

    # -- on-device batch sampling ------------------------------------------
    def _sample(self, dataset, key):
        """One batch drawn on device from the staged dataset — shared
        by the per-step oracle and the scan body so both paths consume
        the identical PRNG stream."""
        return sample_batch(dataset, key, batch=self.cfg.batch,
                            z_dim=Z_DIM, num_classes=gan.NUM_CLASSES)

    # -- per-step path (correctness oracle, fused_epoch=False) -------------
    def _build_step(self) -> Callable:
        spans.count("programs_built.step")
        core = self._step_core
        sample = self._sample

        def step(state, dataset, key):
            key, ks = jax.random.split(key)
            new_state, metrics, mids = core(state, sample(dataset, ks))
            return new_state, key, metrics, mids

        # the trainer replaces self.state right after every call, so the
        # old params/Adam buffers may alias into the update in place
        # (TPU/GPU; CPU XLA ignores donation).
        return jax.jit(step,
                       donate_argnums=(0,) if donate_default() else ())

    def _epoch_unroll(self, n_steps: int) -> int:
        if self.cfg.epoch_unroll is not None:
            return max(1, min(n_steps, self.cfg.epoch_unroll))
        return n_steps if jax.default_backend() == "cpu" else 1

    # -- scan-fused device-resident epoch (fused_epoch=True) ---------------
    def _build_epoch(self, n_steps: int) -> Callable:
        spans.count("programs_built.epoch")
        epoch = make_epoch_fn(self.groups, self._step_core, self._sample,
                              n_steps, unroll=self._epoch_unroll(n_steps))
        # donate the carry's state + EMA so Adam/param buffers update in
        # place across the whole epoch (the dataset argument is
        # read-only and must not be donated)
        return jax.jit(epoch,
                       donate_argnums=(0, 3) if donate_default() else ())

    # -- public API ----------------------------------------------------------
    def train_steps(self, n_steps: int) -> Dict[str, float]:
        with spans.span("train_steps"):
            return self._train_steps(n_steps)

    def _train_steps(self, n_steps: int) -> Dict[str, float]:
        if self.cfg.fused_epoch:
            fn = self._epoch_fns.get(n_steps)
            if fn is None:
                fn = self._epoch_fns[n_steps] = self._build_epoch(n_steps)
            with spans.span("train_steps.dispatch"):
                (self.state, self._train_key, self._mid_ema, self._ema_init,
                 metrics) = fn(self.state, self._dataset, self._train_key,
                               self._mid_ema, self._ema_init)
            # only after the epoch dispatched: a failed first call must
            # leave the fused federate()'s empty-EMA guard armed
            self._trained = True
            with spans.span("train_steps.readback"):
                return {k: float(v[-1]) for k, v in metrics.items()}
        # oracle: one dispatch per step, blocking mid-activation
        # readback + per-client Python EMA each step
        last = {}
        for _ in range(n_steps):
            self.state, self._train_key, metrics, mids = self._step_fn(
                self.state, self._dataset, self._train_key)
            for g in self.groups:
                m = np.asarray(mids[g.name])
                for pos, cid in enumerate(g.client_ids):
                    prev = self._mid_acc.get(cid)
                    self._mid_acc[cid] = (
                        m[pos] if prev is None
                        else _EMA_DECAY * prev + (1 - _EMA_DECAY) * m[pos])
            last = {k: float(v) for k, v in metrics.items()}
            self._trained = True
        return last

    def train_epoch(self) -> Dict[str, float]:
        steps = self.cfg.steps_per_epoch or max(
            1, int(np.median(self.sizes)) // self.cfg.batch)
        metrics = self.train_steps(steps)
        self.epoch += 1
        if self.epoch % self.cfg.federate_every == 0:
            self.federate()
        self.history.append(metrics)
        return metrics

    def middle_activations(self) -> np.ndarray:
        if self.cfg.fused_epoch:
            if not bool(self._ema_init):
                # fail as loudly as the oracle path's empty-dict lookup
                # would — an all-zero EMA would cluster degenerately
                raise RuntimeError(
                    "middle_activations() before any training step: "
                    "the fused-epoch EMA is empty")
            # the EMA lives on device in the scan carry; this is the
            # one device->host readback per epoch (stage-3 clustering)
            return np.asarray(self._mid_ema)
        K = len(self.clients)
        feat = next(iter(self._mid_acc.values()))
        out = np.zeros((K,) + feat.shape, np.float32)
        for cid, v in self._mid_acc.items():
            out[cid] = v
        return out

    _MESH_DEFAULT = object()     # sentinel: mesh=None must stay sayable

    def federate(self, use_label_kld: bool = False,
                 mesh: Any = _MESH_DEFAULT) -> Dict[str, Any]:
        """Stages 3+4. Returns diagnostics.

        With ``cfg.fused_cluster`` (the default) the clustered rounds
        run entirely on device (jitted k-means + silhouette selection
        + log-space Eq. 13-15 + in-jit weight matrix) and the
        diagnostic arrays come back as device arrays. The host numpy
        path is the correctness oracle (``fused_cluster=False``) and
        still serves ``use_label_kld=True``, whose label histograms
        live on the host by construction.

        mesh overrides the trainer's ``fed_mesh`` for this round
        (client-axis-sharded aggregation); pass ``mesh=None``
        explicitly to force the single-device path on a trainer that
        has a ``fed_mesh``. Omitted = trainer default.

        With ``cfg.cohort_size`` each round first samples its cohort
        from the registry (dedicated PRNG chain, on device); Eq. 15
        weights renormalize over the cohort and non-members keep their
        params. ``cfg.agg_chunk`` streams the aggregation in client
        chunks instead of the dense [K, D] buffer."""
        with spans.span("federate"):
            return self._federate(use_label_kld, mesh)

    def _federate(self, use_label_kld: bool, mesh: Any) -> Dict[str, Any]:
        mesh = self.fed_mesh if mesh is self._MESH_DEFAULT else mesh
        self.fed_round += 1
        recut = None
        if (self.cfg.reoptimize_every is not None
                and self.fed_round % self.cfg.reoptimize_every == 0):
            # online cut re-optimization: one cached-program GA
            # dispatch against the current profiles; regroups (and
            # invalidates the plan cache) only on strictly better cuts
            with spans.span("federate.recut"):
                recut = self.reoptimize_cuts()
        cohort_ids = cohort_mask = None
        if self.cfg.cohort_size is not None:
            with spans.span("federate.cohort"):
                self._cohort_key, sub = jax.random.split(self._cohort_key)
                cohort_ids = self.registry.sample_cohort(
                    sub, self.cfg.cohort_size)
                cohort_mask = self.registry.cohort_mask(cohort_ids)
        if self.fed_round <= self.cfg.warmup_fed_rounds:
            spans.count("rounds.fedavg")
            # host fedavg path: the tiny cohort mask is the one
            # readback (warmup rounds predate the device-resident chain
            # anyway — cohort-critical runs set warmup_fed_rounds=0)
            with spans.span("federate.fedavg"):
                mask_np = (None if cohort_mask is None
                           else np.asarray(cohort_mask))
                for net in ("G", "D"):
                    wrapped = {g.name: {net: self.state[net]["client"][
                        g.name]} for g in self.groups}
                    # the trainer drops its references right below, so
                    # the round may donate the old client buffers
                    # (TPU/GPU)
                    out = fedavg_uniform(self.groups, wrapped, self.sizes,
                                         n_layers={net: _N_LAYERS[net]},
                                         use_kernel=self.cfg.use_kernel,
                                         plan_cache=self._fed_plans,
                                         donate=donate_default(),
                                         mesh=mesh,
                                         chunk_size=self.cfg.agg_chunk,
                                         cohort_mask=mask_np)
                    self.state[net]["client"] = {g.name: out[g.name][net]
                                                 for g in self.groups}
            diag = {"round": self.fed_round, "mode": "fedavg"}
            if cohort_ids is not None:
                diag["cohort"] = cohort_ids
            if recut is not None:
                diag["recut"] = recut
            return diag

        spans.count("rounds.clustered")
        if self.cfg.fused_cluster and not use_label_kld:
            diag = self._federate_fused(mesh, cohort_ids, cohort_mask)
            if recut is not None:
                diag["recut"] = recut
            return diag

        with spans.span("federate.cluster"):
            acts = self.middle_activations()
            cl = cluster_activations(acts, k=self.cfg.num_clusters,
                                     seed=self.cfg.seed)
            if use_label_kld:
                hists = np.stack([np.bincount(c.labels,
                                              minlength=gan.NUM_CLASSES)
                                  for c in self.clients])
                weights, klds = kld_mod.label_weights(
                    hists, self.sizes, cl.labels, self.cfg.beta)
            else:
                weights, klds = kld_mod.activation_weights(
                    acts, self.sizes, cl.labels, self.cfg.beta)
            mask_np = None if cohort_mask is None else np.asarray(cohort_mask)
            if mask_np is not None:
                # KLDs stay full-cluster; only the Eq.-15 normalization
                # restricts to the sampled participants.
                weights = kld_mod.cohort_federation_weights(
                    klds, self.sizes, cl.labels, mask_np, self.cfg.beta)
        with spans.span("federate.aggregate"):
            for net in ("G", "D"):
                wrapped = {g.name: {net: self.state[net]["client"][g.name]}
                           for g in self.groups}
                out = federate_client_params(self.groups, wrapped, weights,
                                             cl.labels,
                                             n_layers={net: _N_LAYERS[net]},
                                             use_kernel=self.cfg.use_kernel,
                                             plan_cache=self._fed_plans,
                                             donate=donate_default(),
                                             mesh=mesh,
                                             chunk_size=self.cfg.agg_chunk,
                                             cohort_mask=mask_np)
                self.state[net]["client"] = {g.name: out[g.name][net]
                                             for g in self.groups}
        diag = {"round": self.fed_round, "mode": "clustered",
                "k": cl.k, "silhouette": cl.silhouette,
                "labels": cl.labels, "weights": weights, "klds": klds}
        if cohort_ids is not None:
            diag["cohort"] = cohort_ids
        if recut is not None:
            diag["recut"] = recut
        return diag

    # -- device-resident stage 3+4 (fused_cluster) -------------------------
    def _get_cluster_fn(self) -> Callable:
        """Jitted (acts, sizes, key[, cohort_mask]) -> (labels, k, sil,
        weights, klds) — stage 3+4 compute in one dispatch. Cached per
        (beta, num_clusters, use_kernel) because benchmarks mutate cfg
        fields between rounds."""
        key = (float(self.cfg.beta), self.cfg.num_clusters,
               self.cfg.use_kernel)
        fn = self._cluster_fns.get(key)
        if fn is None:
            spans.count("programs_built.cluster")
            beta, k_cfg = float(self.cfg.beta), self.cfg.num_clusters
            use_kernel = self.cfg.use_kernel

            def cluster_weight(acts, sizes, key, cohort_mask=None):
                with jax.named_scope(spans.SCOPE_CLUSTER):
                    labels, k_sel, sil = cluster_activations_jax(
                        acts, key, k=k_cfg, use_kernel=use_kernel)
                with jax.named_scope(spans.SCOPE_KLD):
                    weights, klds = kld_mod.activation_weights_jax(
                        acts, sizes, labels,
                        k_selection_bound(acts.shape[0], k_cfg), beta,
                        cohort_mask=cohort_mask)
                return labels, k_sel, sil, weights, klds

            fn = self._cluster_fns[key] = jax.jit(cluster_weight)
        return fn

    def _federate_fused(self, mesh, cohort_ids=None,
                        cohort_mask=None) -> Dict[str, Any]:
        """Clustered round without leaving the device: the EMA feeds
        the jitted cluster+weight chain, whose device labels/weights
        feed the in-jit weight-matrix aggregation — zero host<->device
        transfers of activations/labels/weights between train_steps
        and the aggregated params; a sampled cohort (mask + ids device
        arrays from the registry) stays on device too. Diagnostics are
        device arrays (reading them back is the caller's choice)."""
        if not self._trained:
            # same failure mode as the oracle path's empty-EMA check,
            # but off a host flag: no device readback in this method
            raise RuntimeError(
                "federate() before any training step: the middle-"
                "activation EMA is empty")
        with spans.span("federate.cluster"):
            acts = (self._mid_ema if self.cfg.fused_epoch
                    else jnp.asarray(self.middle_activations()))
            self._cluster_key, sub = jax.random.split(self._cluster_key)
            labels, k_sel, sil, weights, klds = self._get_cluster_fn()(
                acts, self._sizes_dev, sub, cohort_mask)
        bound = k_selection_bound(len(self.clients), self.cfg.num_clusters)
        with spans.span("federate.aggregate"):
            for net in ("G", "D"):
                wrapped = {g.name: {net: self.state[net]["client"][g.name]}
                           for g in self.groups}
                out = federate_client_params_device(
                    self.groups, wrapped, weights, labels, bound,
                    n_layers={net: _N_LAYERS[net]},
                    use_kernel=self.cfg.use_kernel,
                    plan_cache=self._fed_plans,
                    donate=donate_default(), mesh=mesh,
                    chunk_size=self.cfg.agg_chunk,
                    cohort_mask=cohort_mask,
                    cohort_size=self.cfg.cohort_size)
                self.state[net]["client"] = {g.name: out[g.name][net]
                                             for g in self.groups}
        diag = {"round": self.fed_round, "mode": "clustered",
                "k": k_sel, "silhouette": sil, "labels": labels,
                "weights": weights, "klds": klds}
        if cohort_ids is not None:
            diag["cohort"] = cohort_ids
        return diag

    # -- online cut re-optimization + population churn ---------------------
    def _get_searcher(self, devices: Optional[Sequence[DeviceProfile]] = None
                      ) -> CutSearcher:
        """Staged fused-GA searcher for a device population (default:
        the current one). Cached so repeat re-optimizations against an
        unchanged population cost one dispatch, not a rebuild; the
        jitted program itself is shared across searchers with the same
        GA shape (genetic._get_search_fn's lru_cache)."""
        devices = self.devices if devices is None else list(devices)
        key = (tuple(devices), self.server_profile, self.cfg.batch,
               dataclasses.astuple(self._ga_config))
        s = self._searchers.get(key)
        if s is None:
            s = self._searchers[key] = CutSearcher(
                devices, self.server_profile, batch=self.cfg.batch,
                config=self._ga_config)
        return s

    def _run_search(self, searcher: CutSearcher):
        """One GA dispatch off the trainer's GA key chain. The guard
        *enforces* that the per-round search is transfer-free (key
        split, staged tables, in-graph generations — device arrays
        only); readbacks happen in to_result, outside, and only when a
        result is adopted or compared."""
        self._ga_key, sub = jax.random.split(self._ga_key)
        with jax.transfer_guard("disallow_explicit"):
            return searcher.run(sub)

    def reoptimize_cuts(self) -> bool:
        """Re-run the (fused, device-resident) GA against the current
        device population; when it finds strictly better cuts than the
        live assignment, regroup online (migrated params, re-staged
        dataset, invalidated FederationPlan cache). Returns whether the
        cuts changed. GA ties / losses against the incumbent must NOT
        churn the population, so a no-better search is a no-op."""
        searcher = self._get_searcher()
        result = searcher.to_result(self._run_search(searcher))
        current = huscf_iteration_latency(self.cuts, self.devices,
                                          self.server_profile,
                                          self.cfg.batch)
        if result.latency >= current * (1 - 1e-9):
            return False
        spans.count("recuts.adopted")
        self.ga_latency = result.latency
        self._rebuild_population(self.clients, self.devices, result.cuts,
                                 old_of=list(range(len(self.clients))))
        return True

    def apply_churn(self, leave: Sequence[int] = (),
                    join: Sequence[Tuple[ClientSpec, DeviceProfile]] = ()
                    ) -> List[Cut]:
        """Registry churn: ``leave`` = global client ids exiting,
        ``join`` = (ClientSpec, DeviceProfile) pairs entering.
        Membership changed, so cuts are re-derived unconditionally
        (unlike ``reoptimize_cuts``'s better-only policy) and the
        population rebuilds: survivors keep their trained params/EMA
        rows under their new global ids, joiners start from the
        server's copies (population mean where the server has none).
        Returns the new per-client cut list."""
        join = list(join)
        _, old_of = self.registry.churn(leave,
                                        [spec.n for spec, _ in join])
        joiners = iter(join)
        new_clients, new_devices = [], []
        for o in old_of:
            if o >= 0:
                new_clients.append(self.clients[o])
                new_devices.append(self.devices[o])
            else:
                spec, dev = next(joiners)
                new_clients.append(spec)
                new_devices.append(dev)
        searcher = self._get_searcher(new_devices)
        result = searcher.to_result(self._run_search(searcher))
        self.ga_latency = result.latency
        self._rebuild_population(new_clients, new_devices, result.cuts,
                                 old_of)
        return list(self.cuts)

    def update_profile(self, cid: int, profile: DeviceProfile) -> List[Cut]:
        """A registered client reports new capabilities (measured
        bandwidth / frequency drift). Re-derives cuts for the updated
        population and regroups — identity-preserving churn, so the
        client keeps its dataset, params and EMA row."""
        if not 0 <= cid < len(self.clients):
            raise ValueError(f"unknown client id {cid}")
        new_devices = list(self.devices)
        new_devices[cid] = profile
        searcher = self._get_searcher(new_devices)
        result = searcher.to_result(self._run_search(searcher))
        self.ga_latency = result.latency
        self._rebuild_population(self.clients, new_devices, result.cuts,
                                 old_of=list(range(len(self.clients))))
        return list(self.cuts)

    def _migrate_client_params(self, net: str,
                               new_groups: Sequence[ProfileGroup],
                               old_of: Sequence[int]) -> Dict[str, Any]:
        """Client-side param migration for a re-cut/churn rebuild.
        ``old_of[new_cid]`` is the old global client id (-1 = joiner).

        Policy: a layer the client already owned keeps its trained
        copy; a layer it newly owns takes the server's trained copy
        (the server held it — that client delegated it until now);
        joiners take server copies too, falling back to the old
        population mean for layers the old server never held (such a
        layer was owned by *every* old client, so the mean exists)."""
        defs = GEN_LAYER_DEFS if net == "G" else DISC_LAYER_DEFS
        n = len(defs)
        old_server = self.state[net]["server"]
        old_client = self.state[net]["client"]
        old_owned = {g.name: set(client_owned_layers(layer_pair(g.cut, net),
                                                     n))
                     for g in self.groups}
        old_loc = {}
        for g in self.groups:
            for pos, cid in enumerate(g.client_ids):
                old_loc[cid] = (g.name, pos)
        mean_cache: Dict[int, Any] = {}

        def pop_mean(l: int):
            if l not in mean_cache:
                stacks = [old_client[g.name][str(l)] for g in self.groups
                          if l in old_owned[g.name]]
                mean_cache[l] = jax.tree_util.tree_map(
                    lambda *xs: jnp.concatenate(xs, 0).mean(0), *stacks)
            return mean_cache[l]

        def one_client(old_cid: int, l: int):
            if old_cid >= 0:
                gname, pos = old_loc[old_cid]
                if l in old_owned[gname]:
                    return jax.tree_util.tree_map(
                        lambda x: x[pos], old_client[gname][str(l)])
            if str(l) in old_server:
                return old_server[str(l)]
            return pop_mean(l)

        out = {}
        for g in new_groups:
            owned = client_owned_layers(layer_pair(g.cut, net), n)
            out[g.name] = {
                str(l): jax.tree_util.tree_map(
                    lambda *xs: jnp.stack(xs, 0),
                    *[one_client(old_of[cid], l) for cid in g.client_ids])
                for l in owned}
        return out

    def _migrate_server_params(self, net: str,
                               new_groups: Sequence[ProfileGroup]
                               ) -> Dict[str, Any]:
        """Server span under the new cuts: layers the server already
        held keep their trained copies; a layer newly delegated to the
        server was owned by every old client that hosted it, so it
        starts from the mean of those trained client copies."""
        defs = GEN_LAYER_DEFS if net == "G" else DISC_LAYER_DEFS
        n = len(defs)
        old_server = self.state[net]["server"]
        old_client = self.state[net]["client"]
        new_server = {}
        for l in server_union_span(new_groups, net, n):
            if str(l) in old_server:
                new_server[str(l)] = old_server[str(l)]
                continue
            stacks = [old_client[g.name][str(l)] for g in self.groups
                      if l in set(client_owned_layers(
                          layer_pair(g.cut, net), n))]
            new_server[str(l)] = jax.tree_util.tree_map(
                lambda *xs: jnp.concatenate(xs, 0).mean(0), *stacks)
        return new_server

    def _rebuild_population(self, new_clients: Sequence[ClientSpec],
                            new_devices: Sequence[DeviceProfile],
                            new_cuts: Sequence[Cut],
                            old_of: Sequence[int]) -> None:
        """Swap in a new (clients, devices, cuts) population online:
        migrate params + optimizer + EMA, re-stage the dataset, rebuild
        the traced programs, and invalidate the FederationPlan cache
        (its keys embed the old group cuts/client ids)."""
        new_groups = group_by_profile(new_devices, new_cuts)
        K_new = len(new_clients)
        new_state: Dict[str, Any] = {}
        for net in ("G", "D"):
            new_state[net] = {
                "client": self._migrate_client_params(net, new_groups,
                                                      old_of),
                "server": self._migrate_server_params(net, new_groups)}
        # Adam moments restart for the migrated structure; the step
        # counter survives so schedules/beta-corrections don't rewind
        new_state["opt_g"] = self._opt_init_g(new_state["G"])
        new_state["opt_d"] = self._opt_init_d(new_state["D"])
        new_state["step"] = self.state["step"]
        # middle-activation EMA is global-client-indexed, so survivors
        # keep their rows under the new ids; joiners start from the
        # survivor mean (neutral for stage-3 clustering until their own
        # activations arrive)
        old_ema = np.asarray(self._mid_ema)
        new_ema = np.zeros((K_new, old_ema.shape[1]), np.float32)
        surv = [(i, o) for i, o in enumerate(old_of) if o >= 0]
        for i, o in surv:
            new_ema[i] = old_ema[o]
        if self._trained and surv and len(surv) < K_new:
            fill = old_ema[[o for _, o in surv]].mean(0)
            for i, o in enumerate(old_of):
                if o < 0:
                    new_ema[i] = fill
        self._mid_acc = {i: self._mid_acc[o] for i, o in enumerate(old_of)
                         if o >= 0 and o in self._mid_acc}

        self.clients = list(new_clients)
        self.devices = list(new_devices)
        self.cuts = list(new_cuts)
        self.groups = new_groups
        self.sizes = np.array([c.n for c in self.clients], np.int64)
        self.registry = ClientRegistry.from_clients(self.clients)
        if self.cfg.cohort_size is not None and not (
                1 <= self.cfg.cohort_size <= K_new):
            raise ValueError(
                f"cohort_size {self.cfg.cohort_size} out of range for "
                f"{K_new} registered clients after churn")
        self._dataset = stage_clients(self.groups, self.clients,
                                      mesh=self.fed_mesh)
        self.state = jax.tree_util.tree_map(self._put_replicated, new_state)
        self._mid_ema = self._put_replicated(jnp.asarray(new_ema))
        self._sizes_dev = self._put_replicated(
            jnp.asarray(self.sizes, jnp.float32))
        # every traced artifact keyed on the old grouping is stale
        self._fed_plans.clear()
        self._epoch_fns.clear()
        self._gen_fn = None
        self._step_core = self._build_step_core()
        self._step_fn = self._build_step()

    # -- generation for evaluation ------------------------------------------
    def generate(self, n_per_client_batch: int, labels: np.ndarray
                 ) -> Tuple[np.ndarray, np.ndarray]:
        """Generate len(labels) images by cycling clients. labels [N]."""
        if self._gen_fn is None:
            spans.count("programs_built.generate")
            build = (build_net_apply if self.cfg.split_program
                     else build_net_apply_legacy)
            gen_apply = build(self.groups, "G")

            def gen(state, z, y):
                out, _, _, _ = gen_apply(state["G"]["client"],
                                         state["G"]["server"], {
                    g.name: (z[g.name], y[g.name]) for g in self.groups},
                    False)
                return out
            self._gen_fn = jax.jit(gen)
        labels = np.asarray(labels)
        n_total = len(labels)
        imgs_all, labels_all = [], []
        pos = 0
        while pos < n_total:
            # each group consumes the next contiguous label chunk (a
            # shared cursor, not a shared window — groups must not
            # recycle each other's labels); only the final partial
            # chunk pads, and the padding is sliced off below.
            z, y = {}, {}
            cursor = pos
            for g in self.groups:
                need = min(n_per_client_batch, max(1, (n_total - pos)
                                                   // max(1, g.size)))
                cnt = g.size * need
                chunk = labels[cursor:cursor + cnt]
                if chunk.shape[0] < cnt:
                    chunk = np.concatenate(
                        [chunk, np.zeros(cnt - chunk.shape[0],
                                         labels.dtype)])
                cursor += cnt
                z[g.name] = self._rng.normal(0, 1, (g.size, need, Z_DIM)
                                             ).astype(np.float32)
                y[g.name] = chunk.reshape(g.size, need).astype(np.int32)
            out = self._gen_fn(self.state, z, y)
            for g in self.groups:
                arr = np.asarray(out[g.name]).reshape(-1, 28, 28, 1)
                imgs_all.append(arr)
                labels_all.append(y[g.name].reshape(-1))
            pos = cursor
        imgs = np.concatenate(imgs_all)[:n_total]
        labs = np.concatenate(labels_all)[:n_total]
        return imgs, labs


def _merge_bn(updated_params, bn_params):
    """Take optimizer-updated learnables but BatchNorm running stats
    (keys 'mean'/'var') from the forward pass."""
    flat_u = jax.tree_util.tree_flatten_with_path(updated_params)[0]
    flat_b = {jax.tree_util.keystr(p): v for p, v in
              jax.tree_util.tree_flatten_with_path(bn_params)[0]}
    out = []
    for path, val in flat_u:
        ks = jax.tree_util.keystr(path)
        if ks.endswith("['mean']") or ks.endswith("['var']"):
            out.append(flat_b.get(ks, val))
        else:
            out.append(val)
    treedef = jax.tree_util.tree_structure(updated_params)
    return jax.tree_util.tree_unflatten(treedef, out)
