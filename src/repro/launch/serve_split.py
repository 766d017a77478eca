"""Executable U-shaped split-serving engine (DESIGN.md §SplitProgram).

Training, the analytic latency model, and serving now execute ONE
compiled `SplitProgram` (core/segments.py). This launcher is the third
consumer: it serves inference requests over the trained split cGAN with
the exact schedule the paper trains under — each request's head runs on
its client's personal weights, the server batches every cut's uplinked
activations per layer (the Eq. 7 join), and the tail returns to the
client — instead of gathering full models to one place (which the
paper's data-sharing constraints forbid: clients never hold the middle
layers, the server never holds the heads/tails).

Engine mechanics:

* Requests are grouped by the owning client's profile group (= cut).
  Each group's request rows pad to a power-of-two bucket
  (`splitting.bucket_size`), so a churning request mix lands on a small
  set of compiled shapes: the jitted executor is cached per
  (active groups, buckets) signature and replayed across calls.
* The executor IS `segments.make_apply` in eval mode over the
  subprogram compiled from the *active* groups only — if no request
  touches a cut, its join barrier and (possibly) server layers drop out
  of the schedule, exactly as `compile_split_program` derives.
  Eval-mode BatchNorm is per-element, so bucket-padding rows cannot
  perturb valid rows.
* The analytic side of the same program (`program_forward_latency`,
  Eq. 7 + Eq. 9 with no backward) predicts the serving latency for the
  executed cohort — `counts=` carries the padded per-cut request
  multiplicities — which `benchmarks/serve_bench.py` compares against
  measured wall-clock per profile mix.

The LM decode tail (`--mode lm`) applies the same U-shape to an
autoregressive transformer: client-owned bottom/top blocks wrap a
server trunk, the server trunk's prefill runs the Pallas
memory-efficient attention kernel (`ops.mem_attention`) and its decode
runs `ops.flash_decode`, and the whole generation loop is one jitted
`lax.scan` (no host round-trips, same shape as launch/serve.py).

  PYTHONPATH=src python -m repro.launch.serve_split --mode gan \
      --mix edge-heavy --requests 24
  PYTHONPATH=src python -m repro.launch.serve_split --mode lm \
      --batch 2 --prompt-len 32 --gen 16
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Any, Dict, List, Mapping, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.latency import Cut, DeviceProfile, PAPER_DEVICES, PAPER_SERVER
from repro.core.segments import (SplitProgram, compile_split_program,
                                 make_apply, program_forward_latency)
from repro.core.splitting import (ProfileGroup, bucket_size,
                                  group_by_profile, server_union_span)
from repro.kernels import ops, ref
from repro.launch.compile_cache import enable_compile_cache
from repro.models import attention as A
from repro.models import nn
from repro.models.gan import (DISC_LAYER_DEFS, GEN_LAYER_DEFS, Z_DIM,
                              generator_forward)
from repro.sharding.policy import (ShardingPolicy, activation_sharding,
                                   cohort_axes)

Array = jnp.ndarray


# ---------------------------------------------------------------------------
# GAN split serving
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ServeRequest:
    """One generation request: which client it belongs to (that client's
    personal head/tail weights serve it) plus the conditional inputs."""
    client_id: int
    z: np.ndarray          # [Z_DIM] latent
    y: int                 # class label


class SplitGanEngine:
    """Batched split-cGAN inference over a heterogeneous population.

    ``client_params`` / ``server_params`` use the trainer's layout
    (``state["G"]["client"]`` / ``["server"]``): per-group dicts of
    client-stacked layer trees, and the server's union-span layers.
    """

    def __init__(self, groups: Sequence[ProfileGroup],
                 client_params: Dict[str, Dict[str, Any]],
                 server_params: Dict[str, Any], net: str = "G",
                 mesh=None, policy: Optional[ShardingPolicy] = None):
        self.groups = list(groups)
        self.net = net
        self.client_params = client_params
        self.server_params = server_params
        self.mesh = mesh
        self.policy = policy or ShardingPolicy()
        self._row_of: Dict[int, Tuple[str, int]] = {}
        for g in self.groups:
            for row, cid in enumerate(g.client_ids):
                self._row_of[cid] = (g.name, row)
        self._group_of = {g.name: g for g in self.groups}
        self._programs: Dict[Tuple[str, ...], SplitProgram] = {}
        self._fns: Dict[Tuple, Any] = {}

    # -- program / executor caches -----------------------------------------
    def program_for(self, active: Tuple[str, ...]) -> SplitProgram:
        """Subprogram over the active groups only: absent cuts drop
        their join barriers (and possibly whole server layers) from the
        schedule — serving executes/bills only work that is present."""
        if active not in self._programs:
            subset = [self._group_of[n] for n in active]
            self._programs[active] = compile_split_program(subset, self.net)
        return self._programs[active]

    def _fn(self, active: Tuple[str, ...], buckets: Tuple[int, ...]):
        key = (active, buckets)
        if key in self._fns:
            return self._fns[key]
        apply = make_apply(self.program_for(active))

        def run(client_params, server_params, rows, z, y):
            # gather each request's personal client weights by row index
            # (traced — one compiled program serves any member mix)
            gathered = {
                g: jax.tree_util.tree_map(
                    lambda x: jnp.take(x, rows[g], axis=0),
                    client_params[g])
                for g in active}
            inputs = {g: (z[g][:, None, :], y[g][:, None]) for g in active}
            out, _, _, _ = apply(gathered, server_params, inputs, False)
            return {g: out[g][:, 0] for g in active}

        fn = jax.jit(run)
        self._fns[key] = fn
        return fn

    # -- serving -------------------------------------------------------------
    def plan(self, requests: Sequence[ServeRequest]
             ) -> Tuple[Tuple[str, ...], Tuple[int, ...], Dict[str, List[int]]]:
        """(active group names, buckets, per-group request indices)."""
        per: Dict[str, List[int]] = {}
        for i, r in enumerate(requests):
            gname, _ = self._row_of[r.client_id]
            per.setdefault(gname, []).append(i)
        active = tuple(g.name for g in self.groups if g.name in per)
        buckets = tuple(bucket_size(len(per[g])) for g in active)
        return active, buckets, per

    def serve(self, requests: Sequence[ServeRequest]) -> np.ndarray:
        """Run the cohort through the U-shaped program; [N, 28, 28, 1]
        images in request order."""
        active, buckets, per = self.plan(requests)
        fn = self._fn(active, buckets)
        rows, z, y = {}, {}, {}
        for g, bkt in zip(active, buckets):
            idxs = per[g]
            n = len(idxs)
            r = np.zeros(bkt, np.int32)
            zz = np.zeros((bkt, Z_DIM), np.float32)
            yy = np.zeros(bkt, np.int32)
            for j, i in enumerate(idxs):
                req = requests[i]
                r[j] = self._row_of[req.client_id][1]
                zz[j] = req.z
                yy[j] = req.y
            # bucket-padding rows replay request 0's operands (row 0 /
            # zeros) — eval-mode BN is per-element so they cannot touch
            # valid rows; they are sliced off below.
            rows[g] = jnp.asarray(r)
            z[g] = jnp.asarray(zz)
            y[g] = jnp.asarray(yy)
        axes = cohort_axes(self.mesh, buckets) if self.mesh is not None \
            else None
        if axes is not None:
            with activation_sharding(self.mesh, self.policy):
                out = fn(self.client_params, self.server_params, rows, z, y)
        else:
            out = fn(self.client_params, self.server_params, rows, z, y)
        out = {g: np.asarray(v) for g, v in out.items()}
        imgs = np.zeros((len(requests),) + out[active[0]].shape[1:],
                        out[active[0]].dtype)
        for g in active:
            for j, i in enumerate(per[g]):
                imgs[i] = out[g][j]
        return imgs

    def predict_latency(self, requests: Sequence[ServeRequest],
                        server: DeviceProfile = PAPER_SERVER,
                        padded: bool = True) -> float:
        """Analytic Eq. 7/9 forward latency for this cohort from the
        same program the executor runs. ``padded=True`` bills the
        bucket-padded multiplicities (what actually executes);
        ``False`` bills only the real requests (the padding overhead is
        the ratio of the two)."""
        active, buckets, per = self.plan(requests)
        program = self.program_for(active)
        profiles = {g: self._group_of[g].profile for g in active}
        counts = {g: float(b) if padded else float(len(per[g]))
                  for g, b in zip(active, buckets)}
        return program_forward_latency(program, profiles, server,
                                       batch=1, counts=counts)


def monolithic_forward(groups: Sequence[ProfileGroup],
                       client: Dict[str, Any], server: Dict[str, Any],
                       req: ServeRequest) -> np.ndarray:
    """The engine's oracle: assemble the requesting client's full
    generator (its personal head/tail rows + the server's middle
    layers) and run it unsplit on one request -> [28, 28, 1]."""
    g = next(gg for gg in groups if req.client_id in gg.client_ids)
    row = g.client_ids.index(req.client_id)
    h, t = g.cut.g_h, g.cut.g_t
    params = []
    for l in range(len(GEN_LAYER_DEFS)):
        if l < h or l >= t:
            params.append(jax.tree_util.tree_map(
                lambda x: x[row], client[g.name][str(l)]))
        else:
            params.append(server[str(l)])
    z = jnp.asarray(req.z)[None]
    y = jnp.asarray([req.y], jnp.int32)
    img, _ = generator_forward(params, z, y, train=False)
    return np.asarray(img[0])


def init_gan_serving_state(key, groups: Sequence[ProfileGroup],
                           net: str = "G"
                           ) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """Random split-cGAN weights in the trainer's state layout (the
    engine normally loads a trained `HuSCFTrainer` state; the launcher
    and benchmarks serve from random weights — latency is
    weight-independent)."""
    defs = GEN_LAYER_DEFS if net == "G" else DISC_LAYER_DEFS
    n = len(defs)
    key, ks = jax.random.split(key)
    server = {}
    for l in server_union_span(groups, net, n):
        ks, sub = jax.random.split(ks)
        server[str(l)] = defs[l][0](sub, jnp.float32)
    client = {}
    for g in groups:
        key, sub = jax.random.split(key)
        h, t = (g.cut.g_h, g.cut.g_t) if net == "G" else (g.cut.d_h, g.cut.d_t)
        keys = jax.random.split(sub, g.size)
        client[g.name] = {
            str(l): jax.vmap(lambda kk, l=l: defs[l][0](kk, jnp.float32))(keys)
            for l in list(range(h)) + list(range(t, n))}
    return client, server


# Two heterogeneous profile mixes (paper Table 4 devices): name ->
# list of (device, cut, n_clients). Weak devices delegate almost
# everything (head 1 / tail 4); strong devices keep two layers per side.
SERVE_MIXES: Dict[str, List[Tuple[DeviceProfile, Cut, int]]] = {
    "edge-heavy": [
        (PAPER_DEVICES[0], Cut(1, 4, 1, 4), 4),   # device1, weakest
        (PAPER_DEVICES[4], Cut(1, 4, 1, 4), 3),   # device5
        (PAPER_DEVICES[1], Cut(2, 3, 2, 3), 2),   # device2
    ],
    "balanced": [
        (PAPER_DEVICES[1], Cut(1, 4, 1, 4), 2),   # device2
        (PAPER_DEVICES[3], Cut(2, 4, 1, 4), 2),   # device4
        (PAPER_DEVICES[2], Cut(2, 3, 2, 3), 2),   # device3
        (PAPER_DEVICES[6], Cut(2, 3, 2, 3), 2),   # device7
    ],
}


def build_mix(mix: str) -> List[ProfileGroup]:
    devices, cuts = [], []
    for dev, cut, n in SERVE_MIXES[mix]:
        devices += [dev] * n
        cuts += [cut] * n
    return group_by_profile(devices, cuts)


# ---------------------------------------------------------------------------
# LM split decode tail — U-shaped transformer serving on the Pallas kernels
# ---------------------------------------------------------------------------

class SplitLMConfig(NamedTuple):
    """A compact decoder-only LM split client-head / server-trunk /
    client-tail: blocks [0, head_end) and [tail_start, n_layers) stay on
    the client, [head_end, tail_start) run on the server with the
    Pallas attention kernels."""
    vocab: int = 256
    d_model: int = 64
    n_layers: int = 4
    n_heads: int = 4
    n_kv: int = 2
    head_dim: int = 16
    d_ff: int = 128
    head_end: int = 1
    tail_start: int = 3
    s_max: int = 160

    def is_server(self, l: int) -> bool:
        return self.head_end <= l < self.tail_start


def _lm_block_init(key, cfg: SplitLMConfig):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "ln1": nn.rmsnorm_init(cfg.d_model),
        "attn": A.attn_init(k1, cfg.d_model, cfg.n_heads, cfg.n_kv,
                            cfg.head_dim),
        "ln2": nn.rmsnorm_init(cfg.d_model),
        "wi": nn.dense_init(k2, cfg.d_model, cfg.d_ff),
        "wo": nn.dense_init(k3, cfg.d_ff, cfg.d_model),
    }


def init_split_lm(key, cfg: SplitLMConfig):
    keys = jax.random.split(key, cfg.n_layers + 1)
    embed = jax.random.normal(keys[0], (cfg.vocab, cfg.d_model),
                              jnp.float32) * 0.02
    return {"embed": embed,
            "blocks": [_lm_block_init(keys[l + 1], cfg)
                       for l in range(cfg.n_layers)],
            "norm_f": nn.rmsnorm_init(cfg.d_model)}


def _lm_mlp(p, x):
    return nn.dense_apply(p["wo"], jax.nn.gelu(nn.dense_apply(p["wi"], x)))


def _lm_block_prefill(cfg: SplitLMConfig, p, x, positions, lens,
                      server: bool):
    """One block over the whole prompt; returns (y, (k, v)) for the
    cache. Server blocks run the Pallas memory-efficient kernel; client
    blocks (tiny head/tail segments) use the dense reference."""
    h = nn.rmsnorm_apply(p["ln1"], x)
    q, k, v = A.qkv_proj(p["attn"], h)
    q = A.apply_rope(q, positions)
    k = A.apply_rope(k, positions)
    if server:
        o = ops.mem_attention(q, k, v, lens, causal=True)
    else:
        o = ref.mem_attention_ref(q, k, v, lens, causal=True)
    x = x + A.out_proj(p["attn"], o)
    return x + _lm_mlp(p, nn.rmsnorm_apply(p["ln2"], x)), (k, v)


def _lm_block_decode(cfg: SplitLMConfig, p, x, ck, cv, t, server: bool):
    """One block for one token at traced position ``t``; appends to the
    [B, s_max, KV, hd] caches in place (dynamic_update_slice on the
    scan carry). Server blocks attend with the flash_decode kernel."""
    h = nn.rmsnorm_apply(p["ln1"], x)
    q, k, v = A.qkv_proj(p["attn"], h)              # [B, 1, N, hd]
    pos = t[None] if t.ndim == 0 else t
    q = A.apply_rope(q, pos)
    k = A.apply_rope(k, pos)
    ck = jax.lax.dynamic_update_slice(ck, k, (0, t, 0, 0))
    cv = jax.lax.dynamic_update_slice(cv, v, (0, t, 0, 0))
    if server:
        o = ops.flash_decode(q[:, 0], ck, cv, t + 1)[:, None]
    else:
        o = ref.flash_decode_ref(q[:, 0], ck, cv, t + 1)[:, None]
    x = x + A.out_proj(p["attn"], o)
    return x + _lm_mlp(p, nn.rmsnorm_apply(p["ln2"], x)), ck, cv


def split_lm_prefill(cfg: SplitLMConfig, params, tokens):
    """U-shaped prefill: client head blocks -> server trunk (Pallas
    mem_attention) -> client tail blocks. Returns (last-position logits
    [B, V], caches tuple)."""
    B, S = tokens.shape
    x = params["embed"][tokens]
    positions = jnp.arange(S)
    lens = jnp.full((B,), S, jnp.int32)
    caches = []
    for l, blk in enumerate(params["blocks"]):
        x, (k, v) = _lm_block_prefill(cfg, blk, x, positions, lens,
                                      cfg.is_server(l))
        ck = jnp.zeros((B, cfg.s_max, cfg.n_kv, cfg.head_dim), k.dtype)
        caches.append((jax.lax.dynamic_update_slice(ck, k, (0, 0, 0, 0)),
                       jax.lax.dynamic_update_slice(ck, v, (0, 0, 0, 0))))
    x = nn.rmsnorm_apply(params["norm_f"], x[:, -1])
    return x @ params["embed"].T, tuple(caches)


def _lm_step(cfg: SplitLMConfig, params, cur, caches, t):
    """One decode token through the U-shape; returns (logits [B, V],
    new caches)."""
    x = params["embed"][cur][:, None, :]
    new = []
    for l, blk in enumerate(params["blocks"]):
        ck, cv = caches[l]
        x, ck, cv = _lm_block_decode(cfg, blk, x, ck, cv, t,
                                     cfg.is_server(l))
        new.append((ck, cv))
    x = nn.rmsnorm_apply(params["norm_f"], x[:, 0])
    return x @ params["embed"].T, tuple(new)


def split_lm_generate(cfg: SplitLMConfig, params, tokens, n_gen: int):
    """Greedy generation, the whole decode tail one jitted lax.scan
    (serve.py's shape): returns [B, n_gen] generated tokens."""
    logits, caches = split_lm_prefill(cfg, params, tokens)
    cur0 = jnp.argmax(logits, -1).astype(jnp.int32)
    t0 = jnp.int32(tokens.shape[1])

    def body(carry, _):
        cur, caches, t = carry
        logits, caches = _lm_step(cfg, params, cur, caches, t)
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)
        return (nxt, caches, t + 1), nxt

    _, ys = jax.lax.scan(body, (cur0, caches, t0), None, length=n_gen - 1)
    return jnp.concatenate([cur0[:, None], ys.T], axis=1)


def split_lm_decode_logits(cfg: SplitLMConfig, params, tokens,
                           prompt_len: int):
    """Teacher-forced per-step decode logits for tokens[:, prompt_len:]
    (the engine-vs-monolithic equivalence probe): [B, S - prompt_len, V]
    where slot i holds the logits emitted *after* consuming
    tokens[:, prompt_len + i - 1] (slot 0 comes from the prefill)."""
    logits0, caches = split_lm_prefill(cfg, params, tokens[:, :prompt_len])
    t0 = jnp.int32(prompt_len)
    feed = tokens[:, prompt_len:-1].T                  # [S-p-1, B]

    def body(carry, cur):
        caches, t = carry
        logits, caches = _lm_step(cfg, params, cur, caches, t)
        return (caches, t + 1), logits

    _, ys = jax.lax.scan(body, (caches, t0), feed)
    return jnp.concatenate([logits0[:, None], ys.transpose(1, 0, 2)], axis=1)


def lm_reference_logits(cfg: SplitLMConfig, params, tokens):
    """Monolithic dense-attention forward over the full sequence (no
    split, no kernels, no caches) — the oracle the U-shaped engine must
    match: [B, S, V]."""
    B, S = tokens.shape
    x = params["embed"][tokens]
    positions = jnp.arange(S)
    lens = jnp.full((B,), S, jnp.int32)
    for blk in params["blocks"]:
        x, _ = _lm_block_prefill(cfg, blk, x, positions, lens, server=False)
    x = nn.rmsnorm_apply(params["norm_f"], x)
    return x @ params["embed"].T


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def _run_gan(args) -> None:
    groups = build_mix(args.mix)
    key = jax.random.PRNGKey(args.seed)
    client, server = init_gan_serving_state(key, groups)
    engine = SplitGanEngine(groups, client, server)
    rng = np.random.default_rng(args.seed)
    n_clients = sum(g.size for g in groups)
    reqs = [ServeRequest(int(rng.integers(0, n_clients)),
                         rng.normal(0, 1, Z_DIM).astype(np.float32),
                         int(rng.integers(0, 10)))
            for _ in range(args.requests)]
    active, buckets, per = engine.plan(reqs)
    print(f"[serve_split] mix={args.mix} requests={len(reqs)} "
          f"active_cuts={len(active)} buckets={list(buckets)}")
    engine.serve(reqs)                       # compile + warm
    t0 = time.time()
    for _ in range(args.iters):
        imgs = engine.serve(reqs)
    measured = (time.time() - t0) / args.iters
    analytic = engine.predict_latency(reqs)
    print(f"[serve_split] images={imgs.shape} "
          f"measured={measured * 1e3:.1f}ms analytic={analytic * 1e3:.2f}ms "
          f"ratio={measured / analytic:.2f}")


def _run_lm(args) -> None:
    cfg = SplitLMConfig(s_max=args.prompt_len + args.gen + 16)
    key = jax.random.PRNGKey(args.seed)
    params = init_split_lm(key, cfg)
    rng = np.random.default_rng(args.seed)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab,
                                      (args.batch, args.prompt_len)),
                         dtype=jnp.int32)
    gen = jax.jit(lambda p, t: split_lm_generate(cfg, p, t, args.gen))
    toks = np.asarray(jax.block_until_ready(gen(params, tokens)))  # warm
    t0 = time.time()
    toks = np.asarray(jax.block_until_ready(gen(params, tokens)))
    dt = time.time() - t0
    print(f"[serve_split] lm decode {args.batch}x{args.gen} "
          f"(server blocks [{cfg.head_end},{cfg.tail_start}) on Pallas "
          f"kernels): {dt:.2f}s "
          f"({args.batch * args.gen / max(dt, 1e-9):.0f} tok/s)")
    print(f"[serve_split] sample continuation (seq 0): "
          f"{toks[0][:16].tolist()}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--mode", choices=("gan", "lm"), default="gan")
    ap.add_argument("--mix", choices=sorted(SERVE_MIXES), default="edge-heavy")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.mode == "gan":
        _run_gan(args)
    else:
        _run_lm(args)


if __name__ == "__main__":
    main()
