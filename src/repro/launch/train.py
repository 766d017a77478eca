"""Training launcher.

Two modes:
  * huscf (default for --arch huscf-gan): the paper's split-federated
    GAN over a heterogeneous client population.
  * centralized: standard data+tensor-parallel LM training on synthetic
    token streams for any assigned --arch (smoke-scale on CPU; the full
    configs are exercised via dryrun.py).

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch huscf-gan \
      --scenario 2dom_noniid --clients 8 --epochs 4
  PYTHONPATH=src python -m repro.launch.train --arch granite-3-2b \
      --smoke --steps 20
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.compile_cache import enable_compile_cache


def train_huscf_gan(args) -> None:
    from repro.core import HuSCFConfig, HuSCFTrainer, PAPER_DEVICES
    from repro.data import build_scenario
    from repro.checkpoint import save_checkpoint
    from repro.launch.mesh import make_federation_mesh

    clients = build_scenario(args.scenario, num_clients=args.clients,
                             base_size=args.base_size, seed=args.seed)
    devices = [PAPER_DEVICES[i % 7] for i in range(args.clients)]
    # one mesh for the whole trainer: the device-resident dataset rows
    # and the federation buffer shard over the same client axis
    # (make_federation_mesh is the single factory for both; a 1-device
    # pool runs the unsharded path).
    n_dev = args.fed_devices or jax.device_count()
    fed_mesh = make_federation_mesh(n_dev) if n_dev > 1 else None
    tr = HuSCFTrainer(clients, devices,
                      config=HuSCFConfig(batch=args.batch,
                                         federate_every=args.federate_every,
                                         seed=args.seed,
                                         use_kernel=args.use_kernel,
                                         fused_epoch=not args.per_step,
                                         cohort_size=args.cohort,
                                         agg_chunk=args.agg_chunk,
                                         reoptimize_every=args.reoptimize_every),
                      fed_mesh=fed_mesh)
    agg = (f"chunked({args.agg_chunk})" if args.agg_chunk else "dense")
    part = (f"cohort {args.cohort}/{args.clients}" if args.cohort
            else "full participation")
    reopt = (f", re-cut every {args.reoptimize_every} rounds"
             if args.reoptimize_every else "")
    print(f"[train] GA latency model: {tr.ga_latency:.2f}s/iter, "
          f"{len(tr.groups)} profile groups, "
          f"mesh={n_dev if fed_mesh is not None else 1}dev, "
          f"{'per-step' if args.per_step else 'fused'} epochs, "
          f"{agg} aggregation, {part}{reopt}")
    for ep in range(args.epochs):
        t0 = time.time()
        m = tr.train_epoch()
        print(f"[train] epoch {ep + 1}: loss_d={m['loss_d']:.3f} "
              f"loss_g={m['loss_g']:.3f} ({time.time() - t0:.1f}s)",
              flush=True)
    if args.ckpt:
        save_checkpoint(args.ckpt, tr.state, step=tr.epoch)
        print(f"[train] checkpoint -> {args.ckpt}")


def train_lm(args) -> None:
    from repro.configs import get_config, get_smoke_config
    from repro.data import lm_batches
    from repro.models import transformer as T
    from repro.optim import adam, warmup_cosine
    from repro.checkpoint import save_checkpoint

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    key = jax.random.PRNGKey(args.seed)
    params = T.init_lm(key, cfg)
    opt = adam(warmup_cosine(args.lr, 10, max(args.steps, 20)),
               grad_clip=1.0)
    train_step, opt_init = T.make_train_step(cfg, opt)
    opt_state = opt_init(params)
    step = jax.jit(train_step)
    gen = lm_batches(cfg.vocab, args.batch, args.seq, seed=args.seed)
    for i in range(args.steps):
        toks, labs = next(gen)
        batch = {"tokens": jnp.asarray(toks), "labels": jnp.asarray(labs)}
        if cfg.frontend == "vision":
            rng = np.random.default_rng(i)
            batch["prefix_embeds"] = jnp.asarray(rng.normal(
                0, 1, (args.batch, cfg.num_prefix_embeds, cfg.d_model)),
                dtype=jnp.float32)
        if cfg.is_encoder_decoder:
            rng = np.random.default_rng(i)
            batch["enc_frames"] = jnp.asarray(rng.normal(
                0, 1, (args.batch, cfg.num_prefix_embeds, cfg.d_model)),
                dtype=jnp.float32)
            batch["tokens"] = batch["tokens"][:, : cfg.max_target_len]
            batch["labels"] = batch["labels"][:, : cfg.max_target_len]
        t0 = time.time()
        params, opt_state, m = step(params, opt_state, batch)
        if i % max(1, args.steps // 10) == 0 or i == args.steps - 1:
            print(f"[train] step {i}: loss={float(m['loss']):.4f} "
                  f"({time.time() - t0:.2f}s)", flush=True)
    if args.ckpt:
        save_checkpoint(args.ckpt, params, step=args.steps)
        print(f"[train] checkpoint -> {args.ckpt}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--scenario", default="2dom_noniid")
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--base-size", type=int, default=128)
    ap.add_argument("--epochs", type=int, default=4)
    ap.add_argument("--federate-every", type=int, default=2)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-friendly)")
    ap.add_argument("--use-kernel", action="store_true",
                    help="Pallas weighted_agg for federation")
    ap.add_argument("--fed-devices", type=int, default=None,
                    help="client-axis mesh size shared by the training "
                         "step and federation (default: every visible "
                         "device; 1 disables sharding)")
    ap.add_argument("--per-step", action="store_true",
                    help="per-step oracle loop instead of scan-fused "
                         "device-resident epochs")
    ap.add_argument("--cohort", type=int, default=None,
                    help="sample this many clients per federation round "
                         "(default: full participation)")
    ap.add_argument("--agg-chunk", type=int, default=None,
                    help="stream aggregation in client chunks of this "
                         "size instead of the dense [K, D] buffer")
    ap.add_argument("--reoptimize-every", type=int, default=None,
                    help="re-run the fused GA cut search every N "
                         "federation rounds; strictly better cuts "
                         "regroup the population online (default: "
                         "static cuts)")
    ap.add_argument("--ckpt", default=None)
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.arch == "huscf-gan":
        train_huscf_gan(args)
    else:
        train_lm(args)


if __name__ == "__main__":
    main()
