"""Serving launcher: batched prefill + autoregressive decode for any
assigned --arch (smoke-scale on CPU).

  PYTHONPATH=src python -m repro.launch.serve --arch xlstm-350m --smoke \
      --batch 4 --prompt-len 64 --gen 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.core.federation import donate_default
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as T


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    key = jax.random.PRNGKey(args.seed)
    params = T.init_lm(key, cfg)
    rng = np.random.default_rng(args.seed)
    kwargs = {}
    if cfg.is_encoder_decoder:
        kwargs["enc_frames"] = jnp.asarray(rng.normal(
            0, 1, (args.batch, cfg.num_prefix_embeds, cfg.d_model)),
            dtype=jnp.float32)
    elif cfg.frontend == "vision":
        kwargs["prefix_embeds"] = jnp.asarray(rng.normal(
            0, 1, (args.batch, cfg.num_prefix_embeds, cfg.d_model)),
            dtype=jnp.float32)
    prompts = jnp.asarray(rng.integers(0, cfg.vocab,
                                       (args.batch, args.prompt_len)),
                          dtype=jnp.int32)

    t0 = time.time()
    prefill = jax.jit(lambda p, t: T.prefill(
        cfg, p, t, margin=args.gen + 16, **kwargs))
    logits, cache = prefill(params, prompts)
    jax.block_until_ready(logits)
    t_prefill = time.time() - t0
    print(f"[serve] prefill {args.batch}x{args.prompt_len}: "
          f"{t_prefill:.2f}s ({args.batch * args.prompt_len / t_prefill:.0f} "
          f"tok/s)")

    # The whole greedy/sampled decode tail is one jitted lax.scan: the
    # old loop round-tripped to host every token (np.asarray per step)
    # and re-dispatched decode_step gen-1 times. The KV cache rides the
    # scan carry and is donated into the call where the backend can
    # alias it (donate_default: TPU/GPU only — CPU XLA ignores it).
    def decode_tail(p, cur0, cache, key):
        def body(carry, _):
            cur, cache, key = carry
            logits, cache = T.decode_step(cfg, p, cur, cache)
            if args.temperature > 0:
                key, sub = jax.random.split(key)
                nxt = jax.random.categorical(
                    sub, logits / args.temperature).astype(jnp.int32)
            else:
                nxt = jnp.argmax(logits, -1).astype(jnp.int32)
            return (nxt, cache, key), nxt

        _, ys = jax.lax.scan(body, (cur0, cache, key), None,
                             length=args.gen - 1)
        return jnp.concatenate([cur0[:, None], ys.T], axis=1)

    decode_fn = jax.jit(
        decode_tail, donate_argnums=(2,) if donate_default() else ())
    cur0 = jnp.argmax(logits, -1).astype(jnp.int32)
    t0 = time.time()
    toks_dev = decode_fn(params, cur0, cache, key)
    jax.block_until_ready(toks_dev)
    t_dec = time.time() - t0
    toks = np.asarray(toks_dev)
    print(f"[serve] decoded {args.gen} tokens/seq: {t_dec:.2f}s "
          f"({args.batch * max(args.gen - 1, 1) / max(t_dec, 1e-9):.0f} tok/s)")
    print(f"[serve] sample continuation (seq 0): {toks[0][:16].tolist()}")


if __name__ == "__main__":
    main()
