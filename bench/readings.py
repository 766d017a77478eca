#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from (not run by the
benchmark's own runs).

    python3 bench/readings.py --workload <cell> --seeds 1,2,3 --mode program [--look 2]
    python3 bench/readings.py --workload <cell> --seeds 1,2,3 --mode control
    python3 bench/readings.py --workload <cell> --seeds 1,2,3 --mode half_batch
    python3 bench/readings.py --workload <cell> --seeds 1,2,3 --mode fedavg

``program``: the harness run for each seed, in one process, with a
window of one federation cycle; prints the numbers compared and
whether the run is correct. With ``--look n`` the first ``n`` seeds
also run the reference at the configuration's own precision (float32
at XLA's default) and in bfloat16, and print the worst leaves of every
pair among program, those two and the float32 HIGHEST reference.
``control``: the reference computed one precision below the
configuration (bfloat16 step, ``Precision.HIGH`` round) in the
program's place, against the reference.
``half_batch``: the reference with half of every client's batch left
out, the mean taken over the rest, in the program's place.
``fedavg``: the reference's round with dataset shares in place of the
Eq.-15 weights, in the program's place.
One JSON line per seed on standard output.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
if sys.path and os.path.abspath(sys.path[0]) == _HERE:
    sys.path.pop(0)
sys.path[:0] = [os.path.dirname(_HERE), os.path.join(os.path.dirname(_HERE), "src")]

from bench import compare, reference, run, spec  # noqa: E402


def _setting(cell: dict, seed: int):
    cfg = spec.read_json(spec.config_path(cell["config"]))
    traffic = spec.read_json(spec.traffic_path(cell["traffic"]))
    clients = run.clients_of(spec.load_model(cfg["model"]), cfg, traffic)
    groups = reference.groups_of(cfg)
    seed31 = seed % run.SEED_MOD
    return (cfg, clients, groups, seed31,
            run.steps_per_epoch(clients, cfg["batch"]),
            run.sampled_rows(groups, seed31))


def numbers_for(mode: str, cell: dict, seed: int) -> dict:
    import jax
    import jax.numpy as jnp
    from jax import lax
    cfg, clients, groups, seed31, n_steps, rows = _setting(cell, seed)
    ref_r = run.reference_round(jax, cfg, groups, clients, seed31, rows)
    if mode == "fedavg":
        out = compare.round_numbers(run.reference_round(
            jax, cfg, groups, clients, seed31, rows, fedavg=True), ref_r)
        return out
    ref_t = run.reference_train(jax, cfg, groups, clients, seed31, n_steps)
    if mode == "control":
        nx = reference.Numerics(jnp.bfloat16, lax.Precision.DEFAULT)
        alt_t = run.reference_train(jax, cfg, groups, clients, seed31,
                                    n_steps, nx=nx)
        alt_r = run.reference_round(jax, cfg, groups, clients, seed31, rows,
                                    precision=lax.Precision.HIGH)
    else:
        alt_t = run.reference_train(jax, cfg, groups, clients, seed31,
                                    n_steps, keep=cfg["batch"] // 2)
        alt_r = ref_r
    out = compare.train_numbers(alt_t, ref_t)
    out.update(compare.round_numbers(alt_r, ref_r))
    return out


def look(cell: dict, seed: int, prog: dict, ref: dict) -> dict:
    """Worst leaves of each pair among the program, the reference at
    HIGHEST, at the configuration's own precision and in bfloat16."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    cfg, clients, groups, seed31, n_steps, _ = _setting(cell, seed)
    runs = {"program": prog, "highest": ref}
    for name, nx in (("default", reference.Numerics(jnp.float32,
                                                    lax.Precision.DEFAULT)),
                     ("bf16", reference.Numerics(jnp.bfloat16,
                                                 lax.Precision.DEFAULT))):
        runs[name] = run.reference_train(jax, cfg, groups, clients, seed31,
                                         n_steps, nx=nx)
    names = prog["names"]
    out = {}
    for a, b in (("program", "highest"), ("default", "highest"),
                 ("bf16", "highest"), ("program", "default")):
        keep = compare.moved(runs[b]["mu"])
        pair = {"numbers": compare.train_numbers(runs[a], runs[b])}
        for key in ("mu", "rms", "delta"):
            pair["worst_" + key] = [
                [n, p, r] for n, p, r in compare.worst(
                    runs[a][key], runs[b][key], names,
                    keep if key == "delta" else None, top=3)]
        out[f"{a}_vs_{b}"] = pair
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--mode", choices=("program", "control", "half_batch",
                                      "fedavg"), required=True)
    p.add_argument("--look", type=int, default=0)
    args = p.parse_args(argv)
    cell = spec.cell(spec.load(), args.workload)
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        line = {"mode": args.mode, "workload": args.workload, "seed": seed}
        if args.mode == "program":
            res = run.run(run.parse(["--workload", args.workload, "--seed",
                                     str(seed), "--seconds", "0"]))
            line.update(correct=res["correct"], numbers=res["numbers"])
            if i < args.look:
                line["look"] = look(cell, seed, res["train"]["program"],
                                    res["train"]["reference"])
        else:
            line["numbers"] = numbers_for(args.mode, cell, seed)
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
