"""The comparison that decides ``correct`` fails what it must, at a
size a CPU test run can hold (8 clients in 2 groups, batch 4, 4-step
epochs, two planted clusters of 4), with the limits of
``cgan28-paper100``:

* the control: the reference computed one precision below the
  configuration (bfloat16 for the step, three bfloat16 passes for the
  round, written out since XLA:CPU ignores ``Precision.HIGH``) in the
  program's place;
* the harness run end to end, without its look for a chip, with the
  timed path broken underneath: a step that returns its state
  unchanged, half of each batch left out, an aggregated answer altered
  where it is produced, the round's Eq.-15 weights altered where they
  are produced.
"""
import jax
import jax.numpy as jnp
import pytest
from jax import lax

from bench import compare, reference, run, spec
from repro.core import huscf

LIMITS = spec.read_json(spec.config_path("cgan28-paper100"))["limits"]
SEED = 3000000001                  # 2 blobs in the planted activations


def _tiny():
    cfg = spec.read_json(spec.config_path("cgan28-paper100"))
    cfg.update(num_clients=8, device_cycle=["device1", "device2"], batch=4)
    traffic = dict(spec.read_json(spec.traffic_path("e1")), base_size=16)
    return cfg, traffic


def _run():
    cfg, traffic = _tiny()
    args = run.parse(["--workload", "tiny", "--seed", str(SEED),
                      "--seconds", "1"])
    cell = {"name": "tiny", "config": "tiny", "traffic": "e1", "chips": 1}
    return run.run(args, require_chip=False, cell=cell, cfg=cfg,
                   traffic=traffic)


def test_sound_run_is_correct():
    result = _run()
    assert result["correct"], result["checked"]


def test_control_fails():
    cfg, traffic = _tiny()
    clients = run.clients_of(spec.load_model(cfg["model"]), cfg, traffic)
    groups = reference.groups_of(cfg)
    seed = SEED % run.SEED_MOD
    n = 4
    ref = run.reference_train(jax, cfg, groups, clients, seed, n)
    ctl = run.reference_train(jax, cfg, groups, clients, seed, n,
                              nx=reference.Numerics(jnp.bfloat16,
                                                    lax.Precision.DEFAULT))
    rows = run.sampled_rows(groups, seed)
    numbers = compare.train_numbers(ctl, ref)
    numbers.update(compare.round_numbers(
        run.reference_round(jax, cfg, groups, clients, seed, rows,
                            precision=reference.BF16_3X),
        run.reference_round(jax, cfg, groups, clients, seed, rows)))
    ok, rows_out = compare.verdict(
        numbers, {k: v for k, v in LIMITS.items() if k in numbers})
    assert not ok, rows_out


def _state_unchanged(monkeypatch):
    build = huscf.HuSCFTrainer._build_epoch

    def broken(self, n_steps):
        epoch = build(self, n_steps)

        def fn(state, *rest):
            out = epoch(state, *rest)
            return (state,) + tuple(out[1:])
        return fn
    monkeypatch.setattr(huscf.HuSCFTrainer, "_build_epoch", broken)


def _half_batch(monkeypatch):
    sample = huscf.sample_batch

    def broken(ds, key, *, batch, **kw):
        out = sample(ds, key, batch=batch, **kw)
        return jax.tree_util.tree_map(lambda x: x[:, :batch // 2], out)
    monkeypatch.setattr(huscf, "sample_batch", broken)


def _answer_altered(monkeypatch):
    federate = huscf.federate_client_params_device

    def broken(*a, **k):
        out = federate(*a, **k)
        g = sorted(out)[0]
        for net in out[g]:
            layer = sorted(out[g][net])[0]
            out[g][net][layer] = jax.tree_util.tree_map(
                lambda x: x * 1.001, out[g][net][layer])
        return out
    monkeypatch.setattr(huscf, "federate_client_params_device", broken)


def _weights_altered(monkeypatch):
    """The round's Eq.-15 weights altered where they are produced:
    dataset shares within each cluster in place of the KLD weights."""
    weights_fn = huscf.kld_mod.activation_weights_jax

    def broken(acts, sizes, labels, bound, beta, **kw):
        _, klds = weights_fn(acts, sizes, labels, bound, beta, **kw)
        onehot = jax.nn.one_hot(labels, bound, dtype=sizes.dtype)
        return sizes / (onehot @ (onehot.T @ sizes)), klds
    monkeypatch.setattr(huscf.kld_mod, "activation_weights_jax", broken)


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _answer_altered, _weights_altered])
def test_fault_fails(fault, monkeypatch):
    fault(monkeypatch)
    result = _run()
    assert not result["correct"], result["checked"]
