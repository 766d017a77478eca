"""The FLOP counter against XLA's own count, on the CPU.

Per conv layer, XLA's ``cost_analysis`` counts exactly the products
that touch neither SAME padding nor a transposed conv's dilation
zeros, which is what ``cgan28.layer_flops`` counts: the ratio is 1.
For a whole training step of a tiny population (2 groups of 2
clients, batch 4) XLA also counts the elementwise work the counter
leaves out (BatchNorm, activations, losses, Adam), and merges the G
update's repeated G forward as the counter does: the ratio lies just
above 1.
"""

import jax
import jax.numpy as jnp
import pytest
from jax import lax

from bench import flops, run, spec
from bench.models import cgan28

DN = ("NHWC", "HWIO", "NHWC")


def _cfg():
    return spec.read_json(spec.config_path("cgan28-paper100"))


def _xla_flops(fn, *args):
    cost = jax.jit(fn).lower(*args).compile().cost_analysis()
    return (cost[0] if isinstance(cost, list) else cost)["flops"]


def _conv_layers():
    # square inputs: (net, layer, h, cin, cout, k, s)
    return [(net, layer, h, cin, cout, k, s) for net, layer, h, w, cin,
            cout, k, s in cgan28.conv_layers(_cfg()) if h == w]


@pytest.mark.parametrize("net,layer,h,cin,cout,k,s", _conv_layers())
def test_conv_layer_matches_xla(net, layer, h, cin, cout, k, s):
    x = jnp.ones((1, h, h, cin), jnp.float32)
    w = jnp.ones((k, k, cin, cout), jnp.float32)
    if net == "G":
        fn = lambda x, w: lax.conv_transpose(x, w, (s, s), "SAME",
                                             dimension_numbers=DN)
    else:
        fn = lambda x, w: lax.conv_general_dilated(x, w, (s, s), "SAME",
                                                   dimension_numbers=DN)
    assert cgan28.layer_flops(_cfg())[net][layer] == _xla_flops(fn, x, w)


def test_dense_layers_match_xla():
    cfg = _cfg()
    f = cgan28.layer_flops(cfg)
    shapes = cgan28.layer_shapes(cfg)
    for net, layer in (("G", 0), ("D", -1)):
        n_in, n_out = shapes[net][layer]["fc"]["w"]
        assert f[net][layer] == _xla_flops(jnp.dot, jnp.ones((1, n_in)),
                                           jnp.ones((n_in, n_out)))


def test_published_widths_count():
    # 3 G forwards and 8 D forwards a sample, padding taps left out
    f = cgan28.layer_flops(_cfg())
    assert sum(f["G"]) == 148127232 and sum(f["D"]) == 34735104
    assert cgan28.step_flops_per_sample(_cfg()) == \
        3 * sum(f["G"]) + 8 * sum(f["D"])


def test_step_flops_against_xla():
    cfg = _cfg()
    cfg.update(num_clients=4, device_cycle=["device1", "device2"], batch=4)
    traffic = dict(spec.read_json(spec.traffic_path("e1")), base_size=18)
    tr = cgan28.build_trainer(cfg, traffic,
                              run.clients_of(cgan28, cfg, traffic), 0)
    fn = tr._build_epoch(1)
    cost = fn.lower(tr.state, tr._dataset, tr._train_key, tr._mid_ema,
                    tr._ema_init).compile().cost_analysis()
    xla = (cost[0] if isinstance(cost, list) else cost)["flops"]
    ours = cgan28.step_flops_per_sample(cfg) * 4 * cfg["batch"]
    # above 1 by the elementwise work only; a fourth G forward counted
    # (1.20) or one D backward missed (0.88) falls outside
    assert 1.0 < xla / ours < 1.08


def test_agg_round_cost():
    layers = {"G": {0: (3, 10), 4: (3, 2)}, "D": {0: (1, 5)}}
    cost = flops.agg_round_cost(layers, k=2)
    read = 3 * 10 + 3 * 2 + 5
    assert cost == {"bytes": 4.0 * (read + 2 * (10 + 2 + 5)),
                    "flops": 2.0 * read}
