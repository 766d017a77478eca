"""The 28x28 conditional GAN of arXiv:2507.12979 Table 3: everything the
benchmark needs that is specific to this net.

``bench/spec.py`` loads this file by a configuration's ``"model":
"cgan28"``. It gives the population's image shape and class count,
the width of D's middle layer (the clustering EMA), the parameter
template and the benchmark's weights, the reference's layers and
training step, the FLOPs a step needs, and the trainer.

The reference (``make_layers``, ``make_step``) follows the published
semantics, with the split, Adam and the batches of
``bench/reference.py``:

* the cGAN of Table 3, layer by layer;
* one training step: a discriminator update (G forward without
  gradient, D on real and on fake, non-saturating BCE), then a
  generator update against the updated D; Adam; BatchNorm running
  statistics from the real-data pass for D and from G's own pass.
"""
from __future__ import annotations

import zlib
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bench import reference
from bench.flops import taps
from bench.reference import Group, Numerics

IMAGE = (28, 28, 1)                 # (H, W, C) of the population's images
NUM_CLASSES = 10
EMA_FEATURES = 7 * 7 * 128          # D's middle layer output, flattened
BN_EPS = 1e-5
BN_MOMENTUM = 0.9
N_LAYERS = 5


def ema_features(cfg: dict) -> int:
    """The width of the middle-activation EMA that stage 3 clusters."""
    return EMA_FEATURES


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def _bn_shapes(ch):
    return {"scale": (ch,), "bias": (ch,), "mean": (ch,), "var": (ch,)}


def layer_shapes(cfg: dict) -> Dict[str, List[dict]]:
    """Leaf shapes of every layer of G and D at the configuration's
    widths, in the trainer's parameter layout."""
    g, d = cfg["generator"], cfg["discriminator"]
    z, ncls = g["z_dim"], g["num_classes"]
    fh, fw, fc = g["fc_out"]
    G = [{"embed": {"table": (ncls, ncls)},
          "fc": {"w": (z + ncls, fh * fw * fc), "b": (fh * fw * fc,)},
          "bn": _bn_shapes(fc)}]
    for i, (cin, cout, k, _) in enumerate(g["convt"]):
        layer = {"convt": {"w": (k, k, cin, cout), "b": (cout,)}}
        if i < len(g["convt"]) - 1:
            layer["bn"] = _bn_shapes(cout)
        G.append(layer)
    ih, iw, _ = d["image"]
    D = []
    for i, (cin, cout, k, _) in enumerate(d["conv"]):
        layer = {"conv": {"w": (k, k, cin, cout), "b": (cout,)},
                 "bn": _bn_shapes(cout)}
        if i == 0:
            layer = {"embed": {"table": (d["num_classes"], ih * iw)}, **layer}
        D.append(layer)
    D.append({"fc": {"w": (d["fc_in"], 1), "b": (1,)}})
    return {"G": G, "D": D}


def _init_leaf(key, name: str, parent: str, shape, lead=()):
    full = tuple(lead) + tuple(shape)
    if parent == "bn":
        return (jnp.ones(full, jnp.float32) if name in ("scale", "var")
                else jnp.zeros(full, jnp.float32))
    if name == "b":
        return jnp.zeros(full, jnp.float32)
    if name == "table":
        return jax.random.normal(key, full, jnp.float32) / np.sqrt(shape[-1])
    if parent == "fc":                      # glorot uniform
        lim = np.sqrt(6.0 / (shape[0] + shape[1]))
        return jax.random.uniform(key, full, jnp.float32, -lim, lim)
    fan_in = int(np.prod(shape[:-1]))       # conv / convT, HWIO: he normal
    return jax.random.normal(key, full, jnp.float32) * np.sqrt(2.0 / fan_in)


def param_template(cfg: dict, groups: Sequence[Group]) -> dict:
    """{net: {"client": {group: {layer: tree}}, "server": {layer: tree}}}
    with leading client axes, as (lead, shape) pairs."""
    shapes = layer_shapes(cfg)
    out = {}
    for net in ("G", "D"):
        client = {g.name: {str(l): (g.size, shapes[net][l])
                           for l in range(N_LAYERS) if g.owns(net, l)}
                  for g in groups}
        server = {str(l): ((), shapes[net][l]) for l in range(N_LAYERS)
                  if any(not g.owns(net, l) for g in groups)}
        out[net] = {"client": client, "server": server}
    return out


def init_params(cfg: dict, groups: Sequence[Group], seed: int):
    """The benchmark's weights for ``seed``, made on the device in one
    jitted call. Every leaf draws from ``fold_in(seed key, crc32(path))``."""
    template = param_template(cfg, groups)

    def build(key):
        def walk(node, path):
            if isinstance(node, tuple):
                lead, shapes = node
                lead = (lead,) if isinstance(lead, int) else lead
                return {part: {name: _init_leaf(
                    jax.random.fold_in(key, zlib.crc32(
                        f"{path}/{part}/{name}".encode()) % (2 ** 31)),
                    name, part, shp, lead)
                    for name, shp in leaves.items()}
                    for part, leaves in shapes.items()}
            return {k: walk(v, f"{path}/{k}") for k, v in node.items()}
        return walk(template, "")

    return jax.jit(build)(jax.random.PRNGKey(seed))


# ---------------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------------

def _bn(p, x, nx: Numerics):
    axes = tuple(range(x.ndim - 1))
    mean = jnp.mean(x, axes)
    var = jnp.mean(jnp.square(x - mean), axes)
    y = (x - mean) * lax.rsqrt(var + BN_EPS) * p["scale"].astype(nx.dtype) \
        + p["bias"].astype(nx.dtype)
    stats = {"mean": BN_MOMENTUM * p["mean"]
             + (1 - BN_MOMENTUM) * mean.astype(jnp.float32),
             "var": BN_MOMENTUM * p["var"]
             + (1 - BN_MOMENTUM) * var.astype(jnp.float32)}
    return y, stats


def _dense(p, x, nx):
    return jnp.dot(x, p["w"].astype(nx.dtype),
                   precision=nx.precision) + p["b"].astype(nx.dtype)


_DN = ("NHWC", "HWIO", "NHWC")


def _conv(p, x, stride, nx):
    y = lax.conv_general_dilated(x, p["w"].astype(nx.dtype), (stride, stride),
                                 "SAME", dimension_numbers=_DN,
                                 precision=nx.precision)
    return y + p["b"].astype(nx.dtype)


def _convt(p, x, stride, nx):
    y = lax.conv_transpose(x, p["w"].astype(nx.dtype), (stride, stride),
                           "SAME", dimension_numbers=_DN,
                           precision=nx.precision)
    return y + p["b"].astype(nx.dtype)


def _lrelu(x):
    return jnp.where(x >= 0, x, 0.2 * x)


def make_layers(cfg: dict, nx: Numerics):
    """[(apply(p, x) -> (y, bn_stats or None))] for G and for D."""
    g, d = cfg["generator"], cfg["discriminator"]
    fh, fw, fc = g["fc_out"]
    ih, iw, _ = d["image"]

    def g0(p, x):
        z, y = x
        h = jnp.concatenate([z.astype(nx.dtype),
                             p["embed"]["table"].astype(nx.dtype)[y]], -1)
        h = _dense(p["fc"], h, nx).reshape(-1, fh, fw, fc)
        h, st = _bn(p["bn"], h, nx)
        return jax.nn.relu(h), st

    def gconvt(stride, last):
        def apply(p, x):
            h = _convt(p["convt"], x, stride, nx)
            if last:
                return jnp.tanh(h), None
            h, st = _bn(p["bn"], h, nx)
            return jax.nn.relu(h), st
        return apply

    def d0(p, x):
        img, y = x
        e = p["embed"]["table"].astype(nx.dtype)[y].reshape(-1, ih, iw, 1)
        h = jnp.concatenate([img.astype(nx.dtype), e], -1)
        h = _conv(p["conv"], h, d["conv"][0][3], nx)
        h, st = _bn(p["bn"], h, nx)
        return _lrelu(h), st

    def dconv(stride):
        def apply(p, x):
            h, st = _bn(p["bn"], _conv(p["conv"], x, stride, nx), nx)
            return _lrelu(h), st
        return apply

    def d4(p, x):
        return _dense(p["fc"], x.reshape(x.shape[0], -1), nx)[:, 0], None

    n_t = len(g["convt"])
    G = [g0] + [gconvt(s, i == n_t - 1) for i, (_, _, _, s)
                in enumerate(g["convt"])]
    D = [d0] + [dconv(s) for (_, _, _, s) in d["conv"][1:]] + [d4]
    return {"G": G, "D": D}


# ---------------------------------------------------------------------------
# the step
# ---------------------------------------------------------------------------

def _bce(logits, target):
    l = logits.astype(jnp.float32)
    return jnp.mean(jnp.maximum(l, 0) - l * target + jnp.log1p(jnp.exp(-jnp.abs(l))))


def _all(out: Dict[str, jnp.ndarray]):
    return jnp.concatenate([v.reshape(-1) for v in out.values()])


def make_step(cfg: dict, groups: Sequence[Group], nx: Numerics,
              keep: int = None):
    """One jitted training step ``(state, data, key) -> (state, key,
    [loss_d, loss_g])``; ``keep`` as in ``reference.sample``."""
    layers = make_layers(cfg, nx)
    lr, b1, batch = cfg["lr"], cfg["adam_b1"], cfg["batch"]
    z_dim = cfg["generator"]["z_dim"]
    n_cls = cfg["generator"]["num_classes"]
    split_forward = reference.split_forward

    def step(state, data, key):
        key, sub = jax.random.split(key)
        bt = reference.sample(data, sub, groups, batch, z_dim, n_cls, keep)
        gp, dp = state["G"], state["D"]

        def d_loss(dp):
            fake, _ = split_forward(layers["G"], "G", groups, gp, bt["fake"])
            fake_in = {g.name: (lax.stop_gradient(fake[g.name]),
                                bt["fake"][g.name][1]) for g in groups}
            real, st = split_forward(layers["D"], "D", groups, dp, bt["real"])
            lf, _ = split_forward(layers["D"], "D", groups, dp, fake_in)
            return _bce(_all(real), 1.0) + _bce(_all(lf), 0.0), st

        (loss_d, st_d), gd = jax.value_and_grad(d_loss, has_aux=True)(dp)
        opt_d, dp_new = reference.adam_update(state["opt_d"], gd, dp, lr, b1)
        dp_new = reference.with_stats(dp_new, st_d)

        def g_loss(gp):
            fake, st = split_forward(layers["G"], "G", groups, gp, bt["fake"])
            fake_in = {g.name: (fake[g.name], bt["fake"][g.name][1])
                       for g in groups}
            lg, _ = split_forward(layers["D"], "D", groups, dp_new, fake_in)
            return _bce(_all(lg), 1.0), st

        (loss_g, st_g), gg = jax.value_and_grad(g_loss, has_aux=True)(gp)
        opt_g, gp_new = reference.adam_update(state["opt_g"], gg, gp, lr, b1)
        gp_new = reference.with_stats(gp_new, st_g)
        new = {"G": gp_new, "D": dp_new, "opt_g": opt_g, "opt_d": opt_d}
        return new, key, jnp.stack([loss_d, loss_g])

    return jax.jit(step, donate_argnums=(0,))


# ---------------------------------------------------------------------------
# FLOPs (bench/flops.py counts the taps)
# ---------------------------------------------------------------------------

def conv_layers(cfg: dict) -> List[tuple]:
    """(net, layer, input height, input width, cin, cout, k, stride) of
    each transposed conv of G and each conv of D, in order."""
    g, d = cfg["generator"], cfg["discriminator"]
    out = []
    h, w, _ = g["fc_out"]
    for i, (cin, cout, k, s) in enumerate(g["convt"]):
        out.append(("G", i + 1, h, w, cin, cout, k, s))
        h, w = h * s, w * s
    h, w, _ = d["image"]
    for i, (cin, cout, k, s) in enumerate(d["conv"]):
        out.append(("D", i, h, w, cin, cout, k, s))
        h, w = -(-h // s), -(-w // s)
    return out


def layer_flops(cfg: dict) -> Dict[str, List[float]]:
    """Forward FLOPs per sample of each layer of G and of D: two per
    multiply-add that touches no padding or dilation zero."""
    g, d = cfg["generator"], cfg["discriminator"]
    fh, fw, fc = g["fc_out"]
    out = {"G": [2.0 * (g["z_dim"] + g["num_classes"]) * fh * fw * fc],
           "D": []}
    for net, _, h, w, cin, cout, k, s in conv_layers(cfg):
        t = net == "G"
        out[net].append(2.0 * taps(h, k, s, t) * taps(w, k, s, t)
                        * cin * cout)
    out["D"].append(2.0 * d["fc_in"])
    return out


def step_flops_per_sample(cfg: dict) -> float:
    """One training step (``core/huscf.py`` ``step``), per sample.

    D update: G forward without gradient, D forward on real and on
    fake, D backward (weight and input gradients) for both.
    G update: D forward on fake with the updated D, D backward for
    input gradients only, G backward (weight and input gradients).
    The G update's own G forward is not counted: it recomputes the D
    update's G forward from the same parameters and inputs, work the
    step does not need (XLA:TPU does not merge the two, so the compiled
    step's cost analysis counts it). A backward pass that needs weight
    and input gradients costs twice its forward; one that needs input
    gradients only, once. Hence 3 G forwards and 8 D forwards a sample.
    """
    f = layer_flops(cfg)
    fg, fd = sum(f["G"]), sum(f["D"])
    d_update = fg + 2 * fd + 2 * (2 * fd)
    g_update = fd + fd + 2 * fg
    return d_update + g_update


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

def build_trainer(cfg, traffic, clients, seed31):
    """``HuSCFTrainer`` over ``clients`` with the configuration's fixed
    cuts, round cadence and hyper-parameters."""
    from repro.core.huscf import HuSCFConfig, HuSCFTrainer
    from repro.core.latency import PAPER_DEVICES, Cut
    from repro.data.partition import ClientSpec
    by_name = {d.name: d for d in PAPER_DEVICES}
    names = reference.device_list(cfg)
    config = HuSCFConfig(batch=cfg["batch"],
                         federate_every=traffic["federate_every"],
                         beta=cfg["beta"], lr=cfg["lr"],
                         adam_b1=cfg["adam_b1"], seed=seed31,
                         use_kernel=cfg["use_kernel"],
                         warmup_fed_rounds=cfg["warmup_fed_rounds"])
    specs = [ClientSpec(i, dom, imgs, labs)
             for i, (dom, imgs, labs) in enumerate(clients)]
    return HuSCFTrainer(specs, [by_name[n] for n in names],
                        cuts=[Cut(*cfg["cuts"][n]) for n in names],
                        config=config)
