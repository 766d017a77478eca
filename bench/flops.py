"""Operations and bytes the timed path needs, from the configuration's
layer shapes. Only the products each layer needs are counted: a
stride-2 transposed convolution counts each input pixel's k x k taps
that land inside the output, not the dilated grid it is convolved
over, and no conv counts taps on its SAME padding. BatchNorm,
activations and Adam are elementwise and not counted.
"""
from __future__ import annotations

from typing import Dict, List


def _taps(n: int, k: int, s: int, transpose: bool) -> int:
    """Valid (output position, kernel tap) pairs along one spatial dim
    of a SAME-padded conv (``lax.conv_general_dilated``) or transposed
    conv (``lax.conv_transpose``) over an input of length ``n``: taps
    that land on padding or on a dilation hole multiply zeros and are
    not needed."""
    if transpose:                      # conv over the s-dilated input
        pad = k + s - 2
        lo = k - 1 if s > k - 1 else -(-pad // 2)
        out, step, span = n * s, 1, (n - 1) * s + 1
        hit = lambda x: 0 <= x < span and x % s == 0
    else:
        out = -(-n // s)
        lo = max((out - 1) * s + k - n, 0) // 2
        step, hit = s, (lambda x: 0 <= x < n)
    return sum(hit(i * step + t - lo) for i in range(out) for t in range(k))


def layer_flops(cfg: dict) -> Dict[str, List[float]]:
    """Forward FLOPs per sample of each layer of G and of D: two per
    multiply-add that touches no padding or dilation zero."""
    g, d = cfg["generator"], cfg["discriminator"]
    fh, fw, fc = g["fc_out"]
    G = [2.0 * (g["z_dim"] + g["num_classes"]) * fh * fw * fc]
    h, w = fh, fw
    for cin, cout, k, s in g["convt"]:
        G.append(2.0 * _taps(h, k, s, True) * _taps(w, k, s, True)
                 * cin * cout)
        h, w = h * s, w * s
    D = []
    h, w, _ = d["image"]
    for cin, cout, k, s in d["conv"]:
        D.append(2.0 * _taps(h, k, s, False) * _taps(w, k, s, False)
                 * cin * cout)
        h, w = -(-h // s), -(-w // s)
    D.append(2.0 * d["fc_in"])
    return {"G": G, "D": D}


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


def agg_round_cost(layers: Dict[str, Dict[int, tuple]], k: int
                   ) -> Dict[str, float]:
    """Bytes and FLOPs an Eq.-16 round must move, for the kernel's
    roofline. ``layers[net][l] = (owners, width)``: how many clients own
    layer ``l`` and its f32 parameter count. Every federated client row
    is read once, and each of the ``k`` clusters writes one aggregate of
    every ownable layer. Padding and the zero columns of the flat
    buffer are not counted."""
    read = sum(n * w for per in layers.values() for n, w in per.values())
    written = k * sum(w for per in layers.values() for _, w in per.values())
    return {"bytes": 4.0 * (read + written), "flops": 2.0 * read}
