"""Operations and bytes the timed path needs, from shapes.

A net's FLOPs a sample are its module's (``bench/models/<model>.py``
``layer_flops``, ``step_flops_per_sample``), counted with ``taps``:
only the products each layer needs count. A stride-2 transposed
convolution counts each input pixel's k x k taps that land inside the
output, not the dilated grid it is convolved over, and no conv counts
taps on its SAME padding. BatchNorm, activations and Adam are
elementwise and not counted. The round's bytes are the same for every
net (``agg_round_cost``).
"""
from __future__ import annotations

from typing import Dict


def taps(n: int, k: int, s: int, transpose: bool) -> int:
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
