"""The trainer's tracing layer: host spans, device scope names, counters.

Host spans are ``jax.profiler.TraceAnnotation``s named ``huscf.<name>``.
They are written into the profiler's own trace, on the clock of the
device ops, and cost one check when no profiler session is active:
tracing is off unless a ``jax.profiler.trace`` is running. A span's
parent is the span that encloses it on the same thread; the dotted
names say the same (``huscf.federate.aggregate`` inside
``huscf.federate``).

Device scopes are ``jax.named_scope`` names. They change the
``op_name`` metadata of the HLO ops traced under them, and nothing the
compiler fuses or computes; the backward ops keep the scope inside
their ``transpose(jvp(...))`` name. Each scope is defined once below.

Counters are a plain in-memory count per name (``count``,
``counters``), e.g. ``programs_built.<cache>`` at each miss of one of
the trainer's program caches, so a compile can be traced back to the
cache that missed.
"""
from __future__ import annotations

from collections import Counter
from typing import Dict

import jax

PREFIX = "huscf."

# device scopes: the U-shaped split (core/segments.py make_apply) ...
SCOPE_HEAD = "split.head"
SCOPE_TRUNK = "split.trunk"
SCOPE_TAIL = "split.tail"
# ... and the stages of a federation round
SCOPE_CLUSTER = "round.cluster"       # stage 3: k-means++, Lloyd, silhouette
SCOPE_KLD = "round.kld"               # Eq. 13-15
SCOPE_AGGREGATE = "round.aggregate"   # Eq. 16 (the clustered_agg kernel too)
SCOPE_GA = "round.ga"                 # on-device genetic cut search

_COUNTS: Counter = Counter()


def span(name: str) -> jax.profiler.TraceAnnotation:
    """A host span ``huscf.<name>`` around a ``with`` block."""
    return jax.profiler.TraceAnnotation(PREFIX + name)


def count(name: str, n: int = 1) -> None:
    _COUNTS[name] += n


def counters() -> Dict[str, int]:
    """A copy of every count so far in this process."""
    return dict(_COUNTS)
