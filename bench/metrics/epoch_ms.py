"""Trainer epoch: summed wall time of the window's ``train_steps`` calls,
from the benchmark's own span around each (the call blocks on its
losses)."""

LAYER = "trainer epoch"
MOVES = "train_samples_per_s"


def read(data, trace):
    spans = [e - s for name, s, e in trace["spans"] if name == "epoch"]
    return 1e3 * sum(spans) if spans else None
