"""Federation round: summed wall time of the window's ``federate()``
calls, each span closed by ``block_until_ready`` on the trainer state
(traced run only)."""

LAYER = "federation round"
MOVES = "train_samples_per_s"


def read(data, trace):
    spans = [e - s for name, s, e in trace["spans"] if name == "federate"]
    return 1e3 * sum(spans) if spans else None
