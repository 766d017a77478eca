"""The Pallas ``clustered_agg`` kernel's share of its roofline: the least
time its rounds need (bytes over HBM bandwidth or FLOPs over peak,
whichever is larger, from ``bench/flops.py``) over the summed device
time of its events in the trace."""

from bench import flops, trace_reduce

LAYER = "kernels"
MOVES = "train_samples_per_s"
KERNEL = "clustered_agg"


def read(data, trace):
    spent = trace_reduce.kernel_seconds(trace["events"], KERNEL)
    ks = [k for mode, k in data["rounds"] if mode == "clustered"]
    if spent <= 0 or not ks:
        return None
    peak = trace_reduce.peaks(data["device"]["kind"])
    least = 0.0
    for k in ks:
        cost = flops.agg_round_cost(data["agg_layers"], int(k))
        least += max(cost["bytes"] / peak["hbm_bytes_per_s"],
                     cost["flops"] / peak["bf16_flops_per_s"])
    return 100.0 * least / spent
