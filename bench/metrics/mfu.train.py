"""The whole training step's share of the chip's bf16 peak: the FLOPs
the steps completed in the traced window need (``bench/flops.py``) over
the window's seconds times the peak of the chip's ``device_kind``."""

from bench import trace_reduce

LAYER = "trainer epoch"
MOVES = "train_samples_per_s"


def read(data, trace):
    if not data["steps"]:
        return None
    peak = trace_reduce.peaks(data["device"]["kind"])["bf16_flops_per_s"]
    work = data["flops_per_sample"] * data["samples"]
    return 100.0 * work / (trace["window_s"] * peak * data["device"]["count"])
