"""Device idle share of the traced window: 1 minus the union of the
device-op intervals over the window, averaged over the chips used."""

LAYER = "device"
MOVES = "train_samples_per_s"


def read(data, trace):
    if trace["window_s"] <= 0 or trace["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
