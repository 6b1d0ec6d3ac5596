LAYER = "device"
MOVES = "train_s_per_iter"
SOURCE = "device_trace"
DRIVERS = ("train",)


def read(record):
    """Share of the traced window in which no leaf operation ran on the chip
    (see harness/xplane_reduce.py for why leaves), averaged over the chips."""
    dev = record["device"]
    return None if dev is None else 100.0 * (1.0 - dev["busy_s"] / dev["window_s"])
