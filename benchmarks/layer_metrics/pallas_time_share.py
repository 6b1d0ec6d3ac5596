LAYER = "kernels"
MOVES = "train_s_per_iter"
SOURCE = "device_trace"
DRIVERS = ("train",)


def read(record):
    """Share of the device's busy time spent inside Mosaic (Pallas) kernels."""
    dev = record["device"]
    return None if dev is None else 100.0 * dev["mosaic_s"] / dev["busy_s"]
