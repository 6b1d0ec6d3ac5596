LAYER = "kernels"
MOVES = "train_s_per_iter"
SOURCE = "device_trace"
DRIVERS = ("train",)


def read(record):
    """Share of the device's busy time in the one operation name that took
    most (its name is first under `breakdown.device_ops` that is no `while`)."""
    dev = record["device"]
    return None if dev is None else 100.0 * max(dev["leaf_op_s"].values()) / dev["busy_s"]
