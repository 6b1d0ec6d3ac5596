LAYER = "grower"
MOVES = "train_s_per_iter"
SOURCE = "device_trace"
DRIVERS = ("train",)


def read(record):
    """Leaf device operations per traced iteration, averaged over the chips:
    the stand-in for the grower's per-level and per-split fixed cost until the
    grower counts its own launches."""
    dev = record["device"]
    return None if dev is None else dev["launches"] / record["iters"]
