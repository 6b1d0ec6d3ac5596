LAYER = "parallel"
MOVES = "train_s_per_iter"
SOURCE = "device_trace"
DRIVERS = ("train",)


def read(record):
    """Share of the traced window in which a chip was in a collective and no
    other leaf operation of that chip ran: what the all-reduces cost end to
    end, since the chip is otherwise never idle (harness/xplane_reduce.py,
    `collective_exposed_s`)."""
    dev = record["device"]
    return None if dev is None else 100.0 * dev["collective_exposed_s"] / dev["window_s"]
