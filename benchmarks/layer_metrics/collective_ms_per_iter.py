LAYER = "parallel"
MOVES = "train_s_per_iter"
SOURCE = "device_trace"
DRIVERS = ("train",)


def read(record):
    """Milliseconds a chip spent in collective operations (all-reduce and its
    kin by opcode: the union of the synchronous ones on `XLA Ops` and the
    start-to-done spans on `Async XLA Ops`, harness/xplane_reduce.py) per
    traced iteration, averaged over the chips.  The histogram all-reduces of
    `tree_learner=data`; on one chip nothing."""
    dev = record["device"]
    return None if dev is None else 1e3 * dev["collective_s"] / record["iters"]
