from harness import collective_ops

LAYER = "parallel"
MOVES = "train_s_per_iter"
SOURCE = "program_counter"
DRIVERS = ("train",)


def read(record):
    """GB (1e9 bytes) of float32 histograms ONE chip hands to the all-reduces
    per traced iteration: the program's `allreduce_bytes` counter on its
    `trees_from_records` spans (the root's, a level's `(slots, 16, lanes)`
    rows, two histograms a tail split).  Payload, not link traffic
    (harness/collective_ops.py).  A program without the counter reports
    nothing."""
    c = collective_ops.counters(record)
    return None if c is None else c["allreduce_bytes"] / record["iters"] / 1e9
