from harness import split_ops

LAYER = "grower"
MOVES = "train_s_per_iter"
SOURCE = "program_counter"
DRIVERS = ("train",)


def read(record):
    """Splits per traced iteration that the replay took the classic way, one
    `split_stream` pass over the parent's segment each, because the level
    phase had not precomputed them (the program's `tail_splits` counter on its
    `trees_from_records` spans).  What a chain-shaped tree costs beyond the
    levels; a program without the counter reports nothing."""
    c = split_ops.counters(record)
    return None if c is None else c["tail_splits"] / record["iters"]
