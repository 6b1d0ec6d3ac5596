from harness import split_ops

LAYER = "kernels"
MOVES = "train_s_per_iter"
SOURCE = "device_trace"
DRIVERS = ("train",)


def read(record):
    """The bytes `split_stream` must move in the window (every channel of every
    row of a tail split's parent read and written once, two histogram blocks a
    tail split, from the program's `tail_rows` and `tail_splits` counters:
    harness/split_ops.py) over the kernel's device time, as a share of the
    chip's peak HBM bandwidth."""
    return split_ops.share(record)
