from harness import hist_ops

LAYER = "kernels"
MOVES = "train_s_per_iter"
SOURCE = "device_trace"
DRIVERS = ("train",)


def read(record):
    """The bytes `level_stream` must move in the window (every channel of every
    streamed row read and written once, one histogram block a segment, from the
    program's counters: harness/hist_ops.py) over the kernel's device time, as
    a share of the chip's peak HBM bandwidth.  The bytes side of its roofline."""
    return hist_ops.share(record, hist_ops.hbm_bytes, "hbm_bytes_per_s")
