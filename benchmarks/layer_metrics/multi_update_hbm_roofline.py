from harness import multi_ops

LAYER = "kernels"
MOVES = "train_s_per_iter"
SOURCE = "device_trace"
DRIVERS = ("train",)


def read(record):
    """The bytes `update_multi_and_hists` must move in the window (every
    channel of every row read once, the 2K gradient rows written, the K root
    histograms: harness/multi_ops.py) over the kernel's device time, as a share
    of the chip's peak HBM bandwidth.  The bytes side of its roofline."""
    return multi_ops.share(record, multi_ops.hbm_bytes, "hbm_bytes_per_s")
