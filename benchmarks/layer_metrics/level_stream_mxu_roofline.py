from harness import hist_ops

LAYER = "kernels"
MOVES = "train_s_per_iter"
SOURCE = "device_trace"
DRIVERS = ("train",)


def read(record):
    """The one-hot histogram dots `level_stream` issued in the window (2 x 14
    value rows x `hist_cells` x `level_rows`, from the program's counters:
    harness/hist_ops.py) over the kernel's device time, as a share of the
    chip's bf16 peak.  The operations side of the kernel's roofline."""
    return hist_ops.share(record, hist_ops.flops, "bf16_flops_per_s")
