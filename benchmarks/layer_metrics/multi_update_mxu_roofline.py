from harness import multi_ops

LAYER = "kernels"
MOVES = "train_s_per_iter"
SOURCE = "device_trace"
DRIVERS = ("train",)


def read(record):
    """The one-hot histogram dots `update_multi_and_hists` issued in the window
    (2 x rows x `update_value_rows` x `hist_cells` an iteration, from the
    program's counters: harness/multi_ops.py) over the kernel's device time, as
    a share of the chip's bf16 peak.  The operations side of its roofline."""
    return multi_ops.share(record, multi_ops.flops, "bf16_flops_per_s")
