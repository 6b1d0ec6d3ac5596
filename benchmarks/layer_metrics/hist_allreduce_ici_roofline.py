from harness import collective_ops

LAYER = "parallel"
MOVES = "train_s_per_iter"
SOURCE = "device_trace"
DRIVERS = ("train",)


def read(record):
    """The bytes one chip must SEND for the window's histogram all-reduces if
    they are done at best (2 x (shards - 1) / shards x the program's
    `allreduce_bytes`: harness/collective_ops.py) over the chip's time in
    collectives, as a share of its published inter-chip bandwidth
    (harness/ici_peaks.json, read so that the share comes out low)."""
    return collective_ops.share(record)
