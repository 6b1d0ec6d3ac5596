LAYER = "device"
MOVES = "train_s_per_iter"
SOURCE = "program_counter"
DRIVERS = ("train",)


def read(record):
    """The largest `bytes_in_use` sampled on any chip during the traced window
    (harness/tracing.py): what training holds, arguments and temporaries of the
    chunk program included, set-up's packing temporaries left out.  The cells'
    rows are sized on this number, not on `peak_hbm_bytes`."""
    return record["window_hbm_bytes"] or None
