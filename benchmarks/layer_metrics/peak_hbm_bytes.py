LAYER = "device"
MOVES = "train_s_per_iter"
SOURCE = "program_counter"
DRIVERS = ("train",)


def read(record):
    """`Device.memory_stats()["peak_bytes_in_use"]` on the fullest chip."""
    return record["memory_peak_bytes"] or None
