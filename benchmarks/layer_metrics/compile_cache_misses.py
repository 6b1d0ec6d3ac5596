LAYER = "fused_trainer"
MOVES = "setup_s"
SOURCE = "program_counter"
DRIVERS = ("train",)


def read(record):
    """Programs compiled during set-up that the persistent cache did not hold."""
    return record["compile_setup"]["cache_misses"]
