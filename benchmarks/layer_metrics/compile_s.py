LAYER = "fused_trainer"
MOVES = "setup_s"
SOURCE = "program_counter"
DRIVERS = ("train",)


def read(record):
    """Seconds of XLA/Mosaic back-end compilation during set-up, cache hits
    included, as `obs/compilewatch.snapshot()` counts them."""
    return record["compile_setup"]["backend_compile_secs"]
