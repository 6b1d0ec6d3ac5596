LAYER = "entry"
MOVES = "setup_s"
SOURCE = "program_span"
DRIVERS = ("train",)


def read(record):
    """Seconds of the program's `sparse_ingest` host spans while the training
    table was set up (CSR to bin mappers, bundles and the bundled matrix:
    lightgbm_tpu/io/sparse.py): tens of seconds on a checkout's first run, 0.0
    on every later one, which loads the program's binary file and ingests
    nothing.  A driver that records no set-up spans reports nothing."""
    if "setup_program_spans" not in record:
        return None
    return sum(s["dur_s"] for s in record["setup_program_spans"] if s["name"] == "sparse_ingest")
