from harness import stages

LAYER = "entry"
MOVES = "setup_s"
SOURCE = "program_span"
DRIVERS = ("train",)


def read(record):
    """Seconds of the search for exclusive feature bundles (`find_bundles` stages:
    `io/dataset.py::ensure_bundles` under `booster_init` on a dense table,
    `io/sparse.py::ingest` on a first run from CSR).  Nothing where no search
    ran: a table whose bundles came with its binary file."""
    return stages.total(record, "find_bundles")
