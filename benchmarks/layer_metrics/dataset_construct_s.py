from harness import stages

LAYER = "entry"
MOVES = "setup_s"
SOURCE = "program_span"
DRIVERS = ("train",)


def read(record):
    """Seconds in `lgb.Dataset.construct` as the program timed them (its
    `dataset_construct` stages before the window, summed: the training table,
    and the validation set where the mix has one): loading the binary dataset
    cache on a warm run, binning or ingesting on a checkout's first."""
    return stages.total(record, "dataset_construct")
