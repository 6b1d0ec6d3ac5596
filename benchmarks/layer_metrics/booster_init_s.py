from harness import stages

LAYER = "boosting_driver"
MOVES = "setup_s"
SOURCE = "program_span"
DRIVERS = ("train",)


def read(record):
    """Seconds in `lgb.Booster(params, train_set)` as the program timed them (its
    `booster_init` stage): upload of the bins, the search for bundles, packing,
    each ended by a wait for the device.  What `pack_upload_s` times from
    outside, and the only reading of it where `lgb.train` builds the booster."""
    return stages.total(record, "booster_init")
