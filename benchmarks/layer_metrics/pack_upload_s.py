from harness.measure import span_total

LAYER = "entry"
MOVES = "setup_s"
SOURCE = "host_clock"
DRIVERS = ("train",)


def read(record):
    """Seconds in `lgb.Booster(params, train_set)` up to the packed matrix being
    on the device: upload of the bins and `ptrainer` packing.  A mix that goes
    through `lgb.train` has no such span of its own and reports nothing."""
    return span_total(record["bench_spans"], "booster")
