from harness.measure import span_total

LAYER = "entry"
MOVES = "setup_s"
SOURCE = "host_clock"
DRIVERS = ("train",)


def read(record):
    """Seconds the benchmark spent in `lgb.Dataset(...).construct()` for the
    training table: generating and binning it on a checkout's first run, loading
    the program's binary dataset cache on every later one."""
    return span_total(record["bench_spans"], "dataset")
