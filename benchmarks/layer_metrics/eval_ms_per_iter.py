from harness.measure import span_total

LAYER = "entry"
MOVES = "train_s_per_iter"
SOURCE = "program_span"
DRIVERS = ("train",)


def read(record):
    """The engine's `eval` span (metrics on the validation set), per traced
    iteration.  Nothing where the mix has no validation set."""
    s = span_total(record["program_spans"], "eval")
    return None if s is None else 1e3 * s / record["iters"]
