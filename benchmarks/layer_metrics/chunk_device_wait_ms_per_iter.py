from harness.measure import span_total

LAYER = "fused_trainer"
MOVES = "train_s_per_iter"
SOURCE = "program_span"
DRIVERS = ("train",)


def read(record):
    """The program's `chunk_program` and `records_fetch` spans, summed: the first
    wraps an asynchronous dispatch and the second absorbs the wait for the
    device, so only their sum means anything.  Per traced iteration."""
    s = span_total(record["program_spans"], "chunk_program", "records_fetch")
    return None if s is None else 1e3 * s / record["iters"]
