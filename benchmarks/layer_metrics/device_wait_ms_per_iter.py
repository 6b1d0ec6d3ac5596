from harness.measure import span_total

LAYER = "fused_trainer"
MOVES = "train_s_per_iter"
SOURCE = "program_span"
DRIVERS = ("train",)


def read(record):
    """The program's `device_wait` span alone (inside `records_fetch`: the
    wait for the chunk program, fenced because tracing is on), per traced
    iteration.  With `records_d2h` and `chunk_program` it accounts for
    `chunk_device_wait_ms_per_iter`; nothing from a program without the span."""
    s = span_total(record["program_spans"], "device_wait")
    return None if s is None else 1e3 * s / record["iters"]
