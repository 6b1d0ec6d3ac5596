from harness.measure import span_total

LAYER = "boosting_driver"
MOVES = "train_s_per_iter"
SOURCE = "program_span"
DRIVERS = ("train",)


def read(record):
    """Host time of the traced window that is neither the chunk program (its
    dispatch and the wait for it) nor evaluation: trees built from records,
    callbacks, the engine's loop.  Per traced iteration."""
    inside = span_total(record["program_spans"], "chunk_program", "records_fetch", "eval")
    return None if inside is None else 1e3 * (record["window_s"] - inside) / record["iters"]
