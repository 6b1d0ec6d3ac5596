from harness.measure import span_total

LAYER = "boosting_driver"
MOVES = "train_s_per_iter"
SOURCE = "program_span"
DRIVERS = ("train",)


def read(record):
    """Host time of the traced window outside the chunk program and evaluation,
    per chunk dispatched (a lap: `chunk_iters` iterations, or one under
    `lgb.train`): fetched scores, trees built from records, the loop.  It is
    paid once a chunk, so a user's chunk of 64 iterations pays it a sixteenth
    as often per iteration as a cell's chunk of 4."""
    inside = span_total(record["program_spans"], "chunk_program", "records_fetch", "eval")
    return None if inside is None else 1e3 * (record["window_s"] - inside) / record["laps"]
