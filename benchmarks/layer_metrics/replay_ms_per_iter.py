from harness import phase_reduce

LAYER = "grower"
MOVES = "train_s_per_iter"
SOURCE = "device_trace"
DRIVERS = ("train",)


def read(record):
    """Leaf device time under the grower's `replay` scope (the best-first
    selection over the candidate tables, with `replay_tail`, the classic
    per-split `split_stream`, inside it), per traced iteration."""
    return phase_reduce.phase_ms(record, "REPLAY", per="iters")
