from harness import phase_reduce

LAYER = "grower"
MOVES = "train_s_per_iter"
SOURCE = "device_trace"
DRIVERS = ("train",)


def read(record):
    """Leaf device time under the grower's `level_phase` scope (the
    level-batched expansion: `level_stream` and the vmapped split search), per
    traced iteration."""
    return phase_reduce.phase_ms(record, "LEVEL_PHASE", per="iters")
