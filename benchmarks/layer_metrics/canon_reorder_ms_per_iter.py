from harness import phase_reduce

LAYER = "fused_trainer"
MOVES = "train_s_per_iter"
SOURCE = "device_trace"
DRIVERS = ("train",)


def read(record):
    """Leaf device time under the chunk program's `canon_reorder` scope (rows
    back to original order at every tree's start), per traced iteration."""
    return phase_reduce.phase_ms(record, "CANON_REORDER", per="iters")
