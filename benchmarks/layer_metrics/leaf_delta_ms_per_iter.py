from harness import phase_reduce

LAYER = "fused_trainer"
MOVES = "train_s_per_iter"
SOURCE = "device_trace"
DRIVERS = ("train",)


def read(record):
    """Leaf device time under the chunk program's `leaf_delta` scope (segment
    values to the per-row score delta), per traced iteration."""
    return phase_reduce.phase_ms(record, "LEAF_DELTA", per="iters")
