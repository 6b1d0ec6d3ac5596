from harness import phase_reduce

LAYER = "grower"
MOVES = "train_s_per_iter"
SOURCE = "device_trace"
DRIVERS = ("train",)


def read(record):
    """Leaf device time under the grower's `split_scan` scope (inside
    `level_phase`: the split search over a level's children histograms, whose
    arrays grow with columns x bins), per traced iteration.  A program whose
    vocabulary has no such word reports nothing."""
    from lightgbm_tpu.obs import phases

    if not hasattr(phases, "SPLIT_SCAN"):
        return None
    return phase_reduce.phase_ms(record, "SPLIT_SCAN", per="iters")
