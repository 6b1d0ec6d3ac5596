from harness import phase_reduce

LAYER = "fused_trainer"
MOVES = "train_s_per_iter"
SOURCE = "device_trace"
DRIVERS = ("train",)


def read(record):
    """Leaf device time under the `multi_update` scope (the one pass that writes
    all K classes' g and h planes and takes their K root histograms), per
    traced iteration.  A program whose vocabulary has no such word reports
    nothing."""
    from lightgbm_tpu.obs import phases

    if not hasattr(phases, "MULTI_UPDATE"):
        return None
    return phase_reduce.phase_ms(record, "MULTI_UPDATE", "iters")
