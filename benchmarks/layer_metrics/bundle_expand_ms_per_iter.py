from harness import phase_reduce

LAYER = "grower"
MOVES = "train_s_per_iter"
SOURCE = "device_trace"
DRIVERS = ("train",)


def read(record):
    """Leaf device time under the grower's `bundle_expand` scope (a `(G, BH, 3)`
    bundle histogram gathered out to `(F, B, 3)` per-feature histograms before
    each split search), per traced iteration: the phases keyed
    `<outer>/bundle_expand`, whichever phase they sit in (each is also counted
    in its outer phase's own reader).  A program whose vocabulary has no such
    word reports nothing."""
    from lightgbm_tpu.obs import phases

    tab = phase_reduce.table()
    if tab is None or not hasattr(phases, "BUNDLE_EXPAND"):
        return None
    busy = sum(row["busy_s"] for name, row in tab["phases"].items()
               if name.split("/")[-1] == phases.BUNDLE_EXPAND)
    return 1e3 * busy / record["iters"]
