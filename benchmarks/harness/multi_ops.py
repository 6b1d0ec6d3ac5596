"""Operations and bytes of the multiclass gradient pass, `update_multi_and_hists`
(the program's `multi_update` scope: all K classes' g and h planes and root
histograms from one score snapshot, one pass a boosting iteration), from the
program's own counters on the `trees_from_records` spans of the traced window,
the record's `rows` (the training rows a chip, which drivers/train_multiclass.py
adds) and the kernel's device time in the same window.  Beside
harness/hist_ops.py, which models `level_stream` the same way.

Per iteration (`trees` / `classes` on the spans):

  operations  the one-hot histogram dots: 2 x rows x `update_value_rows` (the
              value rows a block carries, 6K + 1 padded to the sublane tile: 48
              at K = 7) x `hist_cells` (columns x padded bins, padded to the
              lane tile, as the kernel issues them).
  bytes       every channel of every row read once, C x rows x 4 (`channels`);
              the 2K gradient rows written, 2K x rows x 4; and the K root
              histograms, the (`update_value_rows`, `hist_cells`) float32 block
              the kernel writes.  NOT counted, so that the share reads lower
              rather than higher: the rest of the band the kernel writes back
              with the gradient rows, and the aux rows it reads beside them.

A program without the counters (`classes`, `update_value_rows`) has spans
without them; every function here then returns None and so do the readers."""

KERNEL = "update_multi_and_hists"
COUNTERS = ("trees", "classes", "update_value_rows", "hist_cells", "channels")


def counters(record) -> dict:
    """The window's iterations and the pass's shape, or None if a span lacks a
    counter, the record lacks `rows`, the objective is not multiclass or the
    window trained no tree."""
    spans = [s for s in record["program_spans"] if s["name"] == "trees_from_records"]
    if not spans or "rows" not in record or any(k not in s for s in spans for k in COUNTERS):
        return None
    out = {k: spans[0][k] for k in COUNTERS[1:]}
    if out["classes"] < 2:
        return None
    out["iters"] = sum(s["trees"] for s in spans) // out["classes"]
    out["rows"] = record["rows"]
    return out if out["iters"] else None


def kernel_seconds(record):
    """Device time of the kernel's launches in the window (leaf events of the
    `XLA Ops` line labelled `update_multi_and_hists (...)`)."""
    dev = record["device"]
    if dev is None:
        return None
    s = sum(v for k, v in dev["leaf_op_s"].items() if k.split(" ", 1)[0] == KERNEL)
    return s or None


def flops(c: dict) -> float:
    return 2.0 * c["iters"] * c["rows"] * c["update_value_rows"] * c["hist_cells"]


def hbm_bytes(c: dict) -> float:
    k = c["classes"]
    return c["iters"] * (4.0 * c["rows"] * (c["channels"] + 2 * k)
                         + 4.0 * c["update_value_rows"] * c["hist_cells"])


def share(record, amount, peak: str):
    """100 x (amount(counters) / kernel seconds) / the chip's peak, or None."""
    c, s = counters(record), kernel_seconds(record)
    if c is None or s is None or "peaks" not in record:
        return None
    return 100.0 * amount(c) / s / record["peaks"][peak]

