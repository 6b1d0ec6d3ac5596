"""Operations and bytes of the level-batched histogram kernel, `level_stream`,
computed from the program's own counters (the `trees_from_records` spans of
the traced window) and shapes alone, and the kernel's device time in the same
window.  Beside harness/roofline.py, which models the bytes of every streaming
pass together; this one models ONE kernel, on both sides of the roofline.

Per row that `level_stream` streams (the counter `level_rows`: every row of
every segment it partitions, summed over levels and trees):

  operations  the one-hot histogram dots: (value rows) x (one-hot cells) x 2.
              The value operand has VALUE_ROWS = 14 rows (3-plane gradient,
              3-plane hessian and a count, for the left and the right child);
              the one-hot operand has `hist_cells` cells (the counter: columns
              x padded bins, padded to the lane tile, as the kernel issues
              them).  NOT counted, so that the share reads lower rather than
              higher: the two (4 x channels, 1024) x (1024, 1024) permutation
              dots that move a block's rows to their side, and the (2, 1024) x
              (1024, 1024) running count; they are work the partition adds to
              the histogram, and a later reader can model them.
  bytes       every channel of the row read once and written once:
              channels x 4 x 2.
And per segment (the counter `level_segments`) one (16, hist_cells) float32
histogram block written.

A program older than the counters has spans without them; every function here
then returns None and so do the readers."""

VALUE_ROWS = 14
HIST_ROWS = 16  # what a segment's histogram block holds: VALUE_ROWS padded to the sublane tile
KERNEL = "level_stream"
COUNTERS = ("level_rows", "level_segments", "hist_cells", "channels")


def counters(record) -> dict:
    """The sums of the window's `trees_from_records` counters, or None if a
    span lacks one (a program without them) or the window trained no tree."""
    spans = [s for s in record["program_spans"] if s["name"] == "trees_from_records"]
    if not spans or any(k not in s for s in spans for k in COUNTERS):
        return None
    out = {k: sum(s[k] for s in spans) for k in ("level_rows", "level_segments")}
    out.update({k: spans[0][k] for k in ("hist_cells", "channels")})
    return out if out["level_rows"] else None


def kernel_seconds(record):
    """Device time of the kernel's launches in the window (leaf events of the
    `XLA Ops` line labelled `level_stream (...)`), averaged over the chips."""
    dev = record["device"]
    if dev is None:
        return None
    s = sum(v for k, v in dev["leaf_op_s"].items() if k.split(" ", 1)[0] == KERNEL)
    return s or None


def flops(c: dict) -> float:
    return 2.0 * VALUE_ROWS * c["hist_cells"] * c["level_rows"]


def hbm_bytes(c: dict) -> float:
    return (2.0 * 4 * c["channels"] * c["level_rows"]
            + 4.0 * HIST_ROWS * c["hist_cells"] * c["level_segments"])


def share(record, amount, peak: str):
    """100 x (amount(counters) / kernel seconds) / the chip's peak, or None."""
    c, s = counters(record), kernel_seconds(record)
    if c is None or s is None or "peaks" not in record:
        return None
    return 100.0 * amount(c) / s / record["peaks"][peak]
