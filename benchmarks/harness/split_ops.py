"""Bytes of the per-split partition kernel, `split_stream`, computed from the
program's own counters (the `trees_from_records` spans of the traced window)
and shapes alone, and the kernel's device time in the same window.  Beside
harness/hist_ops.py, which does the same for `level_stream`.

The replay launches `split_stream` for every split it accepts, but only a
split the level phase did not precompute (a TAIL split) streams rows: the
others run on an empty segment.  The counters `tail_splits` and `tail_rows`
say how many there were and how many rows their parents' segments held.

  bytes   every channel of every row of a tail split's parent read once and
          written once, `tail_rows` x `channels` x 4 x 2; and both children's
          histograms written as (HIST_ROWS, `hist_cells`) float32 rows a
          split, `tail_splits` x 2 x 16 x `hist_cells` x 4.
  time    every launch of the kernel in the window, the empty ones included
          (tens of microseconds each), so the share reads lower rather than
          higher.

A program older than the counters has spans without them; every function here
then returns None and so do the readers."""

from .hist_ops import HIST_ROWS

KERNEL = "split_stream"
COUNTERS = ("tail_splits", "tail_rows", "hist_cells", "channels")


def counters(record) -> dict:
    """The sums of the window's `trees_from_records` counters, or None if a
    span lacks one (a program without them) or the window trained no tree."""
    spans = [s for s in record["program_spans"] if s["name"] == "trees_from_records"]
    if not spans or any(k not in s for s in spans for k in COUNTERS):
        return None
    out = {k: sum(s[k] for s in spans) for k in ("tail_splits", "tail_rows")}
    out.update({k: spans[0][k] for k in ("hist_cells", "channels")})
    return out


def kernel_seconds(record):
    """Device time of the kernel's launches in the window (leaf events of the
    `XLA Ops` line labelled `split_stream (...)`), averaged over the chips."""
    dev = record["device"]
    if dev is None:
        return None
    s = sum(v for k, v in dev["leaf_op_s"].items() if k.split(" ", 1)[0] == KERNEL)
    return s or None


def hbm_bytes(c: dict) -> float:
    return (2.0 * 4 * c["channels"] * c["tail_rows"]
            + 2.0 * 4 * HIST_ROWS * c["hist_cells"] * c["tail_splits"])


def share(record):
    """100 x (bytes / kernel seconds) / the chip's peak HBM bandwidth, or None
    (no counters, no device plane, no peaks, or a window without a tail split)."""
    c, s = counters(record), kernel_seconds(record)
    if c is None or s is None or "peaks" not in record or not c["tail_splits"]:
        return None
    return 100.0 * hbm_bytes(c) / s / record["peaks"]["hbm_bytes_per_s"]
