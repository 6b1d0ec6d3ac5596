"""Bytes of the data-parallel trainer's histogram all-reduces, computed from
the program's own counters (the `trees_from_records` spans of the traced
window), and the chip's inter-chip peak they are held against.  Beside
harness/hist_ops.py, which models one kernel's operations and HBM bytes; this
models what crosses chips.

The counters (`lightgbm_tpu/boosting/ptrainer.py::stream_counts`): `shards`,
the mesh's size; `allreduce_calls` and `allreduce_bytes`, the calls ONE chip
made to the histogram `psum`s over the span's trees and the float32 bytes of
their operands (a tree's root histogram, a level's `(slots, 16, lanes)` rows,
two histograms a tail split as `(6, lanes)` planes).  That is payload, what the algorithm hands over;
what a chip must then put on its links depends on how the reduction is done:

  link bytes  2 x (shards - 1) / shards x payload: a bandwidth-optimal
              all-reduce (reduce-scatter, then all-gather, as a ring or a
              bidirectional ring does it) has every chip SEND that much and
              receive as much.  One chip: 0.  NOT counted, so that the share
              reads lower rather than higher: a second pass where the
              collective is done in pieces, and latency terms.

The peak is harness/ici_peaks.json, by `device_kind`, with its source; an
unknown kind is an error, never a default.  A program older than the counters
has spans without them; every function here then returns None and so do the
readers."""

import json
import os

COUNTERS = ("allreduce_calls", "allreduce_bytes", "shards")
_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "ici_peaks.json")


def counters(record) -> dict:
    """The sums of the window's `trees_from_records` counters, or None if a
    span lacks one (a program without them) or the window trained no tree."""
    spans = [s for s in record["program_spans"] if s["name"] == "trees_from_records"]
    if not spans or any(k not in s for s in spans for k in COUNTERS):
        return None
    out = {k: sum(s[k] for s in spans) for k in ("allreduce_calls", "allreduce_bytes")}
    out["shards"] = spans[0]["shards"]
    return out


def link_bytes(c: dict) -> float:
    """Bytes ONE chip must send for the window's all-reduces, done at best."""
    return 2.0 * (c["shards"] - 1) / c["shards"] * c["allreduce_bytes"]


def device_kind() -> str:
    """The kind of the devices this process measures on."""
    import jax

    return jax.devices()[0].device_kind


def ici_bytes_per_s(kind: str) -> float:
    with open(_PEAKS) as f:
        table = json.load(f)
    if kind not in table:
        raise KeyError(f"no inter-chip peak for device kind {kind!r} in {_PEAKS} "
                       f"(have: {sorted(table)})")
    return table[kind]["ici_bytes_per_s"]


def collective_seconds(record):
    """Seconds a chip spent in collective operations in the window (the union
    xplane_reduce takes, averaged over the chips), or None off the chip or
    where there was none."""
    dev = record["device"]
    return (dev["collective_s"] or None) if dev is not None else None


def share(record):
    """100 x (link bytes / collective seconds) / the chip's inter-chip peak,
    or None."""
    c, s = counters(record), collective_seconds(record)
    if c is None or s is None or not c["allreduce_bytes"]:
        return None
    return 100.0 * link_bytes(c) / s / ici_bytes_per_s(device_kind())
