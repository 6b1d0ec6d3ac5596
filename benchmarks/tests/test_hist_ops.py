"""harness/hist_ops.py: the counters' sums, the two models' arithmetic, and
silence (None, no raise) on a record of a program older than the counters."""

from harness import hist_ops

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _span(**counts):
    return {"name": "trees_from_records", "trees": 4, "splits": 1016, **counts}


def _record(spans, seconds=2.0):
    return {"program_spans": spans + [{"name": "chunk_program"}], "peaks": PEAKS,
            "device": {"leaf_op_s": {"level_stream (s32[512,401024], f32[256,16,128000])": seconds,
                                     "split_stream (s32[512,401024])": 9.0}}}


COUNTS = dict(level_rows=13_000_000, level_segments=1020, levels=36, hist_cells=128_000,
              channels=512, col_groups=63)


def test_counters_sum_over_the_windows_spans():
    c = hist_ops.counters(_record([_span(**COUNTS), _span(**COUNTS)]))
    assert c == {"level_rows": 26_000_000, "level_segments": 2040, "hist_cells": 128_000,
                 "channels": 512}


def test_models_and_shares():
    rec = _record([_span(**COUNTS)])
    c = hist_ops.counters(rec)
    assert hist_ops.flops(c) == 2 * 14 * 128_000 * 13_000_000
    assert hist_ops.hbm_bytes(c) == 8 * 512 * 13_000_000 + 4 * 16 * 128_000 * 1020
    assert hist_ops.kernel_seconds(rec) == 2.0  # level_stream alone, not split_stream
    mxu = hist_ops.share(rec, hist_ops.flops, "bf16_flops_per_s")
    assert abs(mxu - 100 * 2 * 14 * 128_000 * 13e6 / 2.0 / 197e12) < 1e-9 and 0 < mxu < 100


def test_a_program_without_the_counters_reads_nothing():
    old = _record([_span()])  # trees and splits only, as the parent writes them
    assert hist_ops.counters(old) is None
    assert hist_ops.share(old, hist_ops.flops, "bf16_flops_per_s") is None
    assert hist_ops.share(_record([]), hist_ops.hbm_bytes, "hbm_bytes_per_s") is None
    rehearsal = {"program_spans": [_span(**COUNTS)], "device": None}
    assert hist_ops.share(rehearsal, hist_ops.flops, "bf16_flops_per_s") is None
