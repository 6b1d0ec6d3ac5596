"""The table of peaks, and the bytes the trainer's streaming passes must move,
computed from shapes and tree sizes alone.  Kept here so that no PR to the
program can move the yardstick."""

import json
import os

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of that kind.  A device that is not in
    peaks.json is an error, never a default."""
    with open(_PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {_PEAKS} "
                       f"(have: {sorted(table)})")
    return table[device_kind]


def packed_channels(num_features: int, bits: int = 8) -> tuple:
    """(bin words, channels) of the trainer's packed (channels, rows) int32
    matrix: 32/bits bins to a word, padded to 8 sublanes, plus one 8-row band
    that holds gradient, hessian, select, score, label, row id and weight."""
    words = -(-num_features * bits // 32)
    return words, -(-words // 8) * 8 + 8


def train_stream_bytes(rows: int, num_features: int, split_parent_rows: int) -> int:
    """Bytes ONE chip must move through HBM for one boosting iteration (one
    tree) over `rows` rows of its own, given the summed row counts of the
    nodes the tree split (`split_parent_rows`, from the trained tree).

    1. Gradient pass with the root histogram: read every row's bin words and
       its score, label, weight and select; write gradient, hessian and score:
       (words + 4 + 3) * 4 bytes a row.  The previous tree's leaf values arrive
       as one more float a row: + 4 bytes.
    2. Each split partitions its parent's rows in place and builds both
       children's histograms in the same pass: every channel of every row of
       the parent is read once and written once, 2 * channels * 4 bytes a row.

    Left out on purpose, so that they show as a lower share: the gather back
    to canonical row order that the trainer makes before every tree (a choice
    for reproducible sums, not part of the algorithm), and the partitions the
    level-batched grower makes for candidate splits it then does not take."""
    words, channels = packed_channels(num_features)
    return (words + 8) * 4 * rows + 2 * channels * 4 * split_parent_rows
