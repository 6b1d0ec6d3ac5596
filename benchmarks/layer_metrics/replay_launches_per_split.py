from harness import phase_reduce

LAYER = "grower"
MOVES = "train_s_per_iter"
SOURCE = "device_trace"
DRIVERS = ("train",)


def read(record):
    """Leaf device launches under the `replay` scope per split of the traced
    trees (the `splits` the program's `trees_from_records` spans carry; every
    chip replays every split): the fixed cost of accepting one split."""
    tab = phase_reduce.table()
    splits = sum(s.get("splits", 0) for s in record["program_spans"]
                 if s["name"] == "trees_from_records")
    if tab is None or not splits:
        return None
    from lightgbm_tpu.obs.phases import REPLAY

    return phase_reduce.phase_total(tab, REPLAY, "launches") / splits
