LAYER = "kernels"
MOVES = "train_s_per_iter"
SOURCE = "program_counter"
DRIVERS = ("train",)


def read(record):
    """EFB bundle columns the packed matrix holds in place of the features (the
    counter on the program's `trees_from_records` spans; 0 for an unbundled
    matrix): what the streaming kernels walk, 10 for the 700 one-hot columns.
    A program without the counter reports nothing."""
    got = [s["bundle_cols"] for s in record["program_spans"]
           if s["name"] == "trees_from_records" and "bundle_cols" in s]
    return max(got) if got else None
