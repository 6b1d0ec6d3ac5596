LAYER = "kernels"
MOVES = "train_s_per_iter"
SOURCE = "program_counter"
DRIVERS = ("train",)


def read(record):
    """Column groups in which a streaming kernel walks one block's bin words
    (the counter on the program's `trees_from_records` spans): 1 up to 31
    columns, 63 at 2,000.  A later change of the grouping shows here."""
    got = [s["col_groups"] for s in record["program_spans"]
           if s["name"] == "trees_from_records" and "col_groups" in s]
    return max(got) if got else None
