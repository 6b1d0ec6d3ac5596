from harness import phase_reduce

LAYER = "grower"
MOVES = "train_s_per_iter"
SOURCE = "device_trace"
DRIVERS = ("train",)


def read(record):
    """Device time in copies of the whole packed matrix (`copy` instructions
    whose result has the shape of the chunk program's largest parameter), all
    phases, per traced iteration.  phases.json has them site by site."""
    tab = phase_reduce.table()
    if tab is None:
        return None
    return 1e3 * sum(s["busy_s"] for s in tab["matrix_copies"]) / record["iters"]
