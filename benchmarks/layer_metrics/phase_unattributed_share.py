from harness import phase_reduce

LAYER = "device"
MOVES = "train_s_per_iter"
SOURCE = "device_trace"
DRIVERS = ("train",)


def read(record):
    """Share of the chunk program's busy time in leaf operations that no phase
    claims: the instrument's own health.  High means a scope is missing in the
    program, or the map was built from another executable than the one traced."""
    tab = phase_reduce.table()
    if tab is None or not tab.get("chunk_program", {}).get("busy_s"):
        return None
    return 100.0 * phase_reduce.phase_total(tab, phase_reduce.NO_PHASE) / tab["chunk_program"]["busy_s"]
