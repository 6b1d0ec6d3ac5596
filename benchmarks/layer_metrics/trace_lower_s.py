from harness import stages

LAYER = "fused_trainer"
MOVES = "setup_s"
SOURCE = "program_span"
DRIVERS = ("train",)


def read(record):
    """Seconds of the programs' first calls that no compile cache removes: each
    `program_build` stage before the window, less its `backend_s` (the back-end
    compile, or the load from the persistent cache).  What is left is tracing
    the program to a jaxpr, lowering that to MLIR, and the dispatch."""
    kept = stages.setup_stages(record)
    built = [s for s in kept or () if s["name"] == "program_build"]
    return sum(s["dur_s"] - s["backend_s"] for s in built) if built else None
