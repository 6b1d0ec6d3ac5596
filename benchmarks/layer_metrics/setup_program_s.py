from harness import stages

LAYER = "entry"
MOVES = "setup_s"
SOURCE = "program_span"
DRIVERS = ("train",)


def read(record):
    """Seconds of set-up inside the program: every stage of depth 0 that ended
    before the window.  The rest of `setup_s` is the interpreter, `import jax`,
    the backend's start, the benchmark's generator and the device's run of the
    warm-up iterations."""
    kept = stages.setup_stages(record)
    if kept is None:
        return None
    durs = [s["dur_s"] for s in kept if s["depth"] == 0]
    return sum(durs) if durs else None
