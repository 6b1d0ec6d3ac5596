from harness import stages

LAYER = "fused_trainer"
MOVES = "setup_s"
SOURCE = "program_span"
DRIVERS = ("train",)


def read(record):
    """Seconds of the calls on which a watched program was built
    (`program_build` stages of `obs/compilewatch.py::JitWatch`, summed over the
    programs built before the window): trace, lowering, compile or cache load,
    and dispatch, up to the call's return; not the device's run."""
    return stages.total(record, "program_build")
