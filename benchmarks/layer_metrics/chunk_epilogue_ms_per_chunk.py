from harness import phase_reduce

LAYER = "fused_trainer"
MOVES = "train_s_per_iter"
SOURCE = "device_trace"
DRIVERS = ("train",)


def read(record):
    """Leaf device time under the chunk program's `chunk_epilogue` scope
    (settle the last delta, scores back to original order), per chunk
    dispatched: paid once a chunk, like `host_ms_per_chunk`."""
    return phase_reduce.phase_ms(record, "CHUNK_EPILOGUE", per="laps")
