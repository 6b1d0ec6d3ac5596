from harness import stages

LAYER = "parallel"
MOVES = "setup_s"
SOURCE = "program_span"
DRIVERS = ("train",)


def read(record):
    """Seconds of the `shard_pack` stage (`ShardedPartitionedTrainer.__init__`):
    the shards packed with numpy on the host one after another, and their upload
    over the mesh, ended by one wait."""
    return stages.total(record, "shard_pack")
