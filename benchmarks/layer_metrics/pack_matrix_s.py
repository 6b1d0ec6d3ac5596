from harness import stages

LAYER = "fused_trainer"
MOVES = "setup_s"
SOURCE = "program_span"
DRIVERS = ("train",)


def read(record):
    """Seconds to put the training matrix on the device as the fused trainer
    wants it: the `bins_upload` stage (`GBDT.init`) plus the `pack_matrix` stage
    (`PartitionedTrainer.__init__`: the bundled matrix's upload, and
    `pack_matrix_device`), each ended by one wait."""
    return stages.total(record, "bins_upload", "pack_matrix")
