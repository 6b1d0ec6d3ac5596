LAYER = "kernels"
MOVES = "train_s_per_iter"
SOURCE = "device_trace"
DRIVERS = ("train",)


def read(record):
    """The bytes the streaming passes must move (harness/roofline.py, from the
    shapes and the traced trees' node sizes) over the device time of the Mosaic
    kernels, all of which stream the packed matrix, as a share of the chip's
    peak HBM bandwidth.  Bound by bytes, not by operations."""
    dev = record["device"]
    if dev is None or not dev["mosaic_s"]:
        return None
    achieved = record["stream_bytes_per_iter"] * record["iters"] / dev["mosaic_s"]
    return 100.0 * achieved / record["peaks"]["hbm_bytes_per_s"]
