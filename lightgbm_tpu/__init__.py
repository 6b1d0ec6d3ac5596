"""lightgbm_tpu — a TPU-native gradient boosting framework.

A from-scratch reimplementation of the capabilities of LightGBM (reference:
sky-noodle/LightGBM) designed for TPU
hardware: binned feature matrices live in HBM as dense device arrays,
histograms are built by XLA/Pallas kernels (one-hot matmul onto the MXU),
split finding is a vectorized prefix-scan, tree growth is a single jitted
`lax.fori_loop`, and distributed training uses `jax.sharding.Mesh` +
`shard_map` with XLA collectives (psum / all_gather / reduce_scatter) over
ICI/DCN in place of the reference's socket/MPI Network layer.

Public API mirrors the reference python-package (python-package/lightgbm):
`Dataset`, `Booster`, `train`, `cv`, sklearn wrappers, callbacks, plotting.
"""

__version__ = "0.1.0"


def enable_compile_cache() -> None:
    """Turn on JAX's persistent compilation cache for this process.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads the
    directory from it and this function sets none: the operator (or the
    machine image) decides where compiled programs live.  Otherwise the
    cache is the one fixed directory ``<checkout>/.jax_cache`` — the
    path is part of the cache key, so it must not depend on the host
    name, on the backend, or on whether the tree is a git checkout.

    Called from the entry points that compile (GBDT construction, serve
    start-up), never at import: it asks JAX which backend it has."""
    import os

    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(checkout, ".jax_cache"))
    if jax.default_backend() == "tpu":
        # on the chip every program is worth keeping, not only those over
        # JAX's 1 s default: the serve ladder's 50 programs compile in
        # ~0.1 s each, and caching them too took a warm server start from
        # 21.4 s to 13.1 s and a warm chip_smoke.py from 100 s to 74 s (my
        # chip runs, PR 22).  CPU test runs keep the default: there the
        # extra writes cost more than the reads save on a cold run.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


from .basic import Booster, Dataset
from .engine import cv, train
from .callback import early_stopping, log_evaluation, record_evaluation, reset_parameter
from .ckpt import CheckpointManager
from .utils.log import LightGBMError

try:  # sklearn wrappers are optional (sklearn is present in CI images)
    from .sklearn import LGBMModel, LGBMRegressor, LGBMClassifier, LGBMRanker
except ImportError:  # pragma: no cover
    pass

try:  # plotting needs matplotlib (graphviz optional for plot_tree)
    from . import plotting
    from .plotting import plot_importance, plot_metric, plot_tree, create_tree_digraph
except ImportError:  # pragma: no cover
    pass

from . import config, metric, objective

__all__ = [
    "Dataset",
    "Booster",
    "LightGBMError",
    "train",
    "cv",
    "CheckpointManager",
    "LGBMModel",
    "LGBMRegressor",
    "LGBMClassifier",
    "LGBMRanker",
    "early_stopping",
    "log_evaluation",
    "record_evaluation",
    "reset_parameter",
    "plot_importance",
    "plot_metric",
    "plot_tree",
    "create_tree_digraph",
]
