"""Versioned training-state snapshots (``TrainState``).

A checkpoint must reproduce training *exactly*, so the state is the
closure of everything the boosting drivers read across an iteration
boundary:

  - the ensemble's trees in **binary** — stacked SoA arrays in the same
    spirit as the serving ``PredictorArtifact`` npz layout (one entry
    per ``Tree`` field, ``(T, M)``/``(T, L)`` padded), but *complete*:
    training needs bin-space thresholds, leaf counts/parents and
    per-tree shrinkage that the inference artifact drops, and a text
    round-trip through ``%g`` formatting would not be bit-faithful;
  - the device score caches (train + every valid set) in f32;
  - every RNG stream: the bagging ``RandomState``, the
    feature-fraction ``utils.random.Random``, DART's drop ``Random``,
    GOSS's chained ``PRNGKey`` (the fused partitioned trainers need no
    RNG state — they fold a static base key with the iteration number);
  - early-stopping bests / messages and the iteration counter;
  - the fused partitioned trainer's physical row permutation (histogram
    accumulation order follows the partition layout, so restarting from
    an identity layout would change float summation order);
  - config + dataset fingerprints: resume **refuses** to run on a
    mismatch instead of silently training a different problem.

Serialization is one ``.npz`` (uncompressed — checkpoint cadence beats
bytes) with a ``__meta__`` JSON entry, mirroring ``serve/artifact.py``.
"""

from __future__ import annotations

import dataclasses
import hashlib
import io
import json
import zlib
from typing import Any, Dict, Optional

import numpy as np

from ..model.tree import Tree
from ..utils.log import Log

FORMAT_VERSION = 1

# Tree SoA fields: (name, dtype, padded-axis) where axis "m" arrays hold
# num_leaves-1 node records and "l" arrays hold num_leaves leaf records.
_TREE_FIELDS = (
    ("left_child", np.int32, "m"),
    ("right_child", np.int32, "m"),
    ("split_feature_inner", np.int32, "m"),
    ("split_feature", np.int32, "m"),
    ("threshold_in_bin", np.int32, "m"),
    ("threshold", np.float64, "m"),
    ("decision_type", np.int8, "m"),
    ("default_value", np.float64, "m"),
    ("zero_bin", np.int32, "m"),
    ("default_bin_for_zero", np.int32, "m"),
    ("split_gain", np.float64, "m"),
    ("internal_value", np.float64, "m"),
    ("internal_count", np.int64, "m"),
    ("leaf_parent", np.int32, "l"),
    ("leaf_value", np.float64, "l"),
    ("leaf_count", np.int64, "l"),
)

# Config fields that may legitimately differ between the original run
# and its resume (paths, task plumbing, run length, verbosity) — they
# never change the per-iteration math, so they stay out of the
# fingerprint.
_FP_VOLATILE = {
    "task", "config_file", "data", "valid_data", "input_model",
    "output_model", "output_result", "convert_model",
    "convert_model_language", "num_iterations", "num_iteration_predict",
    "snapshot_freq", "verbose", "num_threads", "is_save_binary_file",
    "is_predict_leaf_index", "is_predict_raw_score", "output_freq",
    "metric_freq", "machine_list_file", "local_listen_port", "time_out",
    "checkpoint_dir", "checkpoint_freq", "checkpoint_keep",
    "checkpoint_resume", "is_training_metric", "pred_early_stop",
    "pred_early_stop_freq", "pred_early_stop_margin",
    # prefetch depth only changes pipelining, never the math (the
    # math-relevant out_of_core/ooc_chunk_rows stay fingerprinted, and
    # the chunk grid itself is checked via meta["ooc_schedule"])
    "ooc_prefetch_depth",
    # topology-portable checkpoints: the world size is recorded in the
    # canonical container's metadata, not in the config fingerprint — a
    # world-4 checkpoint must resume at world 2/8 (docs/CHECKPOINT.md).
    # The rebalance policy knobs only steer WHEN shards move, never the
    # per-iteration math on a given shard layout.
    "num_machines", "rebalance", "rebalance_threshold",
    "rebalance_patience", "rebalance_max_move_frac",
    # live membership is a transport/topology property, not math: a
    # checkpoint written by an elastic fleet must resume on a static
    # one and vice versa (parallel/membership.py, docs/ROBUSTNESS.md)
    "elastic_membership",
}


class CheckpointMismatch(RuntimeError):
    """Resume refused: the checkpoint was written by a different
    config or against a different dataset."""


# ----------------------------------------------------------------------
# fingerprints
# ----------------------------------------------------------------------
def config_fingerprint(config) -> str:
    """Stable digest of the math-relevant configuration."""
    d = dataclasses.asdict(config)
    for key in _FP_VOLATILE:
        d.pop(key, None)
    blob = json.dumps(d, sort_keys=True, default=str)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def _resident_matrix(binned_ds) -> np.ndarray:
    """What identifies the dataset's rows: its per-feature bins or, for a
    dataset made from sparse input, the bundle columns it holds in their
    place (fingerprinting must not decode an ``(N, F)`` matrix)."""
    if getattr(binned_ds, "has_dense_bins", True):
        return np.asarray(binned_ds.binned)
    return np.asarray(binned_ds.bundled)


def data_fingerprint(binned_ds) -> str:
    """Digest of the constructed dataset (binned matrix + label).  CRC32
    keeps this cheap even at large N; cached on the dataset object so
    periodic checkpoints don't rescan the matrix."""
    cached = getattr(binned_ds, "_ckpt_fingerprint", None)
    if cached is not None:
        return cached
    binned = _resident_matrix(binned_ds)
    # block-wise CRC: chunked zlib.crc32 equals the whole-buffer value,
    # and never materializes a memmapped (out-of-core) matrix
    crc = 0
    step = 65536
    for s in range(0, binned.shape[0], step):
        crc = zlib.crc32(
            np.ascontiguousarray(binned[s: s + step]).tobytes(), crc)
    label = binned_ds.metadata.label
    if label is not None:
        crc = zlib.crc32(np.ascontiguousarray(np.asarray(label)).tobytes(), crc)
    fp = f"{binned.shape[0]}x{binned.shape[1]}:{crc & 0xFFFFFFFF:08x}"
    binned_ds._ckpt_fingerprint = fp
    return fp


# -- shard-composable fingerprints -------------------------------------
# Under the pre-partition contract the global dataset is the row-order
# concatenation of the rank shards, so the global data_fingerprint is
# derivable from per-shard CRC primitives via zlib's crc32_combine
# identity crc(A||B) = combine(crc(A), crc(B), len(B)) — no rank ever
# has to materialize (or even see) another rank's rows.

def _gf2_matrix_times(mat, vec: int) -> int:
    out = 0
    i = 0
    while vec:
        if vec & 1:
            out ^= mat[i]
        vec >>= 1
        i += 1
    return out


def _gf2_matrix_square(square, mat) -> None:
    for n in range(32):
        square[n] = _gf2_matrix_times(mat, mat[n])


def crc32_combine(crc1: int, crc2: int, len2: int) -> int:
    """zlib's crc32_combine: CRC of the concatenation A||B from
    ``crc32(A)``, ``crc32(B)`` and ``len(B)`` (GF(2) matrix powering of
    the CRC polynomial over len2 zero bytes)."""
    if len2 <= 0:
        return crc1 & 0xFFFFFFFF
    even = [0] * 32
    odd = [0] * 32
    odd[0] = 0xEDB88320  # CRC-32 polynomial, reflected
    row = 1
    for n in range(1, 32):
        odd[n] = row
        row <<= 1
    _gf2_matrix_square(even, odd)
    _gf2_matrix_square(odd, even)
    crc1 &= 0xFFFFFFFF
    while True:
        _gf2_matrix_square(even, odd)
        if len2 & 1:
            crc1 = _gf2_matrix_times(even, crc1)
        len2 >>= 1
        if len2 == 0:
            break
        _gf2_matrix_square(odd, even)
        if len2 & 1:
            crc1 = _gf2_matrix_times(odd, crc1)
        len2 >>= 1
        if len2 == 0:
            break
    return (crc1 ^ (crc2 & 0xFFFFFFFF)) & 0xFFFFFFFF


def data_fingerprint_parts(binned_ds) -> Dict[str, int]:
    """CRC primitives of one shard, composable across shards: separate
    binned-matrix and label CRCs plus their byte lengths and the row
    grid.  :func:`combine_fingerprint_parts` folds a rank-ordered list
    of these into the exact string :func:`data_fingerprint` would
    produce over the concatenated rows."""
    cached = getattr(binned_ds, "_ckpt_fp_parts", None)
    if cached is not None:
        return dict(cached)
    binned = _resident_matrix(binned_ds)
    crc_b = 0
    step = 65536
    for s in range(0, binned.shape[0], step):
        crc_b = zlib.crc32(
            np.ascontiguousarray(binned[s: s + step]).tobytes(), crc_b)
    label = binned_ds.metadata.label
    crc_l, len_l = 0, 0
    if label is not None:
        lab = np.ascontiguousarray(np.asarray(label)).tobytes()
        crc_l, len_l = zlib.crc32(lab), len(lab)
    parts = {
        "rows": int(binned.shape[0]), "cols": int(binned.shape[1]),
        "crc_binned": crc_b & 0xFFFFFFFF, "len_binned": int(binned.nbytes),
        "crc_label": crc_l & 0xFFFFFFFF, "len_label": int(len_l),
    }
    binned_ds._ckpt_fp_parts = dict(parts)
    return parts


def combine_fingerprint_parts(parts) -> str:
    """Rank-ordered shard parts -> the global-dataset fingerprint (equal
    to :func:`data_fingerprint` over the row concatenation)."""
    parts = [dict(p) for p in parts]
    rows = sum(int(p["rows"]) for p in parts)
    cols = int(parts[0]["cols"]) if parts else 0
    crc_b = 0
    for p in parts:
        if int(p["cols"]) != cols:
            raise CheckpointMismatch(
                f"shard column counts disagree: {cols} vs {p['cols']}")
        crc_b = crc32_combine(crc_b, int(p["crc_binned"]),
                              int(p["len_binned"]))
    crc_l, len_l = 0, 0
    for p in parts:
        crc_l = crc32_combine(crc_l, int(p["crc_label"]),
                              int(p["len_label"]))
        len_l += int(p["len_label"])
    crc = crc32_combine(crc_b, crc_l, len_l)
    return f"{rows}x{cols}:{crc & 0xFFFFFFFF:08x}"


# ----------------------------------------------------------------------
# binary tree pack/unpack (bit-exact round trip)
# ----------------------------------------------------------------------
def pack_trees(models) -> Dict[str, np.ndarray]:
    """List[Tree] -> stacked ``(T, M)``/``(T, L)`` arrays + per-tree
    scalars, prefixed ``tree_``.  Only the live slices (``num_leaves``)
    are meaningful; padding is zero."""
    t = len(models)
    m = max(max((tr.num_leaves - 1 for tr in models), default=1), 1)
    li = max(max((tr.num_leaves for tr in models), default=2), 2)
    out: Dict[str, np.ndarray] = {
        "tree_num_leaves": np.asarray([tr.num_leaves for tr in models], np.int32),
        "tree_shrinkage": np.asarray(
            [tr.shrinkage_rate for tr in models], np.float64
        ),
    }
    for name, dtype, axis in _TREE_FIELDS:
        width = m if axis == "m" else li
        arr = np.zeros((t, width), dtype)
        for i, tr in enumerate(models):
            n = tr.num_leaves
            k = max(n - 1, 1) if axis == "m" else n
            src = getattr(tr, name)
            arr[i, : min(k, len(src))] = src[: min(k, len(src))]
        out["tree_" + name] = arr
    if any(getattr(tr, "is_linear", False) for tr in models):
        out.update(_pack_linear(models, t, li))
    return out


def _pack_linear(models, t: int, li: int) -> Dict[str, np.ndarray]:
    """Linear-leaf model planes (tree/linear.py plug-in) — emitted only
    when at least one tree carries them, so constant-tree checkpoints
    keep the exact pre-strategy key set (bit-identical containers)."""
    kmax = 1
    for tr in models:
        if getattr(tr, "is_linear", False):
            for fs in tr.leaf_features:
                kmax = max(kmax, len(fs))
    is_lin = np.zeros(t, np.int8)
    const = np.zeros((t, li), np.float64)
    leaf_lin = np.zeros((t, li), np.int8)
    cnt = np.zeros((t, li), np.int32)
    feat = np.zeros((t, li, kmax), np.int32)
    feat_inner = np.zeros((t, li, kmax), np.int32)
    coeff = np.zeros((t, li, kmax), np.float64)
    for i, tr in enumerate(models):
        if not getattr(tr, "is_linear", False):
            continue
        is_lin[i] = 1
        n = tr.num_leaves
        const[i, :n] = tr.leaf_const[:n]
        leaf_lin[i, :n] = tr.leaf_is_linear[:n]
        for lj in range(min(n, len(tr.leaf_features))):
            fs = tr.leaf_features[lj]
            cnt[i, lj] = len(fs)
            if fs:
                feat[i, lj, : len(fs)] = fs
                feat_inner[i, lj, : len(fs)] = tr.leaf_features_inner[lj]
                coeff[i, lj, : len(fs)] = tr.leaf_coeff[lj]
    return {
        "tree_is_linear": is_lin,
        "tree_leaf_const": const,
        "tree_leaf_is_linear": leaf_lin,
        "tree_leaf_feat_cnt": cnt,
        "tree_leaf_feat": feat,
        "tree_leaf_feat_inner": feat_inner,
        "tree_leaf_coeff": coeff,
    }


def unpack_trees(arrays: Dict[str, np.ndarray]):
    """Inverse of :func:`pack_trees` — rebuilds host ``Tree`` objects
    field-for-field (no text round trip)."""
    num_leaves = np.asarray(arrays["tree_num_leaves"])
    shrinkage = np.asarray(arrays["tree_shrinkage"])
    models = []
    for i in range(len(num_leaves)):
        n = int(num_leaves[i])
        tree = Tree(max(n, 2))
        tree.num_leaves = n
        for name, dtype, axis in _TREE_FIELDS:
            k = max(n - 1, 1) if axis == "m" else n
            dst = getattr(tree, name)
            src = np.asarray(arrays["tree_" + name][i][:k], dtype)
            dst[: len(src)] = src
        tree.shrinkage_rate = float(shrinkage[i])
        tree.has_categorical = bool(np.any(tree.decision_type[: max(n - 1, 1)] == 1))
        if "tree_is_linear" in arrays and int(arrays["tree_is_linear"][i]):
            tree.is_linear = True
            tree.leaf_const[:n] = np.asarray(
                arrays["tree_leaf_const"][i][:n], np.float64)
            tree.leaf_is_linear[:n] = (
                np.asarray(arrays["tree_leaf_is_linear"][i][:n]) != 0)
            cnt = np.asarray(arrays["tree_leaf_feat_cnt"][i], np.int64)
            tree.leaf_features = []
            tree.leaf_features_inner = []
            tree.leaf_coeff = []
            for lj in range(n):
                c = int(cnt[lj])
                tree.leaf_features.append(
                    tuple(int(v) for v in arrays["tree_leaf_feat"][i][lj][:c]))
                tree.leaf_features_inner.append(
                    tuple(int(v)
                          for v in arrays["tree_leaf_feat_inner"][i][lj][:c]))
                tree.leaf_coeff.append(
                    tuple(np.asarray(arrays["tree_leaf_coeff"][i][lj][:c],
                                     np.float64)))
        models.append(tree)
    return models


# ----------------------------------------------------------------------
# TrainState
# ----------------------------------------------------------------------
class TrainState:
    """One host's complete training state at an iteration boundary."""

    def __init__(self, meta: Dict[str, Any], py: Dict[str, Any],
                 arrays: Dict[str, np.ndarray]):
        self.meta = dict(meta)
        self.py = dict(py)
        self.arrays = dict(arrays)

    @property
    def iteration(self) -> int:
        return int(self.meta["iteration"])

    # -- serialization -------------------------------------------------
    def to_bytes(self) -> bytes:
        payload = dict(self.arrays)
        header = {"meta": self.meta, "py": self.py}
        payload["__meta__"] = np.asarray(json.dumps(header, default=str))
        buf = io.BytesIO()
        np.savez(buf, **payload)
        return buf.getvalue()

    @classmethod
    def from_bytes(cls, blob: bytes) -> "TrainState":
        with np.load(io.BytesIO(blob), allow_pickle=False) as z:
            if "__meta__" not in z:
                raise ValueError("not a TrainState blob (no __meta__)")
            header = json.loads(str(z["__meta__"]))
            arrays = {k: z[k] for k in z.files if k != "__meta__"}
        meta = header["meta"]
        if int(meta.get("format_version", -1)) != FORMAT_VERSION:
            raise ValueError(
                f"unsupported TrainState format_version "
                f"{meta.get('format_version')} (supported: {FORMAT_VERSION})"
            )
        return cls(meta, header["py"], arrays)


# ----------------------------------------------------------------------
# capture / restore
# ----------------------------------------------------------------------
def capture(booster, extra_py: Optional[Dict[str, Any]] = None) -> TrainState:
    """Snapshot a live ``Booster`` into a :class:`TrainState`.

    Pure reads — device arrays are pulled to host, nothing is mutated.
    ``extra_py`` lets the manager attach callback state (early stopping,
    eval history) captured at the same boundary."""
    from ..obs import tracer

    b = booster.boosting
    with tracer.span("ckpt.capture"):
        arrays, py = b.export_train_state()
        arrays.update(pack_trees(b.models))
        meta = {
            "format_version": FORMAT_VERSION,
            "iteration": int(b.iter),
            "boosting_type": type(b).__name__.lower(),
            "num_models": len(b.models),
            "num_tree_per_iteration": int(b.num_tree_per_iteration),
            "num_data": int(b.num_data),
            "config_fingerprint": config_fingerprint(b.config),
            "data_fingerprint": data_fingerprint(b.train_set),
            # shard-composable CRC primitives: lets host 0 derive the
            # GLOBAL dataset fingerprint for the canonical multi-host
            # container without seeing any other rank's rows
            "data_fingerprint_parts": data_fingerprint_parts(b.train_set),
            "num_valid": len(b.valid_scores),
            "best_iteration": int(getattr(booster, "best_iteration", -1)),
        }
        ooc = getattr(b, "ooc", None)
        if ooc is not None:
            # chunk-schedule identity: a resume streaming a different
            # grid would change float summation order
            meta["ooc_schedule"] = ooc.schedule_fingerprint()
        if extra_py:
            py.update(extra_py)
    return TrainState(meta, py, arrays)


def restore(booster, state: TrainState) -> TrainState:
    """Load a :class:`TrainState` into a freshly-constructed ``Booster``
    (same params, same dataset, valid sets already added).  Refuses on a
    config/dataset fingerprint mismatch."""
    from ..obs import tracer

    b = booster.boosting
    cfp, dfp = config_fingerprint(b.config), data_fingerprint(b.train_set)
    if state.meta["config_fingerprint"] != cfp:
        raise CheckpointMismatch(
            "checkpoint was written under a different training config "
            f"(checkpoint {state.meta['config_fingerprint']}, run {cfp}); "
            "refusing to resume — clear the checkpoint directory to start over"
        )
    if state.meta["data_fingerprint"] != dfp:
        raise CheckpointMismatch(
            "checkpoint was written against a different dataset "
            f"(checkpoint {state.meta['data_fingerprint']}, run {dfp}); "
            "refusing to resume"
        )
    want_bt = type(b).__name__.lower()
    if state.meta["boosting_type"] != want_bt:
        raise CheckpointMismatch(
            f"checkpoint boosting type {state.meta['boosting_type']} != {want_bt}"
        )
    if int(state.meta["num_valid"]) != len(b.valid_scores):
        raise CheckpointMismatch(
            f"checkpoint has {state.meta['num_valid']} valid sets, "
            f"run registered {len(b.valid_scores)}"
        )
    ooc = getattr(b, "ooc", None)
    want_sched = state.meta.get("ooc_schedule")
    have_sched = ooc.schedule_fingerprint() if ooc is not None else None
    if (isinstance(want_sched, str) and isinstance(have_sched, str)
            and want_sched.startswith("dist/")
            and have_sched.startswith("dist/")):
        # rank-sharded streaming (boosting/oocdist.py): the schedule is
        # per-RANK, so an elastic resume at a different world size
        # legitimately streams a different local grid.  That is sound —
        # quantized integer folds are associative and f32 folds stay
        # ROW_BLOCK-aligned within each rank — and the GLOBAL dataset
        # fingerprint above still gates the resume.
        pass
    elif want_sched != have_sched:
        raise CheckpointMismatch(
            "checkpoint out-of-core chunk schedule "
            f"{want_sched!r} != this run's {have_sched!r}; resuming "
            "with a different streaming grid would change float "
            "summation order — rerun with the original "
            "out_of_core/ooc_chunk_rows settings"
        )
    with tracer.span("ckpt.restore", iter=state.iteration):
        b.models = unpack_trees(state.arrays)
        b.import_train_state(state.arrays, state.py)
        bi = int(state.meta.get("best_iteration", -1))
        if bi > 0:
            booster.best_iteration = bi
    tracer.event("ckpt.restored", iter=state.iteration,
                 num_models=len(b.models))
    Log.info("Resumed training state at iteration %d (%d trees)",
             state.iteration, len(b.models))
    return state


# ----------------------------------------------------------------------
# topology-portable canonical layout (multi-host save / elastic resume)
# ----------------------------------------------------------------------
# Under the pre-partition contract the global row order is the rank-order
# concatenation of the shards, so one canonical global-row-order
# TrainState represents the fleet regardless of world size: save gathers
# every rank's local state and merges row arrays by concatenation;
# restore slices the SAME container to whatever partition the current
# topology uses.  Shard rebalancing reuses this pair as "checkpoint
# reshape in RAM" (parallel/shardplan.py) — one mechanism, tested two
# ways.

def merge_to_canonical(states) -> TrainState:
    """Per-rank ``TrainState``s (rank order) -> one canonical global
    TrainState.  Row arrays are concatenated in rank order; replicated
    state (trees, feature RNG, GOSS key) comes from rank 0; genuinely
    per-rank state (bagging RNG stream, early-stopping bests, callback
    closures) is kept per rank so a same-partition resume stays
    byte-identical."""
    if not states:
        raise ValueError("merge_to_canonical needs at least one state")
    base = states[0]
    iters = {int(s.meta["iteration"]) for s in states}
    if len(iters) != 1:
        raise CheckpointMismatch(
            f"cannot merge rank states from divergent iterations: {sorted(iters)}")
    nv = int(base.meta["num_valid"])
    shard_rows = [int(s.meta["num_data"]) for s in states]
    parts = []
    for r, s in enumerate(states):
        p = s.meta.get("data_fingerprint_parts")
        if not p:
            raise ValueError(
                f"rank {r} state lacks data_fingerprint_parts; cannot "
                "derive the global dataset fingerprint")
        parts.append(p)
    valid_shard = [
        [int(np.asarray(s.arrays[f"valid_scores_{i}"]).shape[1])
         for s in states]
        for i in range(nv)
    ]
    arrays = dict(base.arrays)
    arrays["scores"] = np.concatenate(
        [np.asarray(s.arrays["scores"]) for s in states], axis=1)
    arrays["select"] = np.concatenate(
        [np.asarray(s.arrays["select"]) for s in states], axis=0)
    for i in range(nv):
        arrays[f"valid_scores_{i}"] = np.concatenate(
            [np.asarray(s.arrays[f"valid_scores_{i}"]) for s in states],
            axis=1)
    arrays.pop("bag_rng_keys", None)
    for r, s in enumerate(states):
        arrays[f"bag_rng_keys_r{r}"] = np.asarray(
            s.arrays["bag_rng_keys"], np.uint32)
    py = dict(base.py)
    py["per_rank"] = {
        str(r): {
            "py": {k: v for k, v in s.py.items() if k != "per_rank"},
            "best_iteration": int(s.meta.get("best_iteration", -1)),
        }
        for r, s in enumerate(states)
    }
    meta = dict(base.meta)
    meta.pop("data_fingerprint_parts", None)
    meta["world_size"] = len(states)
    meta["shard_rows"] = shard_rows
    meta["valid_shard_rows"] = valid_shard
    meta["num_data"] = int(sum(shard_rows))
    meta["data_fingerprint"] = combine_fingerprint_parts(parts)
    return TrainState(meta, py, arrays)


def reshard_to_local(state: TrainState, rank: int, shard_rows,
                     valid_shard_rows, local_fp: str,
                     bag_seed: int = 0) -> TrainState:
    """Slice a canonical global TrainState down to one rank of the
    CURRENT topology (``shard_rows``/``valid_shard_rows`` describe the
    current contiguous partition, in rank order; the caller has already
    verified the global fingerprint and row totals).

    When the current partition equals the saved one, the rank's own
    bagging stream / bests / callback state are restored exactly —
    same-world resume stays byte-identical.  Otherwise the row arrays
    are resliced (a valid continuation: score caches and the bagging
    mask travel with their rows) and the bagging RNG is reseeded
    deterministically from ``(bag_seed, iteration, rank)`` — replaying
    a sibling rank's stream on a different row count would be
    meaningless anyway."""
    from ..obs import tracer

    meta = dict(state.meta)
    saved_rows = [int(x) for x in meta.get("shard_rows", [])]
    saved_valid = [[int(x) for x in v]
                   for v in meta.get("valid_shard_rows", [])]
    shard_rows = [int(x) for x in shard_rows]
    valid_shard_rows = [[int(x) for x in v] for v in valid_shard_rows]
    total = sum(shard_rows)
    if total != int(meta["num_data"]):
        raise CheckpointMismatch(
            f"checkpoint holds {meta['num_data']} global rows but the "
            f"current topology partitions {total}")
    for i, v in enumerate(valid_shard_rows):
        if i < len(saved_valid) and sum(v) != sum(saved_valid[i]):
            raise CheckpointMismatch(
                f"valid set {i} holds {sum(saved_valid[i])} global rows "
                f"but the current topology partitions {sum(v)}")
    same_partition = (saved_rows == shard_rows
                      and saved_valid == valid_shard_rows)
    start = sum(shard_rows[:rank])
    stop = start + shard_rows[rank]
    with tracer.span("ckpt.reshard", rank=rank,
                     saved_world=int(meta.get("world_size", 1)),
                     world=len(shard_rows),
                     same_partition=same_partition):
        arrays: Dict[str, np.ndarray] = {}
        for key, val in state.arrays.items():
            if key == "scores":
                arrays[key] = np.asarray(val)[:, start:stop]
            elif key == "select":
                arrays[key] = np.asarray(val)[start:stop]
            elif key.startswith("valid_scores_"):
                i = int(key[len("valid_scores_"):])
                vs = sum(valid_shard_rows[i][:rank])
                ve = vs + valid_shard_rows[i][rank]
                arrays[key] = np.asarray(val)[:, vs:ve]
            elif key.startswith("bag_rng_keys_r"):
                continue  # per-rank streams, resolved below
            else:
                arrays[key] = val
        py = {k: v for k, v in state.py.items() if k != "per_rank"}
        if same_partition:
            pr = (state.py.get("per_rank") or {}).get(str(rank))
            if pr is not None:
                py = dict(pr["py"])
                meta["best_iteration"] = int(pr.get("best_iteration", -1))
            arrays["bag_rng_keys"] = np.asarray(
                state.arrays[f"bag_rng_keys_r{rank}"], np.uint32)
        else:
            rs = np.random.RandomState([
                int(bag_seed) & 0xFFFFFFFF,
                int(meta["iteration"]) & 0xFFFFFFFF,
                int(rank),
            ])
            st = rs.get_state()
            arrays["bag_rng_keys"] = np.asarray(st[1], np.uint32)
            py["bag_rng"] = [str(st[0]), int(st[2]), int(st[3]),
                             float(st[4])]
            py["need_re_bagging"] = True
        meta["num_data"] = shard_rows[rank]
        meta["data_fingerprint"] = local_fp
        for key in ("world_size", "shard_rows", "valid_shard_rows"):
            meta.pop(key, None)
    return TrainState(meta, py, arrays)
