"""Binned dataset — counterpart of the reference's Dataset/Metadata
(src/io/dataset.cpp, src/io/metadata.cpp, include/LightGBM/dataset.h).

TPU-first design: instead of per-feature-group Bin objects (dense /
sparse / 4-bit / ordered variants, feature_group.h), the whole dataset is
ONE dense row-major matrix of bin indices that is transferred to HBM once
and stays resident: ``(N, F)`` uint8/uint16 per-feature bins, or, where
EFB finds exclusive features (io/bundle.py), the ``(N, G)`` uint8 matrix
of its bundles.  Histogram construction over it is a single XLA/Pallas
kernel (ops/histogram.py) rather than per-group virtual dispatch.
Storage stays dense on purpose: on TPU, dense with
``sparse_threshold=1.0`` is the recommended configuration in the
reference's own GPU docs (docs/GPU-Performance.md:112).  What may be
sparse is the INPUT: a scipy CSR/CSC table goes through io/sparse.py
(``from_sparse``) to its mappers, bundles and bundled matrix without
being densified, and the dataset then holds ``bundled`` and no ``binned``;
a consumer that works by feature (the mask grower, a checkpoint's
fingerprint) gets ``binned`` decoded from the bundles when it asks.

Parity notes:
- trivial-feature filtering and used-feature mapping ↔ Dataset::Construct
  (dataset.cpp:210)
- metadata (labels/weights/query boundaries/init score) ↔ Metadata
  (dataset.h:36–248, metadata.cpp)
- binary cache save/load ↔ SaveBinaryFile/LoadFromBinFile
  (dataset.cpp, dataset_loader.cpp:263) — here an .npz with a magic key.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional, Sequence

import numpy as np

from ..config import Config
from ..obs import tracer
from ..utils.log import Log
from ..utils.random import Random
from .binning import CATEGORICAL, NUMERICAL, BinMapper

_BINARY_MAGIC = "lightgbm_tpu.dataset.v1"


class Metadata:
    """Labels, weights, query boundaries, init scores (dataset.h:36–248)."""

    def __init__(self, num_data: int = 0):
        self.num_data = num_data
        self.label: np.ndarray = np.zeros(num_data, dtype=np.float32)
        self.weights: Optional[np.ndarray] = None
        self.query_boundaries: Optional[np.ndarray] = None
        self.query_weights: Optional[np.ndarray] = None
        self.init_score: Optional[np.ndarray] = None

    def set_label(self, label: Sequence[float]) -> None:
        label = np.asarray(label, dtype=np.float32).ravel()
        if len(label) != self.num_data:
            Log.fatal("Length of label (%d) != num_data (%d)", len(label), self.num_data)
        self.label = label

    def set_weights(self, weights: Optional[Sequence[float]]) -> None:
        if weights is None:
            self.weights = None
            return
        weights = np.asarray(weights, dtype=np.float32).ravel()
        if len(weights) != self.num_data:
            Log.fatal("Length of weights (%d) != num_data (%d)", len(weights), self.num_data)
        self.weights = weights

    def set_query(self, group: Optional[Sequence[int]]) -> None:
        """``group`` is per-query sizes (python API convention); builds
        cumulative query boundaries like Metadata::SetQuery."""
        if group is None:
            self.query_boundaries = None
            return
        group = np.asarray(group, dtype=np.int64).ravel()
        if int(group.sum()) != self.num_data:
            Log.fatal("Sum of query counts (%d) != num_data (%d)", int(group.sum()), self.num_data)
        self.query_boundaries = np.concatenate([[0], np.cumsum(group)]).astype(np.int64)

    def set_init_score(self, init_score: Optional[Sequence[float]]) -> None:
        if init_score is None:
            self.init_score = None
            return
        self.init_score = np.asarray(init_score, dtype=np.float64).ravel()

    @property
    def num_queries(self) -> int:
        return 0 if self.query_boundaries is None else len(self.query_boundaries) - 1


class BinnedDataset:
    """The device-ready binned training data.

    Attributes
    ----------
    binned : (num_data, num_used_features) np.uint8 or np.uint16
        Bin index of each (row, used-feature).  A dataset made from sparse
        input holds its bundles alone (``has_dense_bins`` is False) and
        decodes this matrix, once, for whoever reads it.
    bin_mappers : list[BinMapper], one per used feature.
    used_feature_map : original feature index of each used feature.
    num_total_features : raw feature count before trivial filtering.
    """

    def __init__(self):
        self._binned: Optional[np.ndarray] = np.zeros((0, 0), dtype=np.uint8)
        self.bin_mappers: List[BinMapper] = []
        self.used_feature_map: np.ndarray = np.array([], dtype=np.int32)
        self.num_total_features: int = 0
        self.metadata = Metadata(0)
        self.feature_names: List[str] = []
        self.max_bin: int = 255
        self.label_idx: int = 0
        self.bundle = None  # EFB BundleInfo (io/bundle.py); None = unbundled
        self.bundled: Optional[np.ndarray] = None  # (N, G) uint8 bundle bins
        self.bundle_conflicts = 0  # entries a later feature of their bundle overwrote
        # set when loaded from a v2 binary cache: the out-of-core trainer
        # streams checksummed chunks straight from this file
        self.cache_path: Optional[str] = None
        # raw (unbinned) copy is not kept — predictions on training data run
        # on the binned representation like the reference's score updater.

    # ------------------------------------------------------------------
    @property
    def has_dense_bins(self) -> bool:
        """Whether the ``(N, F)`` per-feature bins exist on the host."""
        return self._binned is not None

    @property
    def binned(self) -> np.ndarray:
        if self._binned is None:
            # made from sparse input: the bundles are all there is.  Exact
            # but for the cells a conflict overwrote (bundle_conflicts).
            from .bundle import decode_bundled

            n, f = self.num_data, self.num_features
            Log.warning("decoding the (%d, %d) per-feature bins from %d bundle columns: a "
                        "consumer that works by feature asked for them%s", n, f,
                        self.bundle.num_cols,
                        f" ({self.bundle_conflicts} cells lost to bundle conflicts)"
                        if self.bundle_conflicts else "")
            out = np.empty((n, f), np.uint8)
            _map_threads(lambda lo: decode_bundled(
                self.bundled[lo:lo + _DECODE_BLOCK_ROWS], self.bundle, self.bin_mappers,
                out[lo:lo + _DECODE_BLOCK_ROWS]), range(0, n, _DECODE_BLOCK_ROWS))
            self._binned = out
        return self._binned

    @binned.setter
    def binned(self, value: np.ndarray) -> None:
        self._binned = value

    @property
    def bin_dtype(self):
        return np.dtype(np.uint8) if self._binned is None else self._binned.dtype

    @property
    def num_data(self) -> int:
        return (self.bundled if self._binned is None else self._binned).shape[0]

    @property
    def num_features(self) -> int:
        """Number of used (non-trivial) features."""
        return len(self.bin_mappers) if self._binned is None else self._binned.shape[1]

    def num_bin(self, fidx: int) -> int:
        return self.bin_mappers[fidx].num_bin

    @property
    def max_num_bin(self) -> int:
        return max((m.num_bin for m in self.bin_mappers), default=1)

    def real_threshold(self, fidx: int, bin_idx: int) -> float:
        return self.bin_mappers[fidx].bin_to_value(int(bin_idx))

    def inner_to_real_feature(self, fidx: int) -> int:
        return int(self.used_feature_map[fidx])

    # ------------------------------------------------------------------
    @classmethod
    def _shell(cls, n, num_features, config, label, weight, group, init_score,
               feature_names, reference) -> "BinnedDataset":
        """A dataset of ``n`` rows with its metadata and names and no bins
        yet; what is the reference's (a validation set's) comes from it."""
        ds = cls()
        ds.metadata = Metadata(n)
        if label is not None:
            ds.metadata.set_label(label)
        ds.metadata.set_weights(weight)
        ds.metadata.set_query(group)
        ds.metadata.set_init_score(init_score)
        if reference is not None:
            ds.num_total_features = reference.num_total_features
            ds.feature_names = reference.feature_names
            ds.max_bin = reference.max_bin
        else:
            ds.num_total_features = num_features
            ds.feature_names = list(feature_names) if feature_names else [
                f"Column_{i}" for i in range(num_features)]
            ds.max_bin = config.max_bin
        return ds

    @classmethod
    def from_raw(
        cls,
        data: np.ndarray,
        config: Config,
        *,
        label: Optional[Sequence[float]] = None,
        weight: Optional[Sequence[float]] = None,
        group: Optional[Sequence[int]] = None,
        init_score: Optional[Sequence[float]] = None,
        feature_names: Optional[List[str]] = None,
        categorical_features: Optional[Sequence[int]] = None,
        reference: Optional["BinnedDataset"] = None,
        sample_indices: Optional[np.ndarray] = None,
    ) -> "BinnedDataset":
        """Construct from a raw dense float matrix.

        Mirrors DatasetLoader::ConstructBinMappersFromTextData +
        ExtractFeaturesFromMemory (dataset_loader.cpp:661, :840): sample rows,
        find bins per feature, then push every row through the mappers.
        With ``reference`` given, reuses its bin mappers (CreateValid /
        LoadFromFileAlignWithOtherDataset path).
        """
        # a float table is binned as it is: the sample and each block's
        # columns become float64 where they are used (exact from float32),
        # not the whole table beside itself (6.4 GB at 400,000 x 2,000)
        data = np.asarray(data)
        if not np.issubdtype(data.dtype, np.floating):
            data = data.astype(np.float64)
        if data.ndim != 2:
            Log.fatal("data must be 2-dimensional")
        n, num_features = data.shape
        ds = cls._shell(n, num_features, config, label, weight, group, init_score,
                        feature_names, reference)

        if reference is not None:
            ds.bin_mappers = reference.bin_mappers
            ds.used_feature_map = reference.used_feature_map
        else:
            cat_set = set(int(c) for c in categorical_features) if categorical_features else set()
            with tracer.stage("find_bins", features=num_features):
                mappers = _find_bin_mappers_distributed(data, config, cat_set, sample_indices)
            used = [i for i, m in enumerate(mappers) if not m.is_trivial]
            if not used:
                Log.fatal("Cannot construct Dataset: all features are trivial (constant)")
            ds.bin_mappers = [mappers[i] for i in used]
            ds.used_feature_map = np.asarray(used, dtype=np.int32)

        with tracer.stage("bin_rows", rows=n, features=len(ds.bin_mappers)):
            ds.binned = _bin_matrix(data, ds.bin_mappers, ds.used_feature_map)
        return ds

    @classmethod
    def from_sparse(
        cls,
        data,
        config: Config,
        *,
        label: Optional[Sequence[float]] = None,
        weight: Optional[Sequence[float]] = None,
        group: Optional[Sequence[int]] = None,
        init_score: Optional[Sequence[float]] = None,
        feature_names: Optional[List[str]] = None,
        categorical_features: Optional[Sequence[int]] = None,
        reference: Optional["BinnedDataset"] = None,
        sample_indices: Optional[np.ndarray] = None,
    ) -> "BinnedDataset":
        """Construct from a scipy CSR/CSC matrix without densifying it
        (io/sparse.py): the mappers, bundles and matrix ``from_raw`` and
        ``ensure_bundles`` give the densified table, byte for byte.  Where
        EFB finds bundles the dataset holds ``bundled`` and no ``binned``;
        bundling is decided here and not lazily, because the alternative
        is the ``(N, F)`` matrix this path exists to avoid.  With
        ``reference`` (a validation set) the per-feature bins are built."""
        from . import sparse

        csr = sparse.to_csr(data)
        ds = cls._shell(*csr.shape, config, label, weight, group, init_score, feature_names,
                        reference)
        (ds.bin_mappers, ds.used_feature_map, ds._binned, ds.bundled, ds.bundle,
         ds.bundle_conflicts) = sparse.ingest(
            csr, config, categorical=set(int(c) for c in categorical_features or ()),
            sample_indices=sample_indices, reference=reference,
            bundle=bool(getattr(config, "enable_bundle", True)))
        ds._bundle_checked = True
        return ds

    def ensure_bundles(self, config) -> None:
        """Lazily build EFB bundles (io/bundle.py).  Deferred out of
        construction because only the partitioned trainer consumes them —
        CPU runs, ranking, multiclass and distributed configs should not
        pay the grouping scan or hold the extra (N, G) matrix."""
        if self.bundle is not None or getattr(self, "_bundle_checked", False):
            return
        self._bundle_checked = True
        if not getattr(config, "enable_bundle", True) or self.binned.dtype != np.uint8:
            return
        from .bundle import build_bundled_matrix, find_bundles

        # the sparse path's stage names (io/sparse.py): the same two steps
        with tracer.stage("find_bundles", columns=self.num_features) as stage:
            info = find_bundles(self.binned, self.bin_mappers, config)
            stage.attrs["bundles"] = 0 if info is None else info.num_cols
        if info is not None:
            self.bundle = info
            with tracer.stage("build_bundled", rows=self.num_data, bundles=info.num_cols):
                self.bundled = build_bundled_matrix(self.binned, self.bin_mappers, info)

    def create_valid(self, data, **kwargs) -> "BinnedDataset":
        """Validation dataset aligned with this dataset's bin mappers
        (Dataset::CreateValid, dataset.cpp)."""
        from ..config import Config as _C

        return BinnedDataset.from_raw(data, _C(), reference=self, **kwargs)

    def subset(self, indices: np.ndarray) -> "BinnedDataset":
        """Row subset sharing bin mappers (Dataset::CopySubset)."""
        indices = np.asarray(indices)
        ds = BinnedDataset()
        if self._binned is None:  # made from sparse input: the bundles are the rows
            ds._binned, ds.bundled, ds.bundle = None, self.bundled[indices], self.bundle
            ds._bundle_checked = True
        else:
            ds.binned = self.binned[indices]
        ds.bin_mappers = self.bin_mappers
        ds.used_feature_map = self.used_feature_map
        ds.num_total_features = self.num_total_features
        ds.feature_names = self.feature_names
        ds.max_bin = self.max_bin
        ds.metadata = Metadata(len(indices))
        ds.metadata.set_label(self.metadata.label[indices])
        if self.metadata.weights is not None:
            ds.metadata.set_weights(self.metadata.weights[indices])
        if self.metadata.query_boundaries is not None:
            # map each retained row to its query and count per-query
            # retained rows, keeping only non-empty queries in order
            # (Metadata::CheckOrPartition query partitioning)
            qb = self.metadata.query_boundaries
            row_query = np.searchsorted(qb, indices, side="right") - 1
            per_query = np.bincount(row_query, minlength=len(qb) - 1)
            ds.metadata.set_query(per_query[per_query > 0])
        if self.metadata.init_score is not None:
            ns = len(self.metadata.init_score) // max(self.metadata.num_data, 1)
            sc = self.metadata.init_score.reshape(ns, -1)[:, indices] if ns > 1 else None
            if ns > 1:
                ds.metadata.set_init_score(sc.ravel())
            else:
                ds.metadata.set_init_score(self.metadata.init_score[indices])
        return ds

    # ------------------------------------------------------------------
    def feature_infos(self) -> List[str]:
        """feature_infos= strings for the model file, indexed by ORIGINAL
        feature id (trivial features report 'none')."""
        infos = ["none"] * self.num_total_features
        for inner, real in enumerate(self.used_feature_map):
            infos[int(real)] = self.bin_mappers[inner].to_string()
        return infos

    # ------------------------------------------------------------------
    def save_binary(self, path: str, source_path: str = None) -> None:
        """Binary dataset cache (↔ Dataset::SaveBinaryFile), format v2.

        Members are stored UNCOMPRESSED so the bin matrix's bytes are
        contiguous in the file — the out-of-core trainer seeks straight
        into them (data/cache.py).  The ``__cache_meta__`` header records
        the format version, per-block CRCs and — when ``source_path`` is
        given — the source file's identity, so a cache that no longer
        matches its source is refused instead of silently trusted.

        A dataset made from sparse input stores its bundled matrix and
        ``BundleInfo`` in place of ``binned`` (header ``layout: bundled``;
        the CRCs are the bundled matrix's)."""
        from ..data.cache import build_cache_meta, chunk_crcs

        dense = self._binned is not None
        matrix = self._binned if dense else self.bundled
        meta = build_cache_meta(matrix, self.metadata.label,
                                source_path=source_path)
        import json

        if not dense:
            meta["layout"] = "bundled"
        payload: Dict[str, np.ndarray] = {
            "magic": np.asarray(_BINARY_MAGIC),
            "__cache_meta__": np.asarray(json.dumps(meta)),
            "chunk_crc": chunk_crcs(matrix),
            **({"binned": matrix} if dense else
               {"bundled": matrix, **_bundle_state(self.bundle, self.bundle_conflicts)}),
            "used_feature_map": self.used_feature_map,
            "num_total_features": np.asarray(self.num_total_features),
            "feature_names": np.asarray(self.feature_names),
            "max_bin": np.asarray(self.max_bin),
            "label": self.metadata.label,
            "num_mappers": np.asarray(len(self.bin_mappers)),
        }
        if self.metadata.weights is not None:
            payload["weights"] = self.metadata.weights
        if self.metadata.query_boundaries is not None:
            payload["query_boundaries"] = self.metadata.query_boundaries
        if self.metadata.init_score is not None:
            payload["init_score"] = self.metadata.init_score
        for i, m in enumerate(self.bin_mappers):
            st = m.state()
            payload[f"m{i}_meta"] = np.asarray(
                [
                    st["num_bin"],
                    st["bin_type"],
                    int(st["is_trivial"]),
                    st["default_bin"],
                ],
                dtype=np.int64,
            )
            payload[f"m{i}_fl"] = np.asarray(
                [st["sparse_rate"], st["min_val"], st["max_val"]], dtype=np.float64
            )
            payload[f"m{i}_bounds"] = st["bin_upper_bound"]
            payload[f"m{i}_cats"] = st["bin_2_categorical"]
        # write to the EXACT path (np.savez appends .npz to bare names;
        # the reference's SaveBinaryFile writes the filename it was given).
        # Uncompressed on purpose: random access into "binned" needs the
        # raw bytes on disk (and bin matrices barely compress anyway).
        with open(path, "wb") as f:
            np.savez(f, **payload)

    @staticmethod
    def is_binary_cache(path: str) -> bool:
        """True when ``path`` is a saved binary dataset (zip magic +
        our payload) — DatasetLoader checks the binary header before
        falling back to text parsing (dataset_loader.cpp LoadFromBinFile)."""
        try:
            with open(path, "rb") as f:
                if f.read(4) != b"PK\x03\x04":
                    return False
            with np.load(path, allow_pickle=False) as z:
                return "magic" in z and str(z["magic"]) == _BINARY_MAGIC
        except Exception:
            return False

    @classmethod
    def load_binary(cls, path: str) -> "BinnedDataset":
        with tracer.stage("load_binary", bytes=os.path.getsize(path)):
            return cls._load_binary(path)

    @classmethod
    def _load_binary(cls, path: str) -> "BinnedDataset":
        from ..data.cache import (
            CACHE_FORMAT_VERSION,
            open_cache_reader,
            read_cache_meta,
            stale_reason,
        )

        with np.load(path, allow_pickle=False) as z:
            if str(z["magic"]) != _BINARY_MAGIC:
                Log.fatal("File %s is not a lightgbm_tpu binary dataset", path)
            meta = read_cache_meta(z)
            if meta is None:
                Log.fatal(
                    "Binary dataset %s predates cache format v%d (no "
                    "version/fingerprint header) — regenerate it with "
                    "task=ingest", path, CACHE_FORMAT_VERSION)
            if int(meta.get("format_version", 0)) > CACHE_FORMAT_VERSION:
                Log.fatal(
                    "Binary dataset %s has cache format v%s, newer than "
                    "this build supports (v%d)", path,
                    meta.get("format_version"), CACHE_FORMAT_VERSION)
            stale = stale_reason(meta)
            if stale:
                Log.fatal(
                    "Refusing stale binary dataset %s: %s — regenerate "
                    "the cache with task=ingest (or delete it)", path, stale)
            ds = cls()
            # prefer a read-only memmap of the stored matrix: demand-paged
            # host residency, and the out-of-core trainer can stream
            # checksummed chunks straight from the same file
            reader = open_cache_reader(path) if "binned" in z.files else None
            if reader is not None:
                ds.binned = reader.memmap()
                ds.cache_path = path
                reader.close()
            elif "binned" in z.files:
                ds.binned = z["binned"]
            else:  # made from sparse input: the bundles alone
                ds._binned, ds.bundled = None, z["bundled"]
                ds.bundle, ds.bundle_conflicts = _bundle_from_state(z)
                ds._bundle_checked = True
            ds.used_feature_map = z["used_feature_map"]
            ds.num_total_features = int(z["num_total_features"])
            ds.feature_names = [str(s) for s in z["feature_names"]]
            ds.max_bin = int(z["max_bin"])
            ds.metadata = Metadata(ds.num_data)
            ds.metadata.set_label(z["label"])
            if "weights" in z:
                ds.metadata.set_weights(z["weights"])
            if "query_boundaries" in z:
                ds.metadata.query_boundaries = z["query_boundaries"].astype(np.int64)
            if "init_score" in z:
                ds.metadata.set_init_score(z["init_score"])
            for i in range(int(z["num_mappers"])):
                meta = z[f"m{i}_meta"]
                fl = z[f"m{i}_fl"]
                ds.bin_mappers.append(
                    BinMapper.from_state(
                        {
                            "num_bin": meta[0],
                            "bin_type": meta[1],
                            "is_trivial": bool(meta[2]),
                            "default_bin": meta[3],
                            "sparse_rate": fl[0],
                            "min_val": fl[1],
                            "max_val": fl[2],
                            "bin_upper_bound": z[f"m{i}_bounds"],
                            "bin_2_categorical": z[f"m{i}_cats"],
                        }
                    )
                )
        return ds


# ----------------------------------------------------------------------
_BUNDLE_FIELDS = ("col", "off_lo", "off_hi", "bias", "num_bin_col")


def _bundle_state(info, conflicts: int) -> Dict[str, np.ndarray]:
    """A ``BundleInfo`` as arrays of the binary cache."""
    out = {f"bundle_{k}": np.asarray(getattr(info, k)) for k in _BUNDLE_FIELDS}
    out["bundle_group_sizes"] = np.asarray([len(g) for g in info.groups], np.int32)
    out["bundle_groups"] = np.asarray([fe for g in info.groups for fe in g], np.int32)
    out["bundle_conflicts"] = np.asarray(int(conflicts))
    return out


def _bundle_from_state(z):
    from .bundle import BundleInfo

    flat = z["bundle_groups"].tolist()
    ends = np.cumsum(z["bundle_group_sizes"]).tolist()
    groups = [flat[lo:hi] for lo, hi in zip([0] + ends[:-1], ends)]
    fields = {k: z[f"bundle_{k}"] for k in _BUNDLE_FIELDS}
    info = BundleInfo(groups=groups, max_col_bin=int(fields["num_bin_col"].max()), **fields)
    return info, int(z["bundle_conflicts"])


def _find_bin_mappers_distributed(
    data: np.ndarray,
    config: Config,
    categorical: set,
    sample_indices: Optional[np.ndarray],
    find=None,
) -> List[BinMapper]:
    """Distributed find-bin (dataset_loader.cpp:733-835): in a
    multi-process runtime each process finds bins only for its contiguous
    feature block [start_r, start_r + len_r) — step = ceil(F/M), exactly
    the reference's assignment — then the serialized mappers are
    allgathered so every process ends with the identical full list.  The
    reference's max_bin Allreduce exists only to size its fixed-width
    copy buffers; here the pickled states are length-prefixed instead.
    Falls through to the single-process path otherwise.  ``find`` is the
    single-process finder: ``_find_bin_mappers`` for a dense table,
    io/sparse.py's for a CSR one (both slice by column)."""
    find = find or _find_bin_mappers
    if not getattr(config, "is_parallel_find_bin", False):
        return find(data, config, categorical, sample_indices)

    import jax

    from ..parallel.distributed import ensure_initialized

    if not ensure_initialized(config):
        return find(data, config, categorical, sample_indices)

    import pickle

    from ..parallel.collect import allgather_blob_lists

    nproc = jax.process_count()
    rank = jax.process_index()
    f_total = data.shape[1]
    step = max(1, -(-f_total // nproc))
    start = min(rank * step, f_total)
    stop = min(start + step, f_total)

    local_cats = {c - start for c in categorical if start <= c < stop}
    if stop > start:
        local = find(data[:, start:stop], config, local_cats, sample_indices)
    else:
        local = []
    blobs = [pickle.dumps(m.state()) for m in local]
    gathered = allgather_blob_lists(blobs, list_len=step)
    mappers: List[BinMapper] = []
    for f in range(f_total):
        r, i = divmod(f, step)
        mappers.append(BinMapper.from_state(pickle.loads(gathered[r][i])))
    return mappers


def _find_bin_mappers(
    data: np.ndarray,
    config: Config,
    categorical: set,
    sample_indices: Optional[np.ndarray],
) -> List[BinMapper]:
    """Sample rows then FindBin per feature (dataset_loader.cpp:661–776)."""
    n = data.shape[0]
    if sample_indices is None:
        sample_indices = bin_sample_indices(n, config)
    return find_bin_mappers_from_sample(data[sample_indices], n, config, categorical)


def bin_sample_indices(n: int, config: Config) -> np.ndarray:
    """The deterministic bin-construction row sample (DatasetLoader's
    ``random_.Sample(num_data, bin_construct_sample_cnt)``).  Sorted
    ascending, so a streaming pass can collect the rows with a single
    forward cursor and end up with EXACTLY the matrix the in-memory path
    samples — the anchor of streaming/in-memory bit-parity."""
    rng = Random(config.data_random_seed)
    sample_cnt = min(config.bin_construct_sample_cnt, n)
    return rng.sample(n, sample_cnt)


def find_bin_mappers_from_sample(
    sampled: np.ndarray,
    total_rows: int,
    config: Config,
    categorical: set,
) -> List[BinMapper]:
    """FindBin per feature over an already-collected sample matrix.
    ``total_rows`` is the FULL dataset row count — min_data_in_leaf is
    scaled by the sampling fraction, exactly like
    dataset_loader.cpp:491-492 / :709-710 (sampled per-bin counts are
    proportionally smaller than full-data counts)."""
    total = sampled.shape[0]
    filter_cnt = int(config.min_data_in_leaf * total / max(total_rows, 1))
    mappers: List[BinMapper] = []
    for f in range(sampled.shape[1]):
        col = sampled[:, f]
        col = col[~np.isnan(col)]
        nonzero = col[col != 0.0]
        m = BinMapper()
        m.find_bin(
            nonzero,
            total,
            config.max_bin,
            config.min_data_in_bin,
            filter_cnt,
            CATEGORICAL if f in categorical else NUMERICAL,
        )
        mappers.append(m)
    return mappers


_HOST_THREADS = 8
_BIN_BLOCK_ROWS = 1 << 14
_DECODE_BLOCK_ROWS = 1 << 18


def _map_threads(fn, items) -> list:
    """[fn(x) for x in items], on a few threads when there is more than a
    little to do; the result does not depend on how many."""
    items = list(items)
    workers = min(_HOST_THREADS, os.cpu_count() or 1, len(items) // 4)
    if workers < 2:
        return [fn(x) for x in items]
    with ThreadPoolExecutor(max_workers=workers) as pool:
        return list(pool.map(fn, items))  # list(): re-raise a worker's exception


def packed_bin_dtype(mappers: List[BinMapper]):
    """uint8 unless some feature needs >256 bins (the packed-matrix
    sizing rule, shared with the streaming pass-2 preallocation)."""
    max_bins = max((m.num_bin for m in mappers), default=2)
    return np.uint8 if max_bins <= 256 else np.uint16


def bin_rows_into(
    out: np.ndarray,
    start: int,
    data: np.ndarray,
    mappers: List[BinMapper],
    used_map: np.ndarray,
) -> None:
    """Bin raw rows directly into ``out[start:start+len(data)]`` — the
    pass-2 streaming write: each chunk lands in the preallocated packed
    matrix and the raw floats are dropped."""
    used = np.asarray(used_map)
    every = len(used) == data.shape[1] and np.array_equal(used, np.arange(len(used)))

    def block(lo: int) -> None:
        # rows of a table are contiguous and columns are not: a block is
        # turned once, so that each column's pass reads memory in order
        # (a column of the whole table touches a cache line per value)
        rows = data[lo:lo + _BIN_BLOCK_ROWS]
        cols = np.ascontiguousarray((rows if every else rows[:, used]).T)
        binned = np.empty(cols.shape, out.dtype)
        for inner in range(len(used)):
            binned[inner] = mappers[inner].value_to_bin(cols[inner])
        out[start + lo:start + lo + len(rows)] = binned.T

    _map_threads(block, range(0, data.shape[0], _BIN_BLOCK_ROWS))


def _bin_matrix(data: np.ndarray, mappers: List[BinMapper], used_map: np.ndarray) -> np.ndarray:
    out = np.empty((data.shape[0], len(mappers)), dtype=packed_bin_dtype(mappers))
    bin_rows_into(out, 0, data, mappers, used_map)
    return out
