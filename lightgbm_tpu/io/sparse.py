"""Sparse ingest: scipy CSR/CSC in, bin mappers, EFB bundles and the
``(N, G)`` bundled matrix out, with no ``(N, F)`` array in between.

Counterpart of the reference's CSR/CSC constructors
(LGBM_DatasetCreateFromCSR, c_api.cpp) feeding Dataset::Construct's
feature groups.  A one-hot table of 21M x 700 with 8 non-zeros a row is
117.6 GB as float64 and 14.7 GB as per-feature bins; its ten bundle
columns are 210 MB.  Storage on the device stays dense (README, sparse
bins decision): what is sparse here is the INPUT.

Everything is made to be byte-equal to the dense path on the densified
table, so the two can be held against each other:

- ``find_bin_mappers``   the mappers ``find_bin_mappers_from_sample`` finds:
  a feature's sampled stored values, NaN and explicit zeros dropped, plus
  the implied zeros as ``total_sample_cnt``;
- ``find_bundles``       ``io/bundle.find_bundles``' ``BundleInfo``: the same
  row sample, each feature's non-default rows as an index set instead of
  a column of the bin matrix;
- ``build_columns``      ``io/bundle.build_bundled_matrix``' bytes, written
  from the rows' entries in group order (later features win a conflict).

Row blocks are turned to CSC (scipy's counting sort) so that each
column's entries are contiguous for ``BinMapper.value_to_bin``.
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np

from ..config import Config
from ..obs import tracer
from ..utils.log import Log
from .binning import CATEGORICAL, NUMERICAL, BinMapper
from .bundle import BundleInfo, bundle_sample_rows, bundles_from_masks, pack_mask

BLOCK_ROWS = 1 << 20  # rows turned to CSC at a time: 8M entries at 8 non-zeros a row


def is_sparse(data) -> bool:
    """A scipy sparse matrix (or anything with its two conversions)."""
    return hasattr(data, "tocsr") and hasattr(data, "toarray")


def to_csr(data):
    csr = data.tocsr()
    if not csr.has_canonical_format:
        csr = csr.copy()
        csr.sum_duplicates()  # also sorts a row's indices
    return csr


def dense_block_rows(num_cols: int, budget_bytes: int = 256 << 20) -> int:
    """Rows of a float64 block of ``num_cols`` columns within the budget: a
    power of two, so that equal blocks share one compiled predictor."""
    rows = max(budget_bytes // (8 * max(num_cols, 1)), 1)
    return 1 << (int(rows).bit_length() - 1)


def map_dense_blocks(fn, data) -> list:
    """``[fn(block), ...]`` over the matrix's rows as float64 blocks of
    ``dense_block_rows`` rows, in order: the first alone (it compiles what
    the rest share), the rest a few at a time on threads, each densified by
    the thread that uses it, so the table is never dense whole."""
    from .dataset import _map_threads

    csr = to_csr(data)
    rows = dense_block_rows(csr.shape[1])

    def one(lo: int):
        return fn(np.asarray(csr[lo:lo + rows].toarray(), dtype=np.float64))

    starts = range(0, max(csr.shape[0], 1), rows)
    return [one(starts[0])] + _map_threads(one, starts[1:])


# ----------------------------------------------------------------------
def find_bin_mappers(csr, config: Config, categorical: set,
                     sample_indices: Optional[np.ndarray]) -> List[BinMapper]:
    """FindBin per column over the sampled rows' stored values.  The same
    signature as ``io/dataset._find_bin_mappers``, so the distributed
    wrapper there takes either."""
    from .dataset import bin_sample_indices

    n = csr.shape[0]
    if sample_indices is None:
        sample_indices = bin_sample_indices(n, config)
    sample = csr[sample_indices].tocsc()
    total = sample.shape[0]
    filter_cnt = int(config.min_data_in_leaf * total / max(n, 1))
    mappers: List[BinMapper] = []
    for f in range(sample.shape[1]):
        vals = np.asarray(sample.data[sample.indptr[f]:sample.indptr[f + 1]], np.float64)
        vals = vals[~np.isnan(vals)]
        m = BinMapper()
        m.find_bin(vals[vals != 0.0], total, config.max_bin, config.min_data_in_bin,
                   filter_cnt, CATEGORICAL if f in categorical else NUMERICAL)
        mappers.append(m)
    return mappers


def _column_bins(block_csc, mappers: Sequence[BinMapper], used: np.ndarray, order=None):
    """For each used feature of a CSC block, in ``order`` (inner ids; as
    they come by default): (inner id, rows, bins) of its stored entries.  A
    stored zero or NaN bins like an implied zero."""
    for inner in range(len(used)) if order is None else order:
        real = used[inner]
        a, b = block_csc.indptr[real], block_csc.indptr[real + 1]
        if a == b:
            yield inner, block_csc.indices[a:a], np.zeros(0, np.int32)
            continue
        yield inner, block_csc.indices[a:b], mappers[inner].value_to_bin(block_csc.data[a:b])


def find_bundles(csr, mappers: Sequence[BinMapper], used: np.ndarray,
                 config) -> Optional[BundleInfo]:
    """``io/bundle.find_bundles`` from column index sets: the same sampled
    rows, and per feature the packed mask of those whose bin is not the
    default one."""
    n, f = csr.shape[0], len(mappers)
    if f < 2:
        return None
    rows = np.sort(bundle_sample_rows(n, config))  # a mask's order is the same for every feature
    sample = csr[rows].tocsc()
    masks, nz_cnt = [None] * f, np.zeros(f, np.int64)
    for inner, at, bins in _column_bins(sample, mappers, used):
        mask = np.zeros(len(rows), bool)
        mask[at[bins != mappers[inner].default_bin]] = True
        nz_cnt[inner] = int(mask.sum())
        masks[inner] = pack_mask(mask)
    return bundles_from_masks(masks, nz_cnt, len(rows), mappers, config)


def build_columns(csr, mappers: Sequence[BinMapper], used: np.ndarray,
                  info: Optional[BundleInfo]):
    """``(matrix, conflicts)``: the ``(N, G)`` bundled matrix of ``info``
    (``io/bundle.build_bundled_matrix``' bytes), or with ``info`` None the
    ``(N, F)`` per-feature bins, written block by block from the rows'
    entries.  ``conflicts`` counts the entries a later feature of the same
    bundle overwrote."""
    from .dataset import _map_threads, packed_bin_dtype

    n = csr.shape[0]
    default_bin = np.asarray([m.default_bin for m in mappers], np.int64)
    if info is None:
        groups = [[fe] for fe in range(len(mappers))]
        raw = np.ones(len(mappers), bool)
        shift = np.zeros(len(mappers), np.int64)
        out = np.empty((n, len(groups)), packed_bin_dtype(list(mappers)))
    else:
        groups = info.groups
        raw = np.asarray([len(groups[info.col[fe]]) == 1 and info.off_lo[fe] == 0
                          for fe in range(len(mappers))])
        shift = np.asarray(info.off_lo, np.int64) - np.asarray(info.bias, np.int64)
        out = np.empty((n, len(groups)), np.uint8)
    # a raw column starts at its feature's default bin, a shared one at 0
    fill = np.asarray([default_bin[g[0]] if raw[g[0]] else 0 for g in groups], out.dtype)
    shared = np.flatnonzero([not raw[g[0]] for g in groups])
    order = [fe for g in groups for fe in g]  # later features of a group win
    col_of = {fe: g for g, feats in enumerate(groups) for fe in feats}

    def block(lo: int) -> int:
        view = out[lo:lo + BLOCK_ROWS]
        view[:] = fill
        written = 0
        for fe, at, bins in _column_bins(csr[lo:lo + BLOCK_ROWS].tocsc(), mappers, used, order):
            if raw[fe]:
                view[at, col_of[fe]] = bins
                continue
            keep = bins != default_bin[fe]
            view[at[keep], col_of[fe]] = bins[keep] + shift[fe]
            written += int(keep.sum())
        return written - int(np.count_nonzero(view[:, shared])) if len(shared) else 0

    conflicts = sum(_map_threads(block, range(0, n, BLOCK_ROWS)))
    return out, conflicts


# ----------------------------------------------------------------------
def ingest(csr, config: Config, *, categorical: set = frozenset(), sample_indices=None,
           reference=None, bundle: bool = True):
    """``(mappers, used, binned, bundled, info, conflicts)``.  With a
    ``reference`` dataset its mappers are reused and the per-feature bins
    are built (a validation set is scored by feature).  Without one the
    mappers are found from the sampled entries, and where EFB finds
    bundles the bundled matrix and its ``BundleInfo`` come back and
    ``binned`` is None: nothing of ``(N, F)`` is built."""
    from .dataset import _find_bin_mappers_distributed

    span = tracer.stage("sparse_ingest", rows=csr.shape[0], nnz=int(csr.nnz),
                        features=csr.shape[1])
    info, conflicts = None, 0
    with span:
        if reference is not None:
            mappers, used = reference.bin_mappers, np.asarray(reference.used_feature_map)
        else:
            with tracer.stage("csr_bin"):
                found = _find_bin_mappers_distributed(
                    csr, config, set(categorical), sample_indices, find=find_bin_mappers)
            used = np.asarray([i for i, m in enumerate(found) if not m.is_trivial], np.int32)
            if not len(used):
                Log.fatal("Cannot construct Dataset: all features are trivial (constant)")
            mappers = [found[i] for i in used]
            if bundle and max(m.num_bin for m in mappers) <= 256:
                with tracer.stage("find_bundles", columns=len(mappers)) as found_stage:
                    info = find_bundles(csr, mappers, used, config)
                    found_stage.attrs["bundles"] = 0 if info is None else info.num_cols
        with tracer.stage("build_bundled"):
            matrix, conflicts = build_columns(csr, mappers, used, info)
        if info is not None:
            span.attrs.update(bundle_cols=info.num_cols, max_col_bin=int(info.max_col_bin))
    if conflicts:
        Log.warning("EFB: %d of %d entries were overwritten by a later feature of "
                    "their bundle (rows outside the conflict sample)", conflicts, int(csr.nnz))
    if info is None:
        return mappers, used, matrix, None, None, 0
    return mappers, used, None, matrix, info, conflicts
