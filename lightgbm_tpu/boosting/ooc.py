"""Out-of-core tree growth: the bin matrix streams, the vectors stay.

The mask grower (ops/grow.py) needs the whole ``(N, F)`` bin matrix
device-resident, which caps one chip at HBM size.  This trainer drops
that requirement with a "vector-resident, matrix-streamed" split of the
training state:

  - every per-row VECTOR — scores, grad, hess, select, ``leaf_id`` — is
    a handful of N-floats and stays device-resident, so the objective,
    GOSS re-weighting, bagging masks and score updates run the exact
    same programs as the in-memory path;
  - the ``(N, F)`` MATRIX is the only O(N·F) tensor, and the histogram
    is the only thing that reads it — "Out-of-Core GPU Gradient
    Boosting" (PAPERS.md) rests on the same observation — so it streams
    through the double-buffered prefetch ring (data/prefetch.py) in
    row-chunks and peak device residency is O(2 chunks), not O(dataset).

Per tree the trainer replays the grower's best-first loop on the host:
one streamed pass builds the root histogram, then each split makes one
pass that partitions the chunk's ``leaf_id`` slice and folds BOTH
children's histogram partials (ops/ooc.py ``split_chunk`` — 2x flops for
1x transfer, and transfers bound the out-of-core regime).  The directly-
accumulated histogram of the *smaller* child is kept and the larger is
derived by the subtraction trick, exactly as in-memory.

The streaming machinery itself — source selection, the prefetch ring,
and the per-chunk fold loops — lives in ``data/chunksource.py``
(:class:`ChunkStream` / :class:`ChunkFolder`), the seam this trainer
shares with the rank-sharded :class:`~..boosting.oocdist.DistributedOocTrainer`.

Bit-identity contract: with ``chunk_rows`` a ``ROW_BLOCK`` multiple
(enforced by rounding up), the streamed histogram folds reproduce the
in-memory scan's left-to-right block adds bit-for-bit, and every other
op is elementwise/integer or runs on scalars at the in-memory shapes —
so at any scale where the in-memory grower uses the masked full scan
(``N <= TIER_MIN``; above it the in-memory path switches to tiered
gather compaction, which reorders row summation), the out-of-core model
string is byte-identical.  tests/test_ooc.py pins this for gbdt and
GOSS, plus mid-run checkpoint kill/resume.
"""

from __future__ import annotations

import os
from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np

from ..data.chunksource import (
    ChunkFolder,
    ChunkPlan,
    ChunkStream,
    PrefetchStats,
    make_chunk_source,
)
from ..obs import tracer
from ..ops.grow import GrowResult
from ..ops.histogram import ROW_BLOCK
from ..ops.ooc import child_leaf_values, find_best_split, root_totals
from ..ops.qhist import dequantize_hist, dequantize_sums
from ..ops.split import NEG_INF
from ..utils.log import Log

# auto chunk sizing aims each chunk at ~64 MiB of packed bins: big enough
# to amortize dispatch, small enough that two in-flight buffers are noise
# next to HBM.
_AUTO_CHUNK_BYTES = 64 << 20


def _device_budget_bytes() -> Optional[int]:
    """The device-memory budget the auto mode compares the packed matrix
    against: LIGHTGBM_TPU_DEVICE_BUDGET (bytes) when set, else the
    backend's reported per-device limit, else None (auto stays off)."""
    env = os.environ.get("LIGHTGBM_TPU_DEVICE_BUDGET", "").strip()
    if env:
        try:
            return int(env)
        except ValueError:
            Log.warning("LIGHTGBM_TPU_DEVICE_BUDGET=%r is not an integer "
                        "byte count; ignoring", env)
    try:
        import jax

        stats = jax.devices()[0].memory_stats() or {}
        limit = stats.get("bytes_limit")
        return int(limit) if limit else None
    except Exception:
        return None


def resolve_chunk_rows(config, num_features: int, itemsize: int) -> int:
    """The streaming chunk height: ``ooc_chunk_rows`` when set, else
    ~64 MiB of packed rows — always rounded UP to a ``ROW_BLOCK``
    multiple, the bit-identity alignment contract (a 1-row request
    degenerates to one block, never to a shorter summation).  Under the
    distributed trainer the same rounding applies per rank, over that
    rank's shard rows."""
    rows = int(getattr(config, "ooc_chunk_rows", 0) or 0)
    if rows <= 0:
        row_bytes = max(num_features * itemsize, 1)
        rows = max(_AUTO_CHUNK_BYTES // row_bytes, 1)
    return -(-rows // ROW_BLOCK) * ROW_BLOCK


def resolve_out_of_core(config, train_set) -> Tuple[bool, int, str]:
    """Routing decision: ``(enabled, chunk_rows, reason)``.

    ``out_of_core`` = true/false forces; "auto" turns streaming on only
    when the packed matrix exceeds the device budget.  The
    LIGHTGBM_TPU_OOC env var overrides the config knob per-run.  In a
    multi-process run ``train_set`` is this rank's shard, so the budget
    comparison (and the chunk grid) is naturally per rank."""
    mode = os.environ.get("LIGHTGBM_TPU_OOC", "").strip().lower()
    if not mode:
        mode = str(getattr(config, "out_of_core", "auto")).strip().lower()
    if mode in ("false", "0", "off", "no"):
        return False, 0, "out_of_core=false"
    if mode not in ("true", "1", "on", "yes", "auto"):
        Log.fatal("Unknown out_of_core mode %r (expected true/false/auto)",
                  mode)
    # what would be resident: the per-feature bins or, for a dataset made
    # from sparse input, its bundle columns
    itemsize = int(train_set.bin_dtype.itemsize)
    cols = (train_set.num_features if train_set.has_dense_bins
            else train_set.bundle.num_cols)
    packed = int(train_set.num_data) * int(cols) * itemsize
    if mode == "auto":
        budget = _device_budget_bytes()
        if budget is None:
            return False, 0, "auto: no device budget known"
        if packed <= budget:
            return False, 0, (f"auto: packed bins {packed} B fit the "
                              f"{budget} B device budget")
        reason = (f"auto: packed bins {packed} B exceed the {budget} B "
                  "device budget")
    else:
        reason = "out_of_core=true (forced)"
    chunk_rows = resolve_chunk_rows(
        config, train_set.num_features, itemsize)
    return True, chunk_rows, reason


class OocTrainer:
    """Drop-in ``learner`` for GBDT: ``grow()`` matches ShardedLearner's
    signature (the ``bins`` argument is ignored — the matrix is streamed
    from this trainer's chunk source, never device-resident)."""

    def __init__(self, train_set, config, grow_params, chunk_rows: int):
        if grow_params.parallel != "serial":
            raise ValueError("out-of-core training is serial-only")
        self.params = grow_params._replace(compact=False)
        self.num_rows = int(train_set.num_data)
        self.num_features = int(train_set.num_features)
        self.plan = ChunkPlan(self.num_rows, chunk_rows)
        self.stats = PrefetchStats()
        self.depth = max(int(getattr(config, "ooc_prefetch_depth", 2) or 2), 1)
        self.source = make_chunk_source(train_set)
        self.chunks = ChunkStream(self.source, self.plan, self.depth,
                                  self.stats)
        self.folder = ChunkFolder(self.chunks, self.num_features,
                                  self.params.num_bins,
                                  self.params.row_block)
        self._trees_grown = 0
        tracer.event(
            "ooc.plan",
            rows=self.num_rows, features=self.num_features,
            chunk_rows=self.plan.chunk_rows, chunks=self.plan.num_chunks,
            depth=self.depth, source=self.source.describe(),
        )
        Log.info(
            "Out-of-core training: %d rows in %d chunks of %d (%s, "
            "prefetch depth %d)", self.num_rows, self.plan.num_chunks,
            self.plan.chunk_rows, self.source.describe(), self.depth,
        )

    def schedule_fingerprint(self) -> str:
        """Chunk-schedule identity for checkpoints: a resume streaming a
        different grid would change float summation order."""
        return self.plan.fingerprint()

    # ------------------------------------------------------------------
    def grow(self, bins_ignored, grad, hess, select, feature_mask,
             meta, hyper, qscale=None) -> GrowResult:
        """Grow one leaf-wise tree, streaming the matrix per pass.

        Host-driven replay of ``grow_tree``'s best-first loop: the
        per-leaf tables live on host as np.float32 (f32 round-trips are
        exact; ``np.argmax`` keeps the same first-max tie-break), the
        histograms live on device and accumulate chunk-by-chunk through
        the ChunkFolder's streamed folds.

        Quantized training: int16 ``grad``/``hess`` (plus the (2,)
        ``qscale``) switch the streamed folds to exact int32 — integer
        adds are associative, so the chunk grid cannot perturb the
        histogram AT ALL (the f32 contract needs ROW_BLOCK-aligned
        boundaries for that) — and dequantization happens once per
        node, just before the split scan."""
        L = self.params.num_leaves
        use_missing = self.params.use_missing
        stats0 = dict(self.stats.as_dict())
        quant = jnp.issubdtype(grad.dtype, jnp.integer)
        if quant and qscale is None:
            raise ValueError("integer grad/hess require the qscale argument")
        deq = (lambda h: dequantize_hist(h, qscale)) if quant else (lambda h: h)
        # monotone-constraint strategy seam (tree/strategy.py): the
        # host-driven replay carries per-leaf output bounds in the same
        # np.float32 tables as the split state; unconstrained keeps the
        # exact pre-strategy call graph (None kwargs)
        mono_t = self.params.strategy.split_gain.monotone
        use_mono = any(c != 0 for c in mono_t)
        if use_mono and len(mono_t) != self.num_features:
            raise ValueError(
                f"monotone constraint vector has {len(mono_t)} entries "
                f"but the dataset has {self.num_features} inner features")
        mono = jnp.asarray(mono_t, jnp.int32) if use_mono else None
        leaf_lo = np.full((self.params.num_leaves,), NEG_INF, np.float32)
        leaf_hi = np.full((self.params.num_leaves,), np.inf, np.float32)

        with tracer.span("ooc.grow", tree=self._trees_grown,
                         chunks=self.plan.num_chunks):
            # ---- root: LeafSplits::Init on the resident vectors + one
            # streamed histogram pass
            sums_dev = root_totals(grad, hess, select)
            if quant:
                sums_dev = dequantize_sums(sums_dev, qscale)
            hist = self.folder.fold_root(grad, hess, select)
            root_sums = np.asarray(sums_dev, np.float32)
            if use_mono:
                root_res = find_best_split(
                    deq(hist), sums_dev, feature_mask, True, meta, hyper,
                    use_missing, monotone=mono,
                    leaf_lo=leaf_lo[0], leaf_hi=leaf_hi[0])
            else:
                root_res = find_best_split(deq(hist), sums_dev,
                                           feature_mask, True, meta,
                                           hyper, use_missing)

            # host-side per-leaf tables (np.float32 throughout: any f64
            # promotion here would change the replayed arithmetic)
            bs_gain = np.full((L,), NEG_INF, np.float32)
            bs_feat = np.zeros((L,), np.int32)
            bs_thr = np.zeros((L,), np.int32)
            bs_dbz = np.zeros((L,), np.int32)
            bs_left = np.zeros((L, 3), np.float32)
            leaf_sum = np.zeros((L, 3), np.float32)
            leaf_value = np.zeros((L,), np.float32)
            leaf_cnt = np.zeros((L,), np.float32)
            leaf_depth = np.zeros((L,), np.int32)
            leaf_rows = np.zeros((L,), np.int64)
            rec_i = {k: np.zeros((L - 1,), np.int32)
                     for k in ("leaf", "feat", "thr", "dbz")}
            rec_f = {k: np.zeros((L - 1,), np.float32)
                     for k in ("gain", "lval", "rval", "lcnt", "rcnt",
                               "internal_value")}
            leaf_sum[0] = root_sums
            leaf_cnt[0] = root_sums[2]
            leaf_rows[0] = self.num_rows

            def store(leaf: int, res) -> None:
                bs_gain[leaf] = np.float32(res.gain)
                bs_feat[leaf] = np.int32(res.feature)
                bs_thr[leaf] = np.int32(res.threshold_bin)
                bs_dbz[leaf] = np.int32(res.default_bin_for_zero)
                bs_left[leaf] = np.asarray(
                    [res.left_sum_g, res.left_sum_h, res.left_cnt],
                    np.float32)

            store(0, root_res)
            pool = {0: hist}
            leaf_id = jnp.zeros((self.num_rows,), jnp.int32)
            default_bin = np.asarray(meta.default_bin)
            is_categorical = np.asarray(meta.is_categorical)

            num_splits = 0
            while num_splits < L - 1:
                bl = int(np.argmax(bs_gain))
                gain = bs_gain[bl]
                # "No further splits with positive gain"
                if not (gain > 0.0):
                    break
                s = num_splits
                rl = s + 1
                feat = int(bs_feat[bl])
                thr = int(bs_thr[bl])
                dbz = int(bs_dbz[bl])
                left = bs_left[bl].copy()
                right = leaf_sum[bl] - left
                if use_mono:
                    plo, phi = leaf_lo[bl], leaf_hi[bl]
                    lval_d, rval_d = child_leaf_values(
                        left, right, hyper.lambda_l1, hyper.lambda_l2,
                        plo, phi)
                    lval = np.float32(lval_d)
                    rval = np.float32(rval_d)
                    # BasicLeafConstraints mid-point tightening: splitting
                    # a constrained feature bounds the children at the
                    # midpoint of the two (clipped) outputs
                    cdir = int(mono_t[feat])
                    mid = np.float32((lval + rval) * np.float32(0.5))
                    child_lhi = mid if cdir > 0 else phi
                    child_llo = mid if cdir < 0 else plo
                    child_rlo = mid if cdir > 0 else plo
                    child_rhi = mid if cdir < 0 else phi
                    leaf_lo[bl], leaf_hi[bl] = child_llo, child_lhi
                    leaf_lo[rl], leaf_hi[rl] = child_rlo, child_rhi
                else:
                    lval_d, rval_d = child_leaf_values(
                        left, right, hyper.lambda_l1, hyper.lambda_l2)
                    lval = np.float32(lval_d)
                    rval = np.float32(rval_d)

                # ---- one streamed pass: partition + both children hists
                leaf_id, hist_l, hist_r, n_left = self.folder.fold_split(
                    leaf_id, pool[bl], grad, hess, select, feat,
                    int(default_bin[feat]), dbz, thr,
                    bool(is_categorical[feat]), bl, rl,
                )
                n_rows_left = int(n_left)
                n_rows_right = int(leaf_rows[bl]) - n_rows_left
                # smaller child keeps its DIRECT accumulation; the larger
                # is parent - smaller, matching the in-memory numerics
                left_hist, right_hist = ChunkFolder.pick_children(
                    pool[bl], hist_l, hist_r, n_rows_left, n_rows_right)
                pool[bl] = left_hist
                pool[rl] = right_hist

                child_depth = int(leaf_depth[bl]) + 1
                depth_ok = (self.params.max_depth <= 0
                            or child_depth < self.params.max_depth)
                if use_mono:
                    lres = find_best_split(
                        deq(left_hist), left, feature_mask, depth_ok,
                        meta, hyper, use_missing, monotone=mono,
                        leaf_lo=leaf_lo[bl], leaf_hi=leaf_hi[bl])
                    rres = find_best_split(
                        deq(right_hist), right, feature_mask, depth_ok,
                        meta, hyper, use_missing, monotone=mono,
                        leaf_lo=leaf_lo[rl], leaf_hi=leaf_hi[rl])
                else:
                    lres = find_best_split(deq(left_hist), left,
                                           feature_mask, depth_ok, meta,
                                           hyper, use_missing)
                    rres = find_best_split(deq(right_hist), right,
                                           feature_mask, depth_ok, meta,
                                           hyper, use_missing)

                rec_i["leaf"][s] = bl
                rec_i["feat"][s] = feat
                rec_i["thr"][s] = thr
                rec_i["dbz"][s] = dbz
                rec_f["gain"][s] = gain
                rec_f["lval"][s] = lval
                rec_f["rval"][s] = rval
                rec_f["lcnt"][s] = left[2]
                rec_f["rcnt"][s] = right[2]
                rec_f["internal_value"][s] = leaf_value[bl]
                leaf_sum[bl] = left
                leaf_sum[rl] = right
                leaf_value[bl] = lval
                leaf_value[rl] = rval
                leaf_cnt[bl] = left[2]
                leaf_cnt[rl] = right[2]
                leaf_depth[bl] = child_depth
                leaf_depth[rl] = child_depth
                leaf_rows[bl] = n_rows_left
                leaf_rows[rl] = n_rows_right
                store(bl, lres)
                store(rl, rres)
                num_splits += 1

        self._trees_grown += 1
        self._emit_stream_obs(stats0)
        return GrowResult(
            num_splits=np.int32(num_splits),
            leaf_id=leaf_id,
            leaf_value=leaf_value,
            leaf_cnt=leaf_cnt,
            rec_leaf=rec_i["leaf"], rec_feat=rec_i["feat"],
            rec_thr=rec_i["thr"], rec_dbz=rec_i["dbz"],
            rec_gain=rec_f["gain"], rec_lval=rec_f["lval"],
            rec_rval=rec_f["rval"], rec_lcnt=rec_f["lcnt"],
            rec_rcnt=rec_f["rcnt"],
            rec_internal_value=rec_f["internal_value"],
        )

    # ------------------------------------------------------------------
    def add_tree_scores(self, score_k, arrays):
        """Streamed ``predict_binned`` over the chunk grid: the rollback /
        DART score path when the matrix is not device-resident."""
        return self.folder.streamed_scores(score_k, arrays)

    def _emit_stream_obs(self, before: dict, **attrs) -> None:
        if not tracer.enabled:
            return
        now = self.stats.as_dict()
        tracer.counter("ooc.chunks", now["chunks"] - before["chunks"],
                       **attrs)
        tracer.counter("ooc.bytes", now["bytes"] - before["bytes"], **attrs)
        tracer.gauge("ooc.fetch_ms",
                     (now["fetch_s"] - before["fetch_s"]) * 1e3, **attrs)
        tracer.gauge("ooc.stall_ms",
                     (now["stall_s"] - before["stall_s"]) * 1e3, **attrs)
        tracer.gauge("ooc.overlap_pct", now["overlap_pct"], **attrs)
