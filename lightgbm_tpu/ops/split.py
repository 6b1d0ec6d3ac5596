"""Vectorized best-split search over a histogram's three (F, B) planes.

Counterpart of FeatureHistogram::FindBestThreshold*
(src/treelearner/feature_histogram.hpp:71-198, 253-387).  The reference
scans each feature's bins sequentially in two directions with three
zero/missing placements; here every (feature, placement, threshold) cell is
evaluated at once from prefix sums, and the sequential early-`break`s become
masks (they are monotone in the scan direction, so masking is equivalent).

Zero/missing placements (FindBestThresholdNumerical, hpp:85-96): rows whose
value is zero/missing live in the feature's `default_bin`; a split may
route them left (as-if bin 0), naturally (their own bin), or right (as-if
bin B-1).  The chosen placement is recorded as `default_bin_for_zero` and
replayed at partition/prediction time (tree.h DefaultValueForZero).

Tie-breaking parity: the reference keeps the first strictly-better
candidate in scan order, which prefers (a) lower feature index, (b)
placement order zero-left, natural, zero-right, (c) larger threshold for
the right-to-left scans (placements zero-left/natural) and smaller
threshold for the left-to-right scan (zero-right).

Numerical-precision note: the reference accumulates in float64 with
kEpsilon=1e-15 seeds; this implementation uses float32 (the same trade the
reference's own GPU path makes with gpu_use_dp=false) and drops the
epsilons, which are below f32 resolution.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

# host constant: a jnp scalar here would initialize the XLA backend at
# import time, which breaks jax.distributed.initialize (must run first)
NEG_INF = float("-inf")


class SplitHyper(NamedTuple):
    """Split-relevant hyperparameters (TreeConfig, config.h:189-234)."""

    lambda_l1: jnp.ndarray
    lambda_l2: jnp.ndarray
    min_data_in_leaf: jnp.ndarray
    min_sum_hessian_in_leaf: jnp.ndarray
    min_gain_to_split: jnp.ndarray

    @classmethod
    def from_config(cls, config) -> "SplitHyper":
        return cls(
            jnp.float32(config.lambda_l1),
            jnp.float32(config.lambda_l2),
            jnp.float32(config.min_data_in_leaf),
            jnp.float32(config.min_sum_hessian_in_leaf),
            jnp.float32(config.min_gain_to_split),
        )


class FeatureMeta(NamedTuple):
    """Static per-feature metadata arrays (FeatureMetainfo, hpp:14-21)."""

    num_bins: jnp.ndarray  # (F,) int32
    default_bin: jnp.ndarray  # (F,) int32
    is_categorical: jnp.ndarray  # (F,) bool

    @classmethod
    def from_dataset(cls, dataset) -> "FeatureMeta":
        import numpy as np
        from ..io.binning import CATEGORICAL

        return cls(
            jnp.asarray(np.array([m.num_bin for m in dataset.bin_mappers], np.int32)),
            jnp.asarray(np.array([m.default_bin for m in dataset.bin_mappers], np.int32)),
            jnp.asarray(
                np.array([m.bin_type == CATEGORICAL for m in dataset.bin_mappers], bool)
            ),
        )


class SplitResult(NamedTuple):
    """Scalar best split over all features (SplitInfo, split_info.hpp:17)."""

    gain: jnp.ndarray  # already min_gain_shift-subtracted
    feature: jnp.ndarray  # inner feature index, int32
    threshold_bin: jnp.ndarray  # int32
    default_bin_for_zero: jnp.ndarray  # int32
    left_sum_g: jnp.ndarray
    left_sum_h: jnp.ndarray
    left_cnt: jnp.ndarray
    right_sum_g: jnp.ndarray
    right_sum_h: jnp.ndarray
    right_cnt: jnp.ndarray
    left_output: jnp.ndarray
    right_output: jnp.ndarray


def leaf_split_gain(sum_g, sum_h, l1, l2):
    """GetLeafSplitGain (feature_histogram.hpp:230-236)."""
    reg = jnp.maximum(jnp.abs(sum_g) - l1, 0.0)
    return reg * reg / (sum_h + l2)


def leaf_output(sum_g, sum_h, l1, l2):
    """CalculateSplittedLeafOutput (feature_histogram.hpp:244-249)."""
    reg = jnp.maximum(jnp.abs(sum_g) - l1, 0.0)
    return -jnp.sign(sum_g) * reg / (sum_h + l2)


def _threshold_l1(sum_g, l1):
    """ThresholdL1 (feature_histogram.hpp:238-242), signed."""
    return jnp.sign(sum_g) * jnp.maximum(jnp.abs(sum_g) - l1, 0.0)


def leaf_split_gain_given_output(sum_g, sum_h, l1, l2, output):
    """GetLeafSplitGainGivenOutput (feature_histogram.hpp): the gain a
    leaf contributes when its output is FORCED to ``output`` (the
    monotone-clipped value) instead of the unconstrained optimum.  At
    the unconstrained optimum this equals ``leaf_split_gain`` exactly in
    real arithmetic but NOT in f32 — which is why the unconstrained path
    keeps the closed form and stays bit-identical."""
    sg_l1 = _threshold_l1(sum_g, l1)
    return -(2.0 * sg_l1 * output + (sum_h + l2) * output * output)


def _pick(x, idx):
    """``x[f, idx[f]]`` of an (F, n) plane, (F,): SELECTED, by comparing the
    minor axis with the index and OR-ing the one hit's bits out of zeros
    (-0.0 stays -0.0), so that it fuses into whatever reads ``x`` in
    whatever layout that has.  A ``take_along_axis`` here is a gather: under
    the grower's ``vmap`` over slots and siblings the TPU compiler answers it
    by copying the whole plane with the BATCH on the lanes (`f32[4,2,1,2000,63]
    {0,1,4,3,2:T(2,128)}`, 129 MB for 4 MB, a plane; compiled here for the
    v5e, PR 40)."""
    hit = jnp.arange(x.shape[1])[None, :] == idx[:, None]
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    out = jax.lax.reduce(jnp.where(hit, bits, 0), jnp.int32(0), jax.lax.bitwise_or, (1,))
    return jax.lax.bitcast_convert_type(out, x.dtype)


def _argmax_prefer_high(x):
    """argmax returning the HIGHEST index among ties (right-to-left scan)."""
    n = x.shape[-1]
    return n - 1 - jnp.argmax(x[..., ::-1], axis=-1)


def best_split_planes(
    g: jnp.ndarray,
    h: jnp.ndarray,
    c: jnp.ndarray,
    sum_g: jnp.ndarray,
    sum_h: jnp.ndarray,
    num_data: jnp.ndarray,
    meta: FeatureMeta,
    hyper: SplitHyper,
    feature_mask: jnp.ndarray,
    use_missing: bool = True,
    has_categorical: bool = True,
    monotone: jnp.ndarray = None,
    leaf_lo: jnp.ndarray = None,
    leaf_hi: jnp.ndarray = None,
):
    """Per-feature best split: returns (gain_f, thr_f, dbz_f, left_f) with
    shapes (F,), (F,), (F,), (F, 3).  The per-feature half of
    FindBestThresholds — exposed separately so the parallel learners can
    vote / reduce over features before the global argmax.

    g, h, c : (F, B) f32 planes of a histogram, sum_g, sum_h and cnt per
        bin, the bins on the minor axis: the layout the streaming kernels
        emit (ops/pkernels.py ``child_planes``), one lane a cell.  Every
        prefix sum, take and argmax below runs along that axis; the three
        quantities never share one (an ``(F, B, 3)`` array lies in HBM with
        its 3 on the lanes, 43 times its payload at 63 bins).
    sum_g/sum_h/num_data : leaf totals (LeafSplits snapshot) — used for the
        complement side exactly like the reference (right = total - left).
    feature_mask : (F,) f32 0/1 — feature_fraction sampling mask.
    monotone/leaf_lo/leaf_hi : monotone-constraint surface (strategy
        seam, docs/TREES.md).  ``monotone`` is the (F,) int32 direction
        vector (+1/0/-1) and ``leaf_lo``/``leaf_hi`` the leaf's
        inherited output bounds.  ``None`` (the default) compiles the
        EXACT pre-constraint graph — the bit-parity contract for
        unconstrained training.  When set: candidate child outputs are
        clipped to [leaf_lo, leaf_hi], gains are scored at the clipped
        outputs (GetLeafSplitGainGivenOutput), and candidates on a
        constrained feature whose clipped outputs violate the direction
        are invalidated.  Categorical candidates keep unconstrained
        gains (their strategy direction is forced to 0; outputs are
        still bound-clipped by the grower).
    """
    planes = (g, h, c)
    b = g.shape[1]
    l1, l2 = hyper.lambda_l1, hyper.lambda_l2
    min_cnt = hyper.min_data_in_leaf
    min_hess = hyper.min_sum_hessian_in_leaf

    if monotone is None:
        gain_shift = leaf_split_gain(sum_g, sum_h, l1, l2)
    else:
        parent_out = jnp.clip(leaf_output(sum_g, sum_h, l1, l2),
                              leaf_lo, leaf_hi)
        gain_shift = leaf_split_gain_given_output(
            sum_g, sum_h, l1, l2, parent_out)
    min_gain_shift = gain_shift + hyper.min_gain_to_split

    db = meta.default_bin  # (F,)
    nb = meta.num_bins  # (F,)
    hist_db = [_pick(x, db) for x in planes]  # (F,) each

    thr = jnp.arange(b - 1)  # candidate thresholds t: left = bins <= t
    db_gt_t = (db[:, None] > thr[None, :]).astype(g.dtype)  # (F, B-1)
    db_le_t = 1.0 - db_gt_t

    # natural left sums, (F, B-1) each
    base = [jnp.cumsum(x, axis=1)[:, : b - 1] for x in planes]
    # zero-left: default bin's mass always on the left
    left_zl = [x + db_gt_t * d[:, None] for x, d in zip(base, hist_db)]
    # zero-right: default bin's mass always on the right
    left_zr = [x - db_le_t * d[:, None] for x, d in zip(base, hist_db)]

    def eval_placement(left, extra_valid):
        lg, lh, lc = left
        rg, rh, rc = sum_g - lg, sum_h - lh, num_data - lc
        valid = (
            extra_valid
            & (lc >= min_cnt)
            & (rc >= min_cnt)
            & (lh >= min_hess)
            & (rh >= min_hess)
            & (thr[None, :] <= nb[:, None] - 2)
        )
        if monotone is None:
            gain = leaf_split_gain(lg, lh, l1, l2) + leaf_split_gain(rg, rh, l1, l2)
        else:
            lout = jnp.clip(leaf_output(lg, lh, l1, l2), leaf_lo, leaf_hi)
            rout = jnp.clip(leaf_output(rg, rh, l1, l2), leaf_lo, leaf_hi)
            mono = monotone[:, None]  # (F, 1) broadcast over thresholds
            bad = ((mono > 0) & (lout > rout)) | ((mono < 0) & (lout < rout))
            gain = (leaf_split_gain_given_output(lg, lh, l1, l2, lout)
                    + leaf_split_gain_given_output(rg, rh, l1, l2, rout))
            gain = jnp.where(bad, NEG_INF, gain)
        gain = jnp.where(valid & (gain > min_gain_shift), gain, NEG_INF)
        return gain  # (F, B-1)

    interior = (db > 0) & (db < nb - 1)
    always = jnp.ones_like(db_gt_t, dtype=bool)
    if use_missing:
        # placement order and tie preference mirror
        # FindBestThresholdNumerical (hpp:85-96)
        gain_zl = eval_placement(left_zl, always & (thr[None, :] != db[:, None] - 1))
        gain_nat = eval_placement(base, interior[:, None] & always)
        gain_zr = eval_placement(
            left_zr, (nb[:, None] > 2) & (thr[None, :] != db[:, None])
        )
        # One flattened first-max argmax with the reference's tie order
        # baked into the axis layout: zero-left before natural before
        # zero-right (strict > between placements), HIGH threshold
        # preferred within zl/nat (reversed), LOW within zr — collapses
        # the 3x (argmax + takes + wheres) cascade, which dominates the
        # per-split cost inside the grower's while_loop.
        flat_gain = jnp.concatenate(
            [gain_zl[:, ::-1], gain_nat[:, ::-1], gain_zr], axis=1
        )  # (F, 3*(B-1))
        idx = jnp.argmax(flat_gain, axis=1)
        best_gain_f = _pick(flat_gain, idx)
        pl = idx // (b - 1)
        off = idx % (b - 1)
        best_thr_f = jnp.where(pl == 2, off, b - 2 - off).astype(jnp.int32)
        best_dbz_f = jnp.where(
            pl == 0, 0, jnp.where(pl == 1, db, nb - 1)
        ).astype(jnp.int32)
        # the winner's left sums: its natural ones, and the default bin's
        # mass moved as its placement moves it (the cell of ``left_zl`` or
        # ``left_zr`` at the threshold, from the same two operands)
        zl_add = (db > best_thr_f).astype(g.dtype)
        zr_sub = 1.0 - zl_add
        best_left_f = jnp.stack([
            jnp.where(pl == 0, nat + zl_add * d,
                      jnp.where(pl == 1, nat, nat - zr_sub * d))
            for nat, d in zip((_pick(x, best_thr_f) for x in base), hist_db)], axis=1)
    else:
        gain_nat = eval_placement(base, always)
        t_idx = _argmax_prefer_high(gain_nat)
        best_gain_f = _pick(gain_nat, t_idx)
        best_thr_f = t_idx.astype(jnp.int32)
        best_dbz_f = db.astype(jnp.int32)
        best_left_f = jnp.stack([_pick(x, t_idx) for x in base], axis=1)

    if not has_categorical:
        best_gain_f = jnp.where(feature_mask > 0, best_gain_f, NEG_INF)
        best_gain_f = jnp.where(
            jnp.isfinite(best_gain_f), best_gain_f - min_gain_shift, NEG_INF
        )
        return best_gain_f, best_thr_f, best_dbz_f, best_left_f

    # categorical one-vs-rest (FindBestThresholdCategorical, hpp:100-198):
    # left = exactly bin t, decision type "is"; zeros keep their natural bin
    og, oh, oc = sum_g - g, sum_h - h, num_data - c
    cat_valid = (
        (c >= min_cnt)
        & (oc >= min_cnt)
        & (h >= min_hess)
        & (oh >= min_hess)
        & (jnp.arange(b)[None, :] <= nb[:, None] - 1)
    )
    cat_gain = leaf_split_gain(g, h, l1, l2) + leaf_split_gain(og, oh, l1, l2)
    cat_gain = jnp.where(cat_valid & (cat_gain > min_gain_shift), cat_gain, NEG_INF)
    cat_t = _argmax_prefer_high(cat_gain)  # right-to-left scan
    cat_best = _pick(cat_gain, cat_t)
    cat_left = jnp.stack([_pick(x, cat_t) for x in planes], axis=1)

    is_cat = meta.is_categorical
    best_gain_f = jnp.where(is_cat, cat_best, best_gain_f)
    best_thr_f = jnp.where(is_cat, cat_t.astype(jnp.int32), best_thr_f)
    best_dbz_f = jnp.where(is_cat, db, best_dbz_f)
    best_left_f = jnp.where(is_cat[:, None], cat_left, best_left_f)

    best_gain_f = jnp.where(feature_mask > 0, best_gain_f, NEG_INF)
    # subtract the shift so gains are comparable across leaves/shards
    best_gain_f = jnp.where(
        jnp.isfinite(best_gain_f), best_gain_f - min_gain_shift, NEG_INF
    )
    return best_gain_f, best_thr_f, best_dbz_f, best_left_f


def best_split_per_feature(
    hist: jnp.ndarray,
    sum_g: jnp.ndarray,
    sum_h: jnp.ndarray,
    num_data: jnp.ndarray,
    meta: FeatureMeta,
    hyper: SplitHyper,
    feature_mask: jnp.ndarray,
    use_missing: bool = True,
    has_categorical: bool = True,
    monotone: jnp.ndarray = None,
    leaf_lo: jnp.ndarray = None,
    leaf_hi: jnp.ndarray = None,
):
    """``best_split_planes`` on an (F, B, 3) f32 histogram of (sum_g,
    sum_h, cnt) per bin: the entry of every caller that holds one (the mask
    grower, the out-of-core and the host-parallel learners).  The same
    arithmetic in the same order, whichever entry a histogram comes in by."""
    return best_split_planes(
        hist[..., 0], hist[..., 1], hist[..., 2], sum_g, sum_h, num_data,
        meta, hyper, feature_mask, use_missing, has_categorical,
        monotone=monotone, leaf_lo=leaf_lo, leaf_hi=leaf_hi,
    )


def finalize_split(gain_f, thr_f, dbz_f, left_f, sum_g, sum_h, num_data,
                   hyper: SplitHyper, leaf_lo=None, leaf_hi=None
                   ) -> SplitResult:
    """Global argmax over the per-feature arrays (ArrayArgs::ArgMax —
    first/lowest index wins ties) and SplitInfo assembly.
    ``leaf_lo``/``leaf_hi`` (monotone bounds) clip the child outputs;
    None keeps the exact unconstrained graph."""
    l1, l2 = hyper.lambda_l1, hyper.lambda_l2
    fbest = jnp.argmax(gain_f).astype(jnp.int32)
    gain = gain_f[fbest]
    left = left_f[fbest]
    lg, lh, lc = left[0], left[1], left[2]
    rg, rh, rc = sum_g - lg, sum_h - lh, num_data - lc
    lout = leaf_output(lg, lh, l1, l2)
    rout = leaf_output(rg, rh, l1, l2)
    if leaf_lo is not None:
        lout = jnp.clip(lout, leaf_lo, leaf_hi)
        rout = jnp.clip(rout, leaf_lo, leaf_hi)
    return SplitResult(
        gain=gain,
        feature=fbest,
        threshold_bin=thr_f[fbest],
        default_bin_for_zero=dbz_f[fbest],
        left_sum_g=lg,
        left_sum_h=lh,
        left_cnt=lc,
        right_sum_g=rg,
        right_sum_h=rh,
        right_cnt=rc,
        left_output=lout,
        right_output=rout,
    )


def slice_features(meta: FeatureMeta, lo: int, hi: int) -> FeatureMeta:
    """Metadata for the contiguous column block ``[lo, hi)`` — the unit
    the feature-parallel learner shards over."""
    return FeatureMeta(
        meta.num_bins[lo:hi], meta.default_bin[lo:hi],
        meta.is_categorical[lo:hi]
    )


def best_split_feature_block(
    hist: jnp.ndarray,
    lo: jnp.ndarray,
    sum_g: jnp.ndarray,
    sum_h: jnp.ndarray,
    num_data: jnp.ndarray,
    meta_block: FeatureMeta,
    hyper: SplitHyper,
    feature_mask_block: jnp.ndarray,
    use_missing: bool = True,
    monotone: jnp.ndarray = None,
    leaf_lo: jnp.ndarray = None,
    leaf_hi: jnp.ndarray = None,
) -> SplitResult:
    """Best split over a contiguous column block starting at global
    feature index ``lo``; ``hist``/``meta_block``/``feature_mask_block``
    cover only the block's columns and the returned ``feature`` is
    GLOBAL.  The per-feature scan is elementwise in F, so a block's
    result equals the corresponding slice of the full-matrix scan bit
    for bit — the property that lets feature-parallel ranks search only
    their own columns yet reproduce the serial model exactly.
    ``monotone`` covers only the block's columns."""
    gain_f, thr_f, dbz_f, left_f = best_split_per_feature(
        hist, sum_g, sum_h, num_data, meta_block, hyper,
        feature_mask_block, use_missing,
        monotone=monotone, leaf_lo=leaf_lo, leaf_hi=leaf_hi,
    )
    res = finalize_split(
        gain_f, thr_f, dbz_f, left_f, sum_g, sum_h, num_data, hyper,
        leaf_lo=leaf_lo, leaf_hi=leaf_hi,
    )
    return res._replace(feature=res.feature + jnp.int32(lo))


def best_split_all_features(
    hist: jnp.ndarray,
    sum_g: jnp.ndarray,
    sum_h: jnp.ndarray,
    num_data: jnp.ndarray,
    meta: FeatureMeta,
    hyper: SplitHyper,
    feature_mask: jnp.ndarray,
    use_missing: bool = True,
    monotone: jnp.ndarray = None,
    leaf_lo: jnp.ndarray = None,
    leaf_hi: jnp.ndarray = None,
) -> SplitResult:
    """Best split across every feature for one leaf (per-feature scan +
    global argmax)."""
    gain_f, thr_f, dbz_f, left_f = best_split_per_feature(
        hist, sum_g, sum_h, num_data, meta, hyper, feature_mask, use_missing,
        monotone=monotone, leaf_lo=leaf_lo, leaf_hi=leaf_hi,
    )
    return finalize_split(gain_f, thr_f, dbz_f, left_f, sum_g, sum_h,
                          num_data, hyper, leaf_lo=leaf_lo, leaf_hi=leaf_hi)

