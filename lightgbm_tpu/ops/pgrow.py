"""Partitioned in-program leaf-wise grower — the performance tree learner.

Counterpart of SerialTreeLearner::Train + DataPartition
(src/treelearner/serial_tree_learner.cpp:152-207, data_partition.hpp) with
the reference's asymptotics restored on TPU: rows live physically
partitioned by leaf inside the packed (C, N) matrix of ops/pkernels.py,
so each split costs ONE streaming pass over the parent segment
(``split_stream``: two-ended in-place partition + BOTH children's
histograms in the same pass) — not O(N) — and the whole tree grows
inside ONE XLA program (a lax.while_loop over best-first splits).

vs ops/grow.py (the mask-based single-program grower): that pays a full
O(N) masked pass per split (~10 ms at 1M rows -> 2.5 s per 255-leaf
tree).  This grower runs the same tree in tens of ms.  grow.py remains
the shard_map-distributed path (collectives) and the small-data path.

Design notes (v2, measured on v5e):
- The reference's histogram-subtraction trick
  (FeatureHistogram::Subtract, feature_histogram.hpp:63) is SUPERSEDED:
  both children's histograms fall out of the partition pass for free
  (the bin one-hots — the VPU-bound cost — are shared, and the value
  rows just widen 7->14 MXU sublanes), so the (L, F, B, 3) histogram
  pool and its per-split updates are gone entirely.
- Per-split bookkeeping is packed into FOUR wide arrays (seg/bs/leaf/
  recs) updated with one scatter each: per-op dispatch inside a TPU
  while_loop body costs ~1-2 us, so the old ~25 small updates were a
  measured ~150 us/split tax.
- Left/right split search runs as ONE vmapped call over the children's
  g, h and count planes, three arrays (2, F, B): the cells as the kernels
  emit them, one lane a cell.  No (..., B, 3) histogram is built on this
  path (PR 40): with its 3 on the lanes such an array takes 43 times its
  payload.
- The carry contract (PR 27): the packed matrix goes loop carry ->
  aliased kernel -> loop carry and through NO ``lax.cond``, here and in
  the chunk programs that inline this grower (boosting/ptrainer.py).
  XLA's copy insertion answers a conditional that carries the matrix
  with whole-matrix copies (1.34 GB each at 21M rows) that copy removal
  does not elide: one in a branch that returns it untouched, and two in
  the body of EVERY loop nested inside any conditional that carries it,
  the level loop included, which has no conditional of its own.  The
  parent program had three such conditionals (``stopped`` around each
  iteration, ``gain > 0`` and ``has_pre`` in the replay), six static
  copy sites and about 700 launches a tree: 53% of the iteration
  (PERF.md section 5; docs/matrix_copy_variants.py is the reproducer).
  So the replay's stop test lives in the loop's predicate, and
  ``split_stream`` runs outside the ``has_pre`` conditional, on an empty
  segment when the children were precomputed; that conditional carries
  the small tables alone.  tests/test_phases_v5e_compile.py holds the
  compiled 21M-row program to zero copy sites.
"""

from __future__ import annotations

import functools
import os
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..obs.compilewatch import JitWatch
from ..obs.phases import (
    BUNDLE_EXPAND,
    LEVEL_PHASE,
    REPLAY,
    REPLAY_TAIL,
    SPLIT_SCAN,
    UPDATE_ROOT_HIST,
)
from .histogram_pallas import hist_segments
from .pkernels import (
    BLK,
    PLayout,
    hist_dyn,
    hist_lanes,
    level_stream,
    plane_cells,
    sibling_planes,
    split_stream,
)
from .split import (
    NEG_INF,
    FeatureMeta,
    SplitHyper,
    best_split_planes,
    finalize_split,
    leaf_output,
)


class PGrowParams(NamedTuple):
    """Static (compile-time) parameters of the partitioned grower."""

    num_leaves: int
    num_bins: int  # padded per-feature B (<= 256)
    num_features: int
    num_rows: int  # real data rows (P has BLK tail padding)
    max_depth: int = -1
    use_missing: bool = True
    has_categorical: bool = True  # static: skips the categorical split scan
    # EFB: physical matrix columns / histogram bins per column.  0 means
    # unbundled (columns == features, bins == num_bins).
    num_cols: int = 0
    num_bins_hist: int = 0
    # bin word width: 4 (Dense4bitsBin form, 8 bins/word) when every
    # column fits 16 bins, else 8
    bits: int = 8
    # data-parallel mode: shard_map mesh axis to psum histograms over
    # (DataParallelTreeLearner, data_parallel_tree_learner.cpp:148-161).
    # Where the reference reduce-SCATTERS local histograms so that each
    # worker searches a slice of the columns, this grower ALL-reduces
    # them and every device searches every column, then takes the
    # identical best split on its local segment.  Three sites: the root
    # histogram (psum'd by the chunk program), one all-reduce a LEVEL of
    # all ``level_slots`` slots' kernel-layout rows, (slots, 16, lanes)
    # with inactive slots zeroed and not left out (the search after it
    # visits the active ones alone, ``scan_batch`` at a time: the count
    # comes from tables every shard holds, so every shard takes the same
    # trips), and both children's g, h and count planes, (6, lanes), of a
    # tail split.  None/"" = serial.
    axis_name: str = None
    # level-batched expansion (phase 1) toggle.  It used to be an env
    # read (LIGHTGBM_TPU_LEVELGROW) at trace time inside the jitted
    # grower — invisible to the jit cache key, so a mid-process env
    # change silently did nothing.  It is now read ONCE at trainer
    # construction (boosting/ptrainer.py) and threaded here, where the
    # static params tuple IS the cache key.
    levelwise: bool = True


_I32_MAX = 2**31 - 1


def _count_tail(tail, has_pre, cnt):
    """``tail`` (``_PState.tail``: splits, rows) after one replayed split:
    one more split and ``cnt`` more rows unless the level phase had
    precomputed it.  The rows saturate instead of wrapping: 255 tail
    splits of a 21M-row parent pass 2^31."""
    add = jnp.stack([jnp.int32(1), jnp.minimum(cnt, _I32_MAX - tail[1])])
    return tail + jnp.where(has_pre, 0, add)


def level_slots(num_leaves: int) -> int:
    """Slots of the level phase's candidate frontier (``SMAX``): what one
    ``level_stream`` launch can partition, the leading dimension of the
    histograms it returns and, under ``axis_name``, of what a level
    all-reduces."""
    return min(-(-(num_leaves + 1) // 8) * 8, 512)


# Kernel rows one batch of the level's split search reads (``scan_batch``).
# Swept twice at 2,000 columns on the v5e, at 4 / 8 / 16 / 32 slots a batch.
# PR 34, when a slot's temporaries were (..., B, 3) arrays, 43 times their
# payload (my chip run, call A; ms of `split_scan` an iteration): 72.7 / 81.2 /
# 108.0 / 159.8, and 953.3 at all 256.  PR 40, on planes, one lane a cell (my
# chip run: ``level_split_scan`` alone on random kernel rows, ms over a full
# tree's nine levels of 1, 2, 4 ... 128, 128 active slots): 35.5 / 63.5 / 52.2 /
# 58.7, where the parent's search read 107.3 / 104.1 / 140.6 / 186.9; 2 slots
# 37.0, 1 slot 45.9.  Four still wins: a slot costs 0.082 ms there and 0.11-0.15
# in a longer batch, and a level's last batch visits fewer slots that hold
# nothing.  In the cell: 72.7 -> 21.7 ms an iteration.
SCAN_BATCH_BYTES = 32 << 20


def scan_batch(num_leaves: int, lanes: int) -> int:
    """Slots the level's split search takes at a time (``SB``): the largest
    power of two whose kernel rows, 16 of ``lanes`` float32 a slot, fit
    ``SCAN_BATCH_BYTES``, between 4 and ``level_slots``.  A function of the
    shape alone: 4 at 2,000 columns x 63 bins, 32 at 200, and all
    ``level_slots(255)`` = 256 up to 32 columns, where the search is the
    straight-line one and the program has no loop for it."""
    fit = max(SCAN_BATCH_BYTES // (16 * lanes * 4), 1)
    return min(max(1 << (fit.bit_length() - 1), 4), level_slots(num_leaves))


def level_split_scan(find2, hists, sums2, dok2, n_act, batch: int,
                     num_cols: int, num_bins: int):
    """The level phase's split search: ``find2`` on both children of the
    first ``n_act`` slots of a level's kernel-layout histograms ``hists``
    (SMAX, 16, lanes), with ``sums2`` (SMAX, 2, 3) and ``dok2`` (SMAX, 2).
    Returns (``find2``'s fields as (SMAX, 2) tables, slots visited).

    ``batch`` >= SMAX searches every slot at once.  Below it the search
    runs over ``batch`` slots at a time, ceil(n_act / batch) times: a
    slot's reductions never cross slots, so rows < ``n_act`` are the same
    whatever the batch; rows past the last batch visited keep the
    tables' initial zeros (the level phase drops every row >= ``n_act``).
    The loop carries the small tables alone; ``hists`` is its invariant."""
    smax = hists.shape[0]

    def scan(hists, sums2, dok2):
        planes2 = tuple(plane_cells(x, num_cols, num_bins)  # (slots, 2, G, BH) each
                        for x in sibling_planes(hists))
        return jax.vmap(find2)(planes2, sums2, dok2)  # fields (slots, 2)

    if batch >= smax:
        return scan(hists, sums2, dok2), jnp.int32(smax)

    def step(i, tables):
        res = scan(*(jax.lax.dynamic_slice_in_dim(a, i * batch, batch)
                     for a in (hists, sums2, dok2)))
        return jax.tree.map(
            lambda t, r: jax.lax.dynamic_update_slice_in_dim(t, r, i * batch, 0),
            tables, res)

    trips = (n_act + batch - 1) // batch
    one_batch = jax.eval_shape(scan, *(
        jax.ShapeDtypeStruct((batch,) + a.shape[1:], a.dtype) for a in (hists, sums2, dok2)))
    tables0 = jax.tree.map(
        lambda a: jnp.zeros((smax,) + a.shape[1:], a.dtype), one_batch)
    return jax.lax.fori_loop(0, trips, step, tables0), trips * batch


def child_cells(planes, num_cols: int, num_bins: int):
    """``child_planes``, (6, lanes) -> what ``find2`` takes: both children's
    g, h and count planes, three arrays (2, G, BH), the bins on the minor
    axis."""
    return tuple(plane_cells(planes[k::3], num_cols, num_bins) for k in range(3))


def levelgrow_env_params() -> dict:
    """Read the level-grower env knob once — construction-time helper
    for PGrowParams(**levelgrow_env_params())."""
    return {
        "levelwise": os.environ.get("LIGHTGBM_TPU_LEVELGROW", "1") != "0",
    }


class BundleMeta(NamedTuple):
    """Device-side EFB maps (io/bundle.py BundleInfo, shipped once).

    idx maps (feature, feature-bin) -> flat bundle-histogram slot, with
    default/padding bins pointing at the appended zero slot; the default
    bin's mass is reconstructed as leaf_totals - non-default sums
    (exactly the reference's bias/zero-bin subtraction in
    FeatureHistogram::FindBestThreshold)."""

    col: jnp.ndarray  # (F,) int32 bundle column per feature
    off_lo: jnp.ndarray  # (F,) int32
    off_hi: jnp.ndarray  # (F,) int32
    bias: jnp.ndarray  # (F,) int32
    idx: jnp.ndarray  # (F, B) int32 into (G*BH [+1 zero slot], 3)
    defmask: jnp.ndarray  # (F, B) bool


def _expand_bundle_plane(plane, total, bmeta: BundleMeta, f: int, b: int):
    """One (G, BH) plane of a bundle histogram -> the (F, B) plane of the
    per-feature histograms.  Under the ``bundle_expand`` scope wherever it is
    called from (the root's search, a level's, a tail split's): at 700
    features in 10 bundle columns it is a gather of 44,100 cells a plane."""
    with jax.named_scope(BUNDLE_EXPAND):
        flat = jnp.concatenate([plane.reshape(-1), jnp.zeros((1,))])
        hf = flat[bmeta.idx.reshape(-1)].reshape(f, b)
        dfl = total - jnp.sum(hf, axis=1)  # (F,): leaf total less non-default mass
        return jnp.where(bmeta.defmask, dfl[:, None], hf)


class PTreeResult(NamedTuple):
    """One grown tree: split records (same contract as ops/grow.GrowResult
    minus leaf_id — the partitioned layout replaces it with the segment
    table) plus the final leaf segments for the in-place score update."""

    num_splits: jnp.ndarray  # scalar int32
    starts: jnp.ndarray  # (L,) int32 physical segment start per leaf
    cnts: jnp.ndarray  # (L,) int32 physical rows per leaf
    leaf_value: jnp.ndarray  # (L,) raw (pre-shrinkage) outputs
    leaf_cnt: jnp.ndarray  # (L,) f32 selected counts
    recs_raw: jnp.ndarray  # (L-1, 12) f32 packed split records (the
    #   rec_* views below are slices of this; consumers inside fused
    #   loops should store recs_raw whole — one buffer update, not ten)
    rec_leaf: jnp.ndarray
    rec_feat: jnp.ndarray
    rec_thr: jnp.ndarray
    rec_dbz: jnp.ndarray
    rec_gain: jnp.ndarray
    rec_lval: jnp.ndarray
    rec_rval: jnp.ndarray
    rec_lcnt: jnp.ndarray
    rec_rcnt: jnp.ndarray
    rec_internal_value: jnp.ndarray
    # what the level phase did for this tree (zeros under LEVELGROW=0), as
    # (4,) int32: level_stream launches, the rows they streamed, the
    # segments they partitioned and the slots the split search visited
    # (``scan_batch`` x its trips; all ``level_slots`` a level where it
    # has no loop), each summed over the levels
    level_counts: jnp.ndarray = None
    # what the replay's tail did, as (2,) int32: the splits taken the
    # classic way (a ``split_stream`` pass over the parent's segment;
    # under ``axis_name`` each all-reduces its children's histograms) and
    # the rows those passes streamed (one shard's; saturating)
    tail_counts: jnp.ndarray = None


class _PState(NamedTuple):
    p: jnp.ndarray
    num_splits: jnp.ndarray
    seg: jnp.ndarray  # (L, 2) i32 [start, cnt]
    bs: jnp.ndarray  # (L, 8) f32 [gain, feat, thr, dbz, lg, lh, lc, 0]
    leaf: jnp.ndarray  # (L, 8) f32 [sum_g, sum_h, sum_c, value, cnt, depth, 0, 0]
    recs: jnp.ndarray  # (L-1, 12) f32 [leaf, feat, thr, dbz, gain, lval,
    #                                   rval, lcnt, rcnt, ival, 0, 0]
    pslot: jnp.ndarray  # (L,) i32 candidate-table slot of each pool leaf
    #   (>= 0: node came from the level-batched expansion; -1: classic)
    tail: jnp.ndarray = None  # (2,) i32 [splits that took the classic
    #   tail, rows their split_stream passes streamed]


def _meta_table(meta: FeatureMeta, bmeta, f: int, bits: int) -> jnp.ndarray:
    """(F, 8) f32 per-feature lookup (one gather per split instead of
    six): [default_bin, is_cat, col, off_lo, off_hi, bias, 0, 0].
    Integer values < 2^24 are exact in f32."""
    db = meta.default_bin.astype(jnp.float32)
    cat = meta.is_categorical.astype(jnp.float32)
    if bmeta is not None:
        col = bmeta.col.astype(jnp.float32)
        off_lo = bmeta.off_lo.astype(jnp.float32)
        off_hi = bmeta.off_hi.astype(jnp.float32)
        bias = bmeta.bias.astype(jnp.float32)
    else:
        col = jnp.arange(f, dtype=jnp.float32)
        off_lo = jnp.zeros((f,), jnp.float32)
        off_hi = jnp.full((f,), float(1 << bits), jnp.float32)
        bias = jnp.zeros((f,), jnp.float32)
    z = jnp.zeros((f,), jnp.float32)
    return jnp.stack([db, cat, col, off_lo, off_hi, bias, z, z], axis=1)


def sibling_split_search(params: PGrowParams, meta: FeatureMeta, hyper: SplitHyper,
                         feature_mask, bmeta=None):
    """``find2`` of ``params``: the best split of two sibling leaves at once."""
    F, B = params.num_features, params.num_bins

    def find2(planes2, sums2, depth_ok):
        """Best split for sibling leaves at once: planes2 the children's g,
        h and count planes (``child_cells``), three arrays (2, G, BH), sums2
        (2, 3) -> per-leaf scalars stacked on axis 0."""

        def one(planes, s):
            if bmeta is not None:
                planes = [_expand_bundle_plane(x, s[k], bmeta, F, B)
                          for k, x in enumerate(planes)]
            gain_f, thr_f, dbz_f, left_f = best_split_planes(
                *planes, s[0], s[1], s[2], meta, hyper, feature_mask,
                params.use_missing, has_categorical=params.has_categorical,
            )
            return finalize_split(gain_f, thr_f, dbz_f, left_f, s[0], s[1], s[2], hyper)

        res = jax.vmap(one)(planes2, sums2)
        return res._replace(gain=jnp.where(depth_ok, res.gain, NEG_INF))

    return find2


@functools.partial(jax.jit, static_argnames=("params", "interpret", "rows"),
                   donate_argnums=(0,))
def grow_tree_partitioned(
    p: jnp.ndarray,
    feature_mask: jnp.ndarray,
    meta: FeatureMeta,
    hyper: SplitHyper,
    params: PGrowParams,
    bmeta: BundleMeta = None,
    interpret: bool = False,
    root_hist: jnp.ndarray = None,
    rows: tuple = None,
):
    """Grow one leaf-wise tree over the partitioned matrix.

    Returns (PTreeResult, p').  ``p`` arrives with the g/h/sel channels
    freshly written for this tree; row ORDER is whatever the previous
    tree left (irrelevant — the root segment is always the full
    [0, num_rows) range and histograms are order-invariant).

    Two-phase growth (v3): per-split kernel launches carry a fixed
    overhead (not measured on this machine), so phase 1 expands the tree LEVEL-batched (one ``level_stream``
    launch partitions every active segment and emits all children
    histograms; one vmapped split-search per level), then phase 2 replays
    the reference's EXACT best-first selection (SerialTreeLearner::Train's
    argmax-over-leaves order, including the leaf-id tie order) as a cheap
    bookkeeping loop over the precomputed candidate tables.  Nodes the
    selection wants beyond the expanded depth fall back to the classic
    per-split ``split_stream`` path in the same loop.  The final tree is
    identical to the per-split grower's; only the kernel-launch count
    changes (~levels instead of ~num_leaves).  Set
    LIGHTGBM_TPU_LEVELGROW=0 (read once at trainer construction and
    threaded through ``params.levelwise``) to force the classic path."""
    L = params.num_leaves
    F = params.num_features
    B = params.num_bins
    n = params.num_rows
    # physical columns the kernels stream (EFB bundles or plain features)
    G = params.num_cols or F
    BH = params.num_bins_hist or B
    if rows is None:
        # the single-class layout's rows; a K > 1 layout's caller passes
        # its PLayout.rows (class 0's pair, where each class's planes are
        # swapped in turn, and its select row)
        rows = PLayout(G, bits=params.bits).rows
    per = 32 // params.bits
    mtab = _meta_table(meta, bmeta, F, params.bits)
    levelwise = params.levelwise and L > 4

    find2 = sibling_split_search(params, meta, hyper, feature_mask, bmeta)

    with jax.named_scope(UPDATE_ROOT_HIST):
        if root_hist is None:
            if levelwise:
                # multi-leaf segmented histogram kernel (one launch covers a
                # whole level's segments; the root is level 0's single
                # segment) — bit-identical to hist_dyn: same per-block
                # accumulation order, same fchunk tuning, same 3-term re-sum
                seg0_tab = jnp.zeros((8, 2), jnp.int32).at[0, 1].set(n)
                root_hist = hist_segments(
                    p, seg0_tab, 1, num_features=G, num_bins=BH,
                    bits=params.bits, rows=rows, smax=8, interpret=interpret,
                )[0]
            else:
                root_hist = hist_dyn(p, 0, n, G, BH, bits=params.bits, rows=rows,
                                     interpret=interpret)
            if params.axis_name:
                root_hist = jax.lax.psum(root_hist, params.axis_name)
        # (callers passing root_hist in data-parallel mode psum it themselves)
        root_sums = jnp.sum(root_hist[0], axis=0)  # (3,): totals via feature 0
        # once a tree, from the (G, BH, 3) histogram the chunk program hands
        # over (and all-reduces): the one search that is given that form
        rr = find2(tuple(jnp.stack([root_hist[..., k]] * 2) for k in range(3)),
                   jnp.stack([root_sums, root_sums]), jnp.array(True))

        root_val = leaf_output(root_sums[0], root_sums[1], hyper.lambda_l1, hyper.lambda_l2)
        root_bs = jnp.stack([rr.gain[0], rr.feature[0].astype(jnp.float32),
                             rr.threshold_bin[0].astype(jnp.float32),
                             rr.default_bin_for_zero[0].astype(jnp.float32),
                             rr.left_sum_g[0], rr.left_sum_h[0], rr.left_cnt[0],
                             jnp.float32(0.0)])
        root_leaf = jnp.stack([root_sums[0], root_sums[1], root_sums[2], root_val,
                               root_sums[2], jnp.float32(0.0), jnp.float32(0.0),
                               jnp.float32(0.0)])
        seg0 = jnp.zeros((L, 2), jnp.int32).at[0, 1].set(n)
        bs0 = jnp.full((L, 8), NEG_INF, jnp.float32).at[0].set(root_bs)
        leaf0 = jnp.zeros((L, 8), jnp.float32).at[0].set(root_leaf)

    # ---- phase 1: level-batched expansion into candidate tables ------
    with jax.named_scope(LEVEL_PHASE):
        if levelwise:
            SMAX = level_slots(L)
            CANDMAX = 2 * SMAX
            # A table of 2 * SMAX candidates holds a complete tree of
            # log2(SMAX) levels; one level more takes the slots that nodes
            # without a split left free.  Every level streams, and under
            # ``axis_name`` all-reduces, the histograms of all SMAX slots
            # whatever it holds (2.1 GB at 2,000 columns; the search alone
            # stops at the active ones), so a level after that would pay
            # them for the few slots still free, and whether a tree wanted
            # one moved a 255-leaf iteration by 6% from one seed to the
            # next.  Those splits are the replay's, one at a time.
            MAXLVL = (SMAX - 1).bit_length() + 1
            SB = scan_batch(L, hist_lanes(G, BH))
            c_seg0 = jnp.zeros((CANDMAX, 2), jnp.int32).at[0, 1].set(n)
            c_bs0 = jnp.full((CANDMAX, 8), NEG_INF, jnp.float32).at[0].set(root_bs)
            c_leaf0 = jnp.zeros((CANDMAX, 8), jnp.float32).at[0].set(root_leaf)
            c_childlo0 = jnp.full((CANDMAX,), -1, jnp.int32)
            frontier0 = jnp.zeros((SMAX,), jnp.int32)  # slot 0 = root

            def lcond(s):
                # a frontier, a level left, and room for two children: a
                # full table ends the phase (no level with nothing to take)
                return (s[7] > 0) & (s[8] < MAXLVL) & (s[5] + 2 <= CANDMAX)

            def lbody(s):
                (p, c_seg, c_bs, c_leaf, c_childlo, cand_n, frontier,
                 frontier_n, level, streamed) = s
                idx = jnp.arange(SMAX)
                fvalid = idx < frontier_n
                fslots = jnp.clip(frontier, 0, CANDMAX - 1)
                gains = jnp.where(fvalid, c_bs[fslots, 0], NEG_INF)
                active = gains > 0.0
                # cap: children must fit both the frontier array and the
                # candidate table; dropped nodes stay splittable via the
                # phase-2 classic tail
                n_act = jnp.minimum(jnp.sum(active.astype(jnp.int32)), SMAX // 2)
                n_act = jnp.minimum(n_act, jnp.maximum((CANDMAX - cand_n) // 2, 0))
                # compact active slots to the front (stable frontier order)
                order = jnp.argsort(jnp.where(active, 0, 1), stable=True)
                aslots = fslots[order]
                arow = idx < n_act
                segs = c_seg[aslots]  # (SMAX, 2)
                bsr = c_bs[aslots]
                feat = jnp.clip(bsr[:, 1].astype(jnp.int32), 0, F - 1)
                thr = bsr[:, 2].astype(jnp.int32)
                dbz = bsr[:, 3].astype(jnp.int32)
                mrows = mtab[feat]
                col = mrows[:, 2].astype(jnp.int32)
                seg_tab = jnp.stack([
                    segs[:, 0], jnp.where(arow, segs[:, 1], 0),
                    col // per, (col % per) * params.bits,
                    mrows[:, 0].astype(jnp.int32), dbz, thr,
                    mrows[:, 1].astype(jnp.int32),
                    mrows[:, 3].astype(jnp.int32), mrows[:, 4].astype(jnp.int32),
                    mrows[:, 5].astype(jnp.int32), jnp.zeros_like(col),
                ], axis=1)
                p, nl, hists = level_stream(
                    p, seg_tab, n_act, num_features=G, num_bins=BH,
                    bits=params.bits, rows=rows, smax=SMAX, interpret=interpret,
                )
                if params.axis_name:
                    # ONE collective per level (vs per split): global children
                    # histograms keep the tree bit-identical on every device
                    hists = jax.lax.psum(
                        jnp.where(arow[:, None, None], hists, 0.0), params.axis_name
                    )
                lsums = bsr[:, 4:7]
                tots = c_leaf[aslots][:, 0:3]
                rsums = tots - lsums
                cdepth = c_leaf[aslots][:, 5] + 1.0
                sums2 = jnp.stack([lsums, rsums], axis=1)  # (SMAX, 2, 3)
                dok2 = (jnp.ones((SMAX, 2), bool) if params.max_depth <= 0
                        else jnp.stack([cdepth < params.max_depth] * 2, axis=1))
                # 2 x G x BH cells a slot: all SMAX slots at once where that
                # is small (29 MB of histograms a level at 28 columns), SB at
                # a time up to n_act where it is not (2.1 GB at 2,000)
                with jax.named_scope(SPLIT_SCAN):
                    res, scanned = level_split_scan(
                        find2, hists, sums2, dok2, n_act, SB, G, BH)  # fields (SMAX, 2)
                vals2 = leaf_output(sums2[..., 0], sums2[..., 1],
                                    hyper.lambda_l1, hyper.lambda_l2)  # (SMAX, 2)
                il = jnp.where(arow, cand_n + 2 * idx, CANDMAX)
                ir = jnp.where(arow, cand_n + 2 * idx + 1, CANDMAX)
                seg_l = jnp.stack([segs[:, 0], nl], axis=1)
                seg_r = jnp.stack([segs[:, 0] + nl, segs[:, 1] - nl], axis=1)
                c_seg = (c_seg.at[il].set(seg_l, mode="drop")
                         .at[ir].set(seg_r, mode="drop"))

                def bs_rows(k):
                    return jnp.stack([
                        res.gain[:, k], res.feature[:, k].astype(jnp.float32),
                        res.threshold_bin[:, k].astype(jnp.float32),
                        res.default_bin_for_zero[:, k].astype(jnp.float32),
                        res.left_sum_g[:, k], res.left_sum_h[:, k],
                        res.left_cnt[:, k], jnp.zeros((SMAX,), jnp.float32),
                    ], axis=1)

                c_bs = (c_bs.at[il].set(bs_rows(0), mode="drop")
                        .at[ir].set(bs_rows(1), mode="drop"))

                def leaf_rows(k):
                    z = jnp.zeros((SMAX,), jnp.float32)
                    return jnp.stack([
                        sums2[:, k, 0], sums2[:, k, 1], sums2[:, k, 2],
                        vals2[:, k], sums2[:, k, 2], cdepth, z, z,
                    ], axis=1)

                c_leaf = (c_leaf.at[il].set(leaf_rows(0), mode="drop")
                          .at[ir].set(leaf_rows(1), mode="drop"))
                par = jnp.where(arow, aslots, CANDMAX)
                c_childlo = c_childlo.at[par].set(
                    jnp.where(arow, il, -1), mode="drop")
                children = jnp.clip(
                    jnp.stack([il, ir], axis=1).reshape(-1)[:SMAX], 0, CANDMAX - 1
                )
                return (p, c_seg, c_bs, c_leaf, c_childlo, cand_n + 2 * n_act,
                        children, 2 * n_act, level + 1,
                        streamed + jnp.stack([jnp.sum(seg_tab[:, 1]), n_act, scanned]))

            (p, c_seg, c_bs, c_leaf, c_childlo, _, _, _, levels, streamed) = (
                jax.lax.while_loop(
                    lcond, lbody,
                    (p, c_seg0, c_bs0, c_leaf0, c_childlo0, jnp.int32(1), frontier0,
                     jnp.int32(1), jnp.int32(0), jnp.zeros((3,), jnp.int32)),
                ))
            level_counts = jnp.concatenate([levels[None], streamed])
            pslot0 = jnp.full((L,), -1, jnp.int32).at[0].set(0)
        else:
            CANDMAX = 1
            c_seg = jnp.zeros((1, 2), jnp.int32)
            c_bs = jnp.zeros((1, 8), jnp.float32)
            c_leaf = jnp.zeros((1, 8), jnp.float32)
            c_childlo = jnp.full((1,), -1, jnp.int32)
            pslot0 = jnp.full((L,), -1, jnp.int32)
            level_counts = jnp.zeros((4,), jnp.int32)

    # ---- phase 2: exact best-first selection ------------------------
    st = _PState(
        p=p,
        num_splits=jnp.int32(0),
        seg=seg0,
        bs=bs0,
        leaf=leaf0,
        recs=jnp.zeros((L - 1, 12), jnp.float32),
        pslot=pslot0,
        tail=jnp.zeros((2,), jnp.int32),
    )

    # "no leaf left with a positive gain" is part of the predicate, not a
    # lax.cond in the body: no conditional may carry p (module docstring)
    def cond(st: _PState):
        return (st.num_splits < L - 1) & (jnp.max(st.bs[:, 0]) > 0.0)

    def body(st: _PState):
        s = st.num_splits
        bl = jnp.argmax(st.bs[:, 0]).astype(jnp.int32)
        rl = (s + 1).astype(jnp.int32)

        bsrow = st.bs[bl]
        gain = bsrow[0]
        feat = bsrow[1].astype(jnp.int32)
        thr = bsrow[2].astype(jnp.int32)
        dbz = bsrow[3].astype(jnp.int32)
        left = bsrow[4:7]
        leafrow = st.leaf[bl]
        totals = leafrow[0:3]
        pval = leafrow[3]
        child_depth = leafrow[5] + 1.0
        segrow = st.seg[bl]
        start = segrow[0]
        cnt = segrow[1]
        slot = st.pslot[bl]
        childlo = c_childlo[jnp.clip(slot, 0, CANDMAX - 1)]
        has_pre = (slot >= 0) & (childlo >= 0)

        # Children the level phase precomputed need no pass over the rows:
        # the kernel is then launched on the EMPTY segment at 0 (no block
        # is read or written, nl = 0, zero histograms; tens of us) rather
        # than put in a branch, and the has_pre conditional below carries
        # the small tables alone.  Not (start, 0): an unaligned start
        # reads and rewrites one block.
        mrow = mtab[feat]
        zb = mrow[0].astype(jnp.int32)
        cat = mrow[1].astype(jnp.int32)
        colidx = mrow[2].astype(jnp.int32)
        off_lo = mrow[3].astype(jnp.int32)
        off_hi = mrow[4].astype(jnp.int32)
        bias = mrow[5].astype(jnp.int32)
        with jax.named_scope(REPLAY_TAIL):
            # planes: both children's ``child_planes``, (6, lanes)
            p, nl, planes = split_stream(
                st.p, jnp.where(has_pre, 0, start), jnp.where(has_pre, 0, cnt),
                colidx // per, (colidx % per) * params.bits, zb, dbz, thr, cat,
                off_lo=off_lo, off_hi=off_hi, bias=bias,
                num_features=G, num_bins=BH, bits=params.bits, rows=rows,
                interpret=interpret,
            )

        def take_pre(nl, planes):
            clo = jnp.clip(childlo, 0, CANDMAX - 1)
            chi = jnp.clip(childlo + 1, 0, CANDMAX - 1)
            seg2 = jnp.stack([c_seg[clo], c_seg[chi]])
            bs2 = jnp.stack([c_bs[clo], c_bs[chi]])
            leaf2 = jnp.stack([c_leaf[clo], c_leaf[chi]])
            ps2 = jnp.stack([clo, chi])
            return seg2, bs2, leaf2, ps2

        def take_classic(nl, planes):
            with jax.named_scope(REPLAY_TAIL):
                if params.axis_name:
                    # global children histograms; the split decision below is
                    # then bit-identical on every device (local segments
                    # diverge, the tree does not).  Inside the branch: a
                    # precomputed split needs no collective, and has_pre
                    # is replicated.  Reduced as the kernel's PLANES, one
                    # lane a cell (0.12 ms at 2,000 columns, where the
                    # all-reduce of a (2, G, BH, 3) array, its 3 on the
                    # lanes, took 2.65; my chip runs, PR 33): the one thing
                    # the serial and the sharded tail differ by.  The seed
                    # moves how many tail splits a tree takes, so what one
                    # costs is what an iteration differs by from seed to seed.
                    planes = jax.lax.psum(planes, params.axis_name)
                planes2 = child_cells(planes, G, BH)

            right = totals - left
            sums2 = jnp.stack([left, right])  # (2, 3)
            vals2 = leaf_output(sums2[:, 0], sums2[:, 1], hyper.lambda_l1,
                                hyper.lambda_l2)  # (2,)
            depth_ok = (
                jnp.array(True)
                if params.max_depth <= 0
                else child_depth < params.max_depth
            )
            res2 = find2(planes2, sums2, depth_ok)

            seg2 = jnp.stack(
                [jnp.stack([start, nl]), jnp.stack([start + nl, cnt - nl])]
            )
            bs2 = jnp.stack(
                [res2.gain, res2.feature.astype(jnp.float32),
                 res2.threshold_bin.astype(jnp.float32),
                 res2.default_bin_for_zero.astype(jnp.float32),
                 res2.left_sum_g, res2.left_sum_h, res2.left_cnt,
                 jnp.zeros((2,), jnp.float32)], axis=1
            )  # (2, 8)
            leaf2 = jnp.stack(
                [sums2[:, 0], sums2[:, 1], sums2[:, 2], vals2, sums2[:, 2],
                 jnp.full((2,), child_depth),
                 jnp.zeros((2,)), jnp.zeros((2,))], axis=1
            )  # (2, 8)
            ps2 = jnp.full((2,), -1, jnp.int32)
            return seg2, bs2, leaf2, ps2

        seg2, bs2, leaf2, ps2 = jax.lax.cond(
            has_pre, take_pre, take_classic, nl, planes
        )
        # child outputs are recomputed HERE, at one shared (2,)-shaped
        # site outside the cond, from the children's g/h sums.  The
        # level-batched precompute evaluates leaf_output over (SMAX, 2)
        # candidate batches; routing both branches through the SAME
        # division op removes batch-shape / fusion-context rounding as a
        # variable between the LEVELGROW modes, so accepted leaf values
        # depend only on the (psum-exact) integer-scaled g/h sums.  That
        # makes ONE tree the same in both modes from the same row layout.
        # The modes leave different layouts behind it (the level phase
        # partitions candidates the selection never takes), and since
        # PR 30 the next tree starts in the layout it finds, so its f32
        # histogram sums may differ between the modes by an ulp: byte-equal
        # in the first tree, equal in structure and to rounding after it,
        # each mode bit-deterministic (tests/test_row_order.py).
        leaf2 = leaf2.at[:, 3].set(
            leaf_output(leaf2[:, 0], leaf2[:, 1],
                        hyper.lambda_l1, hyper.lambda_l2))
        idx2 = jnp.stack([bl, rl])
        rec = jnp.stack(
            [bl.astype(jnp.float32), feat.astype(jnp.float32),
             thr.astype(jnp.float32), dbz.astype(jnp.float32), gain,
             leaf2[0, 3], leaf2[1, 3], leaf2[0, 2], leaf2[1, 2], pval,
             jnp.float32(0.0), jnp.float32(0.0)]
        )

        return st._replace(
            p=p,
            num_splits=s + 1,
            seg=st.seg.at[idx2].set(seg2),
            bs=st.bs.at[idx2].set(bs2),
            leaf=st.leaf.at[idx2].set(leaf2),
            recs=st.recs.at[s].set(rec),
            pslot=st.pslot.at[idx2].set(ps2),
            tail=_count_tail(st.tail, has_pre, cnt),
        )

    with jax.named_scope(REPLAY):
        st = jax.lax.while_loop(cond, body, st)
        recs = st.recs
        res = PTreeResult(
            num_splits=st.num_splits,
            starts=st.seg[:, 0],
            cnts=st.seg[:, 1],
            leaf_value=st.leaf[:, 3],
            leaf_cnt=st.leaf[:, 4],
            recs_raw=recs,
            rec_leaf=recs[:, 0].astype(jnp.int32),
            rec_feat=recs[:, 1].astype(jnp.int32),
            rec_thr=recs[:, 2].astype(jnp.int32),
            rec_dbz=recs[:, 3].astype(jnp.int32),
            rec_gain=recs[:, 4],
            rec_lval=recs[:, 5],
            rec_rval=recs[:, 6],
            rec_lcnt=recs[:, 7],
            rec_rcnt=recs[:, 8],
            rec_internal_value=recs[:, 9],
            level_counts=level_counts,
            tail_counts=st.tail,
        )
    return res, st.p


# compile/retrace + HLO cost accounting on the standalone grower entry
# (obs/compilewatch.py): when the fused chunk programs trace this
# inline, the call passes straight through the watch
grow_tree_partitioned = JitWatch(grow_tree_partitioned,
                                 "ops.grow_tree_partitioned", phase="tree")


def level_hists(p, seg_tab, n_active, params: PGrowParams, rows=None,
                interpret: bool = False):
    """(smax, G, BH, 3) histograms of every active leaf segment of a
    level in ONE kernel launch (ops/histogram_pallas.hist_segments) —
    the multi-leaf replacement for a per-leaf hist_dyn launch loop.

    The fused grower normally gets level histograms for free from
    ``level_stream``'s partition pass; this helper serves callers that
    need segment histograms OUTSIDE a partition (root histograms,
    numerics tripwires), at one launch
    per level instead of one per leaf.  seg_tab: (smax, 2) int32 rows of
    [start, cnt]."""
    G = params.num_cols or params.num_features
    BH = params.num_bins_hist or params.num_bins
    if rows is None:
        rows = PLayout(G, bits=params.bits).rows
    smax = int(seg_tab.shape[0])
    return hist_segments(
        p, seg_tab, n_active, num_features=G, num_bins=BH,
        bits=params.bits, rows=rows, smax=smax, interpret=interpret,
    )


def segment_values(tree: PTreeResult, num_rows: int, values: jnp.ndarray) -> jnp.ndarray:
    """(N,) vector assigning ``values[leaf]`` to each position of that
    leaf's segment — the partitioned-space replacement for
    leaf_id-indexed lookups.

    The lookup must be EXACT, not merely close: a float range-add
    (+v at starts, -v at ends, cumsum) leaves position-dependent 1-ULP
    residue inside segments because XLA's cumsum is a parallel prefix
    sum whose reassociation differs per position — and the physical
    order of rows inside a segment is NOT layout-stable (the level
    grower's speculative partitions shuffle it), so that residue made
    training scores depend on partition history.  Instead the value is
    SELECTED, never computed: the live segments are sorted by start, a
    position is compared with every sorted bound, and the int32 bits of
    the one segment whose ``[start, next start)`` holds it are OR-ed
    out of an otherwise zero column — every row of a leaf gets the
    bit-identical ``values[leaf]`` (``-0.0`` included).

    One fusion over the row vector: the ``(L, N)`` operand of the
    reduction is never built (6.5 ms at 21M rows x 255 leaves where a
    rank by scatter and cumsum and two gathers took 383; PERF.md, PR 32).
    Jitted here so that an eager caller gets the fusion too.

    Rows that no segment covers (none in the serial programs, whose
    segments tile ``[0, N)``): before the first start they read the
    first segment's value, past a segment's end that segment's, up to
    the next start (a shard's padded rows)."""
    return _segment_lookup(tree.starts, tree.cnts, tree.num_splits, values, num_rows)


@functools.partial(jax.jit, static_argnums=4)
def _segment_lookup(starts, cnts, num_splits, values, num_rows):
    L = starts.shape[0]
    active = jnp.arange(L) <= num_splits
    bits = jax.lax.bitcast_convert_type(
        jnp.where(active, values, 0.0).astype(jnp.float32), jnp.int32)
    # empty segments share their start with a neighbour: park them (and
    # inactive slots) past the end, where no position looks
    s = jnp.where(active & (cnts > 0), starts, num_rows)
    lo, bits = jax.lax.sort((s, bits), num_keys=1)  # physical start order
    lo = lo.at[0].set(0)
    hi = jnp.concatenate([lo[1:], jnp.full((1,), num_rows, lo.dtype)])
    pos = jax.lax.broadcasted_iota(jnp.int32, (L, num_rows), 1)
    mine = (pos >= lo[:, None]) & (pos < hi[:, None])  # one segment a column
    out = jax.lax.reduce(jnp.where(mine, bits[:, None], 0), jnp.int32(0),
                         jax.lax.bitwise_or, (0,))
    return jax.lax.bitcast_convert_type(out, jnp.float32)


def split_audit_rows(gr):
    """Host-side iterator over a GrowResult-like view's accepted splits,
    in acceptance order — the audit-trail hook (obs/audit.py).

    Accepts anything carrying the raw split-record contract that
    ``Tree.from_grow_result`` consumes (``ops/grow.GrowResult``, this
    module's :class:`PTreeResult`, ``ptrainer.grow_result_view``), which
    is exactly why audit trails are comparable across the mask, fused
    classic (LEVELGROW=0) and level-batched (LEVELGROW=1) trainer
    paths: they all converge on these records.  Values are
    pulled once per tree (one host transfer for device-resident views)
    and floats keep their stored f32 identity so two bit-identical
    record buffers yield identical rows."""
    import numpy as np

    ns = int(gr.num_splits)
    if ns <= 0:
        return
    leaf = np.asarray(gr.rec_leaf)
    thr = np.asarray(gr.rec_thr)
    dbz = np.asarray(gr.rec_dbz)
    gain = np.asarray(gr.rec_gain)
    lcnt = np.asarray(gr.rec_lcnt)
    rcnt = np.asarray(gr.rec_rcnt)
    for s in range(ns):
        yield {
            "s": s,
            "leaf": int(leaf[s]),
            "bin": int(thr[s]),
            "dbz": int(dbz[s]),
            "gain": float(gain[s]),
            "lcnt": int(lcnt[s]),
            "rcnt": int(rcnt[s]),
        }


# Rows from which ``in_row_order`` sorts.  The v5e's compiler lowers
# ``zeros(n).at[rowid].set(x)`` to a direct scatter up to ~4.08M rows, and
# from ~4.10M on to a key-value sort plus a scatter fusion through the sorted
# ids (125 ms a chunk at 21M rows, where the sort takes 51.7).  On the v5e's
# host a direct scatter compiles in under 2 s and a sort in 8-19 s, so below
# this the sort would add ~10 s to every cold compile to save 2 ms (400,000
# rows) to 10 ms (2,270,296) a chunk; above it the sort is compiled either
# way and only the scatter fusion goes.
SORT_MIN_ROWS = 4_000_000


def in_row_order(rowid, planes):
    """The K (n,) planes, each in the packed matrix's row order, put back in
    original row order as one (K, n) array through the (n,) ROWID channel:
    ``zeros(n).at[rowid].set(plane)`` a plane below ``SORT_MIN_ROWS``, else
    ONE key-value sort of the K planes laid end to end, plane k keyed by
    ``rowid + k * n``.  The channel is a permutation of 0..n-1
    (``pack_matrix`` writes ``arange(n)``, a shard's padding rows included,
    and trees only permute it), so the two give the same bytes.  Unstable,
    since the keys are distinct: a stable sort adds an iota key.  Two
    operands whatever K is: a sort carrying K + 1 operands takes 27 s to
    compile at K = 3 and over 6 minutes at K = 16, XLA merges K sorts on one
    key into that one, and a (K, n) sort along the rows ran 432 ms against
    178 at K = 3 and 21M rows (v5e).  K * n < 2**31 (K <= 16)."""
    n, K = rowid.shape[0], len(planes)
    if n < SORT_MIN_ROWS:
        return jnp.stack([jnp.zeros(p.shape, p.dtype).at[rowid].set(p) for p in planes])
    keys = jnp.concatenate([rowid + k * n for k in range(K)])
    return jax.lax.sort((keys, jnp.concatenate(planes)), num_keys=1, is_stable=False)[1].reshape(K, n)


def leaf_id_from_segments(tree: PTreeResult, p: jnp.ndarray, layout: PLayout, num_rows: int) -> jnp.ndarray:
    """(N,) int32 leaf index in ORIGINAL row order (via the rowid
    channel) — the GrowResult.leaf_id contract for driver code that needs
    it (one O(N) scatter or sort; avoided on the fast path)."""
    L = tree.starts.shape[0]
    leaf_at_pos = segment_values(
        tree, num_rows, jnp.arange(L, dtype=jnp.float32)
    ).astype(jnp.int32)
    return in_row_order(p[layout.ROWID, :num_rows], [leaf_at_pos])[0]
