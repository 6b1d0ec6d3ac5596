"""Fused partitioned trainers — boosting iterations as ONE device program.

Drives ops/pgrow.py.  The motivation is dispatch latency (a host round
trip per iteration; its cost is not measured on this machine), so the
reference's per-iteration host loop (GBDT::TrainOneIter,
gbdt.cpp:381-495) becomes a ``lax.while_loop`` over iterations INSIDE one
jitted program.  Per iteration:

  K == 1 (binary/regression, incl. GOSS):
    update_and_root_hist kernel (score += PREVIOUS tree's pending delta;
      fresh gradients from the score/label channels; bagging select; the
      root histogram of the fresh values)           [in-place Pallas]
    -> feature sampling -> grow_tree_partitioned    [split_stream kernels]
    -> the tree's score delta (one (n,) vector, each row's leaf value
       selected by comparing its position with the sorted segment
       bounds: ops/pgrow.segment_values, no n-row gather or scatter) is
       carried PENDING to the next iteration's update (the row layout
       doesn't change in between: a tree starts in the order the
       previous tree's partition left) and settled by one extra pass at
       chunk end.
    GOSS prepends a gradient-only pass + device top_k/Bernoulli sampling
    with the (n-top_k)/other_k up-weighting folded into g/h (goss.hpp).

  K > 1 (multiclass): ALL K gradient planes + K root histograms come
    from ONE streaming pass over the same score snapshot
    (update_multi_and_hists — GBDT::Boosting computes every class's
    gradients once per iteration, gbdt.cpp:692-700); the K trees then
    come from ONE traced grower in a fori_loop over the classes
    (class_trees), which reads the class-0 g/h pair, and after each tree
    one score_add band pass lands its leaf deltas on its class's score
    row (IMMEDIATELY, while the delta's partition layout is still
    current) and swaps the next class's planes into that pair.  (Deltas
    must never stay pending across another class's tree: each tree
    physically re-permutes the rows.)

Scores, labels, weights and the row id travel as bitcast channels of
the packed matrix, so nothing is ever gathered back to original row
order during training, the matrix included: every tree streams the
layout the previous one left (until PR 30 the serial program gathered
all of it back at each tree's start, 40% of a 21M-row iteration, to
keep two environment-flag modes byte-equal; the sharded program never
did).  What must follow a row and not a position, the bagging and GOSS
draws, is keyed by the row id (``rowid_uniform``).  What follows the
layout is float summation order: a run is bit-deterministic, a
checkpoint carries the layout (``export_perm``), and two runs that
partition differently (LEVELGROW=1 against =0) agree byte for byte on
the first tree and to an ulp after it.  The original-order score
vectors are rebuilt ONCE per chunk through the rowid channel
(``in_row_order``: a scatter a class below 4M rows, else one key-value
sort of every class's score plane) for metrics/eval.

Why every channel write goes through a Pallas kernel: an XLA-level
write to the packed matrix copies all of it; only
``input_output_aliases`` mutate truly in place.  The same contract
binds control flow (PR 27): no ``lax.cond`` may take or return the
matrix.  The chunk loop used to wrap each iteration in
``lax.cond(stopped, no-op, live)``, and copy insertion then put two
whole-matrix copies into the body of every loop nested inside it, the
grower's level and replay loops: about 530 launches a tree at 255
leaves (ops/pgrow.py has the account; docs/matrix_copy_variants.py the
reproducer).  The stop test is now part of the loop's predicate, and
the compiled 21M-row program has no whole-matrix copy, gather or
scatter (tests/test_phases_v5e_compile.py).

Row-order-free semantics this relies on: histograms, leaf statistics and
elementwise objectives are permutation-invariant.  A query-grouped
objective (lambdarank) is not: its iteration step adds the pending delta to
the score channel, hands that and the ROWID channel to the objective's
``grouped_gradients`` (objective/rank.py: length buckets, (N,) vectors
only) under the ``rank_grad`` scope, and the update pass then writes the
settled scores and the supplied (g, h) and takes their root histogram.

``ShardedPartitionedTrainer`` runs the same fused loop per shard under
``shard_map`` with histogram all-reduces (the root's, one a level, one a
tail split) — the data-parallel learner (data_parallel_tree_learner.cpp)
on the fast kernels.

Deliberate parity divergences from the reference (documented):
- bagging draws a per-row Bernoulli(bagging_fraction) mask with JAX
  threefry, keyed by (seed, bagging period, row id), instead of the
  host RNG's exact-count subset (gbdt.cpp:275-334); same distribution,
  different stream.
- feature_fraction samples exactly ceil(frac*F) features via device
  top_k on uniform keys instead of utils/random.py's host sampler.
- GOSS's rest-sample is Bernoulli(other_k/rest), keyed by (seed,
  iteration, row id), rather than an exact other_k-subset; the top set
  is exact top_k like the reference.
"""

from __future__ import annotations

import functools
import os
import types

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import JitWatch, fence, tracer
from ..obs.phases import (
    CHUNK_EPILOGUE,
    LEAF_DELTA,
    MULTI_UPDATE,
    RANK_GRAD,
    SAMPLE,
    SCORE_ADD,
    UPDATE_ROOT_HIST,
)
from ..ops.pgrow import (
    BundleMeta,
    PGrowParams,
    grow_tree_partitioned,
    in_row_order,
    level_slots,
    levelgrow_env_params,
    segment_values,
)
from ..ops.pkernels import (
    PLayout,
    col_groups,
    hist_lanes,
    level_stream_vmem_bytes,
    perm_tiles,
    pack_matrix_device,
    score_add,
    update_and_root_hist,
    update_multi_and_hists,
    update_value_rows,
)
from ..ops.split import (
    FeatureMeta,
    SplitHyper,
)
from ..utils.log import Log


def _f2i(x):
    return jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)


def _interpret_kernels() -> bool:
    """Whether the Pallas kernels run under the interpreter.  On the TPU
    they never do.  Off it, only the explicit LIGHTGBM_TPU_PGROW=force
    (the CPU tests' switch, the same one ``eligible`` honours) reaches
    them; anything else constructing a fused trainer without a TPU is a
    caller bug, not a reason to interpret quietly."""
    if jax.default_backend() == "tpu":
        return False
    if os.environ.get("LIGHTGBM_TPU_PGROW", "") == "force":
        return True
    Log.fatal(
        "the fused partitioned trainer needs a TPU (found backend %s); "
        "set LIGHTGBM_TPU_PGROW=force to run its kernels interpreted",
        jax.default_backend())


def _i2f(x):
    return jax.lax.bitcast_convert_type(x, jnp.float32)


def rowid_uniform(key, rowid):
    """One U[0, 1) draw per row as a function of ``(key, row id)`` alone:
    the row's id is folded into the key (a counter-based threefry draw,
    elementwise), so a row's draw does not depend on where a partition
    left it and ``rowid_uniform(key, rowid[perm]) ==
    rowid_uniform(key, rowid)[perm]``.  Bagging and GOSS's rest-sample
    draw through it from the ROWID channel, which is what lets a tree
    start in whatever order the previous one left."""
    return jax.vmap(lambda r: jax.random.uniform(jax.random.fold_in(key, r)))(rowid)


def _is_goss(config) -> bool:
    return str(config.boosting_type).lower() == "goss"


def class_trees(p, hists, fmask, lr, slots, *, lay, num_rows, meta, hyper, params, bmeta,
                interpret):
    """The K trees of one multiclass iteration (the per-class loop,
    gbdt.cpp:445-480) as ONE traced grower in a ``fori_loop`` over the
    classes, the serial and the sharded chunk programs alike.  Tree k reads
    the class-0 pair of g and h rows, into which the band pass after tree
    k - 1 swapped class k's (``score_add(class_step=k - 1)``, which also
    lands tree k - 1's delta on its score row; after the last tree the
    pairs rotate back, so an iteration leaves each class's planes where
    ``update_multi_and_hists`` wrote them, from one score snapshot).
    ``hists``: the K root histograms; ``slots``: the
    iteration's record rows (num_splits, raw, levels, tail), each with a
    leading K axis that tree k writes at ``k``.  The matrix goes loop carry
    -> aliased kernel -> loop carry, through no conditional.  Returns (p,
    slots, whether any tree split)."""
    hists = jnp.stack(hists)

    def one_class(k, carry):
        p, slots, any_split = carry
        tree, p = grow_tree_partitioned(
            p, fmask, meta, hyper, params, bmeta=bmeta, interpret=interpret,
            root_hist=hists[k], rows=lay.rows,
        )
        with jax.named_scope(LEAF_DELTA):
            keep = (tree.num_splits > 0).astype(jnp.float32)
            lval = jnp.clip(lr * tree.leaf_value, -100.0, 100.0)
            dk = segment_values(tree, num_rows, keep * lval)
        with jax.named_scope(SCORE_ADD):
            p = score_add(p, lay, dk, num_rows=num_rows, interpret=interpret, class_step=k)
        got = (tree.num_splits, tree.recs_raw, tree.level_counts, tree.tail_counts)
        slots = tuple(s.at[k].set(v) for s, v in zip(slots, got))
        return p, slots, any_split | (tree.num_splits > 0)

    return jax.lax.fori_loop(0, lay.num_score, one_class, (p, slots, jnp.array(False)))


class PartitionedTrainer:
    """Owns the packed matrix + fused train-chunk programs for one GBDT."""

    # a query-grouped objective's bucket arrays (objective/rank.py), an
    # operand of every chunk program; None for a row-wise objective
    _rank_ops = None

    def __init__(self, train_set, config, objective, meta: FeatureMeta, hyper: SplitHyper,
                 bins_dev=None):
        n, f = train_set.num_data, train_set.num_features
        assert train_set.bin_dtype == np.uint8
        md = train_set.metadata
        self.has_weights = md.weights is not None
        # K > 1: multiclass — K score channels, K trees per iteration
        # (per-class tree loop, gbdt.cpp:445-480)
        self.K = int(getattr(objective, "num_tree_per_iteration", 1))
        # EFB: stream the bundled (N, G) matrix instead of (N, F) when the
        # dataset found exclusive bundles (io/bundle.py); split search and
        # the model stay in real-feature space via BundleMeta
        bundle = getattr(train_set, "bundle", None)
        self.bmeta = None
        num_cols, num_bins_hist = 0, 0
        if bundle is not None and train_set.bundled is not None:
            matrix = train_set.bundled
            num_cols = bundle.num_cols
            num_bins_hist = int(bundle.max_col_bin)
            self.bmeta = _build_bundle_meta(bundle, train_set, int(train_set.max_num_bin))
            bins_dev = None  # the unbundled device matrix is not what we pack
            max_col_bin = num_bins_hist
        else:
            matrix = train_set.binned
            max_col_bin = int(train_set.max_num_bin)
        # 4-bit packed words when every column fits 16 bins
        # (dense_nbits_bin.hpp:37): half the resident bin bytes/traffic
        # (LIGHTGBM_TPU_FORCE_BITS=8 disables, e.g. for A/B measurement)
        force_bits = os.environ.get("LIGHTGBM_TPU_FORCE_BITS", "")
        bits = 4 if max_col_bin <= 16 else 8
        if force_bits in ("4", "8"):
            bits = int(force_bits)
            if bits == 4 and max_col_bin > 16:
                bits = 8  # cannot pack >16 bins in 4 bits
        self.layout = PLayout(matrix.shape[1], num_score=self.K, with_weight=True, bits=bits)
        # the matrix's upload where GBDT.init's `bins_upload` has not made it
        # (the bundled matrix), and the packing; ends in one wait, sink on or
        # off, for a buffer the first chunk needs whole (once a Booster)
        with tracer.stage("pack_matrix", rows=n, channels=self.layout.C) as stage:
            if bins_dev is None:
                bins_dev = jnp.asarray(np.asarray(matrix))
            self.p = jax.block_until_ready(pack_matrix_device(
                bins_dev, self.layout, label=md.label,
                weight=md.weights if self.has_weights else None))
            stage.attrs["bytes"] = int(self.p.nbytes)
        self.num_rows = n
        self.meta = meta
        self.hyper = hyper
        self.objective = objective
        self.config = config
        self.params = PGrowParams(
            num_leaves=max(2, int(config.num_leaves)),
            num_bins=int(train_set.max_num_bin),
            num_features=f,
            num_rows=n,
            max_depth=int(config.max_depth),
            use_missing=bool(config.use_missing),
            has_categorical=bool(np.any(np.asarray(meta.is_categorical))),
            num_cols=num_cols,
            num_bins_hist=num_bins_hist,
            bits=bits,
            **levelgrow_env_params(),
        )
        self.interpret = _interpret_kernels()
        # start dirty: init_score / init_model may mutate GBDT.scores after
        # construction; the first chunk syncs the channel (identity-order
        # gather, cheap)
        self.score_dirty = True
        self._progs = {}
        self._apply_prog = None
        self._last_tree = None  # (N,) scaled leaf-delta vector, for rollback
        self._base_key = jax.random.PRNGKey(
            (int(config.bagging_seed) << 1) ^ int(config.feature_fraction_seed)
        )
        if getattr(objective, "grouped", False):
            self._rank_ops = objective.grouped_operands()

    # -- score channel maintenance ------------------------------------
    def _grad_fn(self, score, label, weight):
        obj = self.objective
        return obj.gradients_rowwise(score, label, weight if self.has_weights else None)

    def _grad_all_fn(self, scores, label, weight):
        """All K gradient planes at once from the score snapshot."""
        obj = self.objective
        return obj.gradients_rowwise_all(
            scores, label, weight if self.has_weights else None
        )

    def _apply_delta(self, delta, k: int = 0) -> None:
        """score channel k += delta (N,) — one in-place Pallas pass.
        Gradient channels refresh at the next iteration's update pass, so
        the cheap score-only streamer suffices here."""
        if self._apply_prog is None:
            self._apply_prog = {}
        if k not in self._apply_prog:
            lay = self.layout
            interp = self.interpret

            @functools.partial(jax.jit, donate_argnums=(0,))
            def prog(p, delta):
                return score_add(p, lay, delta, k, num_rows=self.num_rows,
                                 interpret=interp)

            self._apply_prog[k] = prog
        self.p = self._apply_prog[k](self.p, jnp.asarray(delta, jnp.float32))

    def add_score_constant(self, c: float) -> None:
        self._apply_delta(jnp.full((self.num_rows,), np.float32(c)))

    def sync_scores_from(self, scores_orig) -> None:
        """Bring the score channels to an original-order (N,) / (K, N)
        target (rare — init_model / external updates)."""
        lay = self.layout
        rowid = self.p[lay.ROWID, : self.num_rows]
        target = np.atleast_2d(np.asarray(scores_orig, np.float32))
        for k in range(self.K):
            cur = _i2f(self.p[lay.SCORE + k, : self.num_rows])
            tk = jnp.asarray(target[k])[rowid]
            self._apply_delta(tk - cur, k=k)
        self.score_dirty = False

    def scores_original_order(self):
        """(N,) for K == 1, else (K, N)."""
        lay, n = self.layout, self.num_rows
        out = in_row_order(
            self.p[lay.ROWID, :n], [_i2f(self.p[lay.SCORE + k, :n]) for k in range(self.K)])
        return out[0] if self.K == 1 else out

    def rollback_last(self) -> bool:
        """Undo the most recent tree's score contribution (the segment
        layout still matches it — GBDT::RollbackOneIter).  Multiclass
        chunks track only the last class's delta, so they resync via
        score_dirty instead."""
        if self._last_tree is None or self.K != 1:
            return False
        self._apply_delta(-self._last_tree)
        self._last_tree = None
        return True

    # -- checkpoint support -------------------------------------------
    def export_perm(self):
        """The physical row permutation (ROWID channel).  Histogram
        accumulation order follows the partition layout each tree left
        behind, so bit-identical resume must restore it — rebuilding an
        identity layout would change float summation order."""
        lay = self.layout
        return np.asarray(self.p[lay.ROWID, : self.num_rows], np.int32)

    def import_perm(self, rowid) -> None:
        """Re-derive the packed matrix in the checkpointed physical row
        order: column ``j`` must hold original row ``rowid[j]``.  The
        matrix here is still identity-packed (fresh ``__init__``), so a
        single column gather permutes bins/label/weight/rowid together;
        score channels stay zero and re-sync exactly from the restored
        original-order scores at the next chunk."""
        rowid = np.asarray(rowid, np.int32)
        if rowid.shape != (self.num_rows,):
            from ..utils.log import Log

            # topology changed since the save (elastic resume): the
            # saved layout is meaningless for this partition — keep the
            # identity packing (a valid continuation; score channels
            # re-sync from the restored scores) instead of refusing
            Log.warning(
                "checkpoint row permutation has shape %s, expected (%d,); "
                "keeping identity layout (topology changed since save)",
                rowid.shape, self.num_rows,
            )
            self._last_tree = None
            self.score_dirty = True
            return
        head = jnp.take(self.p[:, : self.num_rows], jnp.asarray(rowid), axis=1)
        self.p = jnp.concatenate([head, self.p[:, self.num_rows:]], axis=1)
        self._last_tree = None
        self.score_dirty = True

    # -- the fused chunk program --------------------------------------
    def _build_program(self, T: int, bag_on: bool, bag_freq: int, used_features: int):
        lay = self.layout
        n = self.num_rows
        L = self.params.num_leaves
        F = self.params.num_features
        K = self.K
        grad_fn = self._grad_fn
        grad_all_fn = self._grad_all_fn
        grouped = self._rank_ops is not None
        grouped_gradients = getattr(self.objective, "grouped_gradients", None)
        has_weights = self.has_weights
        params = self.params
        meta = self.meta
        hyper = self.hyper
        bmeta = self.bmeta
        interpret = self.interpret
        bag_frac = float(self.config.bagging_fraction)
        G = params.num_cols or F
        BH = params.num_bins_hist or params.num_bins
        cfg = self.config
        goss_on = _is_goss(cfg) and K == 1
        if goss_on:
            top_cnt = max(1, int(n * float(cfg.top_rate)))
            other_cnt = max(1, int(n * float(cfg.other_rate)))
            goss_mult = float((n - top_cnt) / other_cnt)
            goss_prob = float(other_cnt / max(n - top_cnt, 1))
            goss_warm = int(1.0 / float(cfg.learning_rate))

        @functools.partial(jax.jit, donate_argnums=(0,))
        def prog(p, lr, key, iter0, t_run, rank_ops=None):
            def one_iter(state):
                t, _, p, recs, delta, last_kept = state
                it = iter0 + t
                # ---- a tree starts in the row order the previous tree's
                # partition left (the first, in the order the matrix was
                # packed or a checkpoint restored).  Nothing is gathered
                # back to original order: histograms, leaf statistics and
                # the elementwise objective are permutation-invariant, the
                # positional carries (pending delta, rollback snapshot)
                # were written in this very layout, and every draw below
                # that must follow a ROW is keyed by the ROWID channel
                # that travels with it.  What follows partition history
                # is float summation order alone: LEVELGROW=1 and =0 leave
                # different layouts behind the same tree, so their later
                # trees may differ by an ulp (each mode is deterministic,
                # and export_perm/import_perm carry the layout through a
                # checkpoint).  ShardedPartitionedTrainer always ran so.
                # disjoint purpose-tagged key streams: fold a purpose
                # constant (0=bagging, 1=feature, 2=GOSS) before the
                # iteration number so no two draws share a subkey
                with jax.named_scope(SAMPLE):
                    if bag_on:
                        bkey = jax.random.fold_in(
                            jax.random.fold_in(key, 0), it // bag_freq
                        )
                        sel = (rowid_uniform(bkey, p[lay.ROWID, :n]) < bag_frac
                               ).astype(jnp.float32)
                    else:
                        sel = None
                    if used_features < F:
                        fkey = jax.random.fold_in(jax.random.fold_in(key, 1), it)
                        u = jax.random.uniform(fkey, (F,))
                        _, idx = jax.lax.top_k(u, used_features)
                        fmask = jnp.zeros((F,), jnp.float32).at[idx].set(1.0)
                    else:
                        fmask = jnp.ones((F,), jnp.float32)

                ns_t = recs["num_splits"][t]
                raw_t = recs["raw"][t]
                lv_t = recs["levels"][t]
                tl_t = recs["tail"][t]
                if K == 1:
                    if goss_on:
                        # GOSS (goss.hpp:126-198): settle the pending
                        # delta + fresh gradients first (histogram-FREE
                        # pass — the F*B one-hot/matmul accumulation used
                        # to run here only to be discarded), score |g*h|
                        # on the fresh values, keep exactly top_cnt rows
                        # + a Bernoulli sample of the rest up-weighted
                        # into g/h, then the real pass computes the root
                        # histogram of the selected/scaled gradients.
                        with jax.named_scope(UPDATE_ROOT_HIST):
                            p, _ = update_and_root_hist(
                                p, lay, grad_fn, delta=delta,
                                num_rows=n, num_features=G, num_bins=BH,
                                bits=params.bits, with_hist=False,
                                interpret=interpret,
                            )
                        with jax.named_scope(SAMPLE):
                            gv = _i2f(p[lay.G, :n])
                            hv = _i2f(p[lay.H, :n])
                            gscore = jnp.abs(gv * hv)
                            _, top_idx = jax.lax.top_k(gscore, top_cnt)
                            is_top = jnp.zeros((n,), bool).at[top_idx].set(True)
                            gkey = jax.random.fold_in(jax.random.fold_in(key, 2), it)
                            sampled = (~is_top) & (
                                rowid_uniform(gkey, p[lay.ROWID, :n]) < goss_prob
                            )
                            warm = it < goss_warm
                            selv = jnp.where(
                                warm, 1.0, (is_top | sampled).astype(jnp.float32)
                            )
                            mulv = jnp.where(warm | (~sampled), 1.0, goss_mult)
                        with jax.named_scope(UPDATE_ROOT_HIST):
                            p, root_hist = update_and_root_hist(
                                p, lay, grad_fn, sel=selv, mul=mulv,
                                num_rows=n, num_features=G, num_bins=BH,
                                bits=params.bits, interpret=interpret,
                            )
                        delta = jnp.zeros((n,), jnp.float32)
                    elif grouped:
                        # a document's gradient reads its query: the scores
                        # this iteration starts from (the channel plus the
                        # pending delta, the sum the update pass then writes)
                        # go to the objective with the row ids, and the pass
                        # takes its g and h instead of calling grad_fn
                        with jax.named_scope(RANK_GRAD):
                            score = _i2f(p[lay.SCORE, :n]) + delta
                            gv, hv = grouped_gradients(score, p[lay.ROWID, :n], rank_ops)
                            if has_weights:
                                wv = _i2f(p[lay.WEIGHT, :n])
                                gv, hv = gv * wv, hv * wv
                        with jax.named_scope(UPDATE_ROOT_HIST):
                            p, root_hist = update_and_root_hist(
                                p, lay, None, delta=delta, sel=sel, gh=(gv, hv),
                                num_rows=n, num_features=G, num_bins=BH,
                                bits=params.bits, interpret=interpret,
                            )
                    else:
                        # in-place channel refresh (score += previous
                        # tree's delta, new gradients, bagging select)
                        # FUSED with the root histogram of the fresh
                        # values — one pass.  The delta is PENDING from
                        # the previous iteration: the row layout did not
                        # change in between, so it applies against the
                        # current partition order.
                        with jax.named_scope(UPDATE_ROOT_HIST):
                            p, root_hist = update_and_root_hist(
                                p, lay, grad_fn, delta=delta, sel=sel,
                                num_rows=n, num_features=G, num_bins=BH,
                                bits=params.bits, interpret=interpret,
                            )
                    tree, p = grow_tree_partitioned(
                        p, fmask, meta, hyper, params, bmeta=bmeta,
                        interpret=interpret, root_hist=root_hist,
                    )
                    # score delta: +lr * leaf_value over each segment,
                    # clamped like Tree.shrinkage (tree.h:13
                    # kMaxTreeOutput) so training-time scores match the
                    # stored model.  An empty tree adds nothing (and ends
                    # the loop).
                    with jax.named_scope(LEAF_DELTA):
                        keep = (tree.num_splits > 0).astype(jnp.float32)
                        lval = jnp.clip(lr * tree.leaf_value, -100.0, 100.0)
                        delta = segment_values(tree, n, keep * lval)
                        # rollback needs the last KEPT tree's delta: an empty
                        # tree zeroes the pending carry but must not clobber
                        # what rollback_last would subtract
                        last_kept = jnp.where(keep > 0, delta, last_kept)
                    any_split = tree.num_splits > 0
                    ns_t = ns_t.at[0].set(tree.num_splits)
                    raw_t = raw_t.at[0].set(tree.recs_raw)
                    lv_t = lv_t.at[0].set(tree.level_counts)
                    tl_t = tl_t.at[0].set(tree.tail_counts)
                else:
                    # K trees per iteration from ALL K gradient planes + K
                    # root histograms of the same score snapshot, one pass;
                    # the trees from one looped grower (class_trees), each
                    # tree's delta on its score row IMMEDIATELY after it
                    # (while its partition layout is still current), which
                    # the precomputed planes make snapshot-safe.  No delta
                    # stays pending.
                    with jax.named_scope(UPDATE_ROOT_HIST), jax.named_scope(MULTI_UPDATE):
                        p, hists = update_multi_and_hists(
                            p, lay, grad_all_fn, sel=sel, num_rows=n,
                            num_features=G, num_bins=BH, bits=params.bits,
                            interpret=interpret,
                        )
                    p, (ns_t, raw_t, lv_t, tl_t), any_split = class_trees(
                        p, hists, fmask, lr, (ns_t, raw_t, lv_t, tl_t), lay=lay, num_rows=n,
                        meta=meta, hyper=hyper, params=params, bmeta=bmeta,
                        interpret=interpret)

                # ONE packed record buffer: per-op dispatch inside the
                # loop costs ~1-2 us, so ten separate stores would be a
                # measured ~10 ms/iter tax at 64 iters
                recs = {
                    "num_splits": recs["num_splits"].at[t].set(ns_t),
                    "raw": recs["raw"].at[t].set(raw_t),
                    "levels": recs["levels"].at[t].set(lv_t),
                    "tail": recs["tail"].at[t].set(tl_t),
                }
                return (t + 1, ~any_split, p, recs, delta, last_kept)

            m = L - 1
            recs0 = {
                "num_splits": jnp.zeros((T, K), jnp.int32),
                "raw": jnp.zeros((T, K, m, 12)),
                # per tree (PTreeResult.level_counts): level_stream launches,
                # the rows they streamed (one shard's, in the sharded program),
                # the segments they partitioned, the slots the search visited
                "levels": jnp.zeros((T, K, 4), jnp.int32),
                # per tree (PTreeResult.tail_counts): the replayed splits that
                # took the split_stream tail, and the rows those passes streamed
                "tail": jnp.zeros((T, K, 2), jnp.int32),
            }
            # (t, stopped, p, recs, pending delta, last kept delta)
            state0 = (jnp.int32(0), jnp.array(False), p, recs0,
                      jnp.zeros((n,), jnp.float32), jnp.zeros((n,), jnp.float32))
            t_end = jnp.minimum(t_run, T)
            # once an iteration produced an empty tree, training has
            # logically stopped and the loop is LEFT: growing a throwaway
            # tree would repartition rows and invalidate last_kept's
            # physical layout (which rollback_last applies positionally),
            # and recs0 is zeros, so not running the rest writes what
            # running no-ops would.  The test sits in the predicate, not
            # in a lax.cond around the body: a conditional that carries p
            # costs whole-matrix copies in every loop nested inside it
            # (module docstring)
            _, _, p, recs, last_delta, last_kept = jax.lax.while_loop(
                lambda s: (s[0] < t_end) & ~s[1], one_iter, state0)
            with jax.named_scope(CHUNK_EPILOGUE):
                if K == 1:
                    # settle the last tree's delta into the channel so the
                    # score channel is consistent at chunk boundaries (the
                    # in-loop update applies tree t-1's delta at iteration
                    # t).  Score-only band stream: the old settle ran a full
                    # update_and_root_hist — a whole-matrix pass plus an
                    # F*B histogram that was discarded — purely to add the
                    # delta.  The g/h channels stay stale until the next
                    # chunk's first update pass recomputes them from the
                    # settled scores (nothing reads them in between; the
                    # checkpoint exports scores + perm, never g/h).
                    p = score_add(p, lay, last_delta, 0, num_rows=n,
                                  interpret=interpret)
                # original-order scores for eval, once a chunk
                scores_orig = in_row_order(
                    p[lay.ROWID, :n], [_i2f(p[lay.SCORE + k, :n]) for k in range(K)])
                if K == 1:
                    scores_orig = scores_orig[0]
            return p, recs, scores_orig, last_kept

        return prog

    # record buffers are allocated at CHUNK_ALLOC granularity so a short
    # run (warmup) and a long run reuse one compiled program (the loop
    # bound is traced)
    CHUNK_ALLOC = 64

    def train_chunk(self, T: int, lr: float, iter0: int):
        """Run T fused boosting iterations (T <= CHUNK_ALLOC per call is
        one program invocation; longer runs loop).  Returns (records dict
        of numpy arrays, scores_orig (N,) device array, n_done)."""
        cfg = self.config
        bag_on = cfg.bagging_fraction < 1.0 and cfg.bagging_freq > 0
        bag_freq = max(1, int(cfg.bagging_freq))
        used_features = self.params.num_features
        if cfg.feature_fraction < 1.0:
            used_features = max(1, int(self.params.num_features * cfg.feature_fraction))
        # fixed allocation: every chunk size shares ONE compiled program
        # (the loop bound is traced; record buffers are CHUNK_ALLOC-sized)
        alloc = self.CHUNK_ALLOC
        pkey = (alloc, bag_on, bag_freq, used_features)
        if pkey not in self._progs:
            # JitWatch: compile accounting + unexpected-retrace flagging
            # on the hot entry point (obs/compilewatch.py)
            self._progs[pkey] = JitWatch(
                self._build_program(alloc, bag_on, bag_freq, used_features),
                name=f"ptrainer.chunk(bag={int(bag_on)},ff={used_features})",
                phase="chunk_program",
            )
        prog = self._progs[pkey]
        recs_np = None
        n_done = 0
        remaining = T
        scores_orig = None
        if T <= 0:
            return {}, self.scores_original_order(), 0
        while remaining > 0:
            step = min(remaining, alloc)
            with tracer.span("chunk_program", iters=step):
                self.p, recs, scores_orig, last_kept = prog(
                    self.p, jnp.float32(lr), self._base_key,
                    jnp.int32(iter0 + n_done), jnp.int32(step), self._rank_ops,
                )
            # chunk_program wraps an asynchronous dispatch and
            # records_fetch absorbs the wait for it (readers take their
            # sum); nested inside, each right alone: the device's run
            # (fenced only when tracing) and the copy to the host
            with tracer.span("records_fetch"):
                with tracer.span("device_wait"):
                    fence(recs)
                with tracer.span("records_d2h"):
                    part = jax.device_get(recs)
            ns = part["num_splits"][:step]  # (step, K)
            stop = np.nonzero(np.all(ns == 0, axis=1))[0]
            done_here = int(stop[0]) if stop.size else step
            if done_here > 0:
                # last KEPT tree's settled delta (empty trees keep the
                # previous chunk's value so rollback stays consistent)
                self._last_tree = last_kept
            part = {k: v[:done_here] for k, v in part.items()}
            recs_np = part if recs_np is None else {
                k: np.concatenate([recs_np[k], part[k]]) for k in part
            }
            n_done += done_here
            remaining -= step
            if done_here < step:
                break
        return recs_np, scores_orig, n_done

    def stream_counts(self, recs_np, n_done: int) -> dict:
        """What the streaming kernels did for the first ``n_done``
        iterations of a chunk's records, for the ``trees_from_records``
        span: ``levels`` (level_stream launches), ``level_rows`` and
        ``level_segments`` (the rows they streamed and the segments they
        partitioned, summed over levels; one shard's rows under
        ``tree_learner=data``), ``scan_slots`` (the slots the levels' split
        search visited: ``level_segments`` over it is the share that held
        a segment), and the shapes a launch works on:
        ``hist_cells`` (lanes of one leaf's histogram row as the kernels
        issue it, padding included), ``channels`` (rows of the packed
        matrix) and ``col_groups`` (column groups a kernel walks a block
        in: 1 up to 31 columns), ``perm_tiles`` (one-hot tiles one block's
        compaction and row count multiply: 33 of the 192 a dense permutation
        takes), ``bundle_cols`` (EFB bundle columns the
        matrix holds in place of the features; 0 unbundled); ``tail_splits``
        and ``tail_rows`` (the replayed splits that took the classic
        ``split_stream`` tail and the rows of their parents' segments,
        which those passes read and wrote).  And what crossed chips:
        ``shards`` (the mesh's size, 1 here), ``allreduce_calls`` and
        ``allreduce_bytes`` (0 here; ``ShardedPartitionedTrainer`` counts
        them).  A query-grouped objective adds ``rank_queries``,
        ``rank_buckets``, and over the iterations ``rank_docs``,
        ``rank_pairs`` (sum of n^2 a query, the reference's pair loop) and
        ``rank_pair_cells`` (the cells its length buckets evaluated, padding
        included).  And the gradient pass's shape: ``classes`` (K, the trees
        an iteration) and ``update_value_rows`` (the value rows its
        histogram dots carry a row, padded: 8 at K = 1, 48 at K = 7).
        Called only when tracing is on."""
        cols = self.params.num_cols or self.params.num_features
        bins = self.params.num_bins_hist or self.params.num_bins
        out = {"hist_cells": hist_lanes(cols, bins), "channels": self.layout.C,
               "col_groups": col_groups(cols, self.params.bits).count,
               "perm_tiles": perm_tiles(),
               "classes": self.K, "update_value_rows": update_value_rows(self.K),
               "bundle_cols": self.params.num_cols,
               "shards": 1, "allreduce_calls": 0, "allreduce_bytes": 0}
        out.update(zip(("levels", "level_rows", "level_segments", "scan_slots"),
                       recs_np["levels"][:n_done].sum(axis=(0, 1)).tolist()))
        out.update(zip(("tail_splits", "tail_rows"),
                       recs_np["tail"][:n_done].astype(np.int64).sum(axis=(0, 1)).tolist()))
        if self._rank_ops is not None:
            rc = self.objective.grouped_counts()
            out.update(rank_queries=rc["queries"], rank_buckets=rc["buckets"],
                       rank_docs=n_done * self.num_rows, rank_pairs=n_done * rc["pairs"],
                       rank_pair_cells=n_done * rc["pair_cells"])
        return out

    def grow_result_view(self, recs_np, t, k: int = 0):
        """GrowResult-like view of tree (t, class k)'s records
        (Tree.from_grow_result consumes exactly these fields).  Unpacks
        the (m, 12) raw record columns: [leaf, feat, thr, dbz, gain,
        lval, rval, lcnt, rcnt, ival, 0, 0]."""
        raw = recs_np["raw"][t][k]
        return types.SimpleNamespace(
            num_splits=recs_np["num_splits"][t][k],
            rec_leaf=raw[:, 0].astype(np.int32),
            rec_feat=raw[:, 1].astype(np.int32),
            rec_thr=raw[:, 2].astype(np.int32),
            rec_dbz=raw[:, 3].astype(np.int32),
            rec_gain=raw[:, 4],
            rec_lval=raw[:, 5],
            rec_rval=raw[:, 6],
            rec_lcnt=raw[:, 7],
            rec_rcnt=raw[:, 8],
            rec_internal_value=raw[:, 9],
        )


class ShardedPartitionedTrainer(PartitionedTrainer):
    """Data-parallel fused trainer: the partitioned fast path under
    ``shard_map`` over a device mesh — DataParallelTreeLearner
    (data_parallel_tree_learner.cpp:118-161) with split_stream kernels.

    Rows are split into equal contiguous per-device shards, each with its
    own packed matrix + BLK tail; child/root histograms are psum'd so
    every device takes the bit-identical split on its local segment.
    Grad/hess/scores stay device-resident across trees and chunks — no
    per-tree host round-trips.  The histograms are the ONLY cross-device
    traffic, at three sites: the root's ``(G, BH, 3)`` once a tree; in
    the level phase ONE all-reduce a level of all ``level_slots`` slots'
    kernel-layout rows, ``(SMAX, 16, hist_lanes)`` float32 with the
    inactive slots zeroed (2.1 GB a level at 2,000 columns x 63 bins,
    whatever the rows); and both children's g, h and count planes,
    ``(6, hist_lanes)``, of each replayed split that takes the
    ``split_stream`` tail (``pkernels.child_planes``).  Where the
    reference's data-parallel learner reduce-SCATTERS so that each worker
    searches a slice of the columns, every device here holds the reduced
    histograms whole and repeats the whole split search.
    ``stream_counts`` reports what one chip handed to those all-reduces
    (``allreduce_calls``, ``allreduce_bytes``), from the device program's
    own counts."""

    def __init__(self, train_set, config, objective, meta, hyper, mesh):
        import jax as _jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        n, f = train_set.num_data, train_set.num_features
        md = train_set.metadata
        self.has_weights = md.weights is not None
        self.mesh = mesh
        d = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
        self.d = d
        nproc = _jax.process_count()
        d_local = d // max(nproc, 1)
        # uniform shard length across ALL processes
        if nproc > 1:
            from jax.experimental import multihost_utils

            counts = np.asarray(multihost_utils.process_allgather(np.asarray(n)))
            per_proc = int(counts.max())
        else:
            per_proc = n
        nl = -(-per_proc // d_local)
        self.num_rows = nl  # per-shard rows (the grower's n)
        self.local_rows = n  # this process's real rows
        self.d_local = d_local

        bundle = getattr(train_set, "bundle", None)
        self.bmeta = None
        num_cols, num_bins_hist = 0, 0
        if bundle is not None and train_set.bundled is not None:
            matrix = np.asarray(train_set.bundled)
            num_cols = bundle.num_cols
            num_bins_hist = int(bundle.max_col_bin)
            self.bmeta = _build_bundle_meta(bundle, train_set, int(train_set.max_num_bin))
            max_col_bin = num_bins_hist
        else:
            matrix = np.asarray(train_set.binned)
            max_col_bin = int(train_set.max_num_bin)
        force_bits = os.environ.get("LIGHTGBM_TPU_FORCE_BITS", "")
        bits = 4 if max_col_bin <= 16 else 8
        if force_bits in ("4", "8"):
            bits = int(force_bits)
            if bits == 4 and max_col_bin > 16:
                bits = 8
        # K > 1: multiclass data-parallel — K score channels, K trees per
        # iteration from one gradient pass (same as the serial trainer)
        self.K = int(getattr(objective, "num_tree_per_iteration", 1))
        self.layout = PLayout(matrix.shape[1], num_score=self.K,
                              with_weight=True, bits=bits)

        from ..ops.pkernels import BLK, pack_matrix

        label = np.asarray(md.label, np.float32)
        weight = (np.asarray(md.weights, np.float32)
                  if self.has_weights else np.ones(n, np.float32))
        # The shards are packed with numpy on the host, one after another,
        # and then uploaded.  A shard's block is (C, nl + BLK) widened to
        # whole 128-lane tiles: where C is a multiple of 128 and the width
        # is not (512 channel rows x 101,024 at 2,000 columns and 100,000
        # rows a shard), the TPU's default layout for the array puts the
        # CHANNELS on the lanes, and the chunk program then transposes the
        # whole shard on the way in and on the way out (compiled for the
        # v5e, PR 33: two `copy s32[1,512,101024]`, +397 MB of temporaries).
        # The kernels address rows by `num_rows`, never by the width.
        width = -(-(nl + BLK) // 128) * 128
        with tracer.stage("shard_pack", rows=n, shards=d_local):
            local = np.zeros((d_local, self.layout.C, width), np.int32)
            for k in range(d_local):
                lo, hi = k * nl, min((k + 1) * nl, n)
                nreal = max(0, hi - lo)
                mb = np.zeros((nl, matrix.shape[1]), np.uint8)
                lb = np.zeros((nl,), np.float32)
                wb = np.zeros((nl,), np.float32)
                if nreal:
                    mb[:nreal] = matrix[lo:hi]
                    lb[:nreal] = label[lo:hi]
                    wb[:nreal] = weight[lo:hi]
                local[k, :, : nl + BLK] = np.asarray(
                    pack_matrix(mb, self.layout, label=lb, weight=wb, num_real=nreal)
                )
            sharding = NamedSharding(mesh, P("data"))
            if nproc > 1:
                gshape = (d, local.shape[1], local.shape[2])
                # each per-device buffer keeps the leading shard axis: the
                # (d, C, n) global array sharded on axis 0 has (1, C, n) shards
                bufs = [
                    _jax.device_put(local[i][None], dev)
                    for i, dev in enumerate(mesh.local_devices)
                ]
                self.p = _jax.make_array_from_single_device_arrays(gshape, sharding, bufs)
            else:
                self.p = _jax.device_put(jnp.asarray(local), sharding)
            # one wait, sink on or off: the stage reads the upload, not its
            # dispatch (once a Booster)
            jax.block_until_ready(self.p)

        self.meta = meta
        self.hyper = hyper
        self.objective = objective
        self.config = config
        self.params = PGrowParams(
            num_leaves=max(2, int(config.num_leaves)),
            num_bins=int(train_set.max_num_bin),
            num_features=f,
            num_rows=nl,
            max_depth=int(config.max_depth),
            use_missing=bool(config.use_missing),
            has_categorical=bool(np.any(np.asarray(meta.is_categorical))),
            num_cols=num_cols,
            num_bins_hist=num_bins_hist,
            bits=bits,
            axis_name="data",
            **levelgrow_env_params(),
        )
        self.interpret = _interpret_kernels()
        self.score_dirty = True
        self._progs = {}
        self._apply_prog = None
        self._scores_prog = None
        self._last_tree = None
        self._base_key = jax.random.PRNGKey(
            (int(config.bagging_seed) << 1) ^ int(config.feature_fraction_seed)
        )

    # ------------------------------------------------------------------
    def _shard_map(self, fn, in_specs, out_specs):
        from ..parallel.learner import _shard_map_unchecked

        return _shard_map_unchecked(fn, self.mesh, in_specs, out_specs)

    def _pad_local(self, vec):
        """Process-local (n,) row vector -> (d_local * nl,) shard-padded."""
        v = np.zeros((self.d_local * self.num_rows,), np.float32)
        vv = np.asarray(vec, np.float32)
        nl = self.num_rows
        for k in range(self.d_local):
            lo, hi = k * nl, min((k + 1) * nl, self.local_rows)
            if hi > lo:
                v[k * nl : k * nl + (hi - lo)] = vv[lo:hi]
        return v

    def _make_row_global(self, vec):
        """Shard-padded local vector -> global (d * nl,) row-sharded array."""
        import jax as _jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        nl = self.num_rows
        local = self._pad_local(vec).reshape(self.d_local, nl)
        sharding = NamedSharding(self.mesh, P("data"))
        if _jax.process_count() > 1:
            gshape = (self.d * nl,)
            bufs = [_jax.device_put(local[i], dev)
                    for i, dev in enumerate(self.mesh.local_devices)]
            return _jax.make_array_from_single_device_arrays(gshape, sharding, bufs)
        return _jax.device_put(jnp.asarray(local.reshape(-1)), sharding)

    def _gather_rows(self, garr):
        """Global (d * nl,) — or (K, d * nl), rows on the LAST axis —
        row-sharded array -> process-local (n,) / (K, n) numpy."""
        import jax as _jax

        axis = garr.ndim - 1
        if _jax.process_count() > 1:
            shards = sorted(garr.addressable_shards,
                            key=lambda s: (s.index[axis].start or 0))
            local = np.concatenate([np.asarray(s.data) for s in shards],
                                   axis=axis)
        else:
            local = np.asarray(garr)
        nl = self.num_rows
        parts = []
        for k in range(self.d_local):
            lo, hi = k * nl, min((k + 1) * nl, self.local_rows)
            parts.append(local[..., k * nl : k * nl + max(0, hi - lo)])
        return (np.concatenate(parts, axis=axis) if parts
                else local[..., :0])

    def _apply_delta(self, delta, k: int = 0) -> None:
        """delta in process-row order (n,); applied per shard in place to
        score channel ``k`` (score-only streamer — gradient channels
        refresh at the next chunk's update pass, like the serial path)."""
        from jax.sharding import PartitionSpec as P

        if self._apply_prog is None:
            self._apply_prog = {}
        if k not in self._apply_prog:
            lay = self.layout
            interp = self.interpret
            nl = self.num_rows

            def shard_body(pg, dg, k=k):
                return score_add(pg[0], lay, dg, k, num_rows=nl,
                                 interpret=interp)[None]

            self._apply_prog[k] = jax.jit(
                self._shard_map(shard_body, (P("data"), P("data")), P("data")),
                donate_argnums=(0,),
            )
        dg = delta if hasattr(delta, "sharding") else self._make_row_global(delta)
        self.p = self._apply_prog[k](self.p, dg)

    def add_score_constant(self, c: float) -> None:
        # constant only on REAL rows (padding rows' scores are unused)
        self._apply_delta(np.full((self.local_rows,), np.float32(c)))

    def sync_scores_from(self, scores_orig) -> None:
        """Bring score channels to an original-order target.  The delta
        must be computed in PHYSICAL row order: split_stream permutes
        shard columns, so the in-shard body gathers the row-order target
        through the ROWID channel and subtracts the positional current
        scores (mirrors the serial trainer's rowid gather)."""
        from jax.sharding import PartitionSpec as P

        if getattr(self, "_sync_prog", None) is None:
            self._sync_prog = {}
        lay = self.layout
        interp = self.interpret
        nl = self.num_rows
        target = np.atleast_2d(np.asarray(scores_orig, np.float32))
        for k in range(self.K):
            if k not in self._sync_prog:

                def shard_body(pg, tg, k=k):
                    p = pg[0]
                    rowid = p[lay.ROWID, :nl]
                    cur = _i2f(p[lay.SCORE + k, :nl])
                    dphys = tg[rowid] - cur
                    return score_add(p, lay, dphys, k, num_rows=nl,
                                     interpret=interp)[None]

                self._sync_prog[k] = jax.jit(
                    self._shard_map(shard_body, (P("data"), P("data")), P("data")),
                    donate_argnums=(0,),
                )
            tg = self._make_row_global(target[k])
            self.p = self._sync_prog[k](self.p, tg)
        self.score_dirty = False

    def _scores_global(self):
        from jax.sharding import PartitionSpec as P

        if self._scores_prog is None:
            lay = self.layout
            nl = self.num_rows
            K = self.K

            def shard_body(pg):
                p = pg[0]
                return in_row_order(
                    p[lay.ROWID, :nl], [_i2f(p[lay.SCORE + k, :nl]) for k in range(K)])

            self._scores_prog = jax.jit(
                self._shard_map(shard_body, (P("data"),), P(None, "data"))
            )
        return self._scores_prog(self.p)  # (K, d * nl)

    def scores_original_order(self):
        """(N,) for K == 1, else (K, N)."""
        got = jnp.asarray(self._gather_rows(self._scores_global()))
        return got[0] if self.K == 1 else got

    def rollback_last(self) -> bool:
        """K > 1 chunks track only the last class's delta; they resync
        via score_dirty instead (same contract as the serial trainer)."""
        if self._last_tree is None or self.K != 1:
            return False
        import jax as _jax

        neg = _jax.jit(lambda x: -x)(self._last_tree)
        self._apply_delta(neg)
        self._last_tree = None
        return True

    # -- checkpoint support -------------------------------------------
    def _local_shards_sorted(self):
        return sorted(self.p.addressable_shards,
                      key=lambda s: (s.index[0].start or 0))

    def export_perm(self):
        """(d, nl) int32 — every shard's ROWID channel (shard-LOCAL row
        ids: split_stream permutes columns within a shard only).
        COLLECTIVE in multi-process runs: local shards are allgathered
        over parallel/collect.py so every host returns the full global
        matrix and host 0 can write it."""
        import pickle

        import jax as _jax

        lay = self.layout
        local = np.stack([
            np.asarray(s.data)[0, lay.ROWID, : self.num_rows]
            for s in self._local_shards_sorted()
        ]).astype(np.int32)
        if _jax.process_count() > 1:
            from ..parallel.collect import allgather_bytes

            parts = [pickle.loads(b)
                     for b in allgather_bytes(pickle.dumps(local))]
            return np.concatenate(parts, axis=0)
        return local

    def import_perm(self, rowid) -> None:
        """Permute each addressable shard's columns to the checkpointed
        layout (host-side: the shards were just packed identity-order in
        ``__init__``) and rebuild the global array on the same devices."""
        import jax as _jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        rowid = np.asarray(rowid, np.int64)
        if rowid.shape != (self.d, self.num_rows):
            from ..utils.log import Log

            # elastic resume onto a different device/host grid: the
            # saved shard layout no longer applies — keep identity
            # packing (valid continuation, scores re-sync exactly)
            Log.warning(
                "checkpoint shard permutation has shape %s, expected "
                "(%d, %d); keeping identity layout (topology changed "
                "since save)", rowid.shape, self.d, self.num_rows,
            )
            self._last_tree = None
            self.score_dirty = True
            return
        nl = self.num_rows
        bufs, devs = [], []
        for s in self._local_shards_sorted():
            g = s.index[0].start or 0
            arr = np.array(s.data)  # (1, C, width) host copy
            arr[0, :, :nl] = arr[0, :, :nl][:, rowid[g]]
            bufs.append(arr)
            devs.append(s.device)
        sharding = NamedSharding(self.mesh, P("data"))
        if _jax.process_count() > 1:
            self.p = _jax.make_array_from_single_device_arrays(
                self.p.shape, sharding,
                [_jax.device_put(b, d) for b, d in zip(bufs, devs)],
            )
        else:
            self.p = _jax.device_put(
                jnp.asarray(np.concatenate(bufs, axis=0)), sharding
            )
        self._last_tree = None
        self.score_dirty = True

    # ------------------------------------------------------------------
    def _build_program(self, T: int, bag_on: bool, bag_freq: int, used_features: int):
        from jax.sharding import PartitionSpec as P

        lay = self.layout
        nl = self.num_rows
        L = self.params.num_leaves
        F = self.params.num_features
        K = self.K
        grad_fn = self._grad_fn
        grad_all_fn = self._grad_all_fn
        params = self.params
        meta = self.meta
        hyper = self.hyper
        bmeta = self.bmeta
        interpret = self.interpret
        bag_frac = float(self.config.bagging_fraction)
        G = params.num_cols or F
        BH = params.num_bins_hist or params.num_bins
        cfg = self.config
        # GOSS in data-parallel mode is LOCAL per shard — the reference's
        # distributed GOSS also samples per machine over local indices
        # (goss.hpp Bagging over the local data partition); counts scale
        # with each shard's real rows
        goss_on = _is_goss(cfg) and K == 1
        if goss_on:
            top_rate = float(cfg.top_rate)
            other_rate = float(cfg.other_rate)
            top_cnt_max = max(1, int(np.ceil(top_rate * nl)))
            goss_warm = int(1.0 / float(cfg.learning_rate))

        def shard_body(pg, nreal_g, lr, key, iter0, t_run):
            p = pg[0]
            ax = jax.lax.axis_index("data")
            nreal = nreal_g[0]  # this shard's real-row count

            def one_iter(state):
                t, _, p, recs, delta, last_kept = state
                it = iter0 + t
                # validity must travel WITH the row: split_stream permutes
                # shard columns, so padding is identified by the preserved
                # ROWID channel (local rowid >= nreal), never by position
                with jax.named_scope(SAMPLE):
                    valid = (p[lay.ROWID, :nl] < nreal).astype(jnp.float32)
                    if bag_on:
                        bkey = jax.random.fold_in(
                            jax.random.fold_in(
                                jax.random.fold_in(key, 0), it // bag_freq
                            ), ax
                        )
                        sel = (rowid_uniform(bkey, p[lay.ROWID, :nl]) < bag_frac
                               ).astype(jnp.float32) * valid
                    else:
                        sel = None
                    if used_features < F:
                        fkey = jax.random.fold_in(jax.random.fold_in(key, 1), it)
                        u = jax.random.uniform(fkey, (F,))
                        _, idx = jax.lax.top_k(u, used_features)
                        fmask = jnp.zeros((F,), jnp.float32).at[idx].set(1.0)
                    else:
                        fmask = jnp.ones((F,), jnp.float32)

                ns_t = recs["num_splits"][t]
                raw_t = recs["raw"][t]
                lv_t = recs["levels"][t]
                tl_t = recs["tail"][t]
                if K == 1:
                    if goss_on:
                        # settle pending delta + fresh gradients first
                        # (histogram-free pass), then local top-k +
                        # Bernoulli rest-sample (goss.hpp:126-198 over
                        # the shard's rows)
                        with jax.named_scope(UPDATE_ROOT_HIST):
                            p, _ = update_and_root_hist(
                                p, lay, grad_fn, delta=delta, num_rows=nl,
                                num_features=G, num_bins=BH, bits=params.bits,
                                with_hist=False, interpret=interpret,
                            )
                        with jax.named_scope(SAMPLE):
                            gv = _i2f(p[lay.G, :nl])
                            hv = _i2f(p[lay.H, :nl])
                            gscore = jnp.abs(gv * hv) * valid
                            top_c = jnp.maximum(jnp.floor(top_rate * nreal), 1.0)
                            other_c = jnp.maximum(jnp.floor(other_rate * nreal), 1.0)
                            goss_mult = (nreal - top_c) / other_c
                            goss_prob = other_c / jnp.maximum(nreal - top_c, 1.0)
                            # exactly top_c rows marked top via the top_k
                            # INDICES (ADVICE r5: a >= threshold test admits
                            # every tie — common with integer features — and
                            # can never admit zero-gradient rows, so the
                            # nominal-count goss_mult was biased).  Padding
                            # rows are pushed below every valid row so ties
                            # at zero resolve to real rows first.
                            topc_i = jnp.clip(top_c.astype(jnp.int32), 1, top_cnt_max)
                            _, top_idx = jax.lax.top_k(
                                jnp.where(valid > 0, gscore, -1.0), top_cnt_max
                            )
                            rank_ok = jnp.arange(top_cnt_max) < topc_i
                            is_top = (jnp.zeros((nl,), bool).at[top_idx].set(rank_ok)
                                      & (valid > 0))
                            gkey = jax.random.fold_in(
                                jax.random.fold_in(jax.random.fold_in(key, 2), it), ax
                            )
                            sampled = ((~is_top)
                                       & (rowid_uniform(gkey, p[lay.ROWID, :nl]) < goss_prob)
                                       & (valid > 0))
                            warm = it < goss_warm
                            selv = jnp.where(
                                warm, valid, (is_top | sampled).astype(jnp.float32)
                            )
                            mulv = jnp.where(warm | (~sampled), 1.0, goss_mult)
                        with jax.named_scope(UPDATE_ROOT_HIST):
                            p, root_hist = update_and_root_hist(
                                p, lay, grad_fn, sel=selv, mul=mulv,
                                num_rows=nl, num_features=G, num_bins=BH,
                                bits=params.bits, interpret=interpret,
                            )
                        delta = jnp.zeros((nl,), jnp.float32)
                    else:
                        with jax.named_scope(UPDATE_ROOT_HIST):
                            p, root_hist = update_and_root_hist(
                                p, lay, grad_fn, delta=delta, sel=sel, num_rows=nl,
                                num_features=G, num_bins=BH, bits=params.bits,
                                interpret=interpret,
                            )
                    with jax.named_scope(UPDATE_ROOT_HIST):
                        root_hist = jax.lax.psum(root_hist, "data")
                    tree, p = grow_tree_partitioned(
                        p, fmask, meta, hyper, params, bmeta=bmeta,
                        interpret=interpret, root_hist=root_hist,
                    )
                    with jax.named_scope(LEAF_DELTA):
                        keep = (tree.num_splits > 0).astype(jnp.float32)
                        lval = jnp.clip(lr * tree.leaf_value, -100.0, 100.0)
                        delta = segment_values(tree, nl, keep * lval)
                        last_kept = jnp.where(keep > 0, delta, last_kept)
                    any_split = tree.num_splits > 0
                    ns_t = ns_t.at[0].set(tree.num_splits)
                    raw_t = raw_t.at[0].set(tree.recs_raw)
                    lv_t = lv_t.at[0].set(tree.level_counts)
                    tl_t = tl_t.at[0].set(tree.tail_counts)
                else:
                    # K trees per iteration from one gradient pass (the
                    # serial K > 1 branch, the same class_trees, with
                    # per-level hist psums inside the grower)
                    with jax.named_scope(UPDATE_ROOT_HIST):
                        with jax.named_scope(MULTI_UPDATE):
                            p, hists = update_multi_and_hists(
                                p, lay, grad_all_fn, sel=sel, num_rows=nl,
                                num_features=G, num_bins=BH, bits=params.bits,
                                interpret=interpret,
                            )
                        hists = jax.lax.psum(hists, "data")
                    p, (ns_t, raw_t, lv_t, tl_t), any_split = class_trees(
                        p, hists, fmask, lr, (ns_t, raw_t, lv_t, tl_t), lay=lay, num_rows=nl,
                        meta=meta, hyper=hyper, params=params, bmeta=bmeta,
                        interpret=interpret)

                recs = {
                    "num_splits": recs["num_splits"].at[t].set(ns_t),
                    "raw": recs["raw"].at[t].set(raw_t),
                    "levels": recs["levels"].at[t].set(lv_t),
                    "tail": recs["tail"].at[t].set(tl_t),
                }
                return (t + 1, ~any_split, p, recs, delta, last_kept)

            m = L - 1
            recs0 = {
                "num_splits": jnp.zeros((T, K), jnp.int32),
                "raw": jnp.zeros((T, K, m, 12)),
                # per tree (PTreeResult.level_counts): level_stream launches,
                # the rows they streamed (one shard's, in the sharded program),
                # the segments they partitioned, the slots the search visited
                "levels": jnp.zeros((T, K, 4), jnp.int32),
                # per tree (PTreeResult.tail_counts): the replayed splits that
                # took the tail, each all-reducing its children (with `levels`
                # and the root's, every histogram all-reduce the program
                # issued), and the rows of THIS shard their passes streamed
                "tail": jnp.zeros((T, K, 2), jnp.int32),
            }
            # (t, stopped, p, recs, pending delta, last kept delta)
            state0 = (jnp.int32(0), jnp.array(False), p, recs0,
                      jnp.zeros((nl,), jnp.float32), jnp.zeros((nl,), jnp.float32))
            t_end = jnp.minimum(t_run, T)
            # an empty tree ends the loop (see the serial trainer: the
            # stop test is in the predicate because a lax.cond may not
            # carry p); num_splits is replicated, so every shard leaves
            # at the same iteration
            _, _, p, recs, last_delta, last_kept = jax.lax.while_loop(
                lambda s: (s[0] < t_end) & ~s[1], one_iter, state0)
            with jax.named_scope(CHUNK_EPILOGUE):
                if K == 1:
                    # score-only chunk-end settle (see the serial trainer)
                    p = score_add(p, lay, last_delta, 0, num_rows=nl,
                                  interpret=interpret)
                scores_local = in_row_order(
                    p[lay.ROWID, :nl], [_i2f(p[lay.SCORE + k, :nl]) for k in range(K)])
            return p[None], recs, scores_local, last_kept

        mapped = self._shard_map(
            shard_body,
            (P("data"), P("data"), P(), P(), P(), P()),
            (P("data"), {"num_splits": P(), "raw": P(), "levels": P(), "tail": P()},
             P(None, "data"), P("data")),
        )
        return jax.jit(mapped, donate_argnums=(0,))

    def train_chunk(self, T: int, lr: float, iter0: int):
        cfg = self.config
        bag_on = cfg.bagging_fraction < 1.0 and cfg.bagging_freq > 0
        bag_freq = max(1, int(cfg.bagging_freq))
        used_features = self.params.num_features
        if cfg.feature_fraction < 1.0:
            used_features = max(1, int(self.params.num_features * cfg.feature_fraction))
        alloc = self.CHUNK_ALLOC
        pkey = (alloc, bag_on, bag_freq, used_features)
        if pkey not in self._progs:
            self._progs[pkey] = JitWatch(
                self._build_program(alloc, bag_on, bag_freq, used_features),
                name=f"ptrainer.sharded_chunk(bag={int(bag_on)},ff={used_features})",
                phase="chunk_program",
            )
        prog = self._progs[pkey]
        recs_np = None
        n_done = 0
        remaining = T
        scores = None
        if T <= 0:
            return {}, self.scores_original_order(), 0
        if not hasattr(self, "_nreal_global"):
            # per-shard real-row counts, one scalar per device
            import jax as _jax
            from jax.sharding import NamedSharding, PartitionSpec as P

            nl = self.num_rows
            vals = np.asarray(
                [max(0, min(self.local_rows - k * nl, nl))
                 for k in range(self.d_local)], np.int32,
            ).reshape(self.d_local, 1)
            sharding = NamedSharding(self.mesh, P("data"))
            if _jax.process_count() > 1:
                bufs = [_jax.device_put(vals[i], dev)
                        for i, dev in enumerate(self.mesh.local_devices)]
                self._nreal_global = _jax.make_array_from_single_device_arrays(
                    (self.d,), sharding, bufs
                )
            else:
                self._nreal_global = _jax.device_put(
                    jnp.asarray(vals.reshape(-1)), sharding
                )
        while remaining > 0:
            step = min(remaining, alloc)
            with tracer.span("chunk_program", iters=step):
                self.p, recs, scores, last_kept = prog(
                    self.p, self._nreal_global, jnp.float32(lr), self._base_key,
                    jnp.int32(iter0 + n_done), jnp.int32(step),
                )
            # chunk_program wraps an asynchronous dispatch and
            # records_fetch absorbs the wait for it (readers take their
            # sum); nested inside, each right alone: the device's run
            # (fenced only when tracing) and the copy to the host
            with tracer.span("records_fetch"):
                with tracer.span("device_wait"):
                    fence(recs)
                with tracer.span("records_d2h"):
                    part = jax.device_get(recs)
            ns = part["num_splits"][:step]  # (step, K)
            stop = np.nonzero(np.all(ns == 0, axis=1))[0]
            done_here = int(stop[0]) if stop.size else step
            if done_here > 0:
                # K > 1 resyncs via score_dirty on rollback instead
                self._last_tree = last_kept if self.K == 1 else None
            part = {k: v[:done_here] for k, v in part.items()}
            recs_np = part if recs_np is None else {
                k: np.concatenate([recs_np[k], part[k]]) for k in part
            }
            n_done += done_here
            remaining -= step
            if done_here < step:
                break
        got = jnp.asarray(self._gather_rows(scores))
        scores_orig = got[0] if self.K == 1 else got
        return recs_np, scores_orig, n_done

    def stream_counts(self, recs_np, n_done: int) -> dict:
        """The serial trainer's counts (one shard's rows) and what ONE chip
        handed to the histogram all-reduces over those iterations:
        ``allreduce_calls`` and ``allreduce_bytes`` (payload: the operand's
        float32 bytes, not what an algorithm then moves through the links).
        Three sites (ops/pgrow.py ``PGrowParams.axis_name``): the root's
        ``(G, BH, 3)`` (one call a tree; K > 1 reduces its K roots in one
        call an iteration), a level's ``(level_slots, 16, hist_lanes)`` and
        a tail split's ``(6, hist_lanes)``.  How many levels and tail splits
        a tree took only the device program knows: ``recs["levels"]`` and
        ``recs["tail"]``."""
        out = super().stream_counts(recs_np, n_done)
        cols = self.params.num_cols or self.params.num_features
        bins = self.params.num_bins_hist or self.params.num_bins
        root = 4 * cols * bins * 3
        level = 4 * level_slots(self.params.num_leaves) * 16 * out["hist_cells"]
        tail = 4 * 6 * out["hist_cells"]
        tails = out["tail_splits"]
        out.update(
            shards=self.d,
            allreduce_calls=n_done + out["levels"] + tails,
            allreduce_bytes=n_done * self.K * root + out["levels"] * level + tails * tail,
        )
        return out


def eligible(config, train_set, objective, num_tree_per_iteration: int) -> bool:
    """Can the partitioned trainer drive this configuration?  (The rest
    falls back to the mask-based grower, which handles everything.)"""
    flag = os.environ.get("LIGHTGBM_TPU_PGROW", "")
    if flag == "0":
        return False
    if flag != "force" and jax.default_backend() != "tpu":
        return False
    if objective is None:
        return False
    # quantized training runs through the mask grower's int32 histogram
    # path (ops/qhist.py); the fused kernels' bf16 3-term value split is
    # an f32 pipeline and would break the exact-integer contract
    if getattr(config, "quantized_training", False):
        return False
    # strategy plug-ins (tree/strategy.py): the fused kernels inline the
    # unconstrained split scan and constant leaf outputs; linear leaves
    # and monotone constraints run through the mask grower's strategy
    # seam instead (same decline shape as quantization above)
    if getattr(config, "linear_tree", False):
        return False
    if hasattr(config, "_monotone_active") and config._monotone_active():
        return False
    if num_tree_per_iteration == 1:
        if getattr(objective, "grouped", False):
            # whole queries per shard and GOSS's sampling of documents are
            # not built for a grouped objective's step (bagging is: its draw
            # follows the ROWID channel)
            if config.tree_learner == "data":
                return _declined("query-grouped objective %s under tree_learner=data: "
                                 "the sharded chunk program has no grouped gradient step",
                                 objective.name)
            if _is_goss(config):
                return _declined("query-grouped objective %s with GOSS", objective.name)
        elif not getattr(objective, "rowwise", False):
            return False
    else:
        # multiclass: needs the all-classes row-local gradient plane
        # (gradients_rowwise_all); 6K+1 bf16 value rows must fit the
        # MXU's 128 sublanes in the fused update kernel
        if not getattr(objective, "rowwise_multi", False):
            return False
        if num_tree_per_iteration > 16:
            return False
        # multiclass GOSS: the fused trainers' GOSS sampling is K == 1
        # only — fall back to the mask grower, whose _adjust_gradients
        # hooks apply real GOSS to every class (silently training plain
        # GBDT here would be an algorithm regression)
        if _is_goss(config):
            return False
    # serial -> PartitionedTrainer; data -> ShardedPartitionedTrainer.
    # feature/voting keep the mask grower's collective formulations on a
    # device mesh, or the host-driven learners (parallel/hostlearner.py)
    # across processes — their per-node exchanges don't fuse.
    if config.tree_learner not in ("serial", "data"):
        return False
    if train_set.bin_dtype != np.uint8:
        return False
    if train_set.max_num_bin > 256:
        return False
    # bundling is built lazily, only once a partitioned run is plausible
    if hasattr(train_set, "ensure_bundles"):
        train_set.ensure_bundles(config)
    # Width.  The streaming kernels walk a block's bin words in column
    # groups (ops/pkernels.py: col_groups, a rolled loop, so Mosaic
    # program size does not grow with the columns) and ask Mosaic for the
    # VMEM their whole-block buffers and histogram rows need, so K == 1
    # rides at any width that VMEM holds: at 2,000 columns x 63 bins
    # level_stream holds 12 (512, BLK) blocks, two (16, 128,000)
    # histograms and its compaction's 2 MB, 44 MB of the v5e's 128 MiB.
    # The ceiling that is left is that budget.  Multiclass keeps the old
    # 512 columns: its gradient pass (update_multi_and_hists) holds 6K+1
    # histogram rows of ALL columns at once (53 MB at K = 16 and 2,000
    # columns, twice with its output block).  The K trees of an iteration
    # are one traced grower in a loop over the classes
    # (class_trees), so the chunk program's size and its trace no longer
    # grow with K; what grows with K is that pass alone, and this ceiling
    # is its own, not the grower's.
    bundle = getattr(train_set, "bundle", None)
    cols = bundle.num_cols if bundle is not None else train_set.num_features
    col_bins = int(bundle.max_col_bin) if bundle is not None else int(train_set.max_num_bin)
    if num_tree_per_iteration > 1 and cols > MULTICLASS_MAX_COLS:
        return _declined("multiclass (K = %d) above %d columns (%d): "
                         "update_multi_and_hists holds 6K+1 histogram rows of every "
                         "column in VMEM", num_tree_per_iteration, MULTICLASS_MAX_COLS, cols)
    vmem = level_stream_vmem_bytes(cols, col_bins, num_score=num_tree_per_iteration)
    if vmem > VMEM_BUDGET_BYTES:
        return _declined("%d columns x %d bins: level_stream would hold %.0f MB in "
                         "VMEM (budget %.0f MB)", cols, col_bins, vmem / 1e6,
                         VMEM_BUDGET_BYTES / 1e6)
    return True


MULTICLASS_MAX_COLS = 512
VMEM_BUDGET_BYTES = 80 * 1024 * 1024  # of the v5e's 128 MiB, before Mosaic's own spills


def _declined(reason: str, *args) -> bool:
    """A configuration the fused engine could in principle run and does not:
    say so, because the mask grower it falls to pays a pass over all rows
    for every split."""
    Log.warning("fused partitioned trainer declined, using the mask grower: " + reason, *args)
    return False


def _build_bundle_meta(bundle, train_set, num_bins: int) -> BundleMeta:
    """Host-built device maps for the bundled histogram expansion."""
    f = train_set.num_features
    b = num_bins
    bh = int(bundle.max_col_bin)
    default_bin = np.asarray([m.default_bin for m in train_set.bin_mappers], np.int64)
    nb = np.asarray([m.num_bin for m in train_set.bin_mappers], np.int64)
    zero_slot = bundle.num_cols * bh  # appended all-zero row
    idx = np.full((f, b), zero_slot, np.int32)
    defmask = np.zeros((f, b), bool)
    for fe in range(f):
        if int(bundle.off_lo[fe]) == 0:
            # singleton raw column: every bin (incl. default) maps direct
            for bi in range(int(nb[fe])):
                idx[fe, bi] = int(bundle.col[fe]) * bh + bi
            continue
        for bi in range(int(nb[fe])):
            if bi == int(default_bin[fe]):
                defmask[fe, bi] = True
                continue
            v = int(bundle.off_lo[fe]) + bi - int(bundle.bias[fe])
            idx[fe, bi] = int(bundle.col[fe]) * bh + v
    return BundleMeta(
        col=jnp.asarray(bundle.col),
        off_lo=jnp.asarray(bundle.off_lo),
        off_hi=jnp.asarray(bundle.off_hi),
        bias=jnp.asarray(bundle.bias),
        idx=jnp.asarray(idx),
        defmask=jnp.asarray(defmask),
    )
