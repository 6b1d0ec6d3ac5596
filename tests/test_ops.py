"""Parity tests: JAX ops vs the sequential float64 numpy oracle
(tests/oracle.py), per SURVEY §4's golden-comparison strategy."""

import functools
import os
import zlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lightgbm_tpu.ops import pkernels as pk
from lightgbm_tpu.ops.histogram import build_histogram
from lightgbm_tpu.ops.split import (FeatureMeta, SplitHyper, best_split_all_features,
                                    best_split_per_feature)
from lightgbm_tpu.ops.grow import GrowParams, grow_tree

import oracle


def make_data(rng, n=4000, f=8, b=24, missing_frac=0.2):
    bins = rng.randint(0, b, (n, f)).astype(np.uint8)
    default_bin = rng.randint(0, b, f).astype(np.int32)
    # concentrate mass on the default bin to imitate zero-sparsity
    for j in range(f):
        m = rng.rand(n) < missing_frac
        bins[m, j] = default_bin[j]
    g = rng.randn(n).astype(np.float32)
    h = np.abs(rng.randn(n)).astype(np.float32) + 0.1
    return bins, default_bin, g, h


CFG = dict(lambda_l1=0.0, lambda_l2=0.0, min_data_in_leaf=20,
           min_sum_hessian_in_leaf=1e-3, min_gain_to_split=0.0)


def jax_meta(default_bin, b, f, is_cat=None):
    return FeatureMeta(
        jnp.full((f,), b, jnp.int32),
        jnp.asarray(default_bin),
        jnp.asarray(is_cat if is_cat is not None else np.zeros(f, bool)),
    )


def jax_hyper(cfg):
    return SplitHyper(*(jnp.float32(cfg[k]) for k in (
        "lambda_l1", "lambda_l2", "min_data_in_leaf",
        "min_sum_hessian_in_leaf", "min_gain_to_split")))


class TestHistogram:
    def test_matches_oracle(self, rng):
        bins, _, g, h = make_data(rng)
        sel = (rng.rand(len(g)) < 0.7).astype(np.float32)
        hist = np.asarray(build_histogram(jnp.asarray(bins), jnp.asarray(g),
                                          jnp.asarray(h), jnp.asarray(sel), 24, 512))
        want = oracle.build_histogram_np(bins, g.astype(np.float64),
                                         h.astype(np.float64), sel, 24)
        np.testing.assert_allclose(hist, want, rtol=1e-4, atol=1e-3)

    def test_unpadded_rows(self, rng):
        # n not a multiple of row_block: padding rows must contribute nothing
        bins = rng.randint(0, 8, (777, 3)).astype(np.uint8)
        g = rng.randn(777).astype(np.float32)
        h = np.ones(777, np.float32)
        sel = np.ones(777, np.float32)
        hist = np.asarray(build_histogram(jnp.asarray(bins), jnp.asarray(g),
                                          jnp.asarray(h), jnp.asarray(sel), 8, 256))
        assert hist[:, :, 2].sum() == pytest.approx(3 * 777)


class TestSplit:
    @pytest.mark.parametrize("seed", range(5))
    @pytest.mark.parametrize("cfg_over", [
        {}, {"lambda_l1": 0.5, "lambda_l2": 1.0},
        {"min_data_in_leaf": 200}, {"min_gain_to_split": 0.2},
    ])
    def test_numerical_vs_oracle(self, seed, cfg_over):
        rng = np.random.RandomState(seed)
        cfg = {**CFG, **cfg_over}
        n, f, b = 4000, 8, 24
        bins, default_bin, g, h = make_data(rng, n, f, b)
        hist = oracle.build_histogram_np(bins, g, h, np.ones(n), b)
        sum_g, sum_h = float(g.sum()), float(h.sum())

        want = oracle.best_split_all_features_np(
            hist, sum_g, sum_h, n, default_bin, np.zeros(f, bool),
            np.full(f, b), cfg)
        got = best_split_all_features(
            jnp.asarray(hist, jnp.float32), jnp.float32(sum_g), jnp.float32(sum_h),
            jnp.float32(n), jax_meta(default_bin, b, f), jax_hyper(cfg),
            jnp.ones((f,)))
        if not np.isfinite(want["gain"]):
            assert not np.isfinite(float(got.gain))
            return
        # JAX's best must match the oracle's gain; identical (feat, thr, dbz)
        # unless a float32-level tie
        assert float(got.gain) == pytest.approx(want["gain"], rel=1e-4, abs=1e-4)
        if abs(want["gain"]) > 1e-3:
            assert (int(got.feature), int(got.threshold_bin), int(got.default_bin_for_zero)) == \
                (want["feature"], want["threshold"], want["dbz"])
            lg, lh, lc = want["left"]
            assert float(got.left_cnt) == lc
            assert float(got.left_sum_g) == pytest.approx(lg, rel=1e-4, abs=1e-3)

    def test_categorical_vs_oracle(self, rng):
        n, f, b = 4000, 6, 12
        bins, default_bin, g, h = make_data(rng, n, f, b)
        is_cat = np.array([True, False, True, False, True, True])
        hist = oracle.build_histogram_np(bins, g, h, np.ones(n), b)
        want = oracle.best_split_all_features_np(
            hist, float(g.sum()), float(h.sum()), n, default_bin, is_cat,
            np.full(f, b), CFG)
        got = best_split_all_features(
            jnp.asarray(hist, jnp.float32), jnp.float32(g.sum()), jnp.float32(h.sum()),
            jnp.float32(n), jax_meta(default_bin, b, f, is_cat), jax_hyper(CFG),
            jnp.ones((f,)))
        assert float(got.gain) == pytest.approx(want["gain"], rel=1e-4, abs=1e-4)
        assert int(got.feature) == want["feature"]
        assert int(got.threshold_bin) == want["threshold"]

    def test_feature_mask(self, rng):
        n, f, b = 2000, 4, 16
        bins, default_bin, g, h = make_data(rng, n, f, b)
        hist = oracle.build_histogram_np(bins, g, h, np.ones(n), b).astype(np.float32)
        full = best_split_all_features(
            jnp.asarray(hist), jnp.float32(g.sum()), jnp.float32(h.sum()),
            jnp.float32(n), jax_meta(default_bin, b, f), jax_hyper(CFG), jnp.ones((f,)))
        mask = np.ones(f, np.float32)
        mask[int(full.feature)] = 0.0
        masked = best_split_all_features(
            jnp.asarray(hist), jnp.float32(g.sum()), jnp.float32(h.sum()),
            jnp.float32(n), jax_meta(default_bin, b, f), jax_hyper(CFG), jnp.asarray(mask))
        assert int(masked.feature) != int(full.feature)


# -- the two entries of the split search (PR 40) ------------------------------
SPLIT_CASES = ("default_first", "default_interior", "default_last", "short_features", "ties",
               "masked", "monotone")
SPLIT_MODES = [(um, hc) for um in (True, False) for hc in (False, True)]
SPLIT_GOLDEN = os.path.join(os.path.dirname(__file__), "golden", "split_search.npz")
SPLIT_FIELDS = ("gain_f", "thr_f", "dbz_f", "left_f")


def split_case(name):
    """(hist (F, B, 3) float32, leaf totals, FeatureMeta, SplitHyper, feature
    mask, the monotone surface or {}) of one case of SPLIT_CASES, from its
    name alone: 12 features, two of them categorical (which only a search
    with ``has_categorical`` reads), up to 24 bins."""
    rng = np.random.RandomState(zlib.crc32(name.encode()) & 0x7FFFFFFF)
    n, f, b = 6000, 12, 24
    nb = np.full(f, b)
    if name == "short_features":  # fewer bins than B: the cells past them stay empty
        nb = np.array([2, 3, 5, b, 7, 2, b, 12, 3, b, 24, 9])
    db = {"default_first": np.zeros(f, int), "default_last": nb - 1,
          "default_interior": rng.randint(1, b - 1, f)}.get(name)
    if db is None:
        db = (rng.rand(f) * nb).astype(int)
    bins = (rng.rand(n, f) * nb).astype(np.int64)
    for j in range(f):
        bins[rng.rand(n) < 0.2, j] = db[j]
    g = rng.randn(n).astype(np.float32)
    h = (np.abs(rng.randn(n)) + 0.1).astype(np.float32)
    cfg = dict(CFG)
    if name == "ties":
        # whole numbers, so that sums are exact and gains tie exactly: empty
        # bins tie neighbouring thresholds, an empty default bin ties the three
        # placements, and two columns are one column twice
        g = rng.randint(-1, 2, n).astype(np.float32)
        h = np.ones(n, np.float32)
        bins = bins // 3 * 3
        db = np.where(np.arange(f) % 2 == 0, 1, db // 3 * 3)  # bin 1 holds no row
        bins[:, 4] = bins[:, 3]
        db[4] = db[3]
        cfg["min_data_in_leaf"] = 1
    hist = oracle.build_histogram_np(bins, g, h, np.ones(n), b).astype(np.float32)
    is_cat = np.zeros(f, bool)
    is_cat[[2, 7]] = True
    meta = FeatureMeta(jnp.asarray(nb, jnp.int32), jnp.asarray(db, jnp.int32), jnp.asarray(is_cat))
    mask = np.ones(f, np.float32)
    if name == "masked":
        mask[[0, 3, 5, 7]] = 0.0
    surface = {}
    if name == "monotone":
        surface = dict(monotone=jnp.asarray(rng.choice([-1, 0, 1], f), jnp.int32),
                       leaf_lo=jnp.float32(-0.05), leaf_hi=jnp.float32(0.08))
    totals = (jnp.float32(g.sum()), jnp.float32(h.sum()), jnp.float32(n))
    return hist, totals, meta, jax_hyper(cfg), jnp.asarray(mask), surface


def split_case_on_the_histogram_entry(name, use_missing, has_categorical):
    """``best_split_per_feature`` on the case's (F, B, 3) histogram, jitted:
    what tests/golden/make_split_search.py records."""
    hist, totals, meta, hyper, mask, surface = split_case(name)
    search = jax.jit(functools.partial(
        best_split_per_feature, use_missing=use_missing, has_categorical=has_categorical))
    return [np.asarray(a) for a in search(jnp.asarray(hist), *totals, meta, hyper, mask, **surface)]


class TestSplitEntries:
    """PR 40: the search is written once, on a histogram's g, h and count
    planes with the bins on the minor axis (``best_split_planes``), and
    ``best_split_per_feature`` is three slices and a call of it.  Both return
    the same four arrays bit for bit, and those are what the PARENT's (F, B, 3)
    body returned: tests/golden/split_search.npz was written by
    tests/golden/make_split_search.py from commit 9f34d0a."""

    @pytest.mark.parametrize("use_missing,has_categorical", SPLIT_MODES,
                             ids=[f"missing{int(um)}-cat{int(hc)}" for um, hc in SPLIT_MODES])
    @pytest.mark.parametrize("case", SPLIT_CASES)
    def test_planes_and_histogram_entries_bit_for_bit(self, case, use_missing, has_categorical):
        from lightgbm_tpu.ops.split import best_split_planes

        hist, totals, meta, hyper, mask, surface = split_case(case)
        on_hist = split_case_on_the_histogram_entry(case, use_missing, has_categorical)
        search = jax.jit(functools.partial(
            best_split_planes, use_missing=use_missing, has_categorical=has_categorical))
        planes = [jnp.asarray(np.ascontiguousarray(hist[..., k])) for k in range(3)]
        on_planes = [np.asarray(a) for a in search(*planes, *totals, meta, hyper, mask, **surface)]
        f = hist.shape[0]
        assert [a.shape for a in on_planes] == [(f,), (f,), (f,), (f, 3)]
        assert np.isfinite(on_planes[0]).any()  # a search that found something
        if case == "masked":
            assert np.isneginf(on_planes[0][[0, 3, 5, 7]]).all()
        with np.load(SPLIT_GOLDEN) as golden:
            for field, a, b in zip(SPLIT_FIELDS, on_planes, on_hist):
                want = golden[f"{case}-{int(use_missing)}-{int(has_categorical)}-{field}"]
                assert a.dtype == b.dtype == want.dtype, field
                assert a.tobytes() == b.tobytes() == want.tobytes(), field

    def test_the_tied_case_ties(self):
        """The case `ties` holds what its name says: columns 3 and 4 score
        the same to the bit, and a column with an empty default bin scores the
        same under every placement (so the first, zero-left, is taken)."""
        gain, thr, dbz, left = split_case_on_the_histogram_entry("ties", True, False)
        assert np.isfinite(gain[3]) and gain[3].tobytes() == gain[4].tobytes()
        assert (thr[3], dbz[3]) == (thr[4], dbz[4]) and left[3].tobytes() == left[4].tobytes()
        assert np.isfinite(gain[0]) and dbz[0] == 0


def _kernel_rows(rng, lanes):
    """Random kernel rows ``(16, lanes)``: both children's three-term
    g and h rows and their counts, the spare rows zero."""
    rows = rng.standard_normal((16, lanes)).astype(np.float32)
    rows[[3, 4, 5, 10, 11, 12]] = np.abs(rows[[3, 4, 5, 10, 11, 12]])  # hessians
    rows[[6, 13]] = rng.integers(0, 40, (2, lanes))  # counts
    rows[[7, 14, 15]] = 0.0
    return jnp.asarray(rows)


def _small_bundle_meta(rng, f, b, g, bh, default_bin, num_bins):
    """A BundleMeta as ``ptrainer._build_bundle_meta`` lays one out: column 0
    holds feature 0 raw (every bin maps direct), the other features share
    columns 1..g-1, a non-default bin a slot of its own, the default bin
    rebuilt from the leaf's totals."""
    from lightgbm_tpu.ops.pgrow import BundleMeta

    zero_slot = g * bh
    idx = np.full((f, b), zero_slot, np.int32)
    defmask = np.zeros((f, b), bool)
    idx[0, :num_bins[0]] = np.arange(num_bins[0])
    col = np.zeros(f, np.int32)
    used = np.ones(g, np.int64)  # slot 0 of a shared column is "all default"
    for fe in range(1, f):
        col[fe] = 1 + (fe - 1) % (g - 1)
        for bi in range(num_bins[fe]):
            if bi == default_bin[fe]:
                defmask[fe, bi] = True
            else:
                idx[fe, bi] = col[fe] * bh + used[col[fe]]
                used[col[fe]] += 1
    assert used.max() <= bh
    z = jnp.zeros((f,), jnp.int32)
    return BundleMeta(col=jnp.asarray(col), off_lo=z, off_hi=z, bias=z,
                      idx=jnp.asarray(idx), defmask=jnp.asarray(defmask))


class TestSiblingSearchOnPlanes:
    """PR 40: ``find2`` takes both children's g, h and count planes as the
    kernels emit them, (2, 3, G, BH) with the bins on the minor axis, where the
    parent's took (2, G, BH, 3) histograms.  The parent's stacking
    (``_hist_cells``), its row gather (``_expand_bundle_hist``) and its
    ``find2`` are copied here; ``best_split_per_feature`` under them is held to
    the parent's bytes by tests/test_ops.py::TestSplitEntries."""

    F, B = 9, 16

    @staticmethod
    def _parents_find2(params, meta, hyper, fmask, bmeta):
        from lightgbm_tpu.ops.split import NEG_INF, finalize_split

        f, b = params.num_features, params.num_bins

        def expand(hist_g, sums):
            flat = jnp.concatenate([hist_g.reshape(-1, 3), jnp.zeros((1, 3))], axis=0)
            hf = flat[bmeta.idx.reshape(-1)].reshape(f, b, 3)
            dfl = sums[None, :] - jnp.sum(hf, axis=1)
            return jnp.where(bmeta.defmask[:, :, None], dfl[:, None, :], hf)

        def find2(hist2, sums2, depth_ok):
            if bmeta is not None:
                hist2 = jax.vmap(expand)(hist2, sums2)

            def one(hist, s):
                per_feature = best_split_per_feature(
                    hist, s[0], s[1], s[2], meta, hyper, fmask, params.use_missing,
                    has_categorical=params.has_categorical)
                return finalize_split(*per_feature, s[0], s[1], s[2], hyper)

            res = jax.vmap(one)(hist2, sums2)
            return res._replace(gain=jnp.where(depth_ok, res.gain, NEG_INF))

        return find2, expand

    def _case(self, bundled, has_categorical):
        from lightgbm_tpu.ops.pgrow import PGrowParams

        f, b = self.F, self.B
        rng = np.random.default_rng(20400040 + bundled)
        num_bins = rng.integers(3, b + 1, f)
        default_bin = (rng.random(f) * num_bins).astype(np.int64)
        g, bh = (4, 64) if bundled else (f, b)
        meta = FeatureMeta(jnp.asarray(num_bins, jnp.int32), jnp.asarray(default_bin, jnp.int32),
                           jnp.asarray(np.arange(f) % 4 == 1))
        bmeta = _small_bundle_meta(rng, f, b, g, bh, default_bin, num_bins) if bundled else None
        params = PGrowParams(31, b, f, 1000, -1, True, has_categorical,
                             num_cols=g if bundled else 0, num_bins_hist=bh if bundled else 0)
        fmask = jnp.asarray((np.arange(f) != 5).astype(np.float32))
        return params, meta, jax_hyper({**CFG, "lambda_l2": 0.01}), fmask, bmeta, g, bh, rng

    @pytest.mark.parametrize("has_categorical", [False, True], ids=["numerical", "categorical"])
    @pytest.mark.parametrize("bundled", [False, True], ids=["columns", "bundles"])
    def test_find2_on_planes_is_the_parents_bit_for_bit(self, bundled, has_categorical):
        from lightgbm_tpu.ops import pgrow

        params, meta, hyper, fmask, bmeta, g, bh, rng = self._case(bundled, has_categorical)
        rows = _kernel_rows(rng, pk.hist_lanes(g, bh))
        hist2 = jnp.stack([pk._hist_from_rows(rows, g, bh, row0=r) for r in (0, 7)])
        assert hist2.shape == (2, g, bh, 3)
        sums2 = jnp.sum(hist2[:, 0], axis=1)  # (2, 3): totals via column 0
        depth_ok = jnp.asarray([True, False])
        want = jax.jit(self._parents_find2(params, meta, hyper, fmask, bmeta)[0])(
            hist2, sums2, depth_ok)
        planes2 = pgrow.child_cells(pk.child_planes(rows), g, bh)
        assert [x.shape for x in planes2] == [(2, g, bh)] * 3
        assert np.asarray(jnp.stack(planes2, axis=-1)).tobytes() == np.asarray(hist2).tobytes()
        got = jax.jit(pgrow.sibling_split_search(params, meta, hyper, fmask, bmeta))(
            planes2, sums2, depth_ok)
        assert np.isfinite(np.asarray(want.gain)).tolist() == [True, False]
        for name, a, b in zip(want._fields, want, got):
            a, b = np.asarray(a), np.asarray(b)
            assert a.shape == b.shape == (2,) and a.dtype == b.dtype, name
            assert a.tobytes() == b.tobytes(), name

    def test_bundles_expand_along_the_lanes(self):
        """``_expand_bundle_plane`` (a plane at a time: a gather of cells, the
        default bin rebuilt from the plane's total) is the parent's row
        gather, cell for cell."""
        from lightgbm_tpu.ops import pgrow

        params, meta, hyper, fmask, bmeta, g, bh, rng = self._case(True, False)
        hist = jnp.asarray(rng.standard_normal((g, bh, 3)).astype(np.float32))
        sums = jnp.sum(hist[0], axis=0)
        want = jax.jit(self._parents_find2(params, meta, hyper, fmask, bmeta)[1])(hist, sums)
        got = jax.jit(lambda hist, sums: jnp.stack(
            [pgrow._expand_bundle_plane(hist[..., k], sums[k], bmeta, self.F, self.B)
             for k in range(3)], axis=-1))(hist, sums)
        assert got.shape == (self.F, self.B, 3)
        assert np.asarray(got).tobytes() == np.asarray(want).tobytes()
        assert np.asarray(bmeta.defmask).sum() == self.F - 1  # a default bin a bundled feature


class TestGrow:
    def grow(self, rng, num_leaves=16, n=4000, f=8, b=24, cfg=None, **kw):
        cfg = cfg or CFG
        bins, default_bin, g, h = make_data(rng, n, f, b)
        params = GrowParams(num_leaves=num_leaves, num_bins=b, **kw)
        res = grow_tree(jnp.asarray(bins), jnp.asarray(g), jnp.asarray(h),
                        jnp.ones((n,)), jnp.ones((f,)),
                        jax_meta(default_bin, b, f), jax_hyper(cfg), params)
        return bins, default_bin, g, h, res

    def test_partition_consistency(self, rng):
        _, _, _, _, res = self.grow(rng)
        ns = int(res.num_splits)
        assert 1 <= ns <= 15
        counts = np.bincount(np.asarray(res.leaf_id), minlength=16)
        np.testing.assert_array_equal(counts[: ns + 1], np.asarray(res.leaf_cnt)[: ns + 1])
        assert counts[ns + 1:].sum() == 0

    def test_matches_oracle_tree(self, rng):
        """Full best-first sequence parity with a sequential oracle grower."""
        n, f, b, L = 3000, 6, 16, 8
        bins, default_bin, g, h = make_data(rng, n, f, b)
        params = GrowParams(num_leaves=L, num_bins=b)
        res = grow_tree(jnp.asarray(bins), jnp.asarray(g), jnp.asarray(h),
                        jnp.ones((n,)), jnp.ones((f,)),
                        jax_meta(default_bin, b, f), jax_hyper(CFG), params)

        # oracle best-first grower
        leaf_rows = {0: np.arange(n)}
        best = {}

        def leaf_best(rows):
            hist = oracle.build_histogram_np(bins[rows], g[rows], h[rows],
                                             np.ones(len(rows)), b)
            return oracle.best_split_all_features_np(
                hist, float(g[rows].sum()), float(h[rows].sum()), len(rows),
                default_bin, np.zeros(f, bool), np.full(f, b), CFG)

        best[0] = leaf_best(leaf_rows[0])
        for s in range(int(res.num_splits)):
            bl = max(best, key=lambda k: best[k]["gain"])
            assert bl == int(res.rec_leaf[s]), f"split {s} leaf"
            r = best[bl]
            assert r["feature"] == int(res.rec_feat[s]), f"split {s} feature"
            assert r["threshold"] == int(res.rec_thr[s]), f"split {s} threshold"
            assert r["dbz"] == int(res.rec_dbz[s]), f"split {s} dbz"
            assert r["gain"] == pytest.approx(float(res.rec_gain[s]), rel=1e-3, abs=1e-3)
            rows = leaf_rows[bl]
            col = bins[rows, r["feature"]].astype(np.int64)
            fv = np.where(col == default_bin[r["feature"]], r["dbz"], col)
            lmask = fv <= r["threshold"]
            leaf_rows[bl] = rows[lmask]
            leaf_rows[s + 1] = rows[~lmask]
            best[bl] = leaf_best(leaf_rows[bl])
            best[s + 1] = leaf_best(leaf_rows[s + 1])

    def test_max_depth(self, rng):
        _, _, _, _, res = self.grow(rng, num_leaves=32, max_depth=2)
        # depth-2 tree has at most 4 leaves = 3 splits
        assert int(res.num_splits) <= 3

    def test_leaf_values(self, rng):
        bins, db, g, h, res = self.grow(rng, cfg={**CFG, "lambda_l2": 1.0})
        ns = int(res.num_splits)
        leaf_id = np.asarray(res.leaf_id)
        for leaf in range(ns + 1):
            rows = leaf_id == leaf
            want = oracle.leaf_output(g[rows].sum(), h[rows].sum(), 0.0, 1.0)
            assert float(res.leaf_value[leaf]) == pytest.approx(want, rel=1e-3, abs=1e-4)
