"""The fused trainer above the old 512-column ceiling: the streaming kernels
walk a block's bin words in column groups (ops/pkernels.py ``col_groups``),
and what they build must be what the ungrouped kernels built and what the
plain reference, the mask grower of ops/grow.py, builds.  CPU, seeded,
kernels interpreted."""

import os
from unittest import mock

import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.boosting import ptrainer
from lightgbm_tpu.ops import pkernels as pk
from lightgbm_tpu.utils.log import Log

# the benchmark driver's band (benchmarks/drivers/train.py)
PARITY_RTOL, PARITY_ATOL = 3e-3, 3e-4
PARAMS = {"objective": "binary", "max_bin": 63, "num_leaves": 15, "learning_rate": 0.1,
          "min_data_in_leaf": 1, "min_sum_hessian_in_leaf": 1.0, "verbose": -1}


def _table(rows, cols, seed):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((rows, cols)).astype(np.float32)
    w = rng.standard_normal(8)
    # informative columns on both sides of the old ceiling and in the last group
    inform = [0, 3, cols // 2, 511, 512, cols - 3, cols - 2, cols - 1]
    margin = X[:, inform] @ w + 0.5 * X[:, 0] * X[:, cols - 1]
    y = (rng.random(rows) < 1 / (1 + np.exp(-margin))).astype(np.float32)
    return X, y


def _splits(tree):
    ns = int(tree.num_leaves) - 1
    return (tree.split_feature[:ns].tolist(), tree.threshold_in_bin[:ns].tolist(),
            tree.left_child[:ns].tolist(), tree.right_child[:ns].tolist())


def _train(X, y, env):
    with mock.patch.dict(os.environ, env):
        return lgb.train(dict(PARAMS), lgb.Dataset(X, label=y, params=dict(PARAMS)),
                         num_boost_round=3, verbose_eval=False)


@pytest.fixture(scope="module", params=[(2048, 516), (3072, 1032)],
                ids=["516-columns-partial-last-group", "1032-columns-several-groups"])
def pair(request):
    rows, cols = request.param
    X, y = _table(rows, cols, seed=20290000 + cols)
    fused = _train(X, y, {"LIGHTGBM_TPU_PGROW": "force"})
    plain = _train(X, y, {"LIGHTGBM_TPU_PGROW": "0"})
    return X, cols, fused, plain


def test_the_pair_is_fused_against_mask_grower(pair):
    _, cols, fused, plain = pair
    pt = fused.boosting.ptrainer
    assert type(pt).__name__ == "PartitionedTrainer" and plain.boosting.ptrainer is None
    assert pt.layout.F == cols > 512
    assert pk.col_groups(cols).n_full >= 16


def test_first_tree_split_for_split(pair):
    _, _, fused, plain = pair
    sf, sp = (_splits(b.boosting.models[0]) for b in (fused, plain))
    assert len(sf[0]) == PARAMS["num_leaves"] - 1
    assert sf == sp


def test_a_split_past_the_old_ceiling_is_taken(pair):
    """The comparison would pass on a kernel that never looked past column
    511 if no tree split there."""
    _, _, fused, _ = pair
    feats = {f for t in fused.boosting.models for f in
             t.split_feature[:int(t.num_leaves) - 1].tolist()}
    assert max(feats) >= 512


def test_predictions_inside_the_drivers_band(pair):
    X, _, fused, plain = pair
    np.testing.assert_allclose(fused.predict(X), plain.predict(X),
                               rtol=PARITY_RTOL, atol=PARITY_ATOL)


class TestGroupedHistogramsBitIdentical:
    """One shape, 70 columns x 63 bins on 3,000 rows: every kernel that builds
    a histogram, walked in 2 rolled groups and a tail, against the same
    kernel made to walk it as ONE static group (the only way there was)."""

    F, B, N = 70, 63, 3000

    @pytest.fixture(scope="class")
    def packed(self):
        rng = np.random.default_rng(20290929)
        bins = rng.integers(0, self.B, size=(self.N, self.F)).astype(np.uint8)
        lay = pk.PLayout(self.F)
        p = np.array(pk.pack_matrix(bins, lay, label=rng.random(self.N) < 0.5))
        p[lay.G, :self.N] = rng.standard_normal(self.N).astype(np.float32).view(np.int32)
        p[lay.H, :self.N] = rng.random(self.N).astype(np.float32).view(np.int32)
        return lay, p

    @staticmethod
    def _ungrouped():
        whole = lambda f, bits=8: pk.ColGroups(8, 0, pk.num_words(f, bits))  # noqa: E731
        return mock.patch.object(pk, "col_groups", whole)

    def test_this_shape_is_grouped(self):
        assert pk.col_groups(self.F) == pk.ColGroups(gw=8, n_full=2, tail_w=2)
        assert pk.col_groups(28) == pk.ColGroups(gw=8, n_full=0, tail_w=7)
        assert pk.col_groups(2000).count == 63
        assert pk.bin_pitch(self.B) == 64 and pk.bin_pitch(64) == 64 and pk.bin_pitch(16) == 16
        assert pk.hist_lanes(28, self.B) == 1792 and pk.hist_lanes(2000, self.B) == 128_000

    @pytest.mark.parametrize("kernel", ["hist_dyn", "split_stream", "level_stream",
                                        "update_and_root_hist"])
    def test_kernel(self, packed, kernel):
        lay, p = packed
        kw = dict(num_features=self.F, num_bins=self.B, interpret=True)

        def run():
            # un-jitted bodies: the group plan is read at trace time
            if kernel == "hist_dyn":
                return [pk.hist_dyn.__wrapped__(jnp.asarray(p), 100, 2500, self.F, self.B,
                                                interpret=True)]
            if kernel == "split_stream":
                out = pk.split_stream.__wrapped__(
                    jnp.asarray(p), 37, 2900, 69 // 4, (69 % 4) * 8, 0, 0, 30, 0, **kw)
                return list(out)
            if kernel == "level_stream":
                seg = np.zeros((8, 12), np.int32)
                seg[0, :11] = [0, 1400, 2, 8, 0, 0, 25, 0, 0, 256, 0]
                seg[1, :11] = [1400, 1600, 16, 24, 0, 0, 40, 0, 0, 256, 0]
                pp, nl, hists = pk.level_stream.__wrapped__(
                    jnp.asarray(p), jnp.asarray(seg), 2, smax=8, **kw)
                cells = [pk._hist_from_rows(hists[i], self.F, self.B, row0)
                         for i in range(2) for row0 in (0, 7)]
                return [pp, nl[:2]] + cells
            pp, hist = pk.update_and_root_hist(
                jnp.asarray(p), lay, lambda s, l, w: (s - l, jnp.ones_like(s)),
                delta=jnp.full((self.N,), 0.25, jnp.float32), num_rows=self.N, **kw)
            return [pp, hist]

        grouped = run()
        with self._ungrouped():
            whole = run()
        for a, b in zip(grouped, whole):
            assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


class TestEligibility:
    @staticmethod
    def _dataset(cols, rows=600):
        X, y = _table(rows, cols, seed=5)
        ds = lgb.Dataset(X, label=y, params=dict(PARAMS))
        ds.construct()
        return ds

    @staticmethod
    def _eligible(ds, k=1, **params):
        from lightgbm_tpu.config import Config

        cfg = Config.from_params({**PARAMS, **params})
        obj = mock.Mock(rowwise=True, rowwise_multi=True, num_tree_per_iteration=k)
        with mock.patch.dict(os.environ, {"LIGHTGBM_TPU_PGROW": "force"}):
            return ptrainer.eligible(cfg, ds.construct(), obj, k)

    def test_two_thousand_columns_ride(self):
        assert self._eligible(self._dataset(2000)) is True

    @pytest.mark.parametrize("cols,k,says", [
        (2000, 3, "multiclass (K = 3) above 512 columns"),
        (5000, 1, "level_stream would hold")])
    def test_what_is_still_declined_says_why(self, cols, k, says):
        seen = []
        with mock.patch.object(Log, "warning", lambda fmt, *a: seen.append(fmt % a)):
            assert self._eligible(self._dataset(cols, rows=200), k=k) is False
        assert len(seen) == 1 and says in seen[0] and "mask grower" in seen[0]
