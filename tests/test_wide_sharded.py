"""The data-parallel fused trainer (``tree_learner=data``,
``ShardedPartitionedTrainer`` over four devices) above the old 512-column
ceiling, where the histograms it all-reduces are the size of the problem: what
four shards build must be what the plain reference builds (the mask grower of
ops/grow.py on one device, which shares no kernel, no partition and no
collective with it) and, where float sums are exact, what the serial fused
trainer builds.  CPU, four forced host devices, seeded, kernels interpreted."""

import os
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
import lightgbm_tpu.parallel as par
from lightgbm_tpu.ops import pkernels as pk

# the same seeded tables, parameters and band as the serial fused trainer's tests
from test_wide_fused import PARAMS, PARITY_ATOL, PARITY_RTOL, _splits, _table

SHARDS = 4


def _train(X, y, env, learner="serial"):
    """(booster, the chunk's records or None): 3 iterations through the
    public ``lgb.Booster`` and ``train_iters_partitioned``, the path the
    benchmark's driver takes."""
    params = dict(PARAMS, tree_learner=learner)
    mesh4 = par.make_mesh(SHARDS)
    with mock.patch.dict(os.environ, env), \
            mock.patch.object(par, "make_mesh", lambda n_devices=None: mesh4):
        bst = lgb.Booster(params=params, train_set=lgb.Dataset(X, label=y, params=dict(params)))
        gb = bst.boosting
        if gb.ptrainer is None:
            for _ in range(3):
                bst.update()
            return bst, None
        seen = {}
        run = gb.ptrainer.train_chunk

        def capture(*a, **k):
            out = run(*a, **k)
            seen["recs"] = out[0]
            return out

        with mock.patch.object(gb.ptrainer, "train_chunk", capture):
            gb.train_iters_partitioned(3, is_eval=False)
    return bst, seen["recs"]


# 4,097 rows: shards of 1,025 of which the last holds 1,022 rows and 3 of padding
@pytest.fixture(scope="module", params=[(4097, 516), (3072, 1032)],
                ids=["516-columns-padded-last-shard", "1032-columns-several-groups"])
def triple(request):
    if len(jax.devices()) < SHARDS:
        pytest.skip(f"needs {SHARDS} devices")
    rows, cols = request.param
    X, y = _table(rows, cols, seed=20330000 + cols)
    sharded, sharded_recs = _train(X, y, {"LIGHTGBM_TPU_PGROW": "force"}, learner="data")
    serial, serial_recs = _train(X, y, {"LIGHTGBM_TPU_PGROW": "force"})
    plain, _ = _train(X, y, {"LIGHTGBM_TPU_PGROW": "0"})
    return X, rows, cols, sharded, sharded_recs, serial, serial_recs, plain


def test_the_triple_is_sharded_fused_serial_fused_and_mask_grower(triple):
    _, rows, cols, sharded, _, serial, _, plain = triple
    pt = sharded.boosting.ptrainer
    assert type(pt).__name__ == "ShardedPartitionedTrainer" and pt.d == SHARDS
    assert type(serial.boosting.ptrainer).__name__ == "PartitionedTrainer"
    assert plain.boosting.ptrainer is None
    assert pt.layout.F == cols > 512 and pk.col_groups(cols).n_full >= 16
    assert pt.num_rows == -(-rows // SHARDS)
    # a shard's block ends on a whole 128-lane tile (ptrainer.py says why)
    assert pt.p.shape == (SHARDS, pt.layout.C, -(-(pt.num_rows + pk.BLK) // 128) * 128)
    assert all(len(b.boosting.models) == 3 for b in (sharded, serial, plain))


def test_first_tree_split_for_split_with_the_reference(triple):
    *_, sharded, _, _, _, plain = triple
    sf, sp = (_splits(b.boosting.models[0]) for b in (sharded, plain))
    assert len(sf[0]) == PARAMS["num_leaves"] - 1
    assert sf == sp


def test_predictions_inside_the_drivers_band(triple):
    X, *_, sharded, _, _, _, plain = triple
    np.testing.assert_allclose(sharded.predict(X), plain.predict(X),
                               rtol=PARITY_RTOL, atol=PARITY_ATOL)


def test_first_tree_records_byte_equal_to_the_serial_fused_trainer(triple):
    """What tests/golden/pgrow_recs.npz's ``dp4_*`` cases promise against its
    ``serial_*`` ones: the FIRST tree's records are byte-equal (its gradients
    are +-0.5 and its hessians 0.25, whose float32 sums are exact in any order,
    four partial sums and an all-reduce included); from the second tree on the
    two agree in structure and to rounding."""
    *_, sharded_recs, _, serial_recs, _ = triple
    assert sharded_recs["num_splits"].tolist() == serial_recs["num_splits"].tolist()
    assert sharded_recs["raw"][0].tobytes() == serial_recs["raw"][0].tobytes()
    np.testing.assert_array_equal(sharded_recs["raw"][..., :4], serial_recs["raw"][..., :4])
    np.testing.assert_allclose(sharded_recs["raw"], serial_recs["raw"], rtol=1e-4, atol=1e-6)


def test_a_split_past_column_511_is_taken_under_data_parallel(triple):
    """The comparisons would pass on shards that never looked past column 511
    if no tree split there."""
    *_, sharded, _, _, _, _ = triple
    feats = {f for t in sharded.boosting.models for f in
             t.split_feature[:int(t.num_leaves) - 1].tolist()}
    assert max(feats) >= 512


def test_padding_rows_of_the_last_shard_are_in_no_histogram(triple):
    """400,000 rows over four chips force no padding, 4,097 do: the last
    shard's 3 dummy rows keep select 0 and a row id past its real rows, so
    the root counts the table's rows and nothing else."""
    _, rows, _, sharded, *_ = triple
    pt = sharded.boosting.ptrainer
    pad = SHARDS * pt.num_rows - rows
    assert pad == (3 if rows == 4097 else 0)
    for t in sharded.boosting.models:
        assert int(t.internal_count[0]) == rows
    scores = np.asarray(pt.scores_original_order())
    assert scores.shape == (rows,) and np.all(np.isfinite(scores))


class TestShardHistogramsSumToTheWhole:
    """The tie of the share to the whole, at the kernel that builds what a
    level all-reduces: ``level_stream`` on the four shards' packed blocks, its
    ``(16, lanes)`` histogram rows summed over the shards as the ``psum``
    does, against the same kernel on ONE matrix that holds all the rows.  516
    columns x 63 bins on 4,097 rows (the last shard padded)."""

    F, B, N = 516, 63, 4097

    @pytest.fixture(scope="class")
    def table(self):
        rng = np.random.default_rng(20331001)
        bins = rng.integers(0, self.B, size=(self.N, self.F)).astype(np.uint8)
        label = (rng.random(self.N) < 0.5).astype(np.float32)
        return bins, label, rng.standard_normal(self.N).astype(np.float32), \
            rng.random(self.N).astype(np.float32)

    def _level_hist(self, bins, g, h, nreal=None):
        """Histogram rows of the root segment split at column 513, bin 30."""
        lay = pk.PLayout(self.F)
        n = bins.shape[0]
        p = np.array(pk.pack_matrix(bins, lay, label=np.zeros(n, np.float32), num_real=nreal))
        p[lay.G, :n] = g.view(np.int32)
        p[lay.H, :n] = h.view(np.int32)
        seg = np.zeros((8, 12), np.int32)
        seg[0, :11] = [0, n, 513 // 4, (513 % 4) * 8, 0, 0, 30, 0, 0, 256, 0]
        _, nl, hists = pk.level_stream.__wrapped__(
            jnp.asarray(p), jnp.asarray(seg), 1, num_features=self.F, num_bins=self.B,
            smax=8, interpret=True)
        return int(nl[0]), np.asarray(hists[0])

    def _shards(self, bins, g, h):
        nl = -(-self.N // SHARDS)
        out = []
        for k in range(SHARDS):
            lo, hi = k * nl, min((k + 1) * nl, self.N)
            pad = nl - (hi - lo)
            out.append(self._level_hist(
                np.pad(bins[lo:hi], ((0, pad), (0, 0))), np.pad(g[lo:hi], (0, pad)),
                np.pad(h[lo:hi], (0, pad)), nreal=hi - lo))
        return out

    def test_bit_for_bit_where_sums_are_exact(self, table):
        """A first tree's values (binary objective from a constant score:
        g = +-0.5, h = 0.25): every partial sum is a multiple of 2**-2 below
        2**24, so four shards' rows add up to the whole's in every bit."""
        bins, label, _, _ = table
        g = np.where(label > 0, -0.5, 0.5).astype(np.float32)
        h = np.full(self.N, 0.25, np.float32)
        nl_whole, whole = self._level_hist(bins, g, h)
        parts = self._shards(bins, g, h)
        # the last shard's 3 padding rows hold bin 0 and go left with no weight
        assert sum(nl for nl, _ in parts) == nl_whole + 3
        total = np.sum([hist for _, hist in parts], axis=0, dtype=np.float32)
        assert total.tobytes() == whole.tobytes()
        assert np.count_nonzero(whole) > self.F * self.B  # both children, all columns

    def test_to_rounding_where_they_are_not(self, table):
        """Later trees' values: a cell is a float32 sum of up to N terms, and
        four partial sums round differently from one.  The bound is the usual
        one for a sum, N x 2**-24 x the sum of magnitudes, per cell; a bf16
        accumulation would miss it by four orders of magnitude."""
        bins, _, g, h = table
        _, whole = self._level_hist(bins, g, h)
        _, mags = self._level_hist(bins, np.abs(g), h)
        total = np.sum([hist for _, hist in self._shards(bins, g, h)], axis=0, dtype=np.float32)
        assert np.all(np.abs(total - whole) <= self.N * 2.0 ** -24 * np.abs(mags) + 1e-30)
        assert not np.array_equal(total, np.zeros_like(total))
