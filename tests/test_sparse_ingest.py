"""Sparse input without densifying it (lightgbm_tpu/io/sparse.py): a scipy
CSR/CSC table goes to the mappers, the EFB bundles and the bundled matrix
that the dense path gives the densified table, byte for byte; the dataset
then holds its bundles and no ``(N, F)`` bins; the fused trainer streams them
and grows what the mask grower grows on the dense, unbundled table; and the
device program counts the splits its replay took the classic way.  CPU,
seeded, kernels interpreted, no clock."""

import json
import os
from unittest import mock

import jax
import numpy as np
import pytest
import scipy.sparse

import lightgbm_tpu as lgb
import lightgbm_tpu.parallel as par
from lightgbm_tpu.config import Config
from lightgbm_tpu.io import sparse
from lightgbm_tpu.io.bundle import build_bundled_matrix
from lightgbm_tpu.io.dataset import BinnedDataset
from lightgbm_tpu.obs import tracer

# the benchmark driver's band (benchmarks/drivers/train.py says why)
PARITY_RTOL, PARITY_ATOL = 3e-3, 3e-4
PARAMS = {"objective": "binary", "max_bin": 63, "num_leaves": 31, "learning_rate": 0.1,
          "min_data_in_leaf": 1, "min_sum_hessian_in_leaf": 1, "verbose": -1}


def _one_hot(n, fields, seed=0, numeric_col=None):
    """(dense float32 (n, sum(fields)), y): one category a field set to 1.0,
    the popular categories first (a Zipf draw), labels from a logistic margin."""
    rng = np.random.RandomState(seed)
    cats = [np.minimum(rng.zipf(1.3, n) - 1, c - 1) if c > 40 else rng.randint(0, c, n)
            for c in fields]
    cols = np.stack(cats, 1) + np.cumsum((0,) + tuple(fields[:-1]))
    dense = np.zeros((n, sum(fields)), np.float32)
    dense[np.arange(n)[:, None], cols] = 1.0
    if numeric_col is not None:  # a sparse column of real values among the indicators
        dense[:, numeric_col] *= rng.randn(n).astype(np.float32)
    w = rng.randn(dense.shape[1])
    y = (rng.rand(n) < 1.0 / (1.0 + np.exp(-((dense != 0) @ w)))).astype(np.float32)
    return dense, y


def _splits(tree):
    ns = int(tree.num_leaves) - 1
    return (tree.split_feature[:ns].tolist(), tree.threshold_in_bin[:ns].tolist(),
            tree.left_child[:ns].tolist(), tree.right_child[:ns].tolist())


CASES = {
    "one-hot-csr": dict(n=3000, fields=(12, 31, 7, 20), conv=scipy.sparse.csr_matrix),
    "numeric-column-csc": dict(n=3000, fields=(12, 31, 7), numeric_col=5,
                               conv=scipy.sparse.csc_matrix),
    "a-field-of-300-categories": dict(n=6000, fields=(12, 300, 7), conv=scipy.sparse.csr_matrix),
    "conflicts-outside-the-sample": dict(n=3000, fields=(12, 31), conv=scipy.sparse.csr_matrix,
                                         params={"bin_construct_sample_cnt": 400}, clash=True),
}


@pytest.fixture(scope="module", params=list(CASES), ids=list(CASES))
def pair(request):
    """(case, dense BinnedDataset with its lazy bundles built, the sparse one)."""
    case = dict(CASES[request.param])
    dense, y = _one_hot(case["n"], case["fields"], numeric_col=case.get("numeric_col"))
    if case.get("clash"):
        # two features of one bundle set in the same row, in rows the 400-row
        # conflict sample (data_random_seed 1) does not hold
        cfg0 = Config.from_params({"bin_construct_sample_cnt": 400})
        from lightgbm_tpu.io.bundle import bundle_sample_rows

        outside = np.setdiff1d(np.arange(case["n"]), bundle_sample_rows(case["n"], cfg0))[:25]
        dense[outside, 0] = dense[outside, 1] = 1.0
    cfg = Config.from_params({"max_bin": 63, "min_data_in_leaf": 1, **case.get("params", {})})
    a = BinnedDataset.from_raw(dense, cfg, label=y)
    a.ensure_bundles(cfg)
    b = BinnedDataset.from_sparse(case["conv"](dense), cfg, label=y)
    return case, a, b


def test_mappers_are_the_densified_tables(pair):
    _, a, b = pair
    assert a.used_feature_map.tolist() == b.used_feature_map.tolist()
    assert a.num_total_features == b.num_total_features and a.feature_names == b.feature_names
    for ma, mb in zip(a.bin_mappers, b.bin_mappers):
        sa, sb = ma.state(), mb.state()
        assert all(np.array_equal(np.asarray(sa[k]), np.asarray(sb[k])) for k in sa)


def test_bundle_info_and_bundled_matrix_are_byte_equal(pair):
    case, a, b = pair
    assert a.bundle is not None and a.bundle.groups == b.bundle.groups
    for k in ("col", "off_lo", "off_hi", "bias", "num_bin_col"):
        assert getattr(a.bundle, k).tobytes() == getattr(b.bundle, k).tobytes()
    assert a.bundle.max_col_bin == b.bundle.max_col_bin <= 256
    assert b.bundled.dtype == np.uint8 and b.bundled.tobytes() == a.bundled.tobytes()
    assert b.bundled.tobytes() == build_bundled_matrix(a.binned, a.bin_mappers, a.bundle).tobytes()
    assert (b.bundle_conflicts > 0) == bool(case.get("clash"))
    if case["fields"] == (12, 300, 7):
        # 300 categories do not fit one 256-bin column: the field takes two
        sizes = sorted(len(g) for g in b.bundle.groups)
        assert b.bundle.num_cols == 4 and sizes[-1] == 255 and sum(sizes) == b.num_features


def test_the_dataset_holds_its_bundles_and_decodes_bins_on_demand(pair, tmp_path):
    case, a, b = pair
    assert not b.has_dense_bins and a.has_dense_bins
    assert (b.num_data, b.num_features, b.bin_dtype) == (a.num_data, a.num_features, np.uint8)
    path = str(tmp_path / "sparse.bin")
    b.save_binary(path)
    c = BinnedDataset.load_binary(path)
    assert not c.has_dense_bins and c.bundled.tobytes() == b.bundled.tobytes()
    assert c.bundle.groups == b.bundle.groups and c.bundle_conflicts == b.bundle_conflicts
    assert c.metadata.label.tobytes() == b.metadata.label.tobytes()
    sub = c.subset(np.arange(0, c.num_data, 3))
    assert not sub.has_dense_bins and sub.bundled.tobytes() == b.bundled[::3].tobytes()
    decoded = c.binned  # whoever works by feature: exact but for the conflicts' cells
    assert c.has_dense_bins and decoded.shape == a.binned.shape
    assert int((decoded != a.binned).sum()) == b.bundle_conflicts


def test_valid_set_on_sparse_input_shares_the_mappers_and_has_feature_bins(pair):
    case, a, b = pair
    dense, y = _one_hot(500, case["fields"], seed=9, numeric_col=case.get("numeric_col"))
    va = a.create_valid(dense, label=y)
    vb = BinnedDataset.from_sparse(case["conv"](dense), Config(), label=y, reference=b)
    assert vb.bin_mappers is b.bin_mappers and vb.has_dense_bins and vb.bundle is None
    assert vb.binned.dtype == va.binned.dtype and vb.binned.tobytes() == va.binned.tobytes()


def test_unbundled_sparse_input_gives_the_feature_bins():
    """Nothing to bundle (dense columns handed over as CSR), or bundling off:
    the ``(N, F)`` bins are written from the rows' entries, none densified."""
    rng = np.random.RandomState(3)
    dense = rng.randn(800, 6) * (rng.rand(800, 6) < 0.7)
    cfg = Config.from_params({"max_bin": 63})
    a = BinnedDataset.from_raw(dense, cfg)
    b = BinnedDataset.from_sparse(scipy.sparse.csr_matrix(dense), cfg)
    assert b.has_dense_bins and b.bundle is None and b.binned.tobytes() == a.binned.tobytes()
    hot, _ = _one_hot(800, (5, 9))
    off = Config.from_params({"max_bin": 63, "enable_bundle": False})
    c = BinnedDataset.from_sparse(scipy.sparse.csr_matrix(hot), off)
    assert c.bundle is None and c.binned.tobytes() == BinnedDataset.from_raw(hot, off).binned.tobytes()


def test_a_model_trained_from_csr_is_byte_equal_to_the_dense_one(tmp_path):
    """Off the chip the mask grower trains both: on the dense table's bins,
    and on the bins decoded from the CSR table's bundles.  Prediction on CSR
    walks row blocks; a binary file of the bundled dataset trains the same."""
    dense, y = _one_hot(2500, (12, 31, 7, 20), numeric_col=3)
    csr = scipy.sparse.csr_matrix(dense)
    m_dense = lgb.train(PARAMS, lgb.Dataset(dense, label=y), 4)
    m_csr = lgb.train(PARAMS, lgb.Dataset(csr, label=y), 4)
    assert m_dense.boosting.ptrainer is None
    assert m_csr.model_to_string() == m_dense.model_to_string()
    path = str(tmp_path / "t.bin")
    lgb.Dataset(csr, label=y, params=PARAMS).save_binary(path)
    assert lgb.train(PARAMS, lgb.Dataset(path), 4).model_to_string() == m_dense.model_to_string()
    with mock.patch.object(sparse, "dense_block_rows", lambda cols: 128):  # 20 blocks, on threads
        for kw in ({}, {"raw_score": True}, {"pred_leaf": True}, {"num_iteration": 2}):
            got, want = m_csr.predict(csr, **kw), m_dense.predict(dense, **kw)
            assert got.shape == want.shape and got.tobytes() == want.tobytes()
    assert m_csr.predict(csr[:0]).shape == (0,)


# -- the fused trainer on CSR-made bundles -----------------------------------
def _host_tail(tree, first_tail_depth):
    """(splits, rows) of the internal nodes at or below that depth: what the
    level phase did not precompute, where its tables were never full."""
    ns = int(tree.num_leaves) - 1
    depth = np.zeros(ns, int)
    for i in range(ns):
        for c in (tree.left_child[i], tree.right_child[i]):
            if c >= 0:
                depth[c] = depth[i] + 1
    deep = depth >= first_tail_depth
    return int(deep.sum()), int(tree.internal_count[:ns][deep].sum())


def _traced_fused(X, y, learner, tmp_path_factory, iters=3):
    """(booster, its spans): `iters` iterations of the fused trainer on X
    through lgb.Booster and train_iters_partitioned, the program's tracer on."""
    params = dict(PARAMS, tree_learner=learner)
    path = str(tmp_path_factory.mktemp("trace") / f"{learner}.jsonl")
    mesh4 = par.make_mesh(4) if learner == "data" else None
    with mock.patch.dict(os.environ, LIGHTGBM_TPU_PGROW="force"), \
            mock.patch.object(par, "make_mesh", lambda n_devices=None: mesh4):
        tracer.configure(path)
        try:
            bst = lgb.Booster(params=params, train_set=lgb.Dataset(X, label=y, params=params))
            bst.boosting.train_iters_partitioned(iters, is_eval=False)
        finally:
            tracer.close()
            tracer.path = None
    with open(path) as f:
        return bst, [r for r in map(json.loads, f) if r["ev"] == "span"]


@pytest.fixture(scope="module")
def table():
    return _one_hot(6000, (12, 31, 7, 300, 20))


@pytest.fixture(scope="module")
def fused(table, tmp_path_factory):
    dense, y = table
    return _traced_fused(scipy.sparse.csr_matrix(dense), y, "serial", tmp_path_factory)


@pytest.fixture(scope="module")
def plain(table):
    dense, y = table
    with mock.patch.dict(os.environ, LIGHTGBM_TPU_PGROW="0"):
        return lgb.train(PARAMS, lgb.Dataset(dense, label=y), 3)


def test_the_fused_trainer_streams_the_bundles_and_nothing_of_n_by_f_exists(fused, plain):
    bst, _ = fused
    gb, pt, ds = bst.boosting, bst.boosting.ptrainer, bst.train_dataset.construct()
    assert type(pt).__name__ == "PartitionedTrainer" and pt.bmeta is not None
    assert pt.params.num_cols == ds.bundle.num_cols == 6 < ds.num_features <= 370
    assert pt.params.num_bins_hist == ds.bundle.max_col_bin <= 256
    assert pt.p.shape[0] == 16  # six bundle columns pack like Higgs' 28 features
    assert not ds.has_dense_bins and not gb.has_device_bins
    ps = plain.train_dataset.construct()
    assert plain.boosting.ptrainer is None and ps.bundle is None and ps.has_dense_bins


def test_first_tree_split_for_split_and_predictions_in_the_drivers_band(fused, plain, table):
    bst, _ = fused
    dense, _ = table
    sf, sp = _splits(bst.boosting.models[0]), _splits(plain.boosting.models[0])
    assert len(sf[0]) == PARAMS["num_leaves"] - 1 and sf == sp
    np.testing.assert_allclose(bst.predict(scipy.sparse.csr_matrix(dense)), plain.predict(dense),
                               rtol=PARITY_RTOL, atol=PARITY_ATOL)


def test_a_checkpointed_fused_run_decodes_nothing(table, tmp_path):
    """A checkpoint's data fingerprint is taken over the bundle columns the
    dataset holds, not over bins decoded for the purpose."""
    dense, y = table
    ds = lgb.Dataset(scipy.sparse.csr_matrix(dense), label=y, params=PARAMS)
    with mock.patch.dict(os.environ, LIGHTGBM_TPU_PGROW="force"):
        bst = lgb.train(PARAMS, ds, 2, checkpoint_dir=str(tmp_path), checkpoint_freq=1)
    assert bst.boosting.ptrainer is not None and (tmp_path / "MANIFEST.json").exists()
    assert not ds.construct().has_dense_bins and not bst.boosting.has_device_bins


def test_sparse_ingest_spans_and_their_counts(fused, table):
    _, spans = fused
    by = {s["name"]: s for s in spans}
    ingest = by["sparse_ingest"]
    assert (ingest["rows"], ingest["nnz"], ingest["features"]) == (6000, 30000, 370)  # as handed over
    assert (ingest["bundle_cols"], ingest["max_col_bin"]) == (6, 256)
    inner = [by[k] for k in ("csr_bin", "find_bundles", "build_bundled")]
    assert all(s["parent"] == "sparse_ingest" and s["depth"] == ingest["depth"] + 1 for s in inner)
    assert sum(s["dur_s"] for s in inner) <= ingest["dur_s"]


def _tail_of_the_records(bst, spans):
    from lightgbm_tpu.ops.pgrow import level_slots

    (t,) = [s for s in spans if s["name"] == "trees_from_records"]
    levels = (level_slots(PARAMS["num_leaves"]) - 1).bit_length() + 1  # 6 at 31 leaves
    host = [_host_tail(tree, levels) for tree in bst.boosting.models]
    return t, sum(h[0] for h in host), sum(h[1] for h in host)


def test_tail_counters_against_the_trees_serial(fused):
    """`tail_splits` and `tail_rows` on the `trees_from_records` span, out of
    the device program: a chain-shaped tree (a one-hot split peels one category
    off) goes deeper than the level phase's six levels, and every split below
    them is a `split_stream` pass over its parent's rows."""
    bst, spans = fused
    t, splits, rows = _tail_of_the_records(bst, spans)
    assert (t["tail_splits"], t["tail_rows"]) == (splits, rows) and 0 < splits < t["splits"]
    assert t["bundle_cols"] == 6 and (t["shards"], t["allreduce_calls"]) == (1, 0)


def test_tail_counters_under_a_four_device_shard_map(table, tmp_path_factory):
    """Data-parallel: every tail split all-reduces its children, so the
    all-reduce count IS the tail count (what `tail_psums` was), and
    `tail_rows` is one shard's."""
    if len(jax.devices()) < 4:
        pytest.skip("needs a multi-device mesh")
    dense, y = table
    bst, spans = _traced_fused(scipy.sparse.csr_matrix(dense), y, "data", tmp_path_factory)
    assert type(bst.boosting.ptrainer).__name__ == "ShardedPartitionedTrainer"
    t, splits, rows = _tail_of_the_records(bst, spans)
    assert t["tail_splits"] == splits == t["allreduce_calls"] - t["trees"] - t["levels"] > 0
    assert t["shards"] == 4 and 0 < t["tail_rows"] < rows


def test_tail_rows_saturate_instead_of_wrapping():
    import jax.numpy as jnp

    from lightgbm_tpu.ops.pgrow import _count_tail

    top = np.iinfo(np.int32).max
    tail = jnp.asarray([100, top - 5], jnp.int32)
    assert _count_tail(tail, jnp.array(False), jnp.int32(21_000_000)).tolist() == [101, top]
    assert _count_tail(tail, jnp.array(False), jnp.int32(3)).tolist() == [101, top - 2]
    assert _count_tail(tail, jnp.array(True), jnp.int32(3)).tolist() == [100, top - 5]
