"""Tests for auxiliary subsystems: plotting (Agg, modeled on the
reference's test_plotting.py), PMML export, prediction early stop,
phase timers, and the text parser formats + side files.
"""

import os

import matplotlib

matplotlib.use("Agg")

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.io.parser import load_text_file, sniff_format

EXAMPLES = "/root/reference/examples"


@pytest.fixture(scope="module")
def small_booster():
    rng = np.random.RandomState(0)
    x = rng.randn(400, 5)
    y = (x[:, 0] + x[:, 1] > 0).astype(np.float32)
    ev = {}
    ds = lgb.Dataset(x, label=y, feature_name=[f"f{i}" for i in range(5)])
    bst = lgb.train(
        {"objective": "binary", "metric": "binary_logloss", "verbose": -1,
         "min_data_in_leaf": 5},
        ds, num_boost_round=5,
        valid_sets=[lgb.Dataset(x, label=y, reference=ds)],
        evals_result=ev, verbose_eval=False,
    )
    return bst, ev


def test_plot_importance(small_booster):
    bst, _ = small_booster
    ax = lgb.plotting.plot_importance(bst)
    assert len(ax.patches) > 0
    assert ax.get_title() == "Feature importance"


def test_plot_metric(small_booster):
    _, ev = small_booster
    ax = lgb.plotting.plot_metric(ev)
    assert len(ax.lines) == 1


def test_create_tree_digraph_and_plot_tree(small_booster):
    bst, _ = small_booster
    g = lgb.plotting.create_tree_digraph(bst, 1, show_info=["split_gain"])
    assert "f" in g.source  # feature names appear
    ax = lgb.plotting.plot_tree(bst, 1)
    assert ax is not None


def test_pmml_export(small_booster, tmp_path):
    from lightgbm_tpu.pmml import model_to_pmml, pmml_from_model_file

    bst, _ = small_booster
    pmml = model_to_pmml(bst)
    assert pmml.startswith('<?xml version="1.0"')
    assert "<Segmentation" in pmml and "</PMML>" in pmml
    assert pmml.count("<Segment id=") == bst.num_trees
    # from a saved model file, like the reference script
    path = str(tmp_path / "m.txt")
    bst.save_model(path)
    pmml2 = pmml_from_model_file(path)
    assert pmml2.count("<Segment id=") == bst.num_trees


def test_prediction_early_stop(small_booster):
    from lightgbm_tpu.boosting.pred_early_stop import (
        create_prediction_early_stop_instance,
        predict_with_early_stop,
    )

    bst, _ = small_booster
    rng = np.random.RandomState(1)
    x = rng.randn(20, 5)
    full = bst.predict(x, raw_score=True)
    es = create_prediction_early_stop_instance("binary", round_period=1,
                                               margin_threshold=0.0)
    early = predict_with_early_stop(bst.boosting, x, es)[:, 0]
    # margin 0 stops after the first round on any nonzero row
    assert early.shape == full.shape
    es_none = create_prediction_early_stop_instance("none")
    none_pred = predict_with_early_stop(bst.boosting, x, es_none)[:, 0]
    np.testing.assert_allclose(none_pred, full, rtol=1e-5)


# ----------------------------------------------------------------------
# parser (ADVICE r1 asked for direct tests over all formats + side files)
# ----------------------------------------------------------------------
def test_sniff_formats(tmp_path):
    tsv = tmp_path / "a.tsv"
    tsv.write_text("1.0\t2.0\t3.0\n0.0\t1.0\t2.0\n")
    csv = tmp_path / "a.csv"
    csv.write_text("1.0,2.0,3.0\n0.0,1.0,2.0\n")
    svm = tmp_path / "a.svm"
    svm.write_text("1 0:2.0 2:3.0\n0 1:1.0\n")
    assert sniff_format(str(tsv))[0] == "tsv"
    assert sniff_format(str(csv))[0] == "csv"
    assert sniff_format(str(svm))[0] == "libsvm"


def test_load_tsv_with_label(tmp_path):
    p = tmp_path / "d.tsv"
    p.write_text("1.0\t5.0\t6.0\n0.0\t7.0\t8.0\n")
    X, y, w, g, names, li = load_text_file(str(p), Config())
    np.testing.assert_array_equal(y, [1.0, 0.0])
    np.testing.assert_array_equal(X, [[5.0, 6.0], [7.0, 8.0]])


def test_load_with_weight_and_group_columns(tmp_path):
    """Numeric weight/group specs are label-relative and shift past the
    label column (the ADVICE r1 translation fix)."""
    p = tmp_path / "d.csv"
    # cols: label, f0, weight, qid
    p.write_text("1,10,0.5,0\n0,20,1.5,0\n1,30,2.5,1\n")
    cfg = Config.from_params({"weight_column": "1", "group_column": "2"})
    X, y, w, g, names, li = load_text_file(str(p), cfg)
    np.testing.assert_array_equal(y, [1, 0, 1])
    np.testing.assert_allclose(w, [0.5, 1.5, 2.5])
    np.testing.assert_array_equal(g, [2, 1])  # qid runs 0,0,1
    np.testing.assert_array_equal(X.ravel(), [10, 20, 30])


def test_load_named_columns_with_header(tmp_path):
    p = tmp_path / "d.csv"
    p.write_text("lab,a,wt,b\n1,10,0.5,40\n0,20,1.5,50\n")
    cfg = Config.from_params(
        {"has_header": True, "label_column": "name:lab",
         "weight_column": "name:wt", "ignore_column": "name:b"}
    )
    X, y, w, g, names, li = load_text_file(str(p), cfg)
    np.testing.assert_array_equal(y, [1, 0])
    np.testing.assert_allclose(w, [0.5, 1.5])
    assert names == ["a"]
    np.testing.assert_array_equal(X.ravel(), [10, 20])


def test_side_files(tmp_path):
    p = tmp_path / "d.tsv"
    p.write_text("1\t5\t4\n0\t7\t3\n1\t9\t2\n")
    (tmp_path / "d.tsv.weight").write_text("0.1\n0.2\n0.3\n")
    (tmp_path / "d.tsv.query").write_text("2\n1\n")
    X, y, w, g, names, li = load_text_file(str(p), Config())
    np.testing.assert_allclose(w, [0.1, 0.2, 0.3])
    np.testing.assert_array_equal(g, [2, 1])


def test_libsvm_loading(tmp_path):
    p = tmp_path / "d.svm"
    p.write_text("1 0:1.5 2:2.5\n0 1:3.5\n")
    X, y, w, g, names, li = load_text_file(str(p), Config())
    np.testing.assert_array_equal(y, [1, 0])
    np.testing.assert_allclose(X, [[1.5, 0, 2.5], [0, 3.5, 0]])


def test_pred_early_stop_wired_into_predict():
    """pred_early_stop config keys drive Booster.predict: early-stopped
    predictions match full predictions for high-margin rows and the keys
    are no longer dead (predictor.hpp:24-120)."""
    import lightgbm_tpu as lgb

    rng = np.random.default_rng(3)
    X = rng.standard_normal((800, 6)).astype(np.float32)
    w = rng.standard_normal(6) * 3.0
    y = ((X @ w) > 0).astype(np.float32)  # separable -> large margins
    bst = lgb.train({"objective": "binary", "num_leaves": 15, "verbose": -1},
                    lgb.Dataset(X, label=y), 30)
    full = bst.predict(X[:50])
    bst.boosting.config.pred_early_stop = True
    bst.boosting.config.pred_early_stop_freq = 5
    bst.boosting.config.pred_early_stop_margin = 10.0
    es = bst.predict(X[:50])
    # high-margin rows: sign/class decisions identical, values close for
    # confident rows (stop only fires beyond the margin)
    assert np.array_equal(full > 0.5, es > 0.5)
    conf = np.abs(full - 0.5) > 0.45
    assert conf.any()
    np.testing.assert_allclose(es[conf], full[conf], atol=2e-2)
    # huge margin threshold => never stops => exactly equal
    bst.boosting.config.pred_early_stop_margin = 1e9
    never = bst.predict(X[:50])
    np.testing.assert_allclose(never, full, rtol=1e-6, atol=1e-7)


def test_convert_model_cpp_compiles_and_matches(tmp_path):
    """task=convert_model emits standalone C++ whose predictions match the
    Python predictor (GBDT::ModelToIfElse counterpart)."""
    import ctypes
    import shutil
    import subprocess

    if shutil.which("g++") is None:
        pytest.skip("no C++ toolchain")

    import lightgbm_tpu as lgb
    from lightgbm_tpu.cli import main as cli_main

    rng = np.random.default_rng(5)
    X = rng.standard_normal((1200, 5)).astype(np.float64)
    X[:30, 0] = 0.0  # exercise the zero/missing remap
    w = rng.standard_normal(5)
    y = (rng.random(1200) < 1 / (1 + np.exp(-(X @ w)))).astype(np.float32)
    bst = lgb.train({"objective": "binary", "num_leaves": 15, "verbose": -1},
                    lgb.Dataset(X, label=y), 5)
    model_path = str(tmp_path / "model.txt")
    bst.save_model(model_path)
    cpp_path = str(tmp_path / "pred.cpp")
    rc = cli_main(["task=convert_model", f"input_model={model_path}",
                   f"convert_model={cpp_path}"])
    assert not rc
    so_path = str(tmp_path / "pred.so")
    subprocess.run(["g++", "-O2", "-shared", "-fPIC", "-o", so_path, cpp_path],
                   check=True)
    lib = ctypes.CDLL(so_path)
    lib.Predict.argtypes = [ctypes.POINTER(ctypes.c_double), ctypes.POINTER(ctypes.c_double)]
    assert lib.GetNumClasses() == 1
    assert lib.GetNumFeatures() == 5
    expect = bst.predict(X[:64])
    out = np.zeros(1, np.float64)
    got = np.zeros(64)
    for i in range(64):
        row = np.ascontiguousarray(X[i], np.float64)
        lib.Predict(row.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
                    out.ctypes.data_as(ctypes.POINTER(ctypes.c_double)))
        got[i] = out[0]
    # the Python predictor accumulates in float32 on device; the C code
    # is full float64 — tolerance covers the f32 rounding
    np.testing.assert_allclose(got, expect, rtol=2e-6, atol=2e-7)


def test_scipy_sparse_input():
    """CSR/CSC matrices are accepted (densified; LGBM_DatasetCreateFromCSR
    counterpart at the python surface)."""
    scipy = pytest.importorskip("scipy.sparse")
    import lightgbm_tpu as lgb

    rng = np.random.default_rng(0)
    dense = rng.standard_normal((500, 8)) * (rng.random((500, 8)) < 0.3)
    y = rng.standard_normal(500).astype(np.float32)
    for conv in (scipy.csr_matrix, scipy.csc_matrix):
        bst = lgb.train({"objective": "regression", "num_leaves": 7, "verbose": -1},
                        lgb.Dataset(conv(dense), label=y), 3)
        p_sparse = bst.predict(conv(dense[:50]))
        p_dense = bst.predict(dense[:50])
        np.testing.assert_allclose(p_sparse, p_dense, rtol=1e-7)
