"""The one-hot cell's own pieces: data_onehot.py's tables, the fixed training
table of drivers/train_onehot.py, harness/split_ops.py on shapes, the five new
readers on a hand-made record (and their silence, None and no raise, on a
record of a program older than the counters, or off the chip), and the cell's
rehearsal."""

import json
import os
import subprocess
import sys
from unittest import mock

import numpy as np
import pytest
import scipy.sparse

import data
import data_onehot
from harness import phase_reduce, split_ops
from harness.spec import BENCH_DIR, ROOT, Spec, load_module

CELL = "expo.train-21m-onehot"
FIELDS = (12, 31, 7, 24, 20, 298, 298, 10)
READERS = ("bundle_expand_ms_per_iter", "bundle_cols", "ingest_s", "tail_splits_per_iter",
           "split_stream_hbm_roofline")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# the cell's shape: 10 bundle columns at a 256-bin pitch, 16 channel rows
COUNTS = dict(tail_splits=400, tail_rows=500_000_000, hist_cells=2560, channels=16,
              bundle_cols=10)


# -- the generator ------------------------------------------------------------
def test_eight_nonzeros_a_row_one_in_every_field():
    X, y = data_onehot.make_onehot(20_000, 3, FIELDS)
    assert scipy.sparse.issparse(X) and X.format == "csr" and X.dtype == np.float32
    assert X.shape == (20_000, 700) and X.nnz == 8 * 20_000 and set(np.unique(y)) == {0.0, 1.0}
    assert np.array_equal(np.diff(X.indptr), np.full(20_000, 8)) and np.all(X.data == 1.0)
    cols = X.indices.reshape(-1, 8)
    edges = np.cumsum((0,) + FIELDS)
    assert np.all(cols >= edges[:-1]) and np.all(cols < edges[1:])  # ascending, so canonical
    seen = [len(np.unique(cols[:, k])) for k in range(8)]
    assert seen[:5] == [12, 31, 7, 24, 20] and seen[7] == 10 and 150 < seen[5] <= 298


def test_zipf_hubs_and_near_uniform_calendar():
    X, _ = data_onehot.make_onehot(200_000, 5, FIELDS)
    share = np.bincount(X.indices, minlength=700) / 200_000
    origin = share[94:392]
    h298 = (1.0 / np.arange(1, 299)).sum()
    assert abs(origin[0] - 1 / h298) < 0.01 and abs(origin[9] - 0.1 / h298) < 0.003
    assert np.all(np.abs(share[:12] - 1 / 12) < 0.004)          # month
    assert share[12 + 30] < 0.65 * share[12]                     # the 31st: 7 months of 12


def test_same_seed_same_table_whatever_the_threads(monkeypatch):
    monkeypatch.setattr(data_onehot, "BLOCK_ROWS", 4096)  # five blocks
    X1, y1 = data_onehot.make_onehot(20_000, 3, FIELDS)
    monkeypatch.setattr(os, "cpu_count", lambda: 1)
    X2, y2 = data_onehot.make_onehot(20_000, 3, FIELDS)
    X3, _ = data_onehot.make_onehot(20_000, 4, FIELDS)
    assert np.array_equal(X1.indices, X2.indices) and np.array_equal(y1, y2)
    assert not np.array_equal(X1.indices, X3.indices)


def test_label_seed_redraws_the_labels_over_the_same_rows_and_the_task_is_learnable():
    X1, y1 = data_onehot.make_onehot(100_000, 3, FIELDS)
    Xa, ya = data_onehot.make_onehot(100_000, 3, FIELDS, label_seed=11)
    _, yb = data_onehot.make_onehot(100_000, 3, FIELDS, label_seed=11)
    _, yc = data_onehot.make_onehot(100_000, 3, FIELDS, label_seed=12)
    assert np.array_equal(X1.indices, Xa.indices) and np.array_equal(ya, yb)
    assert 0.3 < np.mean(ya != yc) < 0.5 and 0.3 < np.mean(ya != y1) < 0.5
    w = data_onehot.task_weights(FIELDS)
    cats = X1.indices.reshape(-1, 8) - np.cumsum((0,) + FIELDS[:-1])
    margin = sum(wf[c] for wf, c in zip(w["fields"], cats.T))
    margin += w["pairs"][0][cats[:, 5], cats[:, 3]] + w["pairs"][1][cats[:, 4], cats[:, 0]]
    scale = data_onehot._margin_std(w, data_onehot.field_probs(FIELDS, 1.0))
    assert abs(margin.std() / scale - 1) < 0.05
    assert 0.74 < data.auc(y1, margin) < 0.80  # the margin is the best score there is


# -- the driver's table: fixed whole, whatever --seed --------------------------
def test_training_labels_are_the_same_for_two_seeds_and_heldout_rows_differ():
    driver = load_module("drivers", "train_onehot")
    mix = {**Spec().mix("train-21m-onehot"), "rows_per_chip": 30_000, "heldout_rows": 5_000}
    assert mix["labels_seed"] == 20261003 and mix["features_seed"] == 7

    def tables(seed):  # what run() draws for a --seed: the training table, then the held-out rows
        return (driver._draw(mix, mix["rows_per_chip"], mix["features_seed"], mix["labels_seed"]),
                driver._draw(mix, mix["heldout_rows"], seed + 1))

    (Xa, ya), (Ha, ha) = tables(3600000011)
    (Xb, yb), (Hb, hb) = tables(3600000029)
    assert np.array_equal(Xa.indices, Xb.indices) and np.array_equal(ya, yb)
    assert not np.array_equal(Ha.indices, Hb.indices) and not np.array_equal(ha, hb)
    # and the cached file's name takes in both of the mix's seeds and no other
    src = open(os.path.join(BENCH_DIR, "drivers", "train_onehot.py")).read()
    table_fn = src[src.index("def _table("):src.index("def _parity(")]
    assert 'mix["labels_seed"]' in table_fn and "run.seed" not in table_fn


def test_the_drivers_first_import_is_the_programs_sparse_ingest():
    """A program without io/sparse.py must fail at once, before any table is
    made: the parent of PR 36 would densify 21M x 700 float64."""
    import ast

    tree = ast.parse(open(os.path.join(BENCH_DIR, "drivers", "train_onehot.py")).read())
    first = next(n for n in tree.body if isinstance(n, (ast.Import, ast.ImportFrom)))
    assert isinstance(first, ast.ImportFrom) and first.module == "lightgbm_tpu.io"
    assert [a.name for a in first.names] == ["sparse"]


# -- harness/split_ops.py and the readers --------------------------------------
def _span(**counts):
    return {"name": "trees_from_records", "trees": 4, "splits": 1016, **counts}


def _record(spans, seconds=2.0, iters=4, setup=()):
    return {"driver": "train", "chips": 1, "iters": iters, "peaks": PEAKS,
            "program_spans": spans + [{"name": "chunk_program"}],
            "setup_program_spans": list(setup),
            "device": {"leaf_op_s": {"split_stream (s32[16,21001024], f32[16,2560], s32[1])": seconds,
                                     "level_stream (s32[16,21001024], f32[256,16,2560])": 9.0}}}


def test_split_stream_bytes_from_the_counters():
    rec = _record([_span(**COUNTS), _span(**COUNTS)])
    c = split_ops.counters(rec)
    assert c == {"tail_splits": 800, "tail_rows": 1_000_000_000, "hist_cells": 2560,
                 "channels": 16}
    # a row's 16 channels read and written; two (16, 2560) float32 blocks a tail split
    assert split_ops.hbm_bytes(c) == 2 * 4 * 16 * 1_000_000_000 + 2 * 4 * 16 * 2560 * 800
    assert split_ops.kernel_seconds(rec) == 2.0  # split_stream alone, not level_stream
    share = split_ops.share(rec)
    assert share == pytest.approx(100 * split_ops.hbm_bytes(c) / 2.0 / 819e9) and 0 < share < 100


@pytest.fixture
def phase_table(monkeypatch):
    tab = {"phases": {"level_phase": {"busy_s": 4.0}, "split_scan": {"busy_s": 0.1},
                      "split_scan/bundle_expand": {"busy_s": 0.25},
                      "replay/bundle_expand": {"busy_s": 0.15},
                      "update_root_hist/bundle_expand": {"busy_s": 0.02}}}
    monkeypatch.setattr(phase_reduce, "table", lambda: tab)
    return tab


def test_the_five_readers_on_a_hand_made_record(phase_table):
    ingest = [{"name": "sparse_ingest", "dur_s": 30.5}, {"name": "csr_bin", "dur_s": 2.0}]
    rec = _record([_span(**COUNTS)], setup=ingest)
    got = {name: load_module("layer_metrics", name).read(rec) for name in READERS}
    assert got["bundle_expand_ms_per_iter"] == pytest.approx(1e3 * 0.42 / 4)
    assert got["bundle_cols"] == 10 and got["ingest_s"] == 30.5
    assert got["tail_splits_per_iter"] == 100.0
    assert got["split_stream_hbm_roofline"] == pytest.approx(
        100 * (8 * 16 * 5e8 + 8 * 16 * 2560 * 400) / 2.0 / 819e9)
    assert load_module("layer_metrics", "ingest_s").read(_record([_span(**COUNTS)])) == 0.0  # warm
    # the enclosing phases' own readers still count the expansion (obs/phases.ENCLOSING)
    assert phase_reduce.phase_total(phase_table, "level_phase") == pytest.approx(4.35)


def test_a_program_without_the_counters_reads_nothing(monkeypatch):
    old = _record([_span()])
    del old["setup_program_spans"]
    monkeypatch.setattr(phase_reduce, "table", lambda: None)
    assert split_ops.counters(old) is None and split_ops.share(old) is None
    assert {name: load_module("layer_metrics", name).read(old) for name in READERS} == \
        dict.fromkeys(READERS)
    rehearsal = {"driver": "train", "iters": 2, "program_spans": [_span(**COUNTS)],
                 "setup_program_spans": [], "device": None}
    got = {name: load_module("layer_metrics", name).read(rehearsal) for name in READERS}
    assert got == {"bundle_expand_ms_per_iter": None, "bundle_cols": 10, "ingest_s": 0.0,
                   "tail_splits_per_iter": 200.0, "split_stream_hbm_roofline": None}
    # a window without a tail split has no share
    assert split_ops.share(_record([_span(**{**COUNTS, "tail_splits": 0, "tail_rows": 0})])) is None


def test_the_cell_and_its_files_agree():
    spec = Spec()
    cell = spec.cell(CELL)
    cfg, mix = spec.config(cell["config"]), spec.mix(cell["traffic"])
    higgs, like = spec.config("higgs"), spec.mix("train-21m")
    assert cell["chips"] == cfg["chips"] == 1 and cfg["trainer"] == "PartitionedTrainer"
    assert cfg["params"] == higgs["params"] and cfg["reduced"] == ["num_data", "num_iterations"]
    assert cfg["published"] == {"num_data": 11_000_000, "num_heldout": 1_000_000,
                                "num_features": 700, "num_iterations": 500}
    assert mix["driver"] == "train_onehot" and mix["heldout_rows"] == cfg["published"]["num_heldout"]
    assert tuple(int(c) for c in mix["fields"].split(",")) == FIELDS
    assert sum(FIELDS) == mix["features"] == cfg["published"]["num_features"]
    assert len(FIELDS) == mix["nonzeros_per_row"] == 8 and mix["zipf_exponent"] == 1.0
    same = ("rows_per_chip", "features_seed", "chunk_iters", "warmup_iters", "auc_iters",
            "trace_chunks", "parity_rows", "parity_iters")
    assert {k: mix[k] for k in same} == {k: like[k] for k in same}
    mine = {m["name"] for m in spec.metrics("per_layer", CELL)}
    assert set(READERS) <= mine and "pack_upload_s" in mine and "eval_ms_per_iter" not in mine
    assert not set(READERS) & {m["name"] for m in spec.metrics("per_layer", "higgs.train-21m")}


def test_the_cells_rehearsal_ends_correct():
    env = dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu")
    for k in ("LIGHTGBM_TPU_PGROW", "LIGHTGBM_TPU_TRACE", "XLA_FLAGS"):
        env.pop(k, None)
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload", CELL,
         "--seed", "3600000033", "--seconds", "1", "--trace", "1", "--rehearse"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["failed"] == 0 and result["metrics"] == {}
    assert result["device"]["platform"] == "cpu"
    assert not [ln for ln in lines if ln.startswith("[bench] FAILED")]
    for ok in ("trained on PartitionedTrainer over 1 device(s)", "the trainer streams the bundles",
               "no (N, F) bin matrix on the host or on the device",
               "parity pair is fused trainer on bundles vs mask grower on the dense unbundled",
               "first tree split-for-split equal to the reference"):
        assert any(f"[bench] ok: {ok}" in ln for ln in lines), ok
    found = next(ln for ln in lines if "found: [" in ln)
    for name in ("bundle_expand_ms_per_iter", "bundle_cols", "ingest_s", "tail_splits_per_iter",
                 "pack_upload_s", "level_phase_ms_per_iter"):
        assert f"'{name}'" in found, name
