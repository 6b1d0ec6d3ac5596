"""The multiclass cell's own pieces: data_covtype.py's tables, multiclass_ref.py
(the benchmark's float64 softmax objective) against the program's, the
gradient check's limit between the sound reading and a bfloat16 control,
harness/multi_ops.py and the three readers on a saved record (and their
silence, None and no raise, on a record without the counters or off the chip),
the cell's files, and the cell's rehearsal."""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

import data
import data_covtype
import multiclass_ref
from harness import multi_ops, phase_reduce
from harness.spec import BENCH_DIR, ROOT, Spec, load_module

CELL = "covtype.train-softmax7"
READERS = ("multi_update_ms_per_iter", "multi_update_mxu_roofline", "multi_update_hbm_roofline")
# the record the driver keeps beside a traced run's trace (`record.json`), as a
# chip run of the cell left it (seed 3844000079, TPU v5 lite) and cut to what
# the readers read (PERF.md section 5)
SAVED = os.path.join(os.path.dirname(__file__), "fixtures", "covtype_traced_record.json")


# -- the generator --------------------------------------------------------------
@pytest.fixture(scope="module")
def table():
    return data_covtype.make_covtype(300_000, 44, label_seed=20261019)


def test_the_table_has_the_published_schema_and_shares(table):
    X, y = table
    assert X.shape == (300_000, 54) and X.dtype == np.float32
    lo = np.asarray([c[1] for c in data_covtype.NUMERIC])
    hi = np.asarray([c[2] for c in data_covtype.NUMERIC])
    num = X[:, :10]
    assert np.all(num >= lo) and np.all(num <= hi) and np.all(num == np.rint(num))
    assert num[:, 0].min() == 1859 and num[:, 0].max() == 3858  # elevation spans its range
    wild, soil = X[:, 10:14], X[:, 14:]
    assert np.all(wild.sum(1) == 1) and np.all(soil.sum(1) == 1)
    assert set(np.unique(X[:, 10:])) == {0.0, 1.0}
    assert np.all(np.diff(soil.mean(0)[:5]) < 0)  # Zipf: rank = column
    share = np.bincount(y.astype(int), minlength=7) / len(y)
    assert np.all(np.abs(share - data_covtype.CLASS_SHARES) < 0.01)


def test_same_seeds_same_table_byte_for_byte_whatever_the_threads(monkeypatch):
    monkeypatch.setattr(data_covtype, "BLOCK_ROWS", 1024)  # five blocks
    X1, y1 = data_covtype.make_covtype(5000, 5, label_seed=9)
    monkeypatch.setattr(os, "cpu_count", lambda: 1)
    X2, y2 = data_covtype.make_covtype(5000, 5, label_seed=9)
    assert X1.tobytes() == X2.tobytes() and y1.tobytes() == y2.tobytes()
    X3, y3 = data_covtype.make_covtype(5000, 5, label_seed=10)
    X4, _ = data_covtype.make_covtype(5000, 6, label_seed=9)
    assert X3.tobytes() == X1.tobytes() and not np.array_equal(y1, y3)
    assert not np.array_equal(X1, X4)


# -- multiclass_ref against the program -------------------------------------------
def _scores(n, seed, spread):
    rng = np.random.default_rng(seed)
    return np.zeros((7, n), np.float32) if not spread else \
        (rng.standard_normal((7, n)) * 2).astype(np.float32)


@pytest.mark.parametrize("spread", [False, True])
def test_gradients_equal_the_programs_softmax_objective(table, spread):
    import jax.numpy as jnp

    from lightgbm_tpu.config import Config
    from lightgbm_tpu.objective.multiclass import MulticlassSoftmax

    y = table[1][:20_000]
    obj = MulticlassSoftmax(Config.from_params({"objective": "multiclass", "num_class": 7}))
    obj.init(types.SimpleNamespace(label=y, weights=None), len(y))
    score = _scores(len(y), 3, spread)
    g, h = (np.asarray(v) for v in obj.get_gradients(jnp.asarray(score)))
    ref = multiclass_ref.gradients(score, y)
    assert np.max(np.abs(g - ref["g"])) < 1e-6 and np.max(np.abs(h - ref["h"])) < 1e-6
    assert np.allclose(ref["g"].sum(axis=0), 0.0, atol=1e-12)  # the planes of one softmax
    if not spread:  # iteration 0: every class at 1/K
        assert np.allclose(ref["h"], 2 / 7 * 6 / 7)


def test_class_trees_add_to_their_class_and_the_macro_auc_averages():
    scores = np.zeros((3, 4))
    out = multiclass_ref.add_class_trees(scores, [np.ones(4), 2 * np.ones(4), 3 * np.ones(4),
                                                 10 * np.ones(4)])
    assert out[:, 0].tolist() == [11.0, 2.0, 3.0] and not scores.any()
    y = np.asarray([0, 1, 2, 0, 1, 2])
    prob = np.eye(3)[:, y] * 0.9 + 0.05
    assert multiclass_ref.macro_auc(y, prob, data.auc) == 1.0
    assert multiclass_ref.error(y, prob) == 0.0
    assert multiclass_ref.logloss(y, prob) == pytest.approx(-np.log(0.95))
    prob[:, 0] = [0.05, 0.9, 0.05]  # one row of class 0 now ties class 0's four negatives
    assert multiclass_ref.macro_auc(y, prob, data.auc) == pytest.approx((0.75 + 1 + 1) / 3)


# -- what the gradient check's limit is held against -------------------------------
@pytest.mark.parametrize("spread", [False, True])
def test_the_gradient_limit_lies_between_the_sound_reading_and_the_control(table, spread):
    """The program's row-local softmax gradients (what the update kernel calls,
    jitted in float32) pass the cell's check with room, on the cell's own
    labels, at iteration 0's scores and at spread ones; the same planes rounded
    to bfloat16 fail it.  PERF.md section 6 has the readings."""
    import jax
    import jax.numpy as jnp
    import ml_dtypes

    from lightgbm_tpu.config import Config
    from lightgbm_tpu.objective.multiclass import MulticlassSoftmax

    driver = load_module("drivers", "train_multiclass")
    spec = Spec()
    atol = spec.mix(spec.cell(CELL)["traffic"])["grad_atol"]
    y = table[1][:50_000]
    score = _scores(len(y), 4, spread)
    obj = MulticlassSoftmax(Config.from_params({"objective": "multiclass", "num_class": 7}))
    g, h = jax.jit(obj.gradients_rowwise_all)(jnp.asarray(score), jnp.asarray(y), None)
    ref = multiclass_ref.gradients(score, y)
    sound = driver._gradient_verdict(np.asarray(g), np.asarray(h), ref, atol)
    assert sound["outside"] == 0 and max(sound["worst_g"], sound["worst_h"]) < atol / 10

    def bf16(v):
        return v.astype(ml_dtypes.bfloat16).astype(np.float64)

    rounded = driver._gradient_verdict(bf16(ref["g"]), bf16(ref["h"]), ref, atol)
    assert rounded["outside"] > len(y) // 2 and rounded["worst_g"] > 10 * atol


def test_a_class_tree_is_compared_up_to_its_own_parting():
    """The parity compares each class tree on its own (`_class_parting`): the
    splits equal before a parting, those under another label, and None where
    the two trees send every row alike; `_parity` goes on to the next class
    after a parting."""
    driver = load_module("drivers", "train_multiclass")
    m = [np.asarray(v) for v in ([1, 0, 1], [0, 1, 0], [1, 1, 0], [0, 0, 1])]
    a = [(m[0], m[1]), (m[2], m[3]), (m[0], m[1])]
    same = [[(0, 1), (2, 3), (0, 1)]] * 2
    assert driver._class_parting(a, list(a), same) == (None, 3, 0)
    moved = [[(0, 1), (2, 3), (0, 1)], [(0, 1), (5, 3), (0, 1)]]
    assert driver._class_parting(a, list(a), moved) == (None, 3, 1)
    b = [a[0], (m[3], m[2])]
    assert driver._class_parting(a, b, moved) == (1, 1, 0)
    assert driver._class_parting(a, a[:2], moved) == (2, 2, 1)  # one tree has a split more


# -- harness/multi_ops.py and the readers -----------------------------------------
@pytest.fixture
def saved(monkeypatch):
    """The saved record, with the phase table its run reduced standing in for
    this process's."""
    with open(SAVED) as f:
        rec = json.load(f)
    monkeypatch.setattr(phase_reduce, "table", lambda: rec["phase_table"])
    return rec


def test_the_three_readers_on_the_saved_record(saved):
    c = multi_ops.counters(saved)
    spans = [s for s in saved["program_spans"] if s["name"] == "trees_from_records"]
    assert c["classes"] == 7 and c["update_value_rows"] == 48 and c["channels"] == 40
    assert c["iters"] == sum(s["trees"] for s in spans) // 7 == saved["iters"] == 2
    rows, cells = saved["rows"], c["hist_cells"]
    assert (rows, cells) == (4_648_096, 768)  # 12 columns (2 of them bundles) of 64 lanes
    kernel = multi_ops.kernel_seconds(saved)
    busy = saved["phase_table"]["phases"]["multi_update"]["busy_s"]
    assert 0 < kernel <= busy  # the kernel and the histograms' re-sum after it
    got = {name: load_module("layer_metrics", name).read(saved) for name in READERS}
    assert got["multi_update_ms_per_iter"] == pytest.approx(1e3 * busy / 2)
    flops = 2.0 * 2 * rows * 48 * cells
    hbm = 2 * (4.0 * rows * (40 + 14) + 4.0 * 48 * cells)
    peaks = saved["peaks"]
    assert got["multi_update_mxu_roofline"] == pytest.approx(
        100 * flops / kernel / peaks["bf16_flops_per_s"])
    assert got["multi_update_hbm_roofline"] == pytest.approx(
        100 * hbm / kernel / peaks["hbm_bytes_per_s"])
    assert all(0 < got[name] < 100 for name in READERS[1:])


def test_a_program_without_the_counters_or_the_word_reads_nothing(saved, monkeypatch):
    from lightgbm_tpu.obs import phases

    old = dict(saved, program_spans=[{k: v for k, v in s.items()
                                      if k not in ("classes", "update_value_rows")}
                                     for s in saved["program_spans"]])
    monkeypatch.delattr(phases, "MULTI_UPDATE", raising=False)
    assert {name: load_module("layer_metrics", name).read(old) for name in READERS} == \
        dict.fromkeys(READERS)
    rehearsal = dict(saved, device=None)
    monkeypatch.setattr(phase_reduce, "table", lambda: None)
    assert {name: load_module("layer_metrics", name).read(rehearsal) for name in READERS} == \
        dict.fromkeys(READERS)
    binary = dict(saved, program_spans=[dict(s, classes=1, update_value_rows=8)
                                        for s in saved["program_spans"]])
    assert multi_ops.counters(binary) is None
    assert multi_ops.counters({k: v for k, v in saved.items() if k != "rows"}) is None


def test_the_cell_and_its_files_agree():
    spec = Spec()
    cell = spec.cell(CELL)
    cfg, mix = spec.config(cell["config"]), spec.mix(cell["traffic"])
    higgs = spec.config("higgs")
    assert cell["chips"] == cfg["chips"] == 1 and cfg["trainer"] == "PartitionedTrainer"
    assert {k: cfg["params"][k] for k in higgs["params"] if k != "objective"} == \
        {k: v for k, v in higgs["params"].items() if k != "objective"}
    assert (cfg["params"]["objective"], cfg["params"]["num_class"]) == ("multiclass", 7)
    assert cfg["reduced"] == ["num_data", "num_iterations"]
    assert cfg["published"] == {"num_data": 581_012, "num_features": 54, "num_class": 7}
    assert cfg["assumed"]["num_iterations"].startswith("500,")
    assert mix["driver"] == "train_multiclass" and mix["classes"] == 7
    assert mix["rows_per_chip"] == 8 * cfg["published"]["num_data"]
    assert mix["features"] == data_covtype.COLUMNS == 54 and data_covtype.CLASSES == 7
    assert sum(data_covtype.CLASS_COUNTS) == data_covtype.PUBLISHED_ROWS == 581_012
    assert mix["heldout_rows"] == -(-581_012 // 5) and mix["chunk_iters"] == 2
    mine = {m["name"] for m in spec.metrics("per_layer", CELL)}
    assert set(READERS) <= mine and "level_phase_ms_per_iter" in mine
    assert {"pack_upload_s", "bundle_expand_ms_per_iter", "bundle_cols", "tail_splits_per_iter",
            "split_stream_hbm_roofline"} <= mine and "find_bundles_s" not in mine
    for other in ("higgs.train-21m", "mslr.train-2m-lambdarank"):
        assert not set(READERS) & {m["name"] for m in spec.metrics("per_layer", other)}


def test_the_cells_rehearsal_ends_correct(tmp_path):
    env = dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu")
    for k in ("LIGHTGBM_TPU_PGROW", "LIGHTGBM_TPU_TRACE", "XLA_FLAGS"):
        env.pop(k, None)
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload", CELL,
         "--seed", "3800044123", "--seconds", "1", "--trace", "1", "--rehearse"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["failed"] == 0 and result["metrics"] == {}
    assert not [ln for ln in lines if ln.startswith("[bench] FAILED")]
    for ok in ("trained on PartitionedTrainer over 1 device(s) with 7 classes",
               "predict's probabilities are multiclass_ref's softmax",
               "the chunk program's 7 g and 7 h planes at iteration",
               "the chunk programs' 7 score planes at 2048 rows equal the sum of the model's",
               "parity pair is fused trainer vs mask grower",
               "the first iteration's 7 trees split-for-split equal to the reference"):
        assert any(f"[bench] ok: {ok}" in ln for ln in lines), ok
    found = next(ln for ln in lines if "found: [" in ln)
    for name in ("multi_update_ms_per_iter", "level_phase_ms_per_iter", "pack_upload_s",
                 "bundle_cols", "tail_splits_per_iter"):
        assert f"'{name}'" in found, name
    # the record the readers read, kept beside the trace
    kept = os.path.join(ROOT, "benchmarks", ".cache", "trace", CELL, "record.json")
    with open(kept) as f:
        rec = json.load(f)
    assert rec["rows"] == 4096 and rec["driver"] == "train"
    spans = [s for s in rec["program_spans"] if s["name"] == "trees_from_records"]
    assert spans and {s["classes"] for s in spans} == {7}
    assert {s["update_value_rows"] for s in spans} == {48}


def test_the_saved_record_is_what_a_chip_run_keeps():
    with open(SAVED) as f:
        rec = json.load(f)
    assert rec["driver"] == "train" and rec["peaks"]["hbm_bytes_per_s"] == 819e9
    assert any(k.startswith("update_multi_and_hists ") for k in rec["device"]["leaf_op_s"])
    spans = [s for s in rec["program_spans"] if s["name"] == "trees_from_records"]
    assert [(s["trees"], s["classes"], s["update_value_rows"], s["bundle_cols"])
            for s in spans] == [(14, 7, 48, 12)]
