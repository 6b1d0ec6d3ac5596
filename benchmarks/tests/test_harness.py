import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from harness import cli, roofline
from harness.measure import Outcome, Window, span_total
from harness.spec import BENCH_DIR, ROOT, Spec, SpecError, load_module

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}

TOY_DRIVER = '''
from harness.measure import Outcome

def run(run):
    with run.spans.span("toy_setup"):
        pass
    record = {"driver": "toy", "answer": run.mix["answer"] * run.config["factor"]} if run.trace else None
    return Outcome(values={"setup_s": run.setup_s, "train_s_per_iter": 1.0, "heldout_auc": 0.7},
                   attempted=3, failed=0, checks=[("toy ran", True)], memory_peak_bytes=0,
                   record=record)
'''
TOY_METRIC = '''
LAYER = "toy_layer"
MOVES = "setup_s"
SOURCE = "program_counter"
DRIVERS = ("toy",)

def read(record):
    return record["answer"]
'''


@pytest.fixture
def toy_tree(tmp_path):
    """A copy of the benchmark with one configuration, mix, driver, per-layer
    metric and cell ADDED as new files and entries; no file that was there is
    edited."""
    bench = tmp_path / "benchmarks"
    shutil.copytree(BENCH_DIR, bench, ignore=shutil.ignore_patterns(".cache", "tests", "__pycache__"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    (bench / "configs" / "toy.json").write_text(json.dumps({"factor": 6}))
    (bench / "traffic" / "toy-mix.json").write_text(
        json.dumps({"driver": "toy", "answer": 7, "rehearse": {}}))
    (bench / "drivers" / "toy.py").write_text(TOY_DRIVER)
    (bench / "layer_metrics" / "toy_metric.py").write_text(TOY_METRIC)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    doc["configs"].append({"name": "toy", "source": "none", "file": "benchmarks/configs/toy.json",
                           "reduced": [], "why": "test"})
    doc["workloads"].append({"name": "toy.cell", "config": "toy", "traffic": "toy-mix",
                             "chips": 1, "why": "test"})
    doc["per_layer"].append({"name": "toy_metric", "unit": "n", "better": "higher",
                             "source": "program_counter", "layer": "toy_layer",
                             "moves": "setup_s", "workloads": ["toy.cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    yield tmp_path
    assert all(p.read_bytes() == b for p, b in before.items())


def test_new_files_are_found_and_run_without_an_edit(toy_tree):
    env = dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(toy_tree / "benchmarks" / "run.py"), "--workload", "toy.cell",
         "--seconds", "0.1", "--trace", "1", "--rehearse"],
        capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = out.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert set(result) == RESULT_KEYS
    assert result["correct"] is True and result["attempted"] == 3
    assert result["device"]["platform"] == "cpu" and result["metrics"] == {}
    assert any("found: ['toy_metric']" in ln for ln in lines)


def test_layer_reader_of_a_new_file_reads_the_record(toy_tree):
    spec = Spec(root=str(toy_tree), bench_dir=str(toy_tree / "benchmarks"))
    cell = spec.cell("toy.cell")
    assert spec.config("toy") == {"factor": 6} and spec.mix("toy-mix")["answer"] == 7
    got = cli.layer_metrics(spec, cell, {"setup_s"}, {"driver": "toy", "answer": 42})
    assert got == {"toy_metric": {"value": 42.0, "unit": "n"}}


def test_unknown_names_are_errors(toy_tree):
    spec = Spec(root=str(toy_tree), bench_dir=str(toy_tree / "benchmarks"))
    with pytest.raises(SpecError):
        spec.cell("no.such-cell")
    with pytest.raises(SpecError):
        load_module("drivers", "no_such_driver")


def _outcome(record=None):
    return Outcome(values={"setup_s": 30.5, "train_s_per_iter": 1.25, "heldout_auc": 0.73},
                   attempted=5, failed=0, checks=[("a", True), ("b", True)],
                   memory_peak_bytes=4_500_000_000, record=record)


def test_result_line_has_exactly_the_contracts_keys():
    spec = Spec()
    cell = spec.cell("higgs.train-21m")
    result, found = cli.build_result(spec, cell, False, False, TPU, _outcome())
    assert set(result) == RESULT_KEYS
    assert set(result["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert found == sorted(m["name"] for m in spec.metrics("end_to_end", cell["name"]))
    assert result["metrics"]["train_s_per_iter"] == {"value": 1.25, "unit": "s"}
    json.dumps(result)
    failing = _outcome()
    failing.checks.append(("c", False))
    assert cli.build_result(spec, cell, False, False, TPU, failing)[0]["correct"] is False


def test_traced_result_line_reports_per_layer_metrics_and_breakdown():
    spec = Spec()
    cell = spec.cell("higgs.train-21m")
    device = {"window_s": 8.0, "busy_s": 6.0, "launches": 4000.0, "mosaic_s": 3.0,
              "collective_s": 0.0, "collective_exposed_s": 0.0,
              "op_self_s": {"while": 2.0, "fusion": 3.0}, "leaf_op_s": {"fusion": 3.0},
              "op_launches": {"fusion": 10.0}, "idle_gaps_s": {"records_fetch": 1.5}}
    record = {"driver": "train", "chips": 1, "iters": 8, "laps": 2, "window_s": 8.1,
              "window_hbm_bytes": 4_400_000_000,
              "bench_spans": [{"name": "dataset", "dur_s": 0.5}, {"name": "booster", "dur_s": 4.0}],
              "program_spans": [{"name": "chunk_program", "dur_s": 0.01},
                                {"name": "records_fetch", "dur_s": 7.99}],
              "compile_setup": {"backend_compile_secs": 0.6, "cache_misses": 0},
              "memory_peak_bytes": 4_500_000_000, "stream_bytes_per_iter": 819e9 * 3.0 / 8 / 4,
              "peaks": roofline.peaks("TPU v5 lite"), "device": device}
    result, _ = cli.build_result(spec, cell, True, False, TPU, _outcome(record))
    assert set(result) == RESULT_KEYS | {"breakdown"}
    assert result["device"]["busy_s"] == 6.0 and result["device"]["window_s"] == 8.0
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert m["bin_s"] == 0.5 and m["pack_upload_s"] == 4.0
    assert m["chunk_device_wait_ms_per_iter"] == pytest.approx(1000.0)
    assert m["driver_host_ms_per_iter"] == pytest.approx(12.5)
    assert m["host_ms_per_chunk"] == pytest.approx(50.0)
    assert m["window_hbm_bytes"] == 4.4e9 and m["peak_hbm_bytes"] == 4.5e9
    assert m["device_idle_share"] == pytest.approx(25.0)
    assert m["pallas_time_share"] == pytest.approx(50.0)
    assert m["top_op_share"] == pytest.approx(50.0)
    assert m["stream_kernels_roofline"] == pytest.approx(25.0)
    assert m["device_launches_per_iter"] == pytest.approx(500.0)
    # no validation set: this reader finds nothing and is left out
    assert "eval_ms_per_iter" not in m
    assert result["breakdown"] == {"device_ops": [["fusion", 3.0], ["while", 2.0]],
                                   "idle_gaps": [["records_fetch", 1.5]]}


def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload", "higgs.train-21m",
         "--seconds", "1"], capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode == cli.NO_TPU
    assert not [ln for ln in out.stdout.splitlines() if ln.startswith("{")]
    assert "needs a TPU" in out.stderr


def test_peaks_and_stream_bytes():
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")
    assert roofline.packed_channels(28) == (7, 16)
    # (7 words + 8 band values) * 4 B * rows + 2 * 16 channels * 4 B * parent rows
    assert roofline.train_stream_bytes(1000, 28, 3000) == 60_000 + 384_000


def test_window_counts_laps_and_failures():
    w = Window(seconds=0.0)
    w.start()
    w.lap(4)
    w.lap(4, ok=False)
    assert w.over and w.attempted == 2 and w.failed == 1 and w.units == 4 and len(w.laps) == 1
    assert span_total([{"name": "a", "dur_s": 1.0}, {"name": "b", "dur_s": 2.0}], "a", "b") == 3.0
    assert span_total([], "a") is None


def test_benchmark_json_and_the_files_it_names_agree():
    spec = Spec()
    doc = spec.doc
    assert set(doc) == {"command", "paths", "run_seconds", "configs", "workloads",
                        "end_to_end", "per_layer"}
    names = [e["name"] for k in ("configs", "workloads", "end_to_end", "per_layer") for e in doc[k]]
    assert len(names) == len(set(names))
    assert all(re.fullmatch(r"[A-Za-z0-9][A-Za-z0-9_.-]{0,63}", n) for n in names)
    assert {c["name"] for c in doc["configs"]} == {w["config"] for w in doc["workloads"]}
    four = [w for w in doc["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(doc["workloads"]) // 4)
    e2e = {m["name"] for m in doc["end_to_end"]}
    assert "setup_s" in e2e and all(0.01 <= m["bound"] <= 0.1 for m in doc["end_to_end"])
    for w in doc["workloads"]:
        assert len(w["why"]) <= 200
        cfg, mix = spec.config(w["config"]), spec.mix(w["traffic"])
        assert cfg["chips"] == w["chips"]
        load_module("drivers", mix["driver"])
    for c in doc["configs"]:
        assert c["file"].startswith("benchmarks/") and len(c["why"]) <= 200
        assert spec.config(c["name"])["reduced"] == c["reduced"]
    for m in doc["per_layer"]:
        reader = load_module("layer_metrics", m["name"])
        assert (reader.LAYER, reader.MOVES, reader.SOURCE) == (m["layer"], m["moves"], m["source"])
        assert m["moves"] in e2e
        assert re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}", m["layer"]), m["layer"]
