"""Tier-1 perf-path smoke: the fused chunk program's two growth modes
(level-batched and classic) must produce bit-identical MODELS on a tiny
CPU run under tracing, so future kernel edits can't silently diverge
them — plus the report CLI's one-line phase attribution."""

import json

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.obs import report, tracer


def _toy(n=800, f=6, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, f)).astype(np.float32)
    w = rng.standard_normal(f)
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-(X @ w)))).astype(np.float32)
    return X, y


def _read(path):
    recs = []
    with open(path) as fh:
        for line in fh:
            line = line.strip()
            if line:
                recs.append(json.loads(line))
    return recs


def test_traced_and_fused_iterations_bit_identical_models(tmp_path, monkeypatch):
    """Traced fused runs, level-batched against classic, of the same
    config: model strings must be byte-equal, and both traces carry the
    amortized per-iteration records of ONE chunk program (nothing
    defuses it into per-phase dispatches)."""
    monkeypatch.setenv("LIGHTGBM_TPU_PGROW", "force")
    X, y = _toy()
    params = {"objective": "binary", "num_leaves": 7, "verbose": -1,
              "min_data_in_leaf": 20}
    modes = {"fused_level": "1", "fused_classic": "0"}
    models = {}
    try:
        for mode, levelgrow in modes.items():
            monkeypatch.setenv("LIGHTGBM_TPU_LEVELGROW", levelgrow)
            monkeypatch.setenv("LIGHTGBM_TPU_TRACE",
                               str(tmp_path / f"{mode}.jsonl"))
            bst = lgb.train(dict(params),
                            lgb.Dataset(X, label=y, params=dict(params)),
                            num_boost_round=2, verbose_eval=False)
            assert bst.boosting.ptrainer is not None
            models[mode] = bst.model_to_string()
    finally:
        tracer.close()
        tracer.path = None
    assert models["fused_level"] == models["fused_classic"], \
        "level-batched fused diverged from classic fused"

    for mode in modes:
        recs = _read(tmp_path / f"{mode}.jsonl")
        iters = [r for r in recs if r["ev"] == "iter"]
        assert len(iters) == 2, f"{mode} run emitted {len(iters)} iteration records"
        for r in iters:
            assert r.get("amortized") and r.get("mode") != "traced"
            assert set(r["phases"]) == {"fused_chunk"}
        assert any(r["ev"] == "span" and r["name"] == "chunk_program" for r in recs)


def test_report_top_phases_line():
    summary = {
        "phases": {
            "partition": {"total_s": 6.0, "count": 3, "mean_ms": 2000.0},
            "histogram": {"total_s": 3.0, "count": 3, "mean_ms": 1000.0},
            "split": {"total_s": 0.8, "count": 3, "mean_ms": 266.7},
            "score_update": {"total_s": 0.2, "count": 3, "mean_ms": 66.7},
        },
    }
    line = report.top_phases_line(summary)
    assert line == "top phases: partition 60.0% | histogram 30.0% | split 8.0%"
    assert report.top_phases_line({"phases": {}}) == ""


def test_report_render_includes_top_phases(tmp_path):
    trace = tmp_path / "t.jsonl"
    recs = [
        {"ev": "iter", "iter": 0, "wall_s": 1.0,
         "phases": {"partition": 0.6, "histogram": 0.3, "split": 0.1}},
    ]
    trace.write_text("\n".join(json.dumps(r) for r in recs) + "\n")
    summary = report.summarize(report.load_trace(str(trace)))
    text = report.render(summary, str(trace))
    assert "top phases: partition 60.0% | histogram 30.0% | split 10.0%" in text
