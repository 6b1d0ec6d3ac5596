"""Perf regression gate (bench.py): fires on a synthetic slow result,
passes on a fast one, and skips silently when there is nothing
comparable to gate against."""

import importlib.util
import json
import os

import pytest

_SPEC = importlib.util.spec_from_file_location(
    "bench_under_test",
    os.path.join(os.path.dirname(__file__), "..", "bench.py"),
)
bench = importlib.util.module_from_spec(_SPEC)
_SPEC.loader.exec_module(bench)

METRIC = "sec/iteration (binary, 1000000x28, max_bin=63, num_leaves=255)"


def _capture(tmp_path, name, value, metric=METRIC, **parsed_extra):
    doc = {"n": 1, "rc": 0,
           "parsed": dict({"metric": metric, "value": value}, **parsed_extra)}
    (tmp_path / name).write_text(json.dumps(doc))


def test_gate_fires_on_synthetic_slow_result(tmp_path):
    _capture(tmp_path, "BENCH_r01.json", 0.20)
    _capture(tmp_path, "BENCH_r02.json", 0.10)  # the best prior
    out = {"metric": METRIC, "value": 0.1366}
    rc = bench.apply_regression_gate(out, bench_dir=str(tmp_path), env={})
    assert rc == 1
    assert out["regression"] is True
    assert out["gate"]["best_prior_s_per_iter"] == 0.10
    assert out["gate"]["best_prior_source"] == "BENCH_r02.json"
    assert out["gate"]["threshold_s_per_iter"] == pytest.approx(0.11)


def test_gate_passes_within_threshold(tmp_path):
    _capture(tmp_path, "BENCH_r01.json", 0.10)
    out = {"metric": METRIC, "value": 0.105}  # 5% slower: within the 10% band
    rc = bench.apply_regression_gate(out, bench_dir=str(tmp_path), env={})
    assert rc == 0
    assert "regression" not in out
    assert out["gate"]["best_prior_s_per_iter"] == 0.10


def test_gate_passes_on_improvement(tmp_path):
    _capture(tmp_path, "BENCH_r01.json", 0.1366)
    out = {"metric": METRIC, "value": 0.1000}
    assert bench.apply_regression_gate(out, bench_dir=str(tmp_path), env={}) == 0
    assert "regression" not in out


def test_silent_skip_without_comparable_priors(tmp_path):
    # no files at all
    out = {"metric": METRIC, "value": 9.9}
    assert bench.apply_regression_gate(out, bench_dir=str(tmp_path), env={}) == 0
    assert "gate" not in out and "regression" not in out
    # a dead capture (parsed: null, the BENCH_r05 shape) + garbage file
    (tmp_path / "BENCH_r05.json").write_text(
        json.dumps({"n": 5, "rc": 1, "parsed": None}))
    (tmp_path / "BENCH_r06.json").write_text("{torn json")
    # and a different-metric capture (other row count: not comparable)
    _capture(tmp_path, "BENCH_r04.json", 0.01,
             metric="sec/iteration (binary, 120000x28, max_bin=63, num_leaves=255)")
    out = {"metric": METRIC, "value": 9.9}
    assert bench.apply_regression_gate(out, bench_dir=str(tmp_path), env={}) == 0
    assert "gate" not in out and "regression" not in out



def test_opt_out(tmp_path):
    _capture(tmp_path, "BENCH_r01.json", 0.10)
    out = {"metric": METRIC, "value": 9.9}
    rc = bench.apply_regression_gate(out, bench_dir=str(tmp_path),
                                     env={"BENCH_GATE": "0"})
    assert rc == 0 and "regression" not in out and "gate" not in out


def test_raw_bench_format_accepted(tmp_path):
    (tmp_path / "BENCH_r01.json").write_text(
        json.dumps({"metric": METRIC, "value": 0.10, "unit": "s/iter"}))
    out = {"metric": METRIC, "value": 0.2}
    assert bench.apply_regression_gate(out, bench_dir=str(tmp_path), env={}) == 1
    assert out["regression"] is True


def _ooc(rows=200_000, chunk_rows=65_536, s_per_iter=1.0):
    return {"rows": rows, "chunk_rows": chunk_rows,
            "stream_s_per_iter": s_per_iter}


def test_ooc_gate_fires_on_slow_stream(tmp_path):
    """The streamed s/iter gates independently of the headline metric —
    an OOC regression with a healthy fused number still fails."""
    _capture(tmp_path, "BENCH_r01.json", 0.10, out_of_core=_ooc(s_per_iter=1.0))
    out = {"metric": METRIC, "value": 0.10,  # headline: fine
           "out_of_core": _ooc(s_per_iter=1.2)}  # stream: 20% slower
    rc = bench.apply_regression_gate(out, bench_dir=str(tmp_path), env={})
    assert rc == 1
    assert out.get("regression_ooc") is True
    assert "regression" not in out
    assert out["gate_ooc"]["best_prior_stream_s_per_iter"] == 1.0


def test_ooc_gate_requires_same_grid(tmp_path):
    # a prior at a different chunk grid is a different summation/stream
    # schedule: not comparable
    _capture(tmp_path, "BENCH_r01.json", 0.10,
             out_of_core=_ooc(chunk_rows=4096, s_per_iter=0.5))
    out = {"metric": METRIC, "value": 0.10, "out_of_core": _ooc(s_per_iter=9.9)}
    assert bench.apply_regression_gate(out, bench_dir=str(tmp_path), env={}) == 0
    assert "gate_ooc" not in out and "regression_ooc" not in out


def test_ooc_gate_runs_without_headline_prior(tmp_path):
    # first capture of a new main config, but the ooc grid has history
    _capture(tmp_path, "BENCH_r01.json", 0.10, out_of_core=_ooc(s_per_iter=1.0),
             metric="sec/iteration (binary, 120000x28, max_bin=63, num_leaves=255)")
    out = {"metric": METRIC, "value": 0.10, "out_of_core": _ooc(s_per_iter=1.2)}
    assert bench.apply_regression_gate(out, bench_dir=str(tmp_path), env={}) == 1
    assert out.get("regression_ooc") is True
    assert "gate" not in out  # headline leg silently skipped


def test_ooc_section_error_never_gates(tmp_path):
    _capture(tmp_path, "BENCH_r01.json", 0.10, out_of_core=_ooc(s_per_iter=1.0))
    out = {"metric": METRIC, "value": 0.10,
           "out_of_core": {"error": "RuntimeError: boom"}}
    assert bench.apply_regression_gate(out, bench_dir=str(tmp_path), env={}) == 0
    assert "gate_ooc" not in out


def _factory(rows=8_000, rounds=10, e2e_s=1.0):
    return {"rows": rows, "num_boost_round": rounds,
            "append_to_promoted_s": e2e_s}


def test_factory_gate_fires_on_slow_cycle(tmp_path):
    """The factory append->promoted latency gates independently of the
    headline, at the wider 1.5x host-work threshold."""
    _capture(tmp_path, "BENCH_r01.json", 0.10, factory=_factory(e2e_s=1.0))
    out = {"metric": METRIC, "value": 0.10,
           "factory": _factory(e2e_s=1.6)}  # 60% slower: over the band
    rc = bench.apply_regression_gate(out, bench_dir=str(tmp_path), env={})
    assert rc == 1
    assert out.get("regression_factory") is True
    assert "regression" not in out
    assert out["gate_factory"]["best_prior_append_to_promoted_s"] == 1.0
    assert out["gate_factory"]["threshold_s"] == pytest.approx(1.5)


def test_factory_gate_passes_within_band(tmp_path):
    _capture(tmp_path, "BENCH_r01.json", 0.10, factory=_factory(e2e_s=1.0))
    out = {"metric": METRIC, "value": 0.10, "factory": _factory(e2e_s=1.4)}
    assert bench.apply_regression_gate(out, bench_dir=str(tmp_path), env={}) == 0
    assert "regression_factory" not in out
    assert out["gate_factory"]["best_prior_append_to_promoted_s"] == 1.0


def test_factory_gate_requires_same_grid(tmp_path):
    # a prior at a different (rows, rounds) grid is a different cycle
    _capture(tmp_path, "BENCH_r01.json", 0.10,
             factory=_factory(rows=80_000, e2e_s=0.5))
    out = {"metric": METRIC, "value": 0.10, "factory": _factory(e2e_s=9.9)}
    assert bench.apply_regression_gate(out, bench_dir=str(tmp_path), env={}) == 0
    assert "gate_factory" not in out and "regression_factory" not in out


def test_factory_section_error_never_gates(tmp_path):
    _capture(tmp_path, "BENCH_r01.json", 0.10, factory=_factory(e2e_s=1.0))
    out = {"metric": METRIC, "value": 0.10,
           "factory": {"error": "RuntimeError: boom",
                       "append_to_promoted_s": 9.9,
                       "rows": 8_000, "num_boost_round": 10}}
    assert bench.apply_regression_gate(out, bench_dir=str(tmp_path), env={}) == 0
    assert "gate_factory" not in out


# ----------------------------------------------------------------------
# quantized-serving leg
# ----------------------------------------------------------------------
def _quantized(speedup=2.0, swap_compiles=0, within_bound=True, ratio=2.5):
    return {
        "artifact_bytes": {"payload_ratio": ratio},
        "drift": {"max_abs": 1e-4, "bound": 1e-3,
                  "within_bound": within_bound},
        "batch2048": {"exact": {"rows_per_s": 1e6},
                      "quantized": {"rows_per_s": 1e6 * speedup},
                      "speedup": speedup},
        "swap": {"swaps": 3, "swap_latency_p50_ms": 1.0,
                 "swap_new_compiles": swap_compiles},
    }


def test_quantized_swap_compiles_gate_fires_without_prior(tmp_path):
    out = {"metric": METRIC, "value": 0.10,
           "quantized": _quantized(swap_compiles=2)}
    assert bench.apply_regression_gate(out, bench_dir=str(tmp_path), env={}) == 1
    assert out["regression_quant_swap_compiles"] is True


def test_quantized_drift_gate_fires_without_prior(tmp_path):
    out = {"metric": METRIC, "value": 0.10,
           "quantized": _quantized(within_bound=False)}
    assert bench.apply_regression_gate(out, bench_dir=str(tmp_path), env={}) == 1
    assert out["regression_quant_drift"] is True


def test_quantized_bytes_gate_fires_without_prior(tmp_path):
    out = {"metric": METRIC, "value": 0.10,
           "quantized": _quantized(ratio=1.4)}
    assert bench.apply_regression_gate(out, bench_dir=str(tmp_path), env={}) == 1
    assert out["regression_quant_bytes"] is True


def test_quantized_speedup_gates_against_prior(tmp_path):
    _capture(tmp_path, "BENCH_r01.json", 0.10, quantized=_quantized(2.0))
    out = {"metric": METRIC, "value": 0.10, "quantized": _quantized(1.5)}
    rc = bench.apply_regression_gate(out, bench_dir=str(tmp_path), env={})
    assert rc == 1
    assert out["regression_quantized"] is True
    assert out["gate_quantized"]["best_prior_speedup_batch2048"] == 2.0
    # within the 1.10 band passes
    out = {"metric": METRIC, "value": 0.10, "quantized": _quantized(1.85)}
    assert bench.apply_regression_gate(out, bench_dir=str(tmp_path), env={}) == 0
    assert "regression_quantized" not in out


def test_quantized_section_error_never_gates(tmp_path):
    out = {"metric": METRIC, "value": 0.10,
           "quantized": {"error": "RuntimeError: boom",
                         "swap": {"swap_new_compiles": 9}}}
    assert bench.apply_regression_gate(out, bench_dir=str(tmp_path), env={}) == 0


def test_quantized_clean_run_passes(tmp_path):
    out = {"metric": METRIC, "value": 0.10, "quantized": _quantized()}
    assert bench.apply_regression_gate(out, bench_dir=str(tmp_path), env={}) == 0
    for k in list(out):
        assert not k.startswith("regression"), k


# ----------------------------------------------------------------------
# comms leg (wide-data learners, docs/PARALLEL.md)
# ----------------------------------------------------------------------
def _comms(ratio=48.0, rows=3000, features=2000, ranks=2,
           data_s=0.9, feature_s=0.1, voting_s=0.2):
    return {
        "rows": rows, "features": features, "ranks": ranks,
        "voting_vs_data_payload_ratio": ratio,
        "feature_vs_data_payload_ratio": 1800.0,
        "per_learner": {
            "data": {"bytes_per_iter": 5_568_062, "s_per_iter": data_s},
            "feature": {"bytes_per_iter": 3_031, "s_per_iter": feature_s},
            "voting": {"bytes_per_iter": 114_902, "s_per_iter": voting_s},
        },
    }


def test_comms_payload_gate_fires_without_prior(tmp_path):
    """Voting must cut the data-parallel allreduce payload >=5x; the
    ratio is protocol arithmetic, so it gates with no prior capture."""
    out = {"metric": METRIC, "value": 0.10, "comms": _comms(ratio=3.2)}
    assert bench.apply_regression_gate(out, bench_dir=str(tmp_path), env={}) == 1
    assert out["regression_comms_payload"] is True
    assert out["gate_comms"]["min_voting_vs_data_payload_ratio"] == 5.0
    assert out["gate_comms"]["voting_vs_data_payload_ratio"] == pytest.approx(3.2)



def test_comms_payload_gate_passes(tmp_path):
    out = {"metric": METRIC, "value": 0.10, "comms": _comms(ratio=48.46)}
    assert bench.apply_regression_gate(out, bench_dir=str(tmp_path), env={}) == 0
    assert out["gate_comms"]["voting_vs_data_payload_ratio"] == pytest.approx(48.46)
    for k in list(out):
        assert not k.startswith("regression"), k


def _elastic(recovery=2.5):
    return {
        "rows": 1024, "trees": 14, "ranks": 2,
        "delay_ms_per_collective": 30,
        "no_straggler_s_per_iter": 0.16,
        "straggler_off_s_per_iter": 1.5,
        "straggler_rebalance_s_per_iter": round(1.5 / recovery, 4),
        "straggler_slowdown": 9.2,
        "recovery_ratio": recovery,
        "final_counts": [154, 870],
    }


def test_elastic_gate_fires_without_prior(tmp_path):
    """Rebalance-on must beat rebalance-off >=1.3x under the injected
    straggler; the stall dominates on any backend, so the leg gates
    outright with no prior capture."""
    out = {"metric": METRIC, "value": 0.10, "elastic": _elastic(recovery=1.1)}
    assert bench.apply_regression_gate(out, bench_dir=str(tmp_path), env={}) == 1
    assert out["regression_elastic_recovery"] is True
    assert out["gate_elastic"]["min_recovery_ratio"] == 1.3
    assert out["gate_elastic"]["recovery_ratio"] == pytest.approx(1.1)



def test_elastic_gate_passes(tmp_path):
    out = {"metric": METRIC, "value": 0.10, "elastic": _elastic(recovery=2.67)}
    assert bench.apply_regression_gate(out, bench_dir=str(tmp_path), env={}) == 0
    assert out["gate_elastic"]["recovery_ratio"] == pytest.approx(2.67)
    for k in list(out):
        assert not k.startswith("regression"), k


def test_elastic_section_error_never_gates(tmp_path):
    out = {"metric": METRIC, "value": 0.10,
           "elastic": {"error": "RuntimeError: fleet failed"}}
    assert bench.apply_regression_gate(out, bench_dir=str(tmp_path), env={}) == 0
    assert "gate_elastic" not in out
    assert "regression_elastic_recovery" not in out


def _oocdist(parity=True):
    return {
        "rows": 16384, "trees": 3, "ranks": 2,
        "chunk_grids": [2048, 9999],
        "chunks_per_pass": {2048: 2, 9999: 1},
        "fleet_wall_s": {2048: 21.0, 9999: 19.5},
        "quantized_parity_ok": parity,
    }


def test_oocdist_gate_fires_on_parity_break(tmp_path):
    """Quantized streamed folds are associative int32 adds, so the model
    bytes must match EXACTLY across chunk grids — any mismatch gates
    outright with no prior capture."""
    out = {"metric": METRIC, "value": 0.10,
           "ooc_distributed": _oocdist(parity=False)}
    assert bench.apply_regression_gate(out, bench_dir=str(tmp_path), env={}) == 1
    assert out["regression_oocdist_parity"] is True
    assert out["gate_oocdist"]["require_quantized_parity"] is True
    assert out["gate_oocdist"]["chunk_grids"] == [2048, 9999]



def test_oocdist_gate_passes(tmp_path):
    out = {"metric": METRIC, "value": 0.10,
           "ooc_distributed": _oocdist(parity=True)}
    assert bench.apply_regression_gate(out, bench_dir=str(tmp_path), env={}) == 0
    assert out["gate_oocdist"]["quantized_parity_ok"] is True
    for k in list(out):
        assert not k.startswith("regression"), k


def test_oocdist_section_error_never_gates(tmp_path):
    out = {"metric": METRIC, "value": 0.10,
           "ooc_distributed": {"error": "RuntimeError: fleet failed"}}
    assert bench.apply_regression_gate(out, bench_dir=str(tmp_path), env={}) == 0
    assert "gate_oocdist" not in out
    assert "regression_oocdist_parity" not in out


def test_comms_wall_gate_against_prior(tmp_path):
    _capture(tmp_path, "BENCH_r01.json", 0.10, comms=_comms(data_s=1.0))
    out = {"metric": METRIC, "value": 0.10,
           "comms": _comms(data_s=1.2)}  # 20% slower: over the band
    rc = bench.apply_regression_gate(out, bench_dir=str(tmp_path), env={})
    assert rc == 1
    assert out["regression_comms_wall"] is True
    assert out["gate_comms_wall"]["data"]["best_prior_s_per_iter"] == 1.0
    # within the 1.10 band passes
    out = {"metric": METRIC, "value": 0.10, "comms": _comms(data_s=1.05)}
    assert bench.apply_regression_gate(out, bench_dir=str(tmp_path), env={}) == 0
    assert "regression_comms_wall" not in out


def test_comms_wall_gate_requires_same_grid(tmp_path):
    # a prior at another (rows, features, ranks) grid is not comparable
    _capture(tmp_path, "BENCH_r01.json", 0.10,
             comms=_comms(features=500, data_s=0.01))
    out = {"metric": METRIC, "value": 0.10, "comms": _comms(data_s=9.9)}
    assert bench.apply_regression_gate(out, bench_dir=str(tmp_path), env={}) == 0
    assert "gate_comms_wall" not in out and "regression_comms_wall" not in out


def test_comms_section_error_never_gates(tmp_path):
    out = {"metric": METRIC, "value": 0.10,
           "comms": {"error": "RuntimeError: boom",
                     "voting_vs_data_payload_ratio": 0.1}}
    assert bench.apply_regression_gate(out, bench_dir=str(tmp_path), env={}) == 0
    assert "gate_comms" not in out


def test_comms_opt_out(tmp_path):
    out = {"metric": METRIC, "value": 0.10, "comms": _comms(ratio=0.1)}
    rc = bench.apply_regression_gate(out, bench_dir=str(tmp_path),
                                     env={"BENCH_GATE": "0"})
    assert rc == 0 and "gate_comms" not in out


# ----------------------------------------------------------------------
# multi-model leg
# ----------------------------------------------------------------------
def test_multimodel_admission_gate(tmp_path):
    out = {"metric": METRIC, "value": 0.10,
           "multimodel": {"n_models": 4, "admission_refusal_ok": False}}
    assert bench.apply_regression_gate(out, bench_dir=str(tmp_path), env={}) == 1
    assert out["regression_multimodel_admission"] is True
    out = {"metric": METRIC, "value": 0.10,
           "multimodel": {"n_models": 4, "admission_refusal_ok": True}}
    assert bench.apply_regression_gate(out, bench_dir=str(tmp_path), env={}) == 0
    out = {"metric": METRIC, "value": 0.10,
           "multimodel": {"error": "RuntimeError: boom",
                          "admission_refusal_ok": False}}
    assert bench.apply_regression_gate(out, bench_dir=str(tmp_path), env={}) == 0


# ----------------------------------------------------------------------
# spot-economics leg
# ----------------------------------------------------------------------
def _spot(ratio=0.4, zero_lost=True):
    return {"rows": 600, "trees": 16, "members": 2,
            "cost_ratio_spot_vs_static": ratio,
            "zero_lost_iterations": zero_lost}


def test_spot_gate_fires_on_lost_iterations(tmp_path):
    """Losing a completed iteration to churn voids the elastic premise:
    the leg gates OUTRIGHT, priors or not."""
    out = {"metric": METRIC, "value": 0.10,
           "spot": _spot(zero_lost=False)}
    rc = bench.apply_regression_gate(out, bench_dir=str(tmp_path), env={})
    assert rc == 1
    assert out["regression_spot_lost_iterations"] is True
    assert out["gate_spot"]["require_zero_lost_iterations"] is True


def test_spot_gate_fires_on_cost_above_static(tmp_path):
    out = {"metric": METRIC, "value": 0.10, "spot": _spot(ratio=0.95)}
    rc = bench.apply_regression_gate(out, bench_dir=str(tmp_path), env={})
    assert rc == 1
    assert out["regression_spot_cost"] is True
    assert out["gate_spot"]["max_cost_ratio_spot_vs_static"] == 0.8


def test_spot_gate_passes_on_cheap_clean_run(tmp_path):
    out = {"metric": METRIC, "value": 0.10, "spot": _spot()}
    assert bench.apply_regression_gate(out, bench_dir=str(tmp_path),
                                       env={}) == 0
    assert "regression_spot_cost" not in out
    assert "regression_spot_lost_iterations" not in out
    assert out["gate_spot"]["cost_ratio_spot_vs_static"] == 0.4


def test_spot_section_error_never_gates(tmp_path):
    out = {"metric": METRIC, "value": 0.10,
           "spot": {"error": "RuntimeError: boom",
                    "zero_lost_iterations": False,
                    "cost_ratio_spot_vs_static": 9.9}}
    assert bench.apply_regression_gate(out, bench_dir=str(tmp_path),
                                       env={}) == 0
    assert "gate_spot" not in out


def _serving_tail(hedged_ratio=1.7):
    return {
        "requests_per_leg": 90, "injected_delay_ms": 300.0,
        "hedge_delay_ms": 25.0, "gate_floor_ms": 20.0,
        "healthy_p99_ms": 8.1, "unhedged_chaos_p99_ms": 305.0,
        "hedged_chaos_p99_ms": round(20.0 * hedged_ratio, 2),
        "unhedged_chaos_over_healthy_p99": 15.25,
        "hedged_chaos_over_healthy_p99": hedged_ratio,
        "hedges_launched": 3, "hedge_wins": 3,
    }


def test_serving_tail_gate_fires_without_prior(tmp_path):
    """Hedged p99 under an injected-delay replica must stay <= 3x the
    healthy baseline; the contract is protocol-level, so the leg gates
    outright with no prior capture."""
    out = {"metric": METRIC, "value": 0.10,
           "serving_tail": _serving_tail(hedged_ratio=4.2)}
    assert bench.apply_regression_gate(out, bench_dir=str(tmp_path),
                                       env={}) == 1
    assert out["regression_serving_tail"] is True
    assert out["gate_serving_tail"][
        "max_hedged_chaos_over_healthy_p99"] == 3.0
    assert out["gate_serving_tail"][
        "hedged_chaos_over_healthy_p99"] == pytest.approx(4.2)



def test_serving_tail_gate_passes(tmp_path):
    out = {"metric": METRIC, "value": 0.10,
           "serving_tail": _serving_tail(hedged_ratio=1.66)}
    assert bench.apply_regression_gate(out, bench_dir=str(tmp_path),
                                       env={}) == 0
    assert out["gate_serving_tail"][
        "hedged_chaos_over_healthy_p99"] == pytest.approx(1.66)
    for k in list(out):
        assert not k.startswith("regression"), k


def test_serving_tail_section_error_never_gates(tmp_path):
    out = {"metric": METRIC, "value": 0.10,
           "serving_tail": {"error": "RuntimeError: replica never ready",
                            "hedged_chaos_over_healthy_p99": 9.9}}
    assert bench.apply_regression_gate(out, bench_dir=str(tmp_path),
                                       env={}) == 0
    assert "gate_serving_tail" not in out
    assert "regression_serving_tail" not in out
