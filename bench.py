"""Benchmark harness — the BASELINE.md north-star metric: sec/iteration
on Higgs-shaped data (docs/GPU-Performance.md:101-117 config: max_bin=63,
num_leaves=255, learning_rate=0.1, min_data_in_leaf=1,
min_sum_hessian_in_leaf=100).

The real Higgs download is unavailable (zero egress), so a synthetic
Higgs-shaped dataset is generated.  The informative weight vector is
drawn ONCE from a fixed seed and shared by every split, so train and
held-out rows describe the same task and the AUC is a real quality
signal (cross-checked against sklearn HistGradientBoosting at matched
hyperparameters; see auc_sklearn).

Rows default to 1M (vs Higgs 10.5M) to keep the harness fast;
per-iteration time scales linearly in N, so `vs_baseline` scales the
reference number to the measured row count.  Set BENCH_ROWS=10500000 for
the full-Higgs-scale run.

Prints ONE JSON line: {"metric": ..., "value": ..., "unit": ...,
"vs_baseline": ...}.
"""

import glob
import json
import os
import sys
import time

import numpy as np

_TASK_SEED = 20260730  # the task (informative weights) — NEVER varies
_N_INFORM = 8


# ----------------------------------------------------------------------
# perf regression gate: compare this run's s/iter against the best prior
# driver-captured BENCH_r*.json with the SAME metric line
# ----------------------------------------------------------------------
def best_prior_sec_per_iter(bench_dir: str, metric: str):
    """(best s/iter, source file) over prior BENCH_r*.json captures whose
    parsed metric matches ``metric`` exactly (same rows/config).
    (None, None) when no prior parses — first capture of a new config."""
    best, best_src = None, None
    for path in sorted(glob.glob(os.path.join(bench_dir, "BENCH_r*.json"))):
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            continue
        parsed = doc.get("parsed") if isinstance(doc, dict) else None
        if not isinstance(parsed, dict):
            # tolerate raw bench-format files ({"metric": ..., "value": ...})
            parsed = doc if isinstance(doc, dict) and "metric" in doc else None
        if not parsed or parsed.get("metric") != metric:
            continue
        v = parsed.get("value")
        if isinstance(v, (int, float)) and v > 0 and (best is None or v < best):
            best, best_src = float(v), os.path.basename(path)
    return best, best_src


def apply_regression_gate(out: dict, bench_dir: str = None, env=None) -> int:
    """Annotate ``out`` with the gate verdict; return the process exit
    code (1 when this run is >10% slower than the best comparable prior
    capture).  BENCH_GATE=0 opts out; no matching prior => silent skip."""
    env = env if env is not None else os.environ
    if env.get("BENCH_GATE", "1") == "0":
        return 0
    if bench_dir is None:
        bench_dir = os.path.dirname(os.path.abspath(__file__)) or "."
    rc = 0
    # comms payload-ratio leg FIRST (docs/PARALLEL.md): the bytes/iter
    # numbers are pure protocol arithmetic — deterministic and
    # device-INDEPENDENT — so voting's >=5x allreduce-payload cut over
    # data-parallel gates outright
    cm = out.get("comms") or {}
    ratio_c = cm.get("voting_vs_data_payload_ratio")
    if cm and not cm.get("error") and isinstance(ratio_c, (int, float)):
        out["gate_comms"] = {
            "min_voting_vs_data_payload_ratio": 5.0,
            "voting_vs_data_payload_ratio": round(float(ratio_c), 2),
        }
        if float(ratio_c) < 5.0:
            out["regression_comms_payload"] = True
            rc = 1
    # quantized-hist payload leg, same regime: the f32-vs-int16 histogram
    # wire ratio is protocol arithmetic (F*B*12 vs F*B*4), so the >=3x
    # contract gates outright
    qh = cm.get("quantized_hist") or {}
    ratio_q = qh.get("f32_vs_quantized_payload_ratio")
    if cm and not cm.get("error") and isinstance(ratio_q, (int, float)):
        out["gate_quantized_hist"] = {
            "min_f32_vs_quantized_payload_ratio": 3.0,
            "f32_vs_quantized_payload_ratio": round(float(ratio_q), 2),
        }
        if float(ratio_q) < 3.0:
            out["regression_quantized_hist_payload"] = True
            rc = 1
    # elastic recovery leg, same regime: the injected per-collective
    # stall dominates compute on any backend, so rebalance-on must beat
    # rebalance-off by >=1.3x under the ~4x straggler on EVERY capture —
    # CPU fallback included (docs/ROBUSTNESS.md)
    el = out.get("elastic") or {}
    rr = el.get("recovery_ratio")
    if el and not el.get("error") and isinstance(rr, (int, float)):
        out["gate_elastic"] = {
            "min_recovery_ratio": 1.3,
            "recovery_ratio": round(float(rr), 2),
        }
        if float(rr) < 1.3:
            out["regression_elastic_recovery"] = True
            rc = 1
    # distributed out-of-core quantized-parity leg, same regime: int32
    # per-chunk fold partials are associative, so the model bytes must
    # match EXACTLY across chunk grids — protocol arithmetic, gated
    # outright (docs/DATA.md)
    od = out.get("ooc_distributed") or {}
    if od and not od.get("error") and "quantized_parity_ok" in od:
        out["gate_oocdist"] = {
            "require_quantized_parity": True,
            "quantized_parity_ok": bool(od["quantized_parity_ok"]),
            "chunk_grids": od.get("chunk_grids"),
        }
        if not od["quantized_parity_ok"]:
            out["regression_oocdist_parity"] = True
            rc = 1
    # linear-tree leg, same regime: trees-to-matched-logloss is a
    # quality-per-tree property of the fit math, not of the backend, so
    # the >=20% fewer-trees contract (ratio <= 0.8) gates outright
    # (docs/TREES.md)
    lt = out.get("linear_tree") or {}
    ratio_l = lt.get("trees_to_match_ratio")
    if lt and not lt.get("error") and isinstance(ratio_l, (int, float)):
        out["gate_linear_tree"] = {
            "max_trees_to_match_ratio": 0.8,
            "trees_to_match_ratio": round(float(ratio_l), 3),
        }
        if float(ratio_l) > 0.8:
            out["regression_linear_tree"] = True
            rc = 1
    # spot-economics leg, same regime: cost is member-seconds x price
    # arithmetic and the zero-lost-iterations record is write-once KV
    # bookkeeping — both device-independent, so the <=0.8x spot-vs-
    # static cost contract AND the nothing-redone proof gate outright
    # (docs/FACTORY.md)
    sp = out.get("spot") or {}
    if sp and not sp.get("error"):
        ratio_s = sp.get("cost_ratio_spot_vs_static")
        out["gate_spot"] = {
            "max_cost_ratio_spot_vs_static": 0.8,
            "cost_ratio_spot_vs_static": ratio_s,
            "require_zero_lost_iterations": True,
            "zero_lost_iterations": sp.get("zero_lost_iterations"),
        }
        if not sp.get("zero_lost_iterations"):
            out["regression_spot_lost_iterations"] = True
            rc = 1
        if isinstance(ratio_s, (int, float)) and float(ratio_s) > 0.8:
            out["regression_spot_cost"] = True
            rc = 1
    # serving-tail leg, same regime: the injected per-request delay
    # dominates any backend's own latency, so hedged p99 under chaos
    # staying <= 3x the healthy-baseline p99 is a protocol-level
    # contract of the hedging/breaker machinery — it gates outright
    # (docs/ROBUSTNESS.md)
    stl = out.get("serving_tail") or {}
    ratio_t = stl.get("hedged_chaos_over_healthy_p99")
    if stl and not stl.get("error") and isinstance(ratio_t, (int, float)):
        out["gate_serving_tail"] = {
            "max_hedged_chaos_over_healthy_p99": 3.0,
            "hedged_chaos_over_healthy_p99": round(float(ratio_t), 3),
        }
        if float(ratio_t) > 3.0:
            out["regression_serving_tail"] = True
            rc = 1
    best, src = best_prior_sec_per_iter(bench_dir, out.get("metric"))
    if best is not None:
        threshold = best * 1.10
        out["gate"] = {
            "best_prior_s_per_iter": round(best, 4),
            "best_prior_source": src,
            "threshold_s_per_iter": round(threshold, 4),
        }
        if float(out.get("value", 0.0)) > threshold:
            out["regression"] = True
            rc = 1
    # out-of-core leg: the streamed s/iter gates against prior captures
    # with the same (rows, chunk_rows) streaming grid
    sec = out.get("out_of_core") or {}
    val = sec.get("stream_s_per_iter")
    if isinstance(val, (int, float)) and val > 0 and not sec.get("error"):
        key = (sec.get("rows"), sec.get("chunk_rows"))
        best_o, src_o = None, None
        for path in sorted(glob.glob(os.path.join(bench_dir,
                                                  "BENCH_r*.json"))):
            try:
                with open(path) as f:
                    doc = json.load(f)
            except (OSError, ValueError):
                continue
            parsed = doc.get("parsed") if isinstance(doc, dict) else None
            if not isinstance(parsed, dict):
                parsed = doc if isinstance(doc, dict) else {}
            po = parsed.get("out_of_core") or {}
            pv = po.get("stream_s_per_iter")
            if (po.get("rows"), po.get("chunk_rows")) != key:
                continue
            if isinstance(pv, (int, float)) and pv > 0 and (
                    best_o is None or pv < best_o):
                best_o, src_o = float(pv), os.path.basename(path)
        if best_o is not None:
            thr_o = best_o * 1.10
            out["gate_ooc"] = {
                "best_prior_stream_s_per_iter": round(best_o, 4),
                "best_prior_source": src_o,
                "threshold_s_per_iter": round(thr_o, 4),
            }
            if float(val) > thr_o:
                out["regression_ooc"] = True
                rc = 1
    # serving-swap leg (independent): a hot swap to a same-shape retrain
    # must compile NOTHING (the tree-shape-bucket contract) — any
    # swap_new_compiles is a regression outright, no prior needed.  Swap
    # latency p99 gates against priors with the same swap count, at a
    # wider 1.5x threshold: the op is short host work (load + cache-hit
    # warmup), so its relative run-to-run variance dwarfs the s/iter legs'
    sw = (out.get("serving") or {}).get("swap") or {}
    if not sw.get("error"):
        if isinstance(sw.get("swap_new_compiles"), int) and \
                sw["swap_new_compiles"] > 0:
            out["regression_swap_compiles"] = True
            rc = 1
        val_s = sw.get("swap_latency_p99_ms")
        if isinstance(val_s, (int, float)) and val_s > 0:
            best_s, src_s = None, None
            for path in sorted(glob.glob(os.path.join(bench_dir,
                                                      "BENCH_r*.json"))):
                try:
                    with open(path) as f:
                        doc = json.load(f)
                except (OSError, ValueError):
                    continue
                parsed = doc.get("parsed") if isinstance(doc, dict) else None
                if not isinstance(parsed, dict):
                    parsed = doc if isinstance(doc, dict) else {}
                ps = (parsed.get("serving") or {}).get("swap") or {}
                pv = ps.get("swap_latency_p99_ms")
                if ps.get("swaps") != sw.get("swaps"):
                    continue
                if isinstance(pv, (int, float)) and pv > 0 and (
                        best_s is None or pv < best_s):
                    best_s, src_s = float(pv), os.path.basename(path)
            if best_s is not None:
                thr_s = best_s * 1.5
                out["gate_swap"] = {
                    "best_prior_swap_p99_ms": round(best_s, 3),
                    "best_prior_source": src_s,
                    "threshold_ms": round(thr_s, 3),
                }
                if float(val_s) > thr_s:
                    out["regression_swap"] = True
                    rc = 1
    # quantized leg (independent): three device-independent contracts
    # gate outright, no prior needed — the quantized same-shape swap must
    # compile NOTHING, the measured drift must sit inside its documented
    # bound, and the quantized payload must be at least 2x smaller.  The
    # batch-2048 speedup gates against the best prior capture's speedup
    # (not an absolute floor, so a faster exact baseline can't fail it
    # spuriously) at the same 1.10 slack as the s/iter legs.
    qz = out.get("quantized") or {}
    if qz and not qz.get("error"):
        qsw = qz.get("swap") or {}
        if isinstance(qsw.get("swap_new_compiles"), int) and \
                qsw["swap_new_compiles"] > 0:
            out["regression_quant_swap_compiles"] = True
            rc = 1
        dr = qz.get("drift") or {}
        if dr and not dr.get("within_bound"):
            out["regression_quant_drift"] = True
            rc = 1
        ab = qz.get("artifact_bytes") or {}
        ratio = ab.get("payload_ratio")
        if isinstance(ratio, (int, float)) and ratio < 2.0:
            out["regression_quant_bytes"] = True
            rc = 1
        val_q = (qz.get("batch2048") or {}).get("speedup")
        if isinstance(val_q, (int, float)) and val_q > 0:
            best_q, src_q = None, None
            for path in sorted(glob.glob(os.path.join(bench_dir,
                                                      "BENCH_r*.json"))):
                try:
                    with open(path) as f:
                        doc = json.load(f)
                except (OSError, ValueError):
                    continue
                parsed = doc.get("parsed") if isinstance(doc, dict) else None
                if not isinstance(parsed, dict):
                    parsed = doc if isinstance(doc, dict) else {}
                pq = ((parsed.get("quantized") or {}).get("batch2048")
                      or {}).get("speedup")
                if isinstance(pq, (int, float)) and pq > 0 and (
                        best_q is None or pq > best_q):
                    best_q, src_q = float(pq), os.path.basename(path)
            if best_q is not None:
                thr_q = best_q / 1.10
                out["gate_quantized"] = {
                    "best_prior_speedup_batch2048": round(best_q, 3),
                    "best_prior_source": src_q,
                    "threshold_speedup": round(thr_q, 3),
                }
                if float(val_q) < thr_q:
                    out["regression_quantized"] = True
                    rc = 1
    # multi-model leg (independent): the admission-refusal probe is a
    # device-independent correctness contract — a budget overrun that is
    # NOT refused loudly is a regression outright
    mm = out.get("multimodel") or {}
    if mm and not mm.get("error") and \
            mm.get("admission_refusal_ok") is False:
        out["regression_multimodel_admission"] = True
        rc = 1
    # factory leg (independent): the append->promoted e2e latency gates
    # against priors at the same (rows, num_boost_round) grid.  Wider
    # 1.5x threshold: the cycle is host work (staging, eval, registry
    # I/O) whose run-to-run variance dwarfs the s/iter legs'
    fa = out.get("factory") or {}
    val_f = fa.get("append_to_promoted_s")
    if isinstance(val_f, (int, float)) and val_f > 0 and not fa.get("error"):
        key_f = (fa.get("rows"), fa.get("num_boost_round"))
        best_f, src_f = None, None
        for path in sorted(glob.glob(os.path.join(bench_dir,
                                                  "BENCH_r*.json"))):
            try:
                with open(path) as f:
                    doc = json.load(f)
            except (OSError, ValueError):
                continue
            parsed = doc.get("parsed") if isinstance(doc, dict) else None
            if not isinstance(parsed, dict):
                parsed = doc if isinstance(doc, dict) else {}
            pf = parsed.get("factory") or {}
            if (pf.get("rows"), pf.get("num_boost_round")) != key_f:
                continue
            pv = pf.get("append_to_promoted_s")
            if isinstance(pv, (int, float)) and pv > 0 and (
                    best_f is None or pv < best_f):
                best_f, src_f = float(pv), os.path.basename(path)
        if best_f is not None:
            thr_f = best_f * 1.5
            out["gate_factory"] = {
                "best_prior_append_to_promoted_s": round(best_f, 3),
                "best_prior_source": src_f,
                "threshold_s": round(thr_f, 3),
            }
            if float(val_f) > thr_f:
                out["regression_factory"] = True
                rc = 1
    # comms wall-clock legs (device-bound, so non-fallback captures
    # only — the payload-ratio leg above already ran regardless): each
    # learner's s/iter gates against priors at the same
    # (rows, features, ranks) grid
    if cm and not cm.get("error"):
        key_c = (cm.get("rows"), cm.get("features"), cm.get("ranks"))
        for mode_c in ("data", "feature", "voting"):
            val_c = ((cm.get("per_learner") or {}).get(mode_c)
                     or {}).get("s_per_iter")
            if not (isinstance(val_c, (int, float)) and val_c > 0):
                continue
            best_c, src_c = None, None
            for path in sorted(glob.glob(os.path.join(bench_dir,
                                                      "BENCH_r*.json"))):
                try:
                    with open(path) as f:
                        doc = json.load(f)
                except (OSError, ValueError):
                    continue
                parsed = doc.get("parsed") if isinstance(doc, dict) else None
                if not isinstance(parsed, dict):
                    parsed = doc if isinstance(doc, dict) else {}
                pc = parsed.get("comms") or {}
                if (pc.get("rows"), pc.get("features"),
                        pc.get("ranks")) != key_c:
                    continue
                pv = ((pc.get("per_learner") or {}).get(mode_c)
                      or {}).get("s_per_iter")
                if isinstance(pv, (int, float)) and pv > 0 and (
                        best_c is None or pv < best_c):
                    best_c, src_c = float(pv), os.path.basename(path)
            if best_c is not None:
                thr_c = best_c * 1.10
                out.setdefault("gate_comms_wall", {})[mode_c] = {
                    "best_prior_s_per_iter": round(best_c, 4),
                    "best_prior_source": src_c,
                    "threshold_s_per_iter": round(thr_c, 4),
                }
                if float(val_c) > thr_c:
                    out["regression_comms_wall"] = True
                    rc = 1
    return rc


def _task_weights(n_features: int):
    rng = np.random.RandomState(_TASK_SEED)
    return rng.randn(_N_INFORM), n_features


def make_higgs_shaped(n_rows: int, n_features: int = 28, seed: int = 7):
    """Synthetic binary data with Higgs-like geometry: a few informative
    features plus noise features, mildly non-linear decision surface.
    ``seed`` draws the ROWS only; the task itself is fixed."""
    w, _ = _task_weights(n_features)
    rng = np.random.RandomState(seed)
    X = rng.randn(n_rows, n_features).astype(np.float32)
    margin = X[:, :_N_INFORM] @ w + 0.5 * X[:, 0] * X[:, 1] - 0.3 * X[:, 2] ** 2
    prob = 1.0 / (1.0 + np.exp(-margin / margin.std()))
    y = (rng.rand(n_rows) < prob).astype(np.float32)
    return X, y


def _bench_serving(booster, X, batch_sizes=(1, 128, 2048), reps=20):
    """Warm p50/p99 latency + throughput of the serving predictor at
    fixed batch sizes, with compile accounting (serve subsystem)."""
    from lightgbm_tpu.obs import compilewatch
    from lightgbm_tpu.serve.artifact import PackedPredictor, PredictorArtifact

    section = {}
    try:
        packed = PackedPredictor(PredictorArtifact.from_booster(booster))
        max_bucket = max(batch_sizes)
        c0 = compilewatch.total_compiles()
        warm = packed.warmup(max_bucket)
        section["warmup_s"] = warm["secs"]
        section["warmup_compiles"] = warm["compiles"]
        section["buckets"] = warm["buckets"]
        c1 = compilewatch.total_compiles()
        for bs in batch_sizes:
            bs = min(bs, X.shape[0])
            rows = np.ascontiguousarray(X[:bs], np.float64)
            lat = []
            for _ in range(reps):
                t0 = time.time()
                packed.predict(rows)
                lat.append(time.time() - t0)
            lat.sort()
            p50 = lat[len(lat) // 2]
            p99 = lat[min(len(lat) - 1, int(0.99 * len(lat)))]
            section[f"batch{bs}"] = {
                "p50_ms": round(1e3 * p50, 3),
                "p99_ms": round(1e3 * p99, 3),
                "rows_per_s": round(bs / p50, 1),
            }
        section["measure_new_compiles"] = compilewatch.total_compiles() - c1
        section["swap"] = _bench_swap(packed, max_bucket)
    except Exception as e:  # pragma: no cover — serving must not kill bench
        section["error"] = f"{type(e).__name__}: {e}"
    return section


def _bench_swap(packed, warmup_rows, n_swaps=5):
    """Hot-swap cost (serve/fleet.py): swap a warmed SwappablePredictor
    to a sequence of same-shape "retrains" (leaf values perturbed, tree
    shapes unchanged) and report swap latency p50/p99 plus the XLA
    compiles the swaps cost.  The tree-shape compile-cache buckets make
    the contract swap_new_compiles == 0 — the regression gate fails the
    run on any violation (apply_regression_gate, serving-swap leg)."""
    from lightgbm_tpu.ops.predict import TreeArrays
    from lightgbm_tpu.serve.artifact import PredictorArtifact
    from lightgbm_tpu.serve.fleet import SwappablePredictor

    section = {}
    try:
        art = packed.artifact
        swapper = SwappablePredictor(packed, version=1)
        lat_ms, new_compiles = [], 0
        for i in range(n_swaps):
            fields = {f: np.asarray(getattr(art.arrays, f))
                      for f in TreeArrays.FIELDS}
            fields["leaf_value"] = fields["leaf_value"] * (1.0 + 1e-9 * (i + 1))
            retrain = PredictorArtifact(TreeArrays(**fields), art.meta)
            stats = swapper.swap_to(retrain, version=i + 2,
                                    warmup_max_rows=warmup_rows)
            lat_ms.append(stats["swap_ms"])
            new_compiles += stats["new_compiles"]
        lat_ms.sort()
        section = {
            "swaps": n_swaps,
            "swap_latency_p50_ms": round(lat_ms[len(lat_ms) // 2], 3),
            "swap_latency_p99_ms": round(
                lat_ms[min(len(lat_ms) - 1, int(0.99 * len(lat_ms)))], 3),
            "swap_new_compiles": int(new_compiles),
        }
    except Exception as e:  # pragma: no cover — swap must not kill bench
        section["error"] = f"{type(e).__name__}: {e}"
    return section


def _bench_linear(X, y, base_params):
    """linear_tree section (docs/TREES.md): trees-to-matched-quality A/B
    against constant leaves, plus v3 linear-artifact serving rows/s.

    Both boosters train the same rows/rounds; the A/B counts how many
    linear trees reach the CONSTANT model's final validation logloss
    (``Booster.predict(num_iteration=i)`` makes the scan free — no
    retrains).  ``trees_to_match_ratio`` is the acceptance number: the
    issue's contract is linear reaching constant quality with >=20%
    fewer trees, so the regression gate fails any capture above 0.8 —
    outright, the ratio is a quality-per-tree property of the math, not
    of the backend.  BENCH_LINEAR=0 skips; BENCH_LINEAR_ROWS /
    BENCH_LINEAR_ITERS resize."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.serve.artifact import PackedPredictor, PredictorArtifact

    section = {}
    try:
        rows = min(int(os.environ.get("BENCH_LINEAR_ROWS", 60_000)), len(X))
        iters = int(os.environ.get("BENCH_LINEAR_ITERS", 60))
        n_tr = int(rows * 0.8)
        Xt, yt = X[:n_tr], y[:n_tr]
        Xv, yv = X[n_tr:rows], y[n_tr:rows]
        params = {k: v for k, v in base_params.items()
                  if k not in ("tree_learner", "num_machines")}
        params.update(objective="binary", verbose=-1)
        section["rows"] = rows
        section["iters"] = iters

        def logloss(margin):
            p = 1.0 / (1.0 + np.exp(-np.asarray(margin, np.float64)))
            p = np.clip(p, 1e-15, 1 - 1e-15)
            return float(-np.mean(yv * np.log(p)
                                  + (1 - yv) * np.log(1 - p)))

        t0 = time.time()
        const = lgb.train(dict(params), lgb.Dataset(Xt, label=yt),
                          num_boost_round=iters, verbose_eval=False)
        section["const_train_s"] = round(time.time() - t0, 2)
        target = logloss(const.predict(Xv, raw_score=True))
        section["const_valid_logloss"] = round(target, 6)

        t0 = time.time()
        lin = lgb.train(dict(params, linear_tree=True, linear_lambda=0.01),
                        lgb.Dataset(Xt, label=yt),
                        num_boost_round=iters, verbose_eval=False)
        section["linear_train_s"] = round(time.time() - t0, 2)
        section["linear_valid_logloss"] = round(
            logloss(lin.predict(Xv, raw_score=True)), 6)

        matched = None
        for i in range(1, iters + 1):
            if logloss(lin.predict(Xv, raw_score=True,
                                   num_iteration=i)) <= target:
                matched = i
                break
        section["trees_to_match"] = matched
        section["trees_to_match_ratio"] = round(
            (matched if matched is not None else iters) / iters, 3)

        # v3 bucketed serving throughput (the artifact the A/B winner
        # actually ships): warm batch-2048 rows/s + compile accounting
        from lightgbm_tpu.obs import compilewatch

        packed = PackedPredictor(PredictorArtifact.from_booster(lin))
        bs = min(2048, rows)
        batch = np.ascontiguousarray(Xt[:bs], np.float64)
        packed.predict(batch)  # warm the bucket
        c0 = compilewatch.total_compiles()
        lat = []
        for _ in range(10):
            t0 = time.time()
            packed.predict(batch)
            lat.append(time.time() - t0)
        lat.sort()
        section["serve_batch_rows"] = bs
        section["serve_rows_per_s"] = round(bs / lat[len(lat) // 2], 1)
        section["serve_new_compiles"] = compilewatch.total_compiles() - c0
    except Exception as e:  # pragma: no cover — A/B must not kill bench
        section["error"] = f"{type(e).__name__}: {e}"
    return section


def _bench_quantized(booster, X, batch_sizes=(1, 128, 2048), reps=20):
    """Quantized-serving A/B (docs/SERVING.md): exact vs int16
    rank-quantized predictor at fixed batch sizes, the artifact size of
    both flavors, the measured leaf-narrowing drift against its
    documented bound, and the quantized same-shape hot-swap compile
    count (must be 0, same contract as the exact swap leg)."""
    import io

    from lightgbm_tpu.ops.predict import TreeArrays
    from lightgbm_tpu.ops.qpredict import drift_bound
    from lightgbm_tpu.serve.artifact import PackedPredictor, PredictorArtifact
    from lightgbm_tpu.serve.fleet import SwappablePredictor

    section = {}
    try:
        exact_art = PredictorArtifact.from_booster(booster)
        quant_art = exact_art.quantize()

        def _file_bytes(a):
            buf = io.BytesIO()
            a.save_to_bytes(buf)
            return len(buf.getvalue())

        def _payload_bytes(a):
            return int(sum(arr.nbytes for arr in a._payload().values()))

        exact = PackedPredictor(exact_art, quantized=False)
        quant = PackedPredictor(quant_art)
        section["artifact_bytes"] = {
            "exact_file": _file_bytes(exact_art),
            "quantized_file": _file_bytes(quant_art),
            "exact_payload": _payload_bytes(exact_art),
            "quantized_payload": _payload_bytes(quant_art),
            "exact_device": exact.device_bytes,
            "quantized_device": quant.device_bytes,
            "payload_ratio": round(_payload_bytes(exact_art)
                                   / max(_payload_bytes(quant_art), 1), 2),
            "device_ratio": round(exact.device_bytes
                                  / max(quant.device_bytes, 1), 2),
        }
        max_bucket = max(batch_sizes)
        exact.warmup(max_bucket)
        quant.warmup(max_bucket)
        sample = np.ascontiguousarray(X[:min(2048, X.shape[0])], np.float64)
        diff = float(np.abs(quant.predict(sample, raw_score=True)
                            - exact.predict(sample, raw_score=True)).max())
        bound = drift_bound(exact_art.arrays.leaf_value)
        section["drift"] = {"max_abs": diff, "bound": bound,
                            "within_bound": bool(diff <= bound)}
        for bs in batch_sizes:
            bs = min(bs, X.shape[0])
            rows = np.ascontiguousarray(X[:bs], np.float64)
            sub = {}
            for name, p in (("exact", exact), ("quantized", quant)):
                lat = []
                for _ in range(reps):
                    t0 = time.time()
                    p.predict(rows)
                    lat.append(time.time() - t0)
                lat.sort()
                p50 = lat[len(lat) // 2]
                sub[name] = {
                    "p50_ms": round(1e3 * p50, 3),
                    "p99_ms": round(
                        1e3 * lat[min(len(lat) - 1, int(0.99 * len(lat)))],
                        3),
                    "rows_per_s": round(bs / p50, 1),
                }
            sub["speedup"] = round(sub["quantized"]["rows_per_s"]
                                   / max(sub["exact"]["rows_per_s"], 1e-9), 3)
            section[f"batch{bs}"] = sub
        # quantized same-shape hot swap: zero new XLA compiles
        swapper = SwappablePredictor(quant, version=1)
        lat_ms, new_compiles = [], 0
        for i in range(3):
            fields = {f: np.asarray(getattr(exact_art.arrays, f))
                      for f in TreeArrays.FIELDS}
            fields["leaf_value"] = fields["leaf_value"] * (1.0 + 1e-4 * (i + 1))
            retrain = PredictorArtifact(
                TreeArrays(**fields), exact_art.meta).quantize()
            stats = swapper.swap_to(retrain, version=i + 2,
                                    warmup_max_rows=max_bucket)
            lat_ms.append(stats["swap_ms"])
            new_compiles += stats["new_compiles"]
        lat_ms.sort()
        section["swap"] = {
            "swaps": 3,
            "swap_latency_p50_ms": round(lat_ms[len(lat_ms) // 2], 3),
            "swap_new_compiles": int(new_compiles),
        }
    except Exception as e:  # pragma: no cover — must not kill bench
        section["error"] = f"{type(e).__name__}: {e}"
    return section


def _bench_multimodel(booster, X, n_models=4, reps=10, batch=128):
    """Multi-model bin-packing (docs/SERVING.md): N models behind named
    routes on ONE server process, per-model rows/s through the full
    HTTP + microbatch path, the shared device-bytes admission ledger,
    and a budget-refusal probe (the loud-failure contract)."""
    import json as _json
    import shutil
    import tempfile
    import threading
    import urllib.request

    from lightgbm_tpu.ops.predict import TreeArrays
    from lightgbm_tpu.serve.artifact import PredictorArtifact
    from lightgbm_tpu.serve.registry import ModelRegistry
    from lightgbm_tpu.serve.server import make_server

    section = {}
    tmp = tempfile.mkdtemp(prefix="ltpu-bench-mm-")
    srv = None
    try:
        art = PredictorArtifact.from_booster(booster)
        reg = ModelRegistry(os.path.join(tmp, "reg"))
        reg.publish(art)  # v1 = the default route
        routes = []
        for i in range(n_models - 1):
            fields = {f: np.asarray(getattr(art.arrays, f))
                      for f in TreeArrays.FIELDS}
            fields["leaf_value"] = fields["leaf_value"] * (1.0 + 0.1 * (i + 1))
            retrain = PredictorArtifact(TreeArrays(**fields), art.meta)
            if i % 2 == 0:  # alternate flavors to prove they co-pack
                retrain = retrain.quantize()
            v = reg.publish(retrain, activate=False)
            name = f"m{i + 1}"
            reg.set_route(name, v)
            routes.append(name)
        srv = make_server(registry_dir=reg.dir, port=0,
                          warmup_max_rows=batch, max_delay_ms=1.0,
                          registry_poll_ms=10_000.0)
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        port = srv.server_address[1]
        rows = np.ascontiguousarray(X[:batch], np.float64)
        body = "\n".join(
            _json.dumps([float(v) for v in r]) for r in rows).encode()

        def _rows_per_s(path):
            lat = []
            for _ in range(reps):
                t0 = time.time()
                urllib.request.urlopen(
                    f"http://127.0.0.1:{port}{path}", data=body,
                    timeout=60).read()
                lat.append(time.time() - t0)
            lat.sort()
            return round(len(rows) / lat[len(lat) // 2], 1)

        per_model = {"default": _rows_per_s("/predict")}
        for name in routes:
            per_model[name] = _rows_per_s(f"/predict/{name}")
        section = {
            "n_models": n_models,
            "per_model_rows_per_s": per_model,
            "device_bytes_used": srv.device_bytes_used(),
        }
        # admission-refusal probe: a budget below the current usage must
        # refuse the next route loudly and leave the admitted ones alone
        srv.route_budget_bytes = srv.device_bytes_used() + 1
        reg.set_route("overbudget", 1)
        srv.sync_routes()
        refused = "overbudget" in srv.admission_refused
        still_serving = all(r in srv.routes for r in routes)
        section["admission_refusal_ok"] = bool(refused and still_serving)
    except Exception as e:  # pragma: no cover — must not kill bench
        section["error"] = f"{type(e).__name__}: {e}"
    finally:
        if srv is not None:
            srv.shutdown()
            srv.server_close()
        shutil.rmtree(tmp, ignore_errors=True)
    return section


def _bench_ingest(X, y, n_rows):
    """Streaming-ingest benchmark (docs/DATA.md): write the bench matrix
    as CSV, stream it through the two-pass out-of-core pipeline, and
    report rows/s, chunk count and the peak-RSS bound that proves the
    raw float matrix was never materialized (the acceptance contract:
    peak RSS - start RSS < packed matrix + O(chunk), asserted via the
    obs memory gauges that data/ingest.py records).  BENCH_INGEST=0
    skips, BENCH_INGEST_ROWS caps the row count."""
    import tempfile

    from lightgbm_tpu.basic import Dataset

    section = {}
    rows = min(int(os.environ.get("BENCH_INGEST_ROWS", n_rows)), len(X))
    path = os.path.join(
        os.environ.get("BENCH_INGEST_DIR", tempfile.gettempdir()),
        f"bench_ingest_{rows}.csv",
    )
    try:
        t0 = time.time()
        import pandas as pd

        pd.DataFrame(np.column_stack([y[:rows], X[:rows]])).to_csv(
            path, index=False, header=False, float_format="%.7g"
        )
        section["write_csv_s"] = round(time.time() - t0, 2)
        section["csv_mb"] = round(os.path.getsize(path) / 1e6, 1)

        env_before = os.environ.get("LIGHTGBM_TPU_STREAM_INGEST")
        os.environ["LIGHTGBM_TPU_STREAM_INGEST"] = "1"
        try:
            t0 = time.time()
            ds = Dataset(path).construct()
            ingest_s = time.time() - t0
        finally:
            if env_before is None:
                os.environ.pop("LIGHTGBM_TPU_STREAM_INGEST", None)
            else:
                os.environ["LIGHTGBM_TPU_STREAM_INGEST"] = env_before
        rep = dict(getattr(ds, "ingest_report", {}))
        section.update({
            "rows": rows,
            "ingest_s": round(ingest_s, 2),
            "rows_per_s": round(rows / max(ingest_s, 1e-9), 1),
            "chunks": rep.get("chunks_pass2"),
            "chunk_rows": rep.get("chunk_rows"),
            "packed_mb": rep.get("packed_mb"),
            "rss_start_mb": rep.get("rss_start_mb"),
            "rss_peak_mb": rep.get("rss_peak_mb"),
            "sketch": rep.get("sketch"),
        })
        # the bound: packed matrix + a few in-flight chunk buffers
        # (parser scratch included) + fixed slack.  The raw float64
        # matrix would be rows*cols*8 bytes — reported alongside so the
        # separation is visible at a glance.
        chunk_raw_mb = (rep.get("chunk_rows", 0) * (X.shape[1] + 1) * 8) / 1e6
        bound_mb = (rep.get("packed_mb", 0.0) or 0.0) + 8 * chunk_raw_mb + 128
        increase = (rep.get("rss_peak_mb", 0.0) or 0.0) - (
            rep.get("rss_start_mb", 0.0) or 0.0
        )
        section["raw_matrix_mb"] = round(rows * (X.shape[1] + 1) * 8 / 1e6, 1)
        section["rss_increase_mb"] = round(increase, 1)
        section["rss_bound_mb"] = round(bound_mb, 1)
        section["rss_bound_ok"] = bool(increase <= bound_mb)
    except Exception as e:  # pragma: no cover — ingest must not kill bench
        section["error"] = f"{type(e).__name__}: {e}"
    finally:
        if os.environ.get("BENCH_INGEST_KEEP", "0") != "1":
            try:
                os.unlink(path)
            except OSError:
                pass
    return section


def _bench_checkpoint(X, y, base_params):
    """Checkpoint subsystem benchmark (docs/CHECKPOINT.md): save latency
    p50/p99, checkpoint bytes, and the per-iteration overhead of
    background-write checkpointing at freq in {0, 10, 1} on the standard
    bench config (acceptance: freq=10 overhead < 5%).  BENCH_CKPT=0
    skips; BENCH_CKPT_ROWS / BENCH_CKPT_ITERS resize."""
    import shutil
    import tempfile

    import lightgbm_tpu as lgb
    from lightgbm_tpu.ckpt import CheckpointManager

    section = {}
    rows = min(int(os.environ.get("BENCH_CKPT_ROWS", 200_000)), len(X))
    iters = int(os.environ.get("BENCH_CKPT_ITERS", 30))
    Xb, yb = X[:rows], y[:rows]
    try:
        # warmup run compiles the train programs so the freq=0 baseline
        # isn't charged for compilation the other configs then reuse
        lgb.train(dict(base_params), lgb.Dataset(Xb, label=yb,
                  params=dict(base_params)), 3, verbose_eval=False)
        times = {}
        stats10 = None
        for freq in (0, 10, 1):
            d = tempfile.mkdtemp(prefix="bench_ckpt_")
            mgr = CheckpointManager(d, freq=freq) if freq > 0 else None
            ds = lgb.Dataset(Xb, label=yb, params=dict(base_params))
            t0 = time.time()
            lgb.train(dict(base_params), ds, iters, verbose_eval=False,
                      checkpoint_manager=mgr)
            times[freq] = time.time() - t0
            if mgr is not None:
                mgr.close()
                if freq == 10:
                    stats10 = dict(mgr.stats)
            shutil.rmtree(d, ignore_errors=True)
        base = max(times[0], 1e-9)
        section = {
            "rows": rows,
            "iters": iters,
            "total_s": {f"freq{k}": round(v, 3) for k, v in times.items()},
            "overhead_freq10_pct": round(100.0 * (times[10] - base) / base, 2),
            "overhead_freq1_pct": round(100.0 * (times[1] - base) / base, 2),
        }
        if stats10:
            lat = sorted(stats10["save_s"])
            if lat:
                section["save_p50_ms"] = round(1e3 * lat[len(lat) // 2], 2)
                section["save_p99_ms"] = round(
                    1e3 * lat[min(len(lat) - 1, int(0.99 * len(lat)))], 2
                )
            section["ckpt_bytes"] = stats10["bytes"]
            section["saves_freq10"] = stats10["saves"]
    except Exception as e:  # pragma: no cover — ckpt must not kill bench
        section["error"] = f"{type(e).__name__}: {e}"
    return section


def _bench_ooc(X, y, base_params):
    """Out-of-core streaming benchmark (docs/DATA.md "Out-of-core
    training"): streamed vs resident s/iter over the same rows, prefetch
    overlap (how much of the host->device fetch hid behind compute), and
    the bounded-residency check (peak in-flight chunks <= ring depth, the
    O(2 chunks) contract).  BENCH_OOC=0 skips; BENCH_OOC_ROWS /
    BENCH_OOC_ITERS / BENCH_OOC_CHUNK_ROWS resize.  Model parity at this
    scale is informational only — the byte-identity contract is pinned at
    masked-scan scale by tests/test_ooc.py."""
    import lightgbm_tpu as lgb

    section = {}
    rows = min(int(os.environ.get("BENCH_OOC_ROWS", 200_000)), len(X))
    iters = int(os.environ.get("BENCH_OOC_ITERS", 10))
    chunk_rows = int(os.environ.get("BENCH_OOC_CHUNK_ROWS", 65_536))
    Xb, yb = X[:rows], y[:rows]
    P_mem = dict(base_params, out_of_core="false")
    P_ooc = dict(base_params, out_of_core="true", ooc_chunk_rows=chunk_rows)
    try:
        # warmup compiles both program sets so neither timed leg pays it
        for P in (P_mem, P_ooc):
            lgb.train(dict(P), lgb.Dataset(Xb, label=yb, params=dict(P)),
                      2, verbose_eval=False)
        t0 = time.time()
        b_mem = lgb.train(dict(P_mem),
                          lgb.Dataset(Xb, label=yb, params=dict(P_mem)),
                          iters, verbose_eval=False)
        mem_s = time.time() - t0
        t0 = time.time()
        b_ooc = lgb.train(dict(P_ooc),
                          lgb.Dataset(Xb, label=yb, params=dict(P_ooc)),
                          iters, verbose_eval=False)
        ooc_s = time.time() - t0
        ooc = b_ooc.boosting.ooc
        st = ooc.stats.as_dict()
        section = {
            "rows": rows,
            "iters": iters,
            "chunk_rows": ooc.plan.chunk_rows,
            "chunks": ooc.plan.num_chunks,
            "prefetch_depth": ooc.depth,
            "resident_s_per_iter": round(mem_s / iters, 4),
            "stream_s_per_iter": round(ooc_s / iters, 4),
            "stream_vs_resident": round(ooc_s / max(mem_s, 1e-9), 3),
            "stream_rows_per_s": round(rows * iters / max(ooc_s, 1e-9)),
            "streamed_mb": round(st["bytes"] / 1e6, 1),
            "overlap_pct": st["overlap_pct"],
            "fetch_s": st["fetch_s"],
            "stall_s": st["stall_s"],
            "peak_inflight": st["peak_inflight"],
            "residency_ok": bool(st["peak_inflight"] <= ooc.depth),
            "models_match": bool(
                b_mem.model_to_string() == b_ooc.model_to_string()),
        }
    except Exception as e:  # pragma: no cover — ooc must not kill bench
        section["error"] = f"{type(e).__name__}: {e}"
    return section


def _bench_factory(X, y):
    """Continuous-training factory benchmark (docs/FACTORY.md): the
    append->promoted end-to-end latency of one warm-started cycle
    (canary off — the watcher/retrain/publish/promote path itself), the
    warm-start cost against a tree-count-matched cold retrain over the
    same data, and the canary-window plumbing overhead (replica spawn +
    bounded observation window + teardown, measured against an idle
    proxy with min_requests=0).  BENCH_FACTORY=0 skips;
    BENCH_FACTORY_ROWS resizes."""
    import shutil
    import tempfile
    import threading

    from lightgbm_tpu.factory import FactorySupervisor
    from lightgbm_tpu.serve.fleet import FleetProxy, _free_ports

    section = {}
    rows = min(int(os.environ.get("BENCH_FACTORY_ROWS", 8_000)), len(X))
    rounds = 10
    knobs = {"num_boost_round": rounds, "checkpoint_freq": 5,
             "debounce_ms": 0.0, "canary_fraction": 0.0}
    params = {"objective": "binary", "num_leaves": 31, "verbose": -1,
              "min_data_in_leaf": 5}
    root = tempfile.mkdtemp(prefix="bench_factory_")

    def write_chunk(data_dir, name, lo, hi):
        path = os.path.join(data_dir, name)
        with open(path, "a") as f:
            np.savetxt(f, np.column_stack([y[lo:hi], X[lo:hi]]),
                       fmt="%.6g", delimiter=",")
        t = time.time() - 60  # out of the debounce window
        os.utime(path, (t, t))

    try:
        data_dir = os.path.join(root, "data")
        os.makedirs(data_dir)
        write_chunk(data_dir, "chunk-000.csv", 0, rows // 2)
        sup = FactorySupervisor(data_dir, os.path.join(root, "work"),
                                os.path.join(root, "reg"),
                                params=dict(params), **knobs)
        t0 = time.time()
        v1 = sup.run_cycle()
        bootstrap_s = time.time() - t0
        # the headline number: a chunk append -> warm retrain -> publish
        # -> eval gate -> activate, end to end
        write_chunk(data_dir, "chunk-001.csv", rows // 2, rows)
        t0 = time.time()
        v2 = sup.run_cycle()
        warm_s = time.time() - t0
        # cold control at the same final tree count (v1's rounds + the
        # warm delta) over the same data — what skipping the warm start
        # would have cost
        cold = FactorySupervisor(data_dir, os.path.join(root, "work2"),
                                 os.path.join(root, "reg2"),
                                 params=dict(params),
                                 **dict(knobs, num_boost_round=2 * rounds))
        t0 = time.time()
        vc = cold.run_cycle()
        cold_s = time.time() - t0
        section = {
            "rows": rows,
            "num_boost_round": rounds,
            "bootstrap_cycle_s": round(bootstrap_s, 3),
            "append_to_promoted_s": round(warm_s, 3),
            "warm_start": bool(v2["warm_start"]),
            "cold_equivalent_s": round(cold_s, 3),
            "warm_vs_cold_speedup": round(cold_s / max(warm_s, 1e-9), 3),
            "verdicts_ok": bool(
                v1["verdict"] == v2["verdict"] == vc["verdict"]
                == "promoted"),
        }
        # canary-window overhead: the same cycle shape with the canary
        # plumbing live (pin-version replica spawn + observe window +
        # teardown) against an idle proxy; min_requests=0 keeps the
        # verdict a promote so the two latencies are comparable
        if os.environ.get("BENCH_FACTORY_CANARY", "1") != "0":
            # the proxy only serves its local /fleet/canary endpoint
            # here; its one "backend" is a dead address no /predict ever
            # routes through
            proxy = FleetProxy(("127.0.0.1", 0),
                               [f"127.0.0.1:{_free_ports(1)[0]}"],
                               health_poll_s=0.5, retry_deadline_s=5.0)
            threading.Thread(target=proxy.serve_forever,
                             daemon=True).start()
            try:
                write_chunk(data_dir, "chunk-002.csv", 0, rows // 4)
                csup = FactorySupervisor(
                    data_dir, os.path.join(root, "work"),
                    os.path.join(root, "reg"), params=dict(params),
                    proxy=f"127.0.0.1:{proxy.server_address[1]}",
                    **dict(knobs, canary_fraction=0.25, observe_s=1.0,
                           min_requests=0))
                # the canary replica is a child process and this process
                # holds the chip: the child inherits a CPU pin (the leg
                # times plumbing, not the replica's device)
                plat = os.environ.get("JAX_PLATFORMS")
                os.environ["JAX_PLATFORMS"] = "cpu"
                try:
                    t0 = time.time()
                    v3 = csup.run_cycle()
                    canary_s = time.time() - t0
                finally:
                    if plat is None:
                        del os.environ["JAX_PLATFORMS"]
                    else:
                        os.environ["JAX_PLATFORMS"] = plat
                section["canary_cycle_s"] = round(canary_s, 3)
                section["canary_overhead_s"] = round(canary_s - warm_s, 3)
                section["canary_verdict"] = v3["verdict"]
            finally:
                proxy.shutdown()
                proxy.server_close()
    except Exception as e:  # pragma: no cover — factory must not kill bench
        section["error"] = f"{type(e).__name__}: {e}"
    finally:
        shutil.rmtree(root, ignore_errors=True)
    return section


def _bench_serving_tail(booster, X):
    """Serving-tail benchmark (docs/ROBUSTNESS.md): hedged vs unhedged
    client p99 through a 3-replica subprocess fleet whose first replica
    is wounded with an injected per-request delay via
    ``LIGHTGBM_TPU_SERVE_FAULT`` — the gray-failure scenario the hedging
    + breaker machinery exists for.  Three proxy legs over the same
    fleet: healthy (clean replicas only), chaos unhedged, chaos hedged.
    The hedged-chaos-over-healthy p99 ratio is protocol-level (the
    injected delay dominates any backend's own latency), so it is the
    device-independent leg of the regression gate.  BENCH_SERVING_TAIL=0
    skips; BENCH_SERVING_TAIL_REQS resizes the per-leg request count."""
    import shutil
    import tempfile
    import threading
    import urllib.request

    from lightgbm_tpu.serve import ModelRegistry, PredictorArtifact
    from lightgbm_tpu.serve.fleet import (FleetProxy, _wait_ready,
                                          spawn_replicas)

    section = {}
    reps = int(os.environ.get("BENCH_SERVING_TAIL_REQS", 90))
    delay_ms = 300.0
    hedge_ms = 25.0
    root = tempfile.mkdtemp(prefix="bench_servetail_")
    procs = []

    def p99(lats):
        vals = sorted(lats)
        return vals[min(len(vals) - 1, int(0.99 * len(vals)))]

    def measure(backends, hedge_delay_ms):
        proxy = FleetProxy(("127.0.0.1", 0), backends,
                           health_poll_s=0.2, retry_deadline_s=20.0,
                           backend_timeout_s=5.0,
                           hedge_delay_ms=hedge_delay_ms,
                           hedge_budget_pct=100.0)
        threading.Thread(target=proxy.serve_forever, daemon=True).start()
        lats = []
        try:
            url = f"http://127.0.0.1:{proxy.server_address[1]}/predict"
            for _ in range(reps):
                req = urllib.request.Request(url, data=body)
                req.add_header("X-Deadline-Ms", "15000")
                t0 = time.perf_counter()
                urllib.request.urlopen(req, timeout=60).read()
                lats.append(time.perf_counter() - t0)
            return lats, proxy.stats()
        finally:
            proxy.shutdown()
            proxy.server_close()

    try:
        reg_dir = os.path.join(root, "reg")
        ModelRegistry(reg_dir).publish(
            PredictorArtifact.from_booster(booster))
        # replicas are pinned to CPU: the tail numbers are protocol-
        # level (delay-dominated), and the bench's own device stays free
        cpu = {"JAX_PLATFORMS": "cpu"}
        procs = spawn_replicas(
            3, {"registry": reg_dir, "warmup_max_rows": "64",
                "max_delay_ms": "1", "registry_poll_ms": "200"},
            envs=[dict(cpu, LIGHTGBM_TPU_SERVE_FAULT=f"delay:{delay_ms:g}"),
                  dict(cpu), dict(cpu)])
        for _, port in procs:
            if not _wait_ready("127.0.0.1", port, 180.0):
                raise RuntimeError(f"replica on port {port} never ready")
        addrs = [f"127.0.0.1:{p}" for _, p in procs]
        body = "\n".join(json.dumps(list(map(float, r)))
                         for r in np.asarray(X[:2], float)).encode()

        healthy_lats, _ = measure(addrs[1:], -1.0)
        unhedged_lats, _ = measure(addrs, -1.0)
        hedged_lats, hst = measure(addrs, hedge_ms)

        # the ratio denominator is floored: a microsecond-fast healthy
        # fleet would otherwise turn the fixed hedge delay into a huge
        # "slowdown" that says nothing about tail behavior
        floor_s = 0.020
        healthy_p99 = p99(healthy_lats)
        denom = max(healthy_p99, floor_s)
        section = {
            "requests_per_leg": reps,
            "injected_delay_ms": delay_ms,
            "hedge_delay_ms": hedge_ms,
            "gate_floor_ms": round(1e3 * floor_s, 1),
            "healthy_p99_ms": round(1e3 * healthy_p99, 2),
            "unhedged_chaos_p99_ms": round(1e3 * p99(unhedged_lats), 2),
            "hedged_chaos_p99_ms": round(1e3 * p99(hedged_lats), 2),
            "unhedged_chaos_over_healthy_p99": round(
                p99(unhedged_lats) / denom, 3),
            "hedged_chaos_over_healthy_p99": round(
                p99(hedged_lats) / denom, 3),
            "hedges_launched": hst["hedges"]["launched"],
            "hedge_wins": hst["hedges"]["wins"],
        }
    except Exception as e:  # pragma: no cover — tail bench must not kill bench
        section["error"] = f"{type(e).__name__}: {e}"
    finally:
        for p, _ in procs:
            p.kill()
        for p, _ in procs:
            try:
                p.wait(timeout=30)
            except Exception:
                pass
        shutil.rmtree(root, ignore_errors=True)
    return section


def _bench_kernel_ab():
    """Kernel-level A/B microbenches (PR-6 speed push), interpreted off
    the TPU (where only the parity columns mean anything): (1) one multi-leaf
    hist_segments launch vs per-leaf hist_dyn launches, (2) the score-only
    band settle vs the old full update+hist settle, (3) GOSS's
    histogram-free gradient-prep pass vs the old discarded-histogram
    pass, (4) the tuned one-hot fchunk vs the legacy 512//B rule (cost
    model — fchunk is bit-invariant so only the MXU row count changes).
    Every A/B also reports the max abs diff of the results it compares
    so the wins are demonstrated WITH parity, not instead of it."""
    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.ops import histogram_pallas as hp
    from lightgbm_tpu.ops import pkernels as pk

    interp = jax.default_backend() != "tpu"
    section = {"interpret_mode": interp}
    reps = int(os.environ.get("BENCH_KERNEL_AB_REPS", 3))

    def timed(fn):
        fn()  # warm (compile)
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best

    try:
        rng = np.random.RandomState(3)
        n, f, b, L = 32768, 16, 32, 8
        lay = pk.PLayout(f)
        bins = rng.randint(0, b, size=(n, f)).astype(np.uint8)
        P = pk.pack_matrix(bins, lay, label=(rng.rand(n) < 0.5).astype(np.float32))
        g = rng.randn(n).astype(np.float32)
        h = np.abs(rng.randn(n)).astype(np.float32)
        P = P.at[lay.G, :n].set(jnp.asarray(g.view(np.int32)))
        P = P.at[lay.H, :n].set(jnp.asarray(h.view(np.int32)))

        # ---- (1) multi-leaf level histograms: L launches -> 1 launch
        edges = np.linspace(0, n, L + 1).astype(np.int32)
        segs = np.stack([edges[:-1], edges[1:] - edges[:-1]], 1).astype(np.int32)
        segs_j = jnp.asarray(segs)

        def per_leaf():
            outs = [
                pk.hist_dyn(P, int(s), int(c), f, b, rows=lay.rows,
                            interpret=interp)
                for s, c in segs
            ]
            jax.block_until_ready(outs)
            return outs

        def multi():
            out = hp.hist_segments(P, segs_j, L, num_features=f, num_bins=b,
                                   rows=lay.rows, smax=L, interpret=interp)
            jax.block_until_ready(out)
            return out

        t_per, t_multi = timed(per_leaf), timed(multi)
        diff = float(np.abs(
            np.stack([np.asarray(x) for x in per_leaf()]) - np.asarray(multi())
        ).max())
        section["multi_leaf_hist"] = {
            "launches_per_level_before": L,
            "launches_per_level_after": 1,
            "per_leaf_s": round(t_per, 4),
            "one_launch_s": round(t_multi, 4),
            "speedup": round(t_per / max(t_multi, 1e-9), 2),
            "max_abs_diff": diff,
            # the win this can buy on the device is the per-launch
            # fixed cost (not measured on this machine) x (leaves-1) per
            # level; interpret mode can only demonstrate compute parity
            "note": "device win = per-launch fixed cost x (L-1)/level",
        }

        # ---- (2) chunk-end settle: full update+hist pass -> band settle
        delta = rng.randn(n).astype(np.float32)

        def grad_fn(score, label, weight):
            ps = 1.0 / (1.0 + jnp.exp(-score))
            return (ps - label) * weight, ps * (1.0 - ps) * weight

        def settle_full():
            p2, _ = pk.update_and_root_hist(
                jnp.array(P), lay, grad_fn, delta=jnp.asarray(delta),
                num_rows=n, num_features=f, num_bins=b, interpret=interp)
            jax.block_until_ready(p2)
            return p2

        def settle_band():
            p2 = pk.score_add(jnp.array(P), lay, jnp.asarray(delta), 0,
                              num_rows=n, interpret=interp)
            jax.block_until_ready(p2)
            return p2

        t_full, t_band = timed(settle_full), timed(settle_band)
        s_full = np.asarray(settle_full())[lay.SCORE, :n]
        s_band = np.asarray(settle_band())[lay.SCORE, :n]
        section["score_settle"] = {
            "full_pass_s": round(t_full, 4),
            "band_settle_s": round(t_band, 4),
            "speedup": round(t_full / max(t_band, 1e-9), 2),
            "scores_bit_identical": bool(np.array_equal(s_full, s_band)),
        }

        # ---- (3) GOSS gradient prep: discarded-histogram pass -> hist-free
        def prep(with_hist):
            def run():
                p2, _ = pk.update_and_root_hist(
                    jnp.array(P), lay, grad_fn, delta=jnp.asarray(delta),
                    num_rows=n, num_features=f, num_bins=b,
                    with_hist=with_hist, interpret=interp)
                jax.block_until_ready(p2)
                return p2
            return run

        t_hist, t_free = timed(prep(True)), timed(prep(False))
        a, c = np.asarray(prep(True)()), np.asarray(prep(False)())
        section["goss_prep"] = {
            "with_hist_s": round(t_hist, 4),
            "hist_free_s": round(t_free, 4),
            "speedup": round(t_hist / max(t_free, 1e-9), 2),
            "matrix_bit_identical": bool(np.array_equal(a, c)),
        }

        # ---- (4) tuned one-hot fchunk (bit-invariant; cost model)
        bench_f, bench_b = 28, 63  # the 1Mx28 max_bin=63 bench shape
        legacy = max(1, min(bench_f, 512 // bench_b))
        tuned = hp.tune_fchunk(bench_f, bench_b)
        section["hist_fchunk"] = {
            "shape": f"F={bench_f} B={bench_b}",
            "legacy": legacy,
            "tuned": tuned,
            "est_mxu_rows_legacy": hp.fchunk_cost(bench_f, bench_b, legacy),
            "est_mxu_rows_tuned": hp.fchunk_cost(bench_f, bench_b, tuned),
        }

        # ---- (5) int32 vs f32 histogram accumulation (quantized
        # training, non-gating): same blocked one-hot contraction, int16
        # values with preferred_element_type=int32.  The A/B's real story
        # is the exactness column: the int path is row-order INVARIANT
        # (integer adds are associative) where the f32 path is not, and
        # the Pallas int kernel matches the XLA int path bit for bit —
        # the f32 kernel only matches to float tolerance.
        from lightgbm_tpu.ops import qhist
        from lightgbm_tpu.ops.histogram import build_histogram

        sel = jnp.ones((n,), jnp.float32)
        gj, hj = jnp.asarray(g), jnp.asarray(h)
        scales = qhist.scales_from_max(float(np.abs(g).max()),
                                       float(np.abs(h).max()),
                                       qhist.QUANT_BITS)
        qg, qh2 = qhist.quantize_rows(gj, hj, jnp.asarray(scales),
                                      np.uint32(1), qhist.QUANT_BITS)
        bj = jnp.asarray(bins)

        def acc_f32():
            out = build_histogram(bj, gj, hj, sel, b)
            jax.block_until_ready(out)
            return out

        def acc_int():
            out = build_histogram(bj, qg, qh2, sel, b)
            jax.block_until_ready(out)
            return out

        t_f32a, t_inta = timed(acc_f32), timed(acc_int)
        # row-order invariance: shuffle the rows, rebuild, compare bytes
        perm = rng.permutation(n)
        hist_i = np.asarray(acc_int())
        hist_ip = np.asarray(build_histogram(
            bj[perm], qg[perm], qh2[perm], sel, b))
        hist_f = np.asarray(acc_f32())
        hist_fp = np.asarray(build_histogram(
            bj[perm], gj[jnp.asarray(perm)], hj[jnp.asarray(perm)], sel, b))
        # Pallas interpret-mode parity of the int kernel vs the XLA path
        Pq = hp.pack_columns_q(bj, qg, qh2, sel)
        pall_q = np.asarray(hp.hist_segment_q(
            Pq, jnp.int32(0), jnp.int32(n), num_features=f, num_bins=b,
            interpret=True))
        section["quantized_hist_accum"] = {
            "f32_s": round(t_f32a, 4),
            "int32_s": round(t_inta, 4),
            "speedup": round(t_f32a / max(t_inta, 1e-9), 2),
            "int_row_order_invariant": bool(
                np.array_equal(hist_i, hist_ip)),
            "f32_row_order_invariant": bool(
                np.array_equal(hist_f, hist_fp)),
            "pallas_int_bit_identical_to_xla": bool(
                np.array_equal(pall_q, hist_i)),
            "dequant_max_abs_err": float(np.abs(
                np.asarray(qhist.dequantize_hist(
                    jnp.asarray(hist_i), jnp.asarray(scales))) - hist_f
            ).max()),
            "note": "non-gating; exactness columns are the contract",
        }
    except Exception as e:  # pragma: no cover — A/B must not kill bench
        section["error"] = f"{type(e).__name__}: {e}"
    return section


def _bench_comms():
    """Comms-volume A/B of the three distributed tree learners
    (docs/PARALLEL.md) on a synthetic WIDE matrix (>= 2000 features):
    purpose-tagged bytes/iter and s/iter per learner over an in-process
    2-rank LocalComm group (parallel/comm.py) — the same learner code
    the KV transport drives, minus the network, so the byte ledger is
    exact protocol arithmetic.  The voting-vs-data payload ratio is
    deterministic and device-independent (it gates outright); the
    s/iter numbers are device-bound.
    BENCH_COMMS=0 skips; BENCH_COMMS_FEATURES / BENCH_COMMS_ROWS /
    BENCH_COMMS_ITERS / BENCH_COMMS_TOPK resize."""
    import threading

    import jax.numpy as jnp

    from lightgbm_tpu.ops.grow import GrowParams
    from lightgbm_tpu.ops.split import FeatureMeta, SplitHyper
    from lightgbm_tpu.parallel import HostParallelLearner, LocalGroup

    F = int(os.environ.get("BENCH_COMMS_FEATURES", 2000))
    n = int(os.environ.get("BENCH_COMMS_ROWS", 3000))
    iters = int(os.environ.get("BENCH_COMMS_ITERS", 2))
    top_k = int(os.environ.get("BENCH_COMMS_TOPK", 20))
    B, R = 16, 2
    try:
        rng = np.random.RandomState(23)
        bins = rng.randint(0, B, size=(n, F)).astype(np.uint8)
        grad = (bins[:, :8].astype(np.float32)
                @ rng.randn(8).astype(np.float32) / B
                + 0.05 * rng.randn(n).astype(np.float32)
                ).astype(np.float32)
        hess = np.ones(n, np.float32)
        meta = FeatureMeta(jnp.full((F,), B, jnp.int32),
                           jnp.zeros((F,), jnp.int32),
                           jnp.zeros((F,), bool))
        hyper = SplitHyper(jnp.float32(0.0), jnp.float32(0.1),
                           jnp.float32(20.0), jnp.float32(1e-3),
                           jnp.float32(0.0))
        fmask = jnp.ones((F,), jnp.float32)
        # small row_block: the histogram one-hot tile is
        # row_block x (F*B) f32 — the default 4096 rows would be 1 GB
        # at F=2000
        params = GrowParams(num_leaves=15, num_bins=B, row_block=256,
                            top_k=top_k)
        params_q = params._replace(quantized=True)
        cut = n // 2

        def run(mode, quantized=False):
            sh = ([(bins, grad, hess)] * R if mode == "feature"
                  else [(bins[:cut], grad[:cut], hess[:cut]),
                        (bins[cut:], grad[cut:], hess[cut:])])
            grp = LocalGroup(R)
            ledgers = [None] * R
            errs = []

            def worker(r, comm, reps):
                try:
                    b, g, h = sh[r]
                    ln = HostParallelLearner(
                        mode, comm, params_q if quantized else params)
                    for _ in range(reps):
                        ln.grow(jnp.asarray(b), jnp.asarray(g),
                                jnp.asarray(h),
                                jnp.ones((b.shape[0],), jnp.float32),
                                fmask, meta, hyper)
                    ledgers[r] = dict(comm.ledger)
                except BaseException as e:  # noqa: BLE001
                    errs.append(e)

            def sweep(reps):
                ts = [threading.Thread(target=worker, args=(r, c, reps))
                      for r, c in enumerate(grp.comms())]
                for t in ts:
                    t.start()
                for t in ts:
                    t.join()
                if errs:
                    raise errs[0]

            sweep(1)  # warmup: compile the mode's kernels off the clock
            warm = dict(ledgers[0])
            t0 = time.time()
            sweep(iters)
            wall = time.time() - t0
            total = sum(ledgers[0].values()) - sum(warm.values())
            return {
                "bytes_per_iter": round(total / max(iters, 1), 1),
                "s_per_iter": round(wall / max(iters, 1), 4),
                "ledger_bytes_per_iter": {
                    k: round((ledgers[0][k] - warm.get(k, 0))
                             / max(iters, 1), 1)
                    for k in sorted(ledgers[0])
                },
            }

        per = {m: run(m) for m in ("data", "feature", "voting")}
        d_b = per["data"]["bytes_per_iter"]
        v_b = per["voting"]["bytes_per_iter"]
        f_b = per["feature"]["bytes_per_iter"]
        out = {
            "rows": n, "features": F, "ranks": R, "iters": iters,
            "top_k": top_k,
            "per_learner": per,
            "voting_vs_data_payload_ratio":
                round(d_b / v_b, 2) if v_b else None,
            "feature_vs_data_payload_ratio":
                round(d_b / f_b, 2) if f_b else None,
        }
        # quantized-training histogram wire (docs/PARALLEL.md): the
        # f32-vs-int16 per-histogram payload is pure protocol arithmetic
        # — F*B*12 bytes (f32 g/h/cnt planes) vs F*B*4 (int16 g/h, count
        # derived at the receiver) — so the >=3x ratio is exact and
        # device-independent; a measured data-parallel run over the same
        # LocalComm group corroborates it from the byte ledger (slightly
        # under 3x: the scale maxima + int root sums ride "hist_q" too)
        from lightgbm_tpu.ops import qhist

        f32_hist = qhist.wire_bytes_f32(F, B)
        q_hist = qhist.wire_bytes_q(F, B)
        qdata = run("data", quantized=True)
        led_f = per["data"]["ledger_bytes_per_iter"].get("hist", 0.0)
        led_q = qdata["ledger_bytes_per_iter"].get("hist_q", 0.0)
        out["quantized_hist"] = {
            "f32_bytes_per_hist": f32_hist,
            "int16_bytes_per_hist": q_hist,
            "f32_vs_quantized_payload_ratio": round(f32_hist / q_hist, 2),
            "measured_data_quantized": qdata,
            "measured_hist_bytes_per_iter_f32": led_f,
            "measured_hist_bytes_per_iter_q": led_q,
            "measured_ratio": (round(led_f / led_q, 2) if led_q else None),
        }
        return out
    except Exception as e:  # pragma: no cover
        return {"error": f"{type(e).__name__}: {e}"}


def _bench_elastic():
    """Elastic straggler A/B (docs/ROBUSTNESS.md): three REAL 2-rank
    subprocess fleets (tests/elastic_worker.py over the KV transport)
    training the same data-parallel job —

      no_straggler        — clean baseline
      straggler_off       — rank 0 sleeps ``delay:ms:after:N`` at every
                            hardened collective (a ~4x per-row-slow
                            host), rebalancing DISABLED
      straggler_rebalance — same fault, ``rebalance=true``: the
                            controller moves rows off the slow rank and
                            the injected stall shrinks with them
                            (net.set_delay_scale ties sleep to the
                            current/initial row ratio)

    reporting steady-state s/iter (tail iterations, past warmup and the
    move) and ``recovery_ratio = off / on``.  The injected stall
    dominates compute on ANY backend, so the >=1.3x recovery contract is
    device-independent and gates outright (apply_regression_gate).
    BENCH_ELASTIC=0 skips;
    BENCH_ELASTIC_ROWS / BENCH_ELASTIC_TREES / BENCH_ELASTIC_DELAY_MS
    resize."""
    import socket
    import subprocess
    import sys as _sys
    import tempfile

    worker = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "tests", "elastic_worker.py")
    rows = int(os.environ.get("BENCH_ELASTIC_ROWS", 1024))
    trees = int(os.environ.get("BENCH_ELASTIC_TREES", 14))
    delay_ms = int(os.environ.get("BENCH_ELASTIC_DELAY_MS", 30))
    tail = 5  # steady-state window: past warmup AND past the move
    try:
        if not os.path.exists(worker):
            return {"error": f"FileNotFoundError: {worker}"}

        def fleet(tag, extra_env, tmp):
            s = socket.socket()
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
            s.close()
            base = {k: v for k, v in os.environ.items()
                    if k not in ("XLA_FLAGS", "JAX_PLATFORMS",
                                 "LIGHTGBM_TPU_FAULT",
                                 "LIGHTGBM_TPU_FAULT_RANK",
                                 "LIGHTGBM_TPU_TRACE")}
            repo = os.path.dirname(os.path.abspath(__file__))
            base["PYTHONPATH"] = repo + os.pathsep + base.get(
                "PYTHONPATH", "")
            base.update(ELASTIC_ROWS=str(rows), ELASTIC_TREES=str(trees),
                        ELASTIC_FREQ="100")  # no checkpoint I/O on the clock
            base.update(extra_env)
            outp = os.path.join(tmp, tag)
            procs = [subprocess.Popen(
                [_sys.executable, worker, str(r), "2", str(port), outp,
                 "train", os.path.join(tmp, tag + "_ck")],
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True, env=base) for r in range(2)]
            logs = [p.communicate(timeout=600)[0] for p in procs]
            if any(p.returncode != 0 for p in procs):
                raise RuntimeError(
                    "elastic fleet failed: " + logs[0][-500:])
            res = []
            for r in range(2):
                with open(outp + f".rank{r}.json") as fh:
                    res.append(json.load(fh))
            return res

        fault = {"LIGHTGBM_TPU_FAULT": f"delay:{delay_ms}:after:5",
                 "LIGHTGBM_TPU_FAULT_RANK": "0"}

        def s_per_iter(res):
            # ranks run in lockstep (barrier-synchronized); the fleet
            # pace is either rank's tail-mean
            ts = res[0]["it_times"][-tail:]
            return sum(ts) / max(len(ts), 1)

        with tempfile.TemporaryDirectory(prefix="bench_elastic_") as tmp:
            base_r = fleet("base", {}, tmp)
            off_r = fleet("off", dict(fault), tmp)
            on_r = fleet("on", dict(fault, ELASTIC_REBALANCE="1",
                                    ELASTIC_MOVE_FRAC="0.6"), tmp)
        base_s = s_per_iter(base_r)
        off_s = s_per_iter(off_r)
        on_s = s_per_iter(on_r)
        return {
            "rows": rows, "trees": trees, "ranks": 2,
            "delay_ms_per_collective": delay_ms,
            "no_straggler_s_per_iter": round(base_s, 4),
            "straggler_off_s_per_iter": round(off_s, 4),
            "straggler_rebalance_s_per_iter": round(on_s, 4),
            "straggler_slowdown": (round(off_s / base_s, 2)
                                   if base_s > 0 else None),
            "recovery_ratio": (round(off_s / on_s, 2)
                               if on_s > 0 else None),
            "final_counts": on_r[0]["final_counts"],
        }
    except Exception as e:  # pragma: no cover
        return {"error": f"{type(e).__name__}: {e}"}


def _bench_spot():
    """Spot-economics A/B (docs/FACTORY.md "spot"): one elastic
    2-member fleet (tests/membership_worker.py over the file-KV
    membership runtime, factory/spot.py driver) run through a scripted
    2-preemption capacity trace at the spot price, vs the same fleet
    left static at the on-demand price.  Reports cost-per-completed-
    model on both ledgers, their ratio, resize-pause p50/p99 from the
    survivors, and the zero-lost-iterations proof from the write-once
    per-iteration KV records.  Cost is member-seconds x price
    arithmetic — device-independent — so the <=0.8x ratio and the
    nothing-redone contract gate outright (apply_regression_gate).
    BENCH_SPOT=0 skips;
    BENCH_SPOT_ROWS / BENCH_SPOT_TREES / BENCH_SPOT_PRICE resize."""
    import tempfile

    from lightgbm_tpu.factory.spot import (ON_DEMAND_PRICE, SpotFleet,
                                           SpotSchedule,
                                           run_static_baseline)

    rows = int(os.environ.get("BENCH_SPOT_ROWS", 600))
    trees = int(os.environ.get("BENCH_SPOT_TREES", 16))
    price = float(os.environ.get("BENCH_SPOT_PRICE", "0.3"))
    # pacing keeps the scripted event times inside the run on a fast
    # box; it inflates spot and static member-seconds identically, so
    # the cost ratio is pacing-invariant
    pace = {"MEMBER_ITER_SLEEP": os.environ.get("BENCH_SPOT_PACE", "0.8")}
    # preempt member 1 early (the fleet resizes to one survivor), spawn
    # replacement capacity right after (it auto-resumes from the
    # coordinator handoff), then preempt member 0 late — the replacement
    # finishes the model alone
    script = "preempt@5=1;spawn@6;preempt@20=0"
    try:
        with tempfile.TemporaryDirectory(prefix="bench_spot_") as tmp:
            static = run_static_baseline(
                os.path.join(tmp, "static"), 2,
                os.path.join(tmp, "static_ledger.json"),
                trees=trees, rows=rows, extra_env=dict(pace))
            if static["cost"] is None:
                raise RuntimeError(
                    f"static fleet incomplete: exits={static['exits']}")
            fleet = SpotFleet(
                os.path.join(tmp, "spot"),
                SpotSchedule.from_script(script, price), 2,
                os.path.join(tmp, "spot_ledger.json"),
                trees=trees, rows=rows, extra_env=dict(pace))
            spot = fleet.run()
            if spot["cost"] is None:
                raise RuntimeError(
                    f"spot fleet incomplete: exits={spot['exits']}")
            pauses = sorted(
                p for meta in spot["metas"].values()
                for p in meta.get("resize_pauses") or [])

        def pct(q):
            if not pauses:
                return None
            return round(pauses[min(len(pauses) - 1,
                                    int(q * len(pauses)))], 4)

        return {
            "rows": rows, "trees": trees, "members": 2,
            "schedule": script,
            "spot_price": price, "on_demand_price": ON_DEMAND_PRICE,
            "static_cost_per_model": round(static["cost"], 3),
            "spot_cost_per_model": round(spot["cost"], 3),
            "cost_ratio_spot_vs_static": round(
                spot["cost"] / static["cost"], 3),
            "preemptions": sum(1 for e in fleet.schedule.events
                               if e.kind == "preempt"),
            "resize_pauses": len(pauses),
            "resize_pause_p50_s": pct(0.50),
            "resize_pause_p99_s": pct(0.99),
            "zero_lost_iterations": bool(spot["zero_lost_iterations"]),
            "static_wall_s": static["wall_s"],
            "spot_wall_s": spot["wall_s"],
        }
    except Exception as e:  # pragma: no cover
        return {"error": f"{type(e).__name__}: {e}"}


def _bench_ooc_distributed():
    """Distributed out-of-core section (docs/DATA.md "Distributed
    streaming", docs/PARALLEL.md): two REAL 2-rank subprocess fleets
    (tests/oocdist_worker.py — every rank streams its own shard through
    the prefetch ring, node histograms allreduced on the ``hist_q``
    wire) trained under quantized_training at two DIFFERENT per-rank
    chunk grids, then a byte-compare of the final models.

    ``quantized_parity_ok`` is the integer-fold associativity contract:
    per-chunk int32 partials cannot depend on the chunk grid, so the
    model bytes must match EXACTLY — protocol arithmetic, not a timing,
    which is why the gate holds it outright (apply_regression_gate).
    BENCH_OOCDIST=0 skips; BENCH_OOCDIST_ROWS / BENCH_OOCDIST_TREES
    resize."""
    import socket
    import subprocess
    import sys as _sys
    import tempfile

    worker = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "tests", "oocdist_worker.py")
    rows = int(os.environ.get("BENCH_OOCDIST_ROWS", 16384))
    trees = int(os.environ.get("BENCH_OOCDIST_TREES", 3))
    grids = (2048, 9999)  # round to 4096 (2 chunks/rank) vs 12288 (1)
    try:
        if not os.path.exists(worker):
            return {"error": f"FileNotFoundError: {worker}"}

        def fleet(tag, grid, tmp):
            s = socket.socket()
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
            s.close()
            base = {k: v for k, v in os.environ.items()
                    if k not in ("XLA_FLAGS", "JAX_PLATFORMS",
                                 "LIGHTGBM_TPU_FAULT",
                                 "LIGHTGBM_TPU_FAULT_RANK",
                                 "LIGHTGBM_TPU_TRACE",
                                 "LIGHTGBM_TPU_OOC",
                                 "LIGHTGBM_TPU_DEVICE_BUDGET")}
            repo = os.path.dirname(os.path.abspath(__file__))
            base["PYTHONPATH"] = repo + os.pathsep + base.get(
                "PYTHONPATH", "")
            base.update(OOCDIST_ROWS=str(rows), OOCDIST_TREES=str(trees),
                        OOCDIST_OOC="true", OOCDIST_QUANT="1",
                        OOCDIST_LEAVES="15",
                        OOCDIST_CHUNK_ROWS=str(grid))
            outp = os.path.join(tmp, tag)
            t0 = time.time()
            procs = [subprocess.Popen(
                [_sys.executable, worker, str(r), "2", str(port), outp,
                 "train", "-"],
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True, env=dict(base)) for r in range(2)]
            logs = [p.communicate(timeout=600)[0] for p in procs]
            if any(p.returncode != 0 for p in procs):
                raise RuntimeError(
                    "oocdist fleet failed: " + logs[0][-500:])
            wall = time.time() - t0
            models, stats = [], []
            for r in range(2):
                with open(outp + f".rank{r}.txt") as fh:
                    models.append(fh.read())
                with open(outp + f".rank{r}.json") as fh:
                    stats.append(json.load(fh))
            return models, stats, wall

        with tempfile.TemporaryDirectory(prefix="bench_oocdist_") as tmp:
            runs = {g: fleet(f"g{g}", g, tmp) for g in grids}
        ref = runs[grids[0]][0][0]
        parity = all(m == ref for models, _, _ in runs.values()
                     for m in models)
        g0 = runs[grids[0]][1][0]
        return {
            "rows": rows, "trees": trees, "ranks": 2,
            "chunk_grids": list(grids),
            "chunks_per_pass": {
                g: runs[g][1][0]["chunks_per_pass"] for g in grids},
            "fleet_wall_s": {
                g: round(runs[g][2], 2) for g in grids},
            "stream_stats_rank0": g0["stream_stats"],
            "quantized_parity_ok": parity,
        }
    except Exception as e:  # pragma: no cover
        return {"error": f"{type(e).__name__}: {e}"}


def _auc(y, s):
    """AUC via the library's own metric (one implementation to trust)."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.metric.binary import AUCMetric

    class _Meta:
        label = y
        weights = None

    m = AUCMetric(Config())
    m.init(_Meta, len(y))
    return m.eval(s)[0][1]


def main():
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        # a time taken off the chip is not this benchmark's metric: no
        # probe-and-downgrade, no CPU sizing, no exit 0 with a null value
        print(f"bench.py needs a TPU: JAX found platform {dev.platform!r} "
              f"({dev.device_kind})", file=sys.stderr)
        return 2

    n_rows = int(os.environ.get("BENCH_ROWS", 1_000_000))
    n_leaves = 255
    # 96 iters / 3 windows: each window is ONE fused chunk dispatch of 32
    # iterations, so the per-dispatch fixed cost (not measured on this
    # machine) is amortized over 32 iterations
    n_iters = int(os.environ.get("BENCH_ITERS", 96))
    warmup = int(os.environ.get("BENCH_WARMUP", 3))
    n_windows_default = 3
    crosscheck = os.environ.get("BENCH_SKIP_CROSSCHECK", "0") != "1"
    # eval-overhead A/B: measured by DEFAULT (it was built in r5 and then
    # never ran because it was opt-in); BENCH_VALID=0 skips
    with_valid = os.environ.get("BENCH_VALID", "1") == "1"

    import lightgbm_tpu as lgb
    from lightgbm_tpu.basic import Booster, Dataset

    X, y = make_higgs_shaped(n_rows, seed=7)
    Xt, yt = make_higgs_shaped(200_000, seed=11)  # held-out rows, SAME task
    params = {
        "objective": "binary",
        "metric": "auc",
        "max_bin": 63,
        "num_leaves": n_leaves,
        "learning_rate": 0.1,
        "min_data_in_leaf": 1,
        "min_sum_hessian_in_leaf": 100,
        "verbose": -1,
    }
    t0 = time.time()
    ds = Dataset(X, label=y, params=dict(params))
    booster = Booster(params=params, train_set=ds)
    gb = booster.boosting
    fused = gb.ptrainer is not None
    prep_s = time.time() - t0

    def run_iters(k):
        if fused:
            gb.train_iters_partitioned(k, is_eval=False)
        else:
            for _ in range(k):
                booster.update()
        # force completion with a host read (chip_smoke.py's fence check
        # shows jax.block_until_ready waits just as well on this runtime)
        np.asarray(gb.scores[0, :1])

    t0 = time.time()
    run_iters(warmup)
    warmup_s = time.time() - t0

    # timed windows, median: per-tree cost grows slightly as boosting
    # deepens trees, so the median window is the sustained rate; min is
    # reported too (run-to-run spread on this machine: not measured)
    n_windows = int(os.environ.get("BENCH_NWINDOWS", n_windows_default))
    windows = []
    per = max(1, n_iters // n_windows)
    total_iters = warmup + n_windows * per
    for _ in range(n_windows):
        t0 = time.time()
        run_iters(per)
        windows.append((time.time() - t0) / per)
    sec_per_iter = float(np.median(windows))

    # ---- phase attribution pass (after the timed windows, so the
    # defused traced mode cannot pollute the s/iter number): a few extra
    # iterations with per-phase fencing explain where the time goes ----
    from lightgbm_tpu.obs import compilewatch, tracer

    attrib_iters = int(os.environ.get("BENCH_ATTRIB_ITERS", 2))
    if tracer.enabled and fused and attrib_iters > 0 and getattr(
        gb.ptrainer, "supports_traced", False
    ) and gb.num_tree_per_iteration == 1:
        phases_before = os.environ.get("LIGHTGBM_TPU_TRACE_PHASES")
        os.environ["LIGHTGBM_TPU_TRACE_PHASES"] = "1"
        tracer._phases_env = "1"
        try:
            gb.train_iters_partitioned(attrib_iters, is_eval=False)
            total_iters += attrib_iters
        finally:
            if phases_before is None:
                os.environ.pop("LIGHTGBM_TPU_TRACE_PHASES", None)
                tracer._phases_env = ""
            else:
                os.environ["LIGHTGBM_TPU_TRACE_PHASES"] = phases_before
                tracer._phases_env = phases_before

    # ---- xprof capture (LIGHTGBM_TPU_XPROF=dir): bounded device-
    # profiler window over a few already-warm iterations, after the
    # timed windows so the profiler overhead cannot touch s/iter ----
    from lightgbm_tpu.utils.profiling import maybe_xprof_capture

    xprof = maybe_xprof_capture()
    xprof_info = None
    if xprof is not None:
        xprof.skip = 0  # the timed windows above already warmed up
        for _ in range(xprof.iters):
            xprof.on_iter_start()
            run_iters(1)
            xprof.on_iter_end()
        xprof.close()
        total_iters += xprof.iters
        xprof_info = {"dir": xprof.log_dir, "iters": xprof.iters}

    # ---- quality signal on held-out rows of the SAME task ----
    prob = booster.predict(Xt)
    auc = _auc(yt, prob)

    auc_sk = None
    if crosscheck:
        try:
            from sklearn.ensemble import HistGradientBoostingClassifier

            sk = HistGradientBoostingClassifier(
                max_iter=total_iters,
                learning_rate=0.1,
                max_leaf_nodes=n_leaves,
                max_bins=63,
                min_samples_leaf=1,
                l2_regularization=0.0,
                early_stopping=False,
                validation_fraction=None,
            )
            sk_n = min(n_rows, 1_000_000)
            sk.fit(X[:sk_n], y[:sk_n])
            auc_sk = _auc(yt, sk.predict_proba(Xt)[:, 1])
        except Exception as e:  # pragma: no cover
            auc_sk = f"failed: {type(e).__name__}"

    # vs_baseline: the reference GPU (GTX 1080) trains Higgs-10.5M at about
    # 0.58 s/iter at this config (docs/GPU-Performance.md external chart,
    # commonly-cited ~290 s / 500 iters); scale to the measured row count.
    ref_gpu_sec_per_iter_higgs = 0.58
    ref_scaled = ref_gpu_sec_per_iter_higgs * (n_rows / 10_500_000)
    vs_baseline = ref_scaled / sec_per_iter if sec_per_iter > 0 else 0.0

    out = {
        "metric": f"sec/iteration (binary, {n_rows}x28, max_bin=63, num_leaves={n_leaves})",
        "value": round(sec_per_iter, 4),
        "unit": "s/iter",
        "vs_baseline": round(vs_baseline, 3),
        f"auc_heldout_{total_iters}iters": round(float(auc), 5),
        "auc_sklearn_same_iters": (round(float(auc_sk), 5) if isinstance(auc_sk, float) else auc_sk),
        "windows_s_per_iter": [round(w, 4) for w in windows],
        "window_min_s_per_iter": round(float(np.min(windows)), 4),
        "prep_s": round(prep_s, 2),
        "warmup_s": round(warmup_s, 2),
        "learner": "partitioned-fused" if fused else "mask-grower",
        "device": str(jax.devices()[0]).split(":")[0],
    }
    if xprof_info is not None:
        out["xprof"] = xprof_info

    # same-box measured CPU baseline (refbuild/measure_baseline.py writes
    # it into BASELINE.json "published"); the GPU number above remains
    # chart hearsay, so the measured ratio is reported alongside
    try:
        with open(os.path.join(os.path.dirname(__file__) or ".", "BASELINE.json")) as f:
            pub = json.load(f).get("published", {})
        key = "ref_cpu_sec_per_iter_1m_rows"
        if key in pub:
            ref_cpu = float(pub[key]) * (n_rows / 1_000_000)
            # only the 1M-row config is genuinely measured; other row
            # counts are a linear extrapolation and labeled as such
            suffix = "" if n_rows == 1_000_000 else "_extrapolated_linear"
            out["ref_cpu_measured_s_per_iter" + suffix] = round(ref_cpu, 4)
            out["ref_cpu_threads"] = pub.get("ref_cpu_threads")
            out["vs_ref_cpu_same_box" + suffix] = round(ref_cpu / sec_per_iter, 3)
    except Exception:
        pass

    # eval-alive fused path (BENCH_VALID=1): train WITH a valid set +
    # device AUC at output_freq-period eval points; reports s/iter with
    # eval included so the eval overhead vs the eval-free number above is
    # directly visible (target: within ~15%)
    if with_valid:
        # end-to-end A/B at matched iteration count: a fresh eval-free
        # run vs a fresh run with a valid set + device AUC at output_freq
        # eval points.  Both include prep + compile, so the RATIO is the
        # honest eval overhead (timing only the iterations isn't possible
        # through lgb.train's single call).
        pv = dict(params)
        pv["output_freq"] = 16
        t0 = time.time()
        lgb.train(dict(params), lgb.Dataset(X, label=y, params=dict(params)),
                  num_boost_round=total_iters, verbose_eval=False)
        ref_total = time.time() - t0
        dtr = lgb.Dataset(X, label=y, params=dict(pv))
        # reference= shares the TRAIN bin mappers: tree thresholds are
        # train-mapper bin ids, so the valid set must be binned with them
        dv = lgb.Dataset(Xt, label=yt, reference=dtr)
        t0 = time.time()
        lgb.train(pv, dtr, num_boost_round=total_iters,
                  valid_sets=[dv], verbose_eval=False)
        eval_total = time.time() - t0
        out["valid_run_total_s"] = round(eval_total, 2)
        out["evalfree_run_total_s"] = round(ref_total, 2)
        out["valid_overhead_ratio"] = round(eval_total / max(ref_total, 1e-9), 3)
        out["eval_overhead_pct"] = round(
            100.0 * (eval_total / max(ref_total, 1e-9) - 1.0), 2
        )

    # serving section (docs/SERVING.md): warm inference latency through
    # the packed-artifact + bucketed-compile-cache path, so BENCH_r*
    # tracks inference regressions alongside training ones.  Warmup
    # compiles the bucket ladder; the measured loop must then show zero
    # new compiles (the serving acceptance contract).
    if os.environ.get("BENCH_SERVING", "1") != "0":
        out["serving"] = _bench_serving(booster, X)

    # quantized-serving section (docs/SERVING.md): exact vs int16
    # rank-quantized predictor rows/s, both artifact flavors' bytes, the
    # measured leaf drift vs its bound, and the quantized same-shape
    # swap compile count — its own regression-gate leg
    if os.environ.get("BENCH_QUANT", "1") != "0":
        out["quantized"] = _bench_quantized(booster, X)

    # linear-tree section (docs/TREES.md): trees-to-matched-logloss A/B
    # vs constant leaves + v3 serving rows/s.  The fewer-trees ratio
    # is quality-per-tree math, a device-independent leg of the gate.
    if os.environ.get("BENCH_LINEAR", "1") != "0":
        out["linear_tree"] = _bench_linear(X, y, params)

    # multi-model section (docs/SERVING.md): N=4 models bin-packed on
    # one chip behind named routes, per-model rows/s through the full
    # HTTP path, and the admission-refusal probe
    if os.environ.get("BENCH_MULTIMODEL", "1") != "0":
        out["multimodel"] = _bench_multimodel(booster, X)

    # streaming-ingest section (docs/DATA.md): rows/s + the peak-RSS
    # bound proving the raw float matrix never materialized.  At
    # BENCH_ROWS=10500000 this is the Higgs-scale ingest entry.
    if os.environ.get("BENCH_INGEST", "1") != "0":
        out["ingest"] = _bench_ingest(X, y, n_rows)

    # checkpoint section (docs/CHECKPOINT.md): save latency + the
    # per-iteration cost of fault tolerance at freq 0/10/1
    if os.environ.get("BENCH_CKPT", "1") != "0":
        out["checkpoint"] = _bench_checkpoint(X, y, params)

    # out-of-core section (docs/DATA.md): streamed vs resident s/iter,
    # prefetch overlap, bounded residency — the chunk-streaming cost line
    if os.environ.get("BENCH_OOC", "1") != "0":
        out["out_of_core"] = _bench_ooc(X, y, params)

    # factory section (docs/FACTORY.md): append->promoted e2e latency of
    # one warm-started continuous-training cycle, warm-start cost vs the
    # tree-count-matched cold retrain, canary-window plumbing overhead
    if os.environ.get("BENCH_FACTORY", "1") != "0":
        out["factory"] = _bench_factory(X, y)

    # serving-tail section (docs/ROBUSTNESS.md): hedged vs unhedged
    # client p99 through a 3-replica fleet with one delay-injected
    # replica.  The injected delay dominates, so the hedged-chaos-over-healthy ratio is a
    # device-independent leg of the regression gate.
    if os.environ.get("BENCH_SERVING_TAIL", "1") != "0":
        out["serving_tail"] = _bench_serving_tail(booster, X)

    # comms section (docs/PARALLEL.md): bytes/iter + s/iter of the
    # data/feature/voting distributed learners on a >=2000-feature
    # synthetic.  The payload numbers are protocol arithmetic, and the
    # voting-vs-data ratio is the device-independent leg of the gate.
    if os.environ.get("BENCH_COMMS", "1") != "0":
        out["comms"] = _bench_comms()

    # elastic section (docs/ROBUSTNESS.md): straggler A/B over real
    # 2-rank subprocess fleets — s/iter {no-straggler, straggler with
    # rebalance off, straggler with rebalance on} and the recovery
    # ratio.  The injected stall dominates on any backend, so the >=1.3x
    # recovery contract is the device-independent leg of the gate.
    if os.environ.get("BENCH_ELASTIC", "1") != "0":
        out["elastic"] = _bench_elastic()

    # spot-economics section (docs/FACTORY.md): elastic 2-member fleet
    # under a scripted 2-preemption trace vs the static on-demand
    # reference — cost-per-model ratio, resize-pause p50/p99, and the
    # zero-lost-iterations proof.  The cost ratio is price arithmetic,
    # the device-independent leg of the regression gate.
    if os.environ.get("BENCH_SPOT", "1") != "0":
        out["spot"] = _bench_spot()

    # distributed out-of-core section (docs/DATA.md): 2-rank streaming
    # fleets at two chunk grids + the quantized byte-parity contract.
    # Integer-fold associativity is
    # protocol arithmetic, the device-independent leg of the gate.
    if os.environ.get("BENCH_OOCDIST", "1") != "0":
        out["ooc_distributed"] = _bench_ooc_distributed()

    # kernel A/B section (docs/PERFORMANCE.md): the PR-6 kernel wins
    # measured head-to-head WITH parity checks
    if os.environ.get("BENCH_KERNEL_AB", "1") != "0":
        out["kernel_ab"] = _bench_kernel_ab()

    # run-trace embedding (docs/OBSERVABILITY.md): the per-phase span
    # totals and compile accounting gathered during THIS run, so the
    # BENCH_*.json line finally explains its own s/iter number
    if tracer.enabled:
        snap = tracer.snapshot()
        out["trace_path"] = tracer.path
        out["phase_breakdown"] = snap["spans"]
        cw = compilewatch.snapshot()
        out["compile_stats"] = {
            "backend_compiles": cw["backend_compiles"],
            "backend_compile_secs": cw["backend_compile_secs"],
            "retraces_flagged": sum(
                w["retraces"] for w in cw["watched"].values()
            ),
        }
        # Prometheus dump next to the trace (docs/OBSERVABILITY.md): the
        # same registry the serve front end scrapes, frozen at end of
        # bench — every mirrored trace counter/gauge + compile totals
        from lightgbm_tpu.obs.metrics import registry as _metrics_registry

        metrics_path = tracer.path + ".metrics.txt"
        try:
            _metrics_registry.dump(metrics_path)
            out["metrics_path"] = metrics_path
        except OSError:
            pass
        # HLO cost model (obs/costmodel.py): the jax_cost program
        # inventory joined against the measured phase spans — per-phase
        # efficiency vs the roofline, and the machine-picked next
        # kernel target (the line ROADMAP item 1 asks every capture to
        # end with)
        from lightgbm_tpu.obs import costmodel

        cm = costmodel.process_summary()
        out["cost_model"] = cm
        for row in cm["table"]:
            if row.get("efficiency_pct") is not None:
                tracer.gauge("cost.efficiency_pct", row["efficiency_pct"],
                             phase=row["phase"], program=row["program"])
        if cm.get("next_target_line"):
            print("# " + cm["next_target_line"], file=sys.stderr)

    # device memory footprint (validates the no-scratch-copy design at
    # Higgs scale)
    ms = dev.memory_stats()
    out["device_mb_in_use"] = round(ms["bytes_in_use"] / 1e6, 1)
    out["device_mb_peak"] = round(ms["peak_bytes_in_use"] / 1e6, 1)

    # perf regression gate: >10% slower than the best comparable prior
    # BENCH_r*.json => "regression": true + nonzero exit (BENCH_GATE=0
    # opts out; silent skip when no prior parses)
    rc = apply_regression_gate(out)
    print(json.dumps(out))
    if rc:
        print("# REGRESSION: s/iter is >10% above the best prior capture "
              f"({out['gate']['best_prior_source']}: "
              f"{out['gate']['best_prior_s_per_iter']} s/iter)",
              file=sys.stderr)
    return rc


if __name__ == "__main__":
    sys.exit(main())
