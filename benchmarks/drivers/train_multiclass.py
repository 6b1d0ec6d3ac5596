"""Driver `train_multiclass`: driver `train`'s chunked run on a seven-class
table trained with the softmax objective, K = 7 trees an iteration.

Its record says `"driver": "train"`, so every reader of `train` reads this
cell as it reads the others; it adds `rows`, the training rows a chip, which
the `multi_update` rooflines multiply (harness/multi_ops.py).  It differs from
drivers/train.py in six places:

1. The table comes from data_covtype.py (54 columns, 44 of them one-hot) and
   is fixed WHOLE, features from the mix's `features_seed` and labels from its
   `labels_seed`, as at train_onehot and train_rank: every seed grows the same
   trees.  What is cached under benchmarks/.cache/ is the program's binary
   file and beside it the labels as the generator made them.
2. `--seed` draws what is outside the timed work: the held-out rows (`seed +
   1`), the parity rows (`seed + 2`) and the rows the score check reads
   (`seed + 3`).
3. `heldout_auc` is the MACRO mean over the 7 classes of one-vs-rest AUC:
   each class's probability from `predict` at `auc_iters` iterations, on the
   held-out rows, against whether the row is of that class (data.auc), and the
   seven averaged (multiclass_ref.macro_auc).  `multi_logloss` and
   `multi_error` of the same probabilities are notes.
4. The gradients are checked at the timed size: after the held-out reads, one
   more iteration runs from the settled scores, and the K g and K h planes it
   wrote to the packed matrix (read back through the row-id channel) must
   agree with multiclass_ref.py's float64 planes of those very scores, for
   every training row, within `grad_atol` (the mix says why).
5. The score update is checked: at `score_rows` rows of the table's first
   block, made again by the generator, the K score planes the chunk programs
   left must equal the model's class trees summed in float64, within
   `score_atol` (the mix says why).  The gradient check reads its planes
   from those very scores, so it alone could not see an update that landed
   nothing or landed on another class's row.
6. The parity pair is the first iteration's K trees of the fused trainer
   against the mask grower's on `parity_rows` rows, every class tree compared
   as the rows each split sends either way (drivers/train_rank.py's
   partitions); where a class tree's two first part, the fused trainer's
   choice must hold in float64 (TIE_RTOL), and the next class is compared.
"""

import hashlib
import json
import os
import zlib
from unittest import mock

import numpy as np

import data
import data_covtype
import multiclass_ref
from harness import roofline
from harness.measure import Outcome, Window
from harness.spec import load_module
from harness.tracing import TraceWindow

train = load_module("drivers", "train")  # unedited: its constants, chunk loop and tree view
rank = load_module("drivers", "train_rank")  # partitions and the float64 tie check


def _table(run, params: dict):
    """(the binned training table, its labels): made and binned on a
    checkout's first run, the program's binary file after."""
    import lightgbm_tpu as lgb

    mix = run.mix
    key = json.dumps([mix["rows_per_chip"], mix["features_seed"], mix["labels_seed"], params],
                     sort_keys=True)
    name = f"table-covtype-{mix['rows_per_chip']}-{hashlib.sha256(key.encode()).hexdigest()[:12]}"
    path = os.path.join(run.cache_dir, name + ".bin")
    truth = os.path.join(run.cache_dir, name + ".labels.npz")
    if not (os.path.exists(path) and os.path.exists(truth)):
        with run.spans.span("table_generate"):
            X, y = data_covtype.make_covtype(mix["rows_per_chip"], mix["features_seed"],
                                             label_seed=mix["labels_seed"])
        with run.spans.span("table_bin"):
            made = lgb.Dataset(X, label=y, params=dict(params), free_raw_data=True)
            made.construct()
        os.makedirs(run.cache_dir, exist_ok=True)
        made.save_binary(path + ".tmp")
        np.savez(truth + ".tmp.npz", y=y.astype(np.int8))
        os.replace(truth + ".tmp.npz", truth)
        os.replace(path + ".tmp", path)  # a killed run leaves no half file
        del made, X
    with run.spans.span("table_load"):
        table = lgb.Dataset(path, params=dict(params))
        table.construct()
        got = np.load(truth)
    return table, got["y"].astype(np.float32)


def _gradient_verdict(g, h, ref, atol: float) -> dict:
    """The comparison `correct` makes of the (K, n) planes: `outside`, the
    rows whose g or h, in any class, is off multiclass_ref's by more than
    atol; `worst_g`, `worst_h`, the largest |off| (what atol is held against:
    benchmarks/tests/test_multiclass_cell.py has the sound reading and the
    bfloat16 control's)."""
    off_g, off_h = np.abs(g - ref["g"]), np.abs(h - ref["h"])
    bad = np.any((off_g > atol) | (off_h > atol), axis=0)
    return {"outside": int(np.sum(bad)), "worst_g": float(off_g.max()),
            "worst_h": float(off_h.max())}


def _gradient_check(gb, pt, y, mix) -> tuple:
    """(check, notes): one more iteration from the settled scores, whose K g
    and K h planes (the packed matrix's class row pairs, to original order
    through ROWID) against multiclass_ref on the same scores."""
    import jax
    import jax.numpy as jnp

    scores = np.asarray(gb.scores, np.float32)  # (K, n) original order, what the channels hold
    gb.train_iters_partitioned(1, is_eval=False)
    lay, n, K = pt.layout, pt.num_rows, pt.K
    rows = [lay.g_row(k) for k in range(K)] + [lay.h_row(k) for k in range(K)] + [lay.ROWID]
    got = np.asarray(jax.device_get(pt.p[jnp.asarray(rows), :n]))
    planes = np.empty((2 * K, n), np.float32)
    planes[:, got[-1]] = got[:-1].view(np.float32)
    ref = multiclass_ref.gradients(scores, y)
    atol = mix["grad_atol"]
    v = _gradient_verdict(planes[:K], planes[K:], ref, atol)
    check = (f"the chunk program's {K} g and {K} h planes at iteration {gb.iter} equal "
             f"multiclass_ref's on its scores, every row within {atol} ({v['outside']} of {n} "
             f"outside; worst g off by {v['worst_g']:.2e}, h by {v['worst_h']:.2e})",
             v["outside"] == 0 and bool(np.any(ref["g"] != 0)))
    return check, {"grad_worst_abs": v["worst_g"], "hess_worst_abs": v["worst_h"],
                   "grad_rows_outside": v["outside"]}


def _score_check(gb, y, run) -> tuple:
    """(check, notes): the K score planes the chunk programs left, at
    `score_rows` rows of the table's first block drawn from the seed, against
    the model's own class trees summed in float64 on those rows as the
    generator makes them again (multiclass_ref.add_class_trees).  Two controls
    read the same rows: the scores without the last iteration's trees (an
    update that landed nothing) and with its trees one class over (deltas on
    another class's row); both must lie outside the limit."""
    mix, K = run.mix, gb.num_class
    m = min(mix["rows_per_chip"], data_covtype.BLOCK_ROWS)  # block 0 is made as the table's
    X, y_block = data_covtype.make_covtype(m, mix["features_seed"], label_seed=mix["labels_seed"])
    ids = np.sort(np.random.default_rng(run.seed + 3).choice(m, mix["score_rows"], replace=False))
    outs = [_tree_output(tree, X[ids]) for tree in gb.models]
    got = np.asarray(gb.scores, np.float64)[:, ids]

    def off(trees) -> float:
        return float(np.max(np.abs(got - multiclass_ref.add_class_trees(
            np.zeros((K, len(ids))), trees))))

    sound = off(outs)
    frozen = off(outs[:-K])
    moved = off(outs[:-K] + outs[-K + 1:] + outs[-K:-K + 1])
    atol = mix["score_atol"]
    check = (f"the chunk programs' {K} score planes at {len(ids)} rows equal the sum of the "
             f"model's {len(outs)} class trees within {atol} (off by {sound:.2e}; the controls, "
             f"the last iteration's trees left out or one class over, by {frozen:.2e} and "
             f"{moved:.2e})",
             len(outs) == gb.iter * K and np.array_equal(y_block[ids], y[ids])
             and sound <= atol < min(frozen, moved))
    return check, {"score_worst_abs": sound, "score_control_frozen_abs": frozen,
                   "score_control_moved_abs": moved}


def _tree_output(tree, X) -> np.ndarray:
    with np.errstate(invalid="ignore"):  # the categorical test casts every row's value
        return np.asarray(tree.leaf_value, np.float64)[tree.predict_leaf_index(X)]


def _class_parting(a, b, labels) -> tuple:
    """(the first split where one class tree's partitions `a` and `b` part, or
    None; the splits compared equal before it; those of them under another
    feature or threshold)."""
    relabeled = 0
    for j in range(max(len(a), len(b))):
        if j >= min(len(a), len(b)) or not all(map(np.array_equal, a[j], b[j])):
            return j, j, relabeled
        relabeled += labels[0][j] != labels[1][j]
    return None, len(a), relabeled


def _parity(run, params: dict, K: int) -> list:
    """The first iteration's K trees of the fused trainer against the plain
    reference's (the mask grower on one device) on rows drawn from the seed.
    All K grow from one snapshot of the scores (all 0), so each class tree is
    compared on its own: the rows each split sends either way, split by split;
    where the two part, the fused choice must hold in float64 from
    multiclass_ref's planes, and that class is compared no further."""
    import lightgbm_tpu as lgb

    mix = run.mix
    X, y = data_covtype.make_covtype(mix["parity_rows"], run.seed + 2)

    def trained(p):
        return lgb.train(dict(p), lgb.Dataset(X, label=y, params=dict(p)),
                         num_boost_round=mix["parity_iters"], verbose_eval=False)

    fused = trained(params)
    with mock.patch.dict(os.environ, LIGHTGBM_TPU_PGROW="0"):
        plain = trained(dict(params, tree_learner="serial"))
    checks = [(f"parity pair is fused trainer vs mask grower ({len(y)} rows, {K} classes)",
               fused.boosting.ptrainer is not None and plain.boosting.ptrainer is None)]
    ref = multiclass_ref.gradients(np.zeros((K, len(y))), y)  # iteration 0: every score 0
    min_h = float(params["min_sum_hessian_in_leaf"])
    compared, relabeled, partings = [], 0, []
    for k in range(K):
        trees = [m.boosting.models[k] for m in (fused, plain)]
        a, b = (rank._partitions(tree, X) for tree in trees)
        labels = [list(zip(*train._tree_splits(tree)[:2])) for tree in trees]
        j, equal, moved = _class_parting(a, b, labels)
        compared.append(equal)
        relabeled += moved
        if j is not None:
            partings.append((k, j) + tuple(rank._split_stats(p_, j, ref["g"][k], ref["h"][k])
                                           for p_ in (a, b)))
    checks.append((f"the first iteration's {K} trees split-for-split equal to the reference, "
                   f"every class compared ({sum(compared)} splits equal, by class {compared}; "
                   f"the rows each split sends either way, {relabeled} of them under another "
                   f"feature or threshold at equal gains; {len(partings)} class(es) part)",
                   sum(compared) > 0))
    for k, j, fused_at, plain_at in partings:
        checks.append((f"class {k} parts at split {j}, where the fused trainer's choice holds "
                       f"in float64: gains {fused_at[0]:.9g} / {plain_at[0]:.9g} (terms "
                       f"{max(fused_at[1], plain_at[1]):.6g}), smaller children's hessians "
                       f"{fused_at[2]:.9g} / {plain_at[2]:.9g} against {min_h:g}",
                       rank._fused_choice_holds(fused_at, plain_at, min_h)))
    if not partings:
        pf = fused.predict(X, num_iteration=1).T
        pp = multiclass_ref.softmax(multiclass_ref.add_class_trees(
            np.zeros((K, len(y))), [_tree_output(plain.boosting.models[k], X) for k in range(K)]))
        close = np.isclose(pf, pp, rtol=train.PARITY_RTOL, atol=train.PARITY_ATOL)
        checks.append((f"probabilities of the first iteration within rtol {train.PARITY_RTOL} / "
                       f"atol {train.PARITY_ATOL} of the reference's softmax of its class trees "
                       f"({int((~close).sum())} of {close.size} outside, max abs diff "
                       f"{float(np.max(np.abs(pf - pp))):.2e})", bool(close.all())))
    return checks


def _keep(trace_dir: str, record: dict) -> None:
    """The record the readers read, with the phase table its trace reduces to,
    kept beside the trace as `record.json`: a reader can be checked on it again
    (benchmarks/tests/fixtures/covtype_traced_record.json is one, cut)."""
    from harness import phase_reduce

    with open(os.path.join(trace_dir, "record.json"), "w") as f:
        json.dump({**record, "phase_table": phase_reduce.table()}, f, default=str)


def run(run) -> Outcome:
    import jax

    import lightgbm_tpu as lgb
    from lightgbm_tpu.obs import compilewatch

    mix = run.mix
    params = {**run.config["params"], **mix.get("params", {})}
    chips, K = run.cell["chips"], int(params["num_class"])
    compilewatch.install()

    with run.spans.span("heldout_generate"):
        Xh, yh = data_covtype.make_covtype(mix["heldout_rows"], run.seed + 1)
    with run.spans.span("dataset"):
        table, y = _table(run, run.config["params"])

    window = Window(run.seconds)
    tracewin = TraceWindow(os.path.join(run.cache_dir, "trace", run.cell["name"])) \
        if run.trace else None
    booster, setup_s, after_setup, first_window_iter = train._train_chunks(
        run, lgb, table, params, window, tracewin)
    final = compilewatch.snapshot()
    gb, pt = booster.boosting, booster.boosting.ptrainer

    with run.spans.span("heldout_auc"):
        prob = np.asarray(booster.predict(Xh, num_iteration=mix["auc_iters"]), np.float64).T
        raw = np.asarray(booster.predict(Xh, num_iteration=mix["auc_iters"], raw_score=True))
        auc = multiclass_ref.macro_auc(yh, prob, data.auc)
    ref_prob = multiclass_ref.softmax(raw.T)
    first, last = first_window_iter * K, (first_window_iter + window.units) * K
    window_leaves = [int(t.num_leaves) for t in gb.models[first:last]]
    short = sum(n < params["num_leaves"] for n in window_leaves)
    retraces = sum(w["retraces"] for w in final["watched"].values())
    compiles = final["backend_compiles"] - after_setup["backend_compiles"]
    model_crc = zlib.crc32(booster.model_to_string(num_iteration=mix["auc_iters"]).encode())
    checks = [
        (f"trained on {run.config['trainer']} over {chips} device(s) with {K} classes "
         f"(got {type(pt).__name__}, {getattr(pt, 'd', 1)}, K = {getattr(pt, 'K', None)})",
         type(pt).__name__ == run.config["trainer"] and getattr(pt, "d", 1) == chips
         and getattr(pt, "K", None) == K),
        (f"zero jax_retrace flags ({retraces})", retraces == 0),
        (f"zero compiles after warm-up ({compiles})", compiles == 0),
        (f"held-out macro AUC {auc:.5f} over {K} classes at {mix['auc_iters']} iterations >= "
         f"floor {mix['auc_floor']}", auc >= mix["auc_floor"]),
        (f"predict's probabilities are multiclass_ref's softmax of its raw class scores "
         f"(max abs diff {float(np.max(np.abs(prob - ref_prob))):.2e})",
         bool(np.allclose(prob, ref_prob, rtol=0, atol=1e-6))),
        (f"every lap delivered its iterations ({window.failed} failed)", window.failed == 0),
    ]
    if not run.rehearse:  # the rehearsal needs PGROW=force, interprets, and has few rows
        set_ = [v for v in train.OVERRIDES if v in os.environ]
        checks += [
            (f"no grower override in the environment ({set_})", not set_),
            ("kernels compiled through Mosaic (interpret is False)", pt.interpret is False),
            (f"every class tree of the window has {params['num_leaves']} leaves ({short} of "
             f"{len(window_leaves)} short, min {min(window_leaves)})", short == 0),
        ]

    def peak_now() -> int:
        return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in jax.local_devices())

    # the peak never falls, so it is read before the checks, which hold arrays of their own:
    # what the cell's memory is measured by is set-up, training and the held-out predict
    peak = peak_now()
    with run.spans.span("score_check"):
        check, score_notes = _score_check(gb, y, run)
    checks.append(check)
    with run.spans.span("gradient_check"):  # after every read of the model: it trains one more
        check, grad_notes = _gradient_check(gb, pt, y, mix)
    checks.append(check)
    checks += _parity(run, params, K)

    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    values = {"setup_s": setup_s, "heldout_auc": auc}
    notes = {"iterations": int(gb.iter), "classes": K, "leaves_min": min(window_leaves),
             "trees_short": short, "model_crc32": model_crc,
             "multi_logloss": multiclass_ref.logloss(yh, prob),
             "multi_error": multiclass_ref.error(yh, prob), "heldout_rows": len(yh),
             **grad_notes, **score_notes, "peak_bytes_before_checks": peak,
             "peak_bytes_after_checks": max(st.get("peak_bytes_in_use", 0) for st in stats),
             "memory_limit_bytes": stats[0].get("bytes_limit"),
             "compile_s": final["backend_compile_secs"],
             "cache_hits": final["cache_hits"], "cache_misses": final["cache_misses"],
             "spans": {s["name"]: round(s["dur_s"], 3) for s in run.spans.done
                       if s["name"] != "chunk"}}
    record = None
    if tracewin is None:
        values["train_s_per_iter"] = window.percentile(50)
        notes.update(laps=len(window.laps), s_per_iter_p90=window.percentile(90),
                     s_per_iter_min=min(window.laps), s_per_iter_max=max(window.laps),
                     s_per_iter_by_lap=[round(lap, 6) for lap in window.laps])
    else:
        traced = gb.models[first:last]
        parent_rows = sum(int(t.internal_count[:int(t.num_leaves) - 1].sum()) for t in traced)
        cols = pt.params.num_cols or pt.params.num_features  # what the matrix holds
        record = {
            "driver": "train", "chips": chips, "iters": window.units, "laps": len(window.laps),
            "rows": mix["rows_per_chip"],
            "window_s": tracewin.window_s, "bench_spans": run.spans.done,
            "compile_setup": after_setup, "memory_peak_bytes": peak,
            "stream_bytes_per_iter": roofline.train_stream_bytes(
                mix["rows_per_chip"], cols, parent_rows / chips / window.units),
            **tracewin.reduce(on_device=not run.rehearse),
        }
        if not run.rehearse:
            record["peaks"] = roofline.peaks(run.device["kind"])
        _keep(tracewin.dir, record)
    return Outcome(values=values, attempted=window.attempted, failed=window.failed,
                   checks=checks, memory_peak_bytes=peak, record=record, notes=notes)
