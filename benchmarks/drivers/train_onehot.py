"""Driver `train_onehot`: driver `train`'s chunked run on a one-hot table that
is handed to the program as scipy CSR and trained from its EFB bundles.

Its record says `"driver": "train"`, so every reader of `train` reads this
cell as it reads the others.  It differs from drivers/train.py in four places:

1. The table comes from data_onehot.py as CSR and goes through the program's
   sparse ingest (`lgb.Dataset(<CSR>)`, lightgbm_tpu/io/sparse.py); what is
   cached under benchmarks/.cache/ is the program's binary file of its BUNDLED
   matrix.  The table is fixed WHOLE: features from the mix's `features_seed`
   and labels from the mix's `labels_seed`, not from `--seed`.  The published
   experiment is one table, which a user re-trains; and on a one-hot table the
   trees are chains whose tail (the splits the replay takes one `split_stream`
   pass at a time) moves with the labels by 10% of an iteration, which no
   window a run can afford medians away (PERF.md section 6, PR 35/36).  So
   every seed grows the same trees and does the same work.
2. `--seed` draws what is outside the timed work: the held-out rows
   (`seed + 1`) and the parity table (`seed + 2`).
3. `stream_bytes_per_iter` is reckoned over the bundle columns the trainer
   streams, not the 700 indicator columns.
4. `correct` gains three checks: the trainer streams the bundles; no `(N, F)`
   bin matrix exists on the host or on the device; and the parity pair is the
   fused trainer on CSR-made bundles against the mask grower on the DENSE,
   UNBUNDLED copy of the same rows, which shares neither the ingest, nor the
   bundles, nor a kernel with what is timed.

The first import below is the program's sparse ingest, on purpose: a program
without it fails here at once with an ImportError, before any table is made
(it would otherwise `toarray()` 21M x 700 float64, 117.6 GB).
"""

from lightgbm_tpu.io import sparse as _sparse_ingest  # noqa: F401  (first, on purpose: see above)

import contextlib  # noqa: E402
import hashlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import zlib  # noqa: E402
from unittest import mock  # noqa: E402

import numpy as np  # noqa: E402

import data  # noqa: E402
import data_onehot  # noqa: E402
from harness import roofline  # noqa: E402
from harness.measure import Outcome, Window  # noqa: E402
from harness.spec import load_module  # noqa: E402
from harness.tracing import TraceWindow  # noqa: E402

train = load_module("drivers", "train")  # unedited: its constants, chunk loop and tree view


def _fields(mix) -> list:
    fields = [int(c) for c in mix["fields"].split(",")]
    if sum(fields) != mix["features"] or len(fields) != mix["nonzeros_per_row"]:
        raise SystemExit(f"train_onehot: fields {mix['fields']!r} do not make {mix['features']} "
                         f"columns with {mix['nonzeros_per_row']} non-zeros a row")
    return fields


def _draw(mix, rows: int, seed: int, label_seed: int = None):
    return data_onehot.make_onehot(rows, seed, _fields(mix), mix["zipf_exponent"],
                                   label_seed=label_seed)


@contextlib.contextmanager
def _program_spans(path: str, into: list):
    """The program's span tracer on for a stretch of set-up, its spans appended
    to `into` (layer_metrics/ingest_s.py reads `sparse_ingest` there).  The
    traced window switches the tracer on again, to a file of its own."""
    from lightgbm_tpu.obs import tracer

    os.makedirs(os.path.dirname(path), exist_ok=True)
    with mock.patch.dict(os.environ, LIGHTGBM_TPU_TRACE=path, LIGHTGBM_TPU_TRACE_PHASES="0"):
        tracer.refresh_from_env()
        try:
            yield
        finally:
            tracer.close()
    tracer.refresh_from_env()
    with open(path) as f:
        into += [rec for rec in map(json.loads, f) if rec.get("ev") == "span"]


def _table(run, rows: int, params: dict):
    """The training table, features AND labels from the mix's own seeds: made
    as CSR and ingested on a checkout's first run, the program's binary file
    after."""
    import lightgbm_tpu as lgb

    mix = run.mix
    key = json.dumps([rows, mix["fields"], mix["zipf_exponent"], mix["features_seed"],
                      mix["labels_seed"], params], sort_keys=True)
    name = f"table-onehot-{rows}-{hashlib.sha256(key.encode()).hexdigest()[:12]}.bin"
    path = os.path.join(run.cache_dir, name)
    if not os.path.exists(path):
        with run.spans.span("table_generate"):
            X, y = _draw(mix, rows, mix["features_seed"], mix["labels_seed"])
        with run.spans.span("table_bin"):
            made = lgb.Dataset(X, label=y, params=dict(params), free_raw_data=True)
            made.construct()
        os.makedirs(run.cache_dir, exist_ok=True)
        made.save_binary(path + ".tmp")
        os.replace(path + ".tmp", path)  # a killed run leaves no half file
        del made, X, y
    with run.spans.span("table_load"):
        table = lgb.Dataset(path, params=dict(params))
        table.construct()
    return table


def _parity(run, params: dict) -> list:
    """The fused trainer on CSR-made bundles against the plain reference: the
    mask grower (`ops/grow.py`, one device) on the dense, unbundled copy of the
    same rows, drawn from the seed."""
    import lightgbm_tpu as lgb

    mix = run.mix
    X, y = _draw(mix, mix["parity_rows"], run.seed + 2)

    def trained(table, p):
        booster = lgb.train(dict(p), lgb.Dataset(table, label=y, params=dict(p)),
                            num_boost_round=mix["parity_iters"], verbose_eval=False)
        return booster, booster.train_dataset.construct()

    fused, fused_set = trained(X, params)
    dense = X.toarray()
    with mock.patch.dict(os.environ, LIGHTGBM_TPU_PGROW="0"):
        plain, plain_set = trained(dense, dict(params, tree_learner="serial"))
    pt = fused.boosting.ptrainer
    checks = [("parity pair is fused trainer on bundles vs mask grower on the dense "
               "unbundled table",
               pt is not None and pt.bmeta is not None and not fused_set.has_dense_bins
               and plain.boosting.ptrainer is None and plain_set.bundle is None
               and plain_set.has_dense_bins)]
    sf, sp = (train._tree_splits(b.boosting.models[0]) for b in (fused, plain))
    checks.append((f"first tree split-for-split equal to the reference ({len(sf[0])} splits)",
                   len(sf[0]) > 0 and sf == sp))
    pf, pp = fused.predict(X), plain.predict(dense)
    close = np.isclose(pf, pp, rtol=train.PARITY_RTOL, atol=train.PARITY_ATOL)
    checks.append((f"predictions within rtol {train.PARITY_RTOL} / atol {train.PARITY_ATOL} "
                   f"of the reference ({int((~close).sum())} of {len(pf)} rows outside, "
                   f"max abs diff {float(np.max(np.abs(pf - pp))):.2e})", bool(close.all())))
    return checks


def run(run) -> Outcome:
    import jax

    import lightgbm_tpu as lgb
    from lightgbm_tpu.obs import compilewatch

    mix = run.mix
    params = {**run.config["params"], **mix.get("params", {})}
    chips = run.cell["chips"]
    compilewatch.install()

    with run.spans.span("heldout_generate"):
        Xh, yh = _draw(mix, mix["heldout_rows"], run.seed + 1)
    setup_spans = []
    with contextlib.ExitStack() as stack:
        if run.trace:
            stack.enter_context(_program_spans(
                os.path.join(run.cache_dir, "trace", run.cell["name"] + ".setup.jsonl"),
                setup_spans))
        with run.spans.span("dataset"):
            table = _table(run, mix["rows_per_chip"] * chips, run.config["params"])

    window = Window(run.seconds)
    tracewin = TraceWindow(os.path.join(run.cache_dir, "trace", run.cell["name"])) \
        if run.trace else None
    booster, setup_s, after_setup, first_window_tree = train._train_chunks(
        run, lgb, table, params, window, tracewin)
    final = compilewatch.snapshot()
    gb, pt = booster.boosting, booster.boosting.ptrainer
    binned = table.construct()
    cols, col_bins = pt.params.num_cols, pt.params.num_bins_hist

    with run.spans.span("heldout_auc"):
        auc = data.auc(yh, booster.predict(Xh, num_iteration=mix["auc_iters"]))
    leaves = [int(t.num_leaves) for t in gb.models[first_window_tree:]]
    retraces = sum(w["retraces"] for w in final["watched"].values())
    compiles = final["backend_compiles"] - after_setup["backend_compiles"]
    checks = [
        (f"trained on {run.config['trainer']} over {chips} device(s) "
         f"(got {type(pt).__name__}, {getattr(pt, 'd', 1)})",
         type(pt).__name__ == run.config["trainer"] and getattr(pt, "d", 1) == chips),
        (f"the trainer streams the bundles ({cols} columns of at most {col_bins} bins for "
         f"{binned.num_features} features)",
         pt.bmeta is not None and 0 < cols < binned.num_features and 0 < col_bins <= 256),
        ("no (N, F) bin matrix on the host or on the device",
         not binned.has_dense_bins and not gb.has_device_bins),
        (f"zero jax_retrace flags ({retraces})", retraces == 0),
        (f"zero compiles after warm-up ({compiles})", compiles == 0),
        (f"held-out AUC {auc:.5f} at {mix['auc_iters']} iterations >= floor {mix['auc_floor']}",
         auc >= mix["auc_floor"]),
        (f"every lap delivered its iterations ({window.failed} failed)", window.failed == 0),
    ]
    if not run.rehearse:  # the rehearsal needs PGROW=force, interprets, and has few rows
        set_ = [v for v in train.OVERRIDES if v in os.environ]
        checks += [
            (f"no grower override in the environment ({set_})", not set_),
            ("kernels compiled through Mosaic (interpret is False)", pt.interpret is False),
            (f"trees of the window have {params['num_leaves']} leaves (min {min(leaves)})",
             min(leaves) == params["num_leaves"]),
        ]
    checks += _parity(run, params)

    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    peak = max(st.get("peak_bytes_in_use", 0) for st in stats)
    values = {"setup_s": setup_s, "heldout_auc": auc}
    # the table is fixed, so every run should grow these very trees: the sum says whether
    # a run that read another time did other work or the same work at another speed
    notes = {"iterations": int(gb.iter), "leaves_min": min(leaves),
             "model_crc32": zlib.crc32(booster.model_to_string().encode()),
             "bundle_cols": cols, "max_col_bin": col_bins,
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
        traced = gb.models[first_window_tree:first_window_tree + window.units]
        parent_rows = sum(int(t.internal_count[:int(t.num_leaves) - 1].sum()) for t in traced)
        record = {
            "driver": "train", "chips": chips, "iters": window.units, "laps": len(window.laps),
            "window_s": tracewin.window_s, "bench_spans": run.spans.done,
            "compile_setup": after_setup, "memory_peak_bytes": peak,
            "setup_program_spans": setup_spans,
            "stream_bytes_per_iter": roofline.train_stream_bytes(
                mix["rows_per_chip"], cols, parent_rows / chips / window.units),
            **tracewin.reduce(on_device=not run.rehearse),
        }
        if not run.rehearse:
            record["peaks"] = roofline.peaks(run.device["kind"])
    return Outcome(values=values, attempted=window.attempted, failed=window.failed,
                   checks=checks, memory_peak_bytes=peak, record=record, notes=notes)
