"""Driver `train`: boosting iterations on the fused trainer, timed over a
window of the host's clock.

A mix without `valid` drives `GBDT.train_iters_partitioned(chunk_iters)`
chunk after chunk, each ended by `block_until_ready` on the scores, as
`engine.train` does between its callbacks.  A mix with `valid` goes
through `lgb.train(..., valid_sets=[...])` itself, so that the cell sees
whatever path the engine chooses when it must evaluate, and is timed from a
callback, one lap an iteration.

The training table's features stand for the user's dataset file: drawn once
from the mix's `features_seed`, binned by the program, and kept as the
program's own binary dataset cache under benchmarks/.cache/, so that only the
first run in a checkout pays for binning (PERF.md section 4 has the price of
binning per seed).  Its labels are drawn from `--seed` in every run and laid
over the cached file (`lgb.Dataset(<file>, label=...)`), so every seed trains
on another sample of the task and grows other trees.  `--seed` also draws the
held-out rows and the rows of the parity check.
"""

import hashlib
import json
import os
from unittest import mock

import numpy as np

import data
from harness import roofline
from harness.measure import Outcome, Window
from harness.tracing import TraceWindow

# knobs that select another grower or kernel variant: a run with one set does
# not measure the program's defaults
OVERRIDES = ("LIGHTGBM_TPU_PGROW", "LIGHTGBM_TPU_LEVELGROW", "LIGHTGBM_TPU_MAXLVL",
             "LIGHTGBM_TPU_FORCE_BITS", "LIGHTGBM_TPU_HIST_FCHUNK")
# fused vs mask grower: both accumulate histograms in float32 but in different
# orders, so leaf values agree to rounding (2.4e-7 seen on the chip, PR 22) and
# a near-tied split may differ from the second tree on.  chip_smoke.py's band:
# wide enough for that, far too tight for a bf16 accumulation (errors ~1e-2).
PARITY_RTOL, PARITY_ATOL = 3e-3, 3e-4
_NO_LIMIT = 1_000_000  # lgb.train's round count where the window callback ends the run
NO_FUSED_TRAINER = ("train driver: the program chose no fused trainer for this configuration; "
                    "timing the mask grower is not this benchmark")


def _table(run, rows: int, params: dict):
    """The binned training table: features from the cache (or binned and
    cached), labels drawn from `--seed`."""
    import lightgbm_tpu as lgb

    mix = run.mix
    key = json.dumps([rows, mix["features"], mix["features_seed"], params], sort_keys=True)
    name = f"table-{rows}-{hashlib.sha256(key.encode()).hexdigest()[:12]}.bin"
    path = os.path.join(run.cache_dir, name)
    with run.spans.span("table_generate"):  # the labels need the features' margin
        X, y = data.make_higgs_shaped(rows, mix["features_seed"], mix["features"],
                                      label_seed=run.seed)
    if not os.path.exists(path):
        with run.spans.span("table_bin"):
            made = lgb.Dataset(X, label=y, params=dict(params), free_raw_data=True)
            made.construct()
        os.makedirs(run.cache_dir, exist_ok=True)
        made.save_binary(path + ".tmp")
        os.replace(path + ".tmp", path)  # a killed run leaves no half file
        del made
    del X
    with run.spans.span("table_load"):
        table = lgb.Dataset(path, label=y, params=dict(params))
        table.construct()
    return table


def _tree_splits(tree):
    ns = int(tree.num_leaves) - 1
    return (tree.split_feature[:ns].tolist(), tree.threshold_in_bin[:ns].tolist(),
            tree.left_child[:ns].tolist(), tree.right_child[:ns].tolist())


def _parity(run, params: dict) -> list:
    """The fused trainer against the plain reference (`ops/grow.py`, the mask
    grower on one device) on a small draw from the seed."""
    import lightgbm_tpu as lgb

    mix = run.mix
    X, y = data.make_higgs_shaped(mix["parity_rows"], run.seed + 2, mix["features"])

    def train(p):
        return lgb.train(dict(p), lgb.Dataset(X, label=y, params=dict(p)),
                         num_boost_round=mix["parity_iters"], verbose_eval=False)

    fused = train(params)
    with mock.patch.dict(os.environ, LIGHTGBM_TPU_PGROW="0"):
        plain = train(dict(params, tree_learner="serial"))
    checks = [("parity pair is fused trainer vs mask grower",
               fused.boosting.ptrainer is not None and plain.boosting.ptrainer is None)]
    sf, sp = (_tree_splits(b.boosting.models[0]) for b in (fused, plain))
    checks.append((f"first tree split-for-split equal to the reference ({len(sf[0])} splits)",
                   len(sf[0]) > 0 and sf == sp))
    pf, pp = fused.predict(X), plain.predict(X)
    diff = float(np.max(np.abs(pf - pp)))
    checks.append((f"predictions within rtol {PARITY_RTOL} / atol {PARITY_ATOL} of the "
                   f"reference (max abs diff {diff:.2e})",
                   bool(np.allclose(pf, pp, rtol=PARITY_RTOL, atol=PARITY_ATOL))))
    return checks


class _Laps:
    """An `lgb.train` callback that runs the window: warm-up iterations, then
    one lap an iteration until the window is over (or `trace_iters` are traced)
    and `auc_iters` are done."""

    order = 100  # after the engine's own callbacks

    def __init__(self, run, window: Window, tracewin):
        self.run, self.window, self.tracewin = run, window, tracewin
        self.engine_auc = {}
        self.setup_s = self.after_setup = None
        self.closed = False

    def __call__(self, env) -> None:
        from lightgbm_tpu.callback import EarlyStopException
        from lightgbm_tpu.obs import compilewatch

        mix = self.run.mix
        done = env.iteration + 1
        for _, name, value, _ in env.evaluation_result_list or []:
            if name == "auc":
                self.engine_auc[done] = float(value)
        if done < mix["warmup_iters"]:
            return
        if done == mix["warmup_iters"]:
            self.setup_s = self.run.setup_s
            self.after_setup = compilewatch.snapshot()
            if self.tracewin is not None:
                self.tracewin.start()
            self.window.start()
            return
        if not self.closed:
            self.window.lap(1)
            if self.tracewin is None:
                self.closed = self.window.over
            elif self.window.units == mix["trace_iters"]:
                self.tracewin.stop()
                self.closed = True
        if self.closed and done >= mix["auc_iters"]:
            raise EarlyStopException(env.iteration, None)


def _train_chunks(run, lgb, table, params, window, tracewin):
    """Chunks of `chunk_iters` iterations through `train_iters_partitioned`.
    Returns (booster, setup_s, compile snapshot after set-up, first window tree)."""
    import jax

    from lightgbm_tpu.obs import compilewatch

    mix, k = run.mix, run.mix["chunk_iters"]
    with run.spans.span("booster"):
        booster = lgb.Booster(params=params, train_set=table)
        gb = booster.boosting
        if gb.ptrainer is None:
            raise SystemExit(NO_FUSED_TRAINER)
        jax.block_until_ready(gb.ptrainer.p)

    def chunk() -> bool:
        before = gb.iter
        with run.spans.span("chunk"):
            gb.train_iters_partitioned(k, is_eval=False)
            jax.block_until_ready(gb.scores)
        return gb.iter - before == k

    with run.spans.span("warmup"):
        while gb.iter < mix["warmup_iters"]:
            chunk()
    after_setup = compilewatch.snapshot()
    first_window_tree = gb.iter
    setup_s = run.setup_s
    if tracewin is None:
        window.start()
        while not window.over:
            window.lap(k, chunk())
    else:
        tracewin.start()
        window.start()
        for _ in range(mix["trace_chunks"]):
            window.lap(k, chunk())
        tracewin.stop()
    while gb.iter < mix["auc_iters"]:  # untimed: the AUC is of a fixed model
        chunk()
    return booster, setup_s, after_setup, first_window_tree


def run(run) -> Outcome:
    import jax

    import lightgbm_tpu as lgb
    from lightgbm_tpu.obs import compilewatch

    mix = run.mix
    params = {**run.config["params"], **mix.get("params", {})}
    chips = run.cell["chips"]
    compilewatch.install()

    with run.spans.span("heldout_generate"):
        Xh, yh = data.make_higgs_shaped(mix["heldout_rows"], run.seed + 1, mix["features"])
    with run.spans.span("dataset"):
        table = _table(run, mix["rows_per_chip"] * chips, run.config["params"])

    window = Window(run.seconds)
    tracewin = TraceWindow(os.path.join(run.cache_dir, "trace", run.cell["name"])) \
        if run.trace else None
    engine_auc = None
    if mix.get("valid"):
        laps = _Laps(run, window, tracewin)
        booster = lgb.train(
            params, table, num_boost_round=_NO_LIMIT,
            valid_sets=[lgb.Dataset(Xh, label=yh, reference=table)],
            verbose_eval=False, callbacks=[laps])
        setup_s, after_setup = laps.setup_s, laps.after_setup
        first_window_tree = mix["warmup_iters"]
        engine_auc = laps.engine_auc.get(mix["auc_iters"])
    else:
        booster, setup_s, after_setup, first_window_tree = _train_chunks(
            run, lgb, table, params, window, tracewin)
    final = compilewatch.snapshot()
    gb, pt = booster.boosting, booster.boosting.ptrainer
    if pt is None:
        raise SystemExit(NO_FUSED_TRAINER)

    with run.spans.span("heldout_auc"):
        auc = data.auc(yh, booster.predict(Xh, num_iteration=mix["auc_iters"]))
    leaves = [int(t.num_leaves) for t in gb.models[first_window_tree:]]
    retraces = sum(w["retraces"] for w in final["watched"].values())
    compiles = final["backend_compiles"] - after_setup["backend_compiles"]
    checks = [
        (f"trained on {run.config['trainer']} over {chips} device(s) "
         f"(got {type(pt).__name__}, {getattr(pt, 'd', 1)})",
         type(pt).__name__ == run.config["trainer"] and getattr(pt, "d", 1) == chips),
        (f"zero jax_retrace flags ({retraces})", retraces == 0),
        (f"zero compiles after warm-up ({compiles})", compiles == 0),
        (f"held-out AUC {auc:.5f} at {mix['auc_iters']} iterations >= floor {mix['auc_floor']}",
         auc >= mix["auc_floor"]),
        (f"every lap delivered its iterations ({window.failed} failed)", window.failed == 0),
    ]
    if engine_auc is not None:
        checks.append((f"the engine's own valid AUC at that iteration agrees ({engine_auc:.5f})",
                       abs(engine_auc - auc) < 1e-4))
    if not run.rehearse:  # the rehearsal needs PGROW=force, interprets, and has few rows
        set_ = [v for v in OVERRIDES if v in os.environ]
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
    notes = {"iterations": int(gb.iter), "leaves_min": min(leaves),
             "memory_limit_bytes": stats[0].get("bytes_limit"),
             "compile_s": final["backend_compile_secs"],
             "cache_hits": final["cache_hits"], "cache_misses": final["cache_misses"],
             "spans": {s["name"]: round(s["dur_s"], 3) for s in run.spans.done
                       if s["name"] != "chunk"}}
    record = None
    if tracewin is None:
        values["train_s_per_iter"] = window.percentile(50)
        notes.update(laps=len(window.laps), s_per_iter_p90=window.percentile(90),
                     s_per_iter_min=min(window.laps), s_per_iter_max=max(window.laps))
    else:
        traced = gb.models[first_window_tree:first_window_tree + window.units]
        parent_rows = sum(int(t.internal_count[:int(t.num_leaves) - 1].sum()) for t in traced)
        record = {
            "driver": "train", "chips": chips, "iters": window.units, "laps": len(window.laps),
            "window_s": tracewin.window_s, "bench_spans": run.spans.done,
            "compile_setup": after_setup, "memory_peak_bytes": peak,
            "stream_bytes_per_iter": roofline.train_stream_bytes(
                mix["rows_per_chip"], mix["features"], parent_rows / chips / window.units),
            **tracewin.reduce(on_device=not run.rehearse),
        }
        if not run.rehearse:
            record["peaks"] = roofline.peaks(run.device["kind"])
    return Outcome(values=values, attempted=window.attempted, failed=window.failed,
                   checks=checks, memory_peak_bytes=peak, record=record, notes=notes)
