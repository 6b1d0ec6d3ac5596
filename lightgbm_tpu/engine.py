"""Training/cv entry points — counterpart of
python-package/lightgbm/engine.py (train:17, cv:~250).
"""

from __future__ import annotations

import collections
import copy
from typing import Any, Dict, List, Optional

import numpy as np

from . import callback as callback_mod
from .basic import Booster, Dataset
from .ckpt.manager import PreemptionExit
from .config import canonicalize_params
from .obs import tracer
from .obs.audit import audit
from .parallel.net import NetError
from .utils.log import Log


def train(
    params: Dict[str, Any],
    train_set: Dataset,
    num_boost_round: int = 100,
    valid_sets=None,
    valid_names=None,
    fobj=None,
    feval=None,
    init_model=None,
    feature_name="auto",
    categorical_feature="auto",
    early_stopping_rounds: Optional[int] = None,
    evals_result: Optional[dict] = None,
    verbose_eval=True,
    learning_rates=None,
    keep_training_booster: bool = True,
    callbacks=None,
    checkpoint_dir: Optional[str] = None,
    checkpoint_freq: int = 0,
    checkpoint_keep: int = 3,
    checkpoint_resume="auto",
    checkpoint_manager=None,
) -> Booster:
    """lgb.train (engine.py:17-199).

    Fault tolerance (TPU extension, docs/CHECKPOINT.md): pass
    ``checkpoint_dir``/``checkpoint_freq`` (or a prebuilt
    ``CheckpointManager`` via ``checkpoint_manager``) to write full
    training-state checkpoints every ``checkpoint_freq`` iterations.
    ``checkpoint_resume`` is ``"auto"`` (resume only an interrupted
    run), ``False`` (never), or ``"force"`` (require a checkpoint).
    A resumed run is bit-identical to one that never died.  Multihost
    checkpoints are saved in a canonical topology-free layout, so a
    run may resume on a *different* world size (elastic resume — same
    world stays byte-identical; a resized fleet reshards and continues
    from the same iteration).  ``rebalance=True`` additionally lets a
    data-parallel fleet shift shard boundaries off a persistently slow
    host at iteration boundaries (docs/ROBUSTNESS.md)."""
    tracer.refresh_from_env()  # LIGHTGBM_TPU_TRACE=trace.jsonl
    audit.refresh_from_env()   # LIGHTGBM_TPU_AUDIT=audit.jsonl
    params = dict(params or {})
    canon = canonicalize_params(params)
    num_boost_round = int(canon.pop("num_iterations", num_boost_round))
    if "early_stopping_round" in canon:
        early_stopping_rounds = int(canon["early_stopping_round"])
    # strip the loop-controlling keys: the python loop owns iteration count
    # and early stopping (engine.py:100-118), not the inner driver
    for alias in ("num_iterations", "num_iteration", "num_tree", "num_trees",
                  "num_round", "num_rounds", "num_boost_round",
                  "early_stopping_round", "early_stopping_rounds",
                  "early_stopping"):
        params.pop(alias, None)

    if fobj is not None:
        params.setdefault("objective", "none")
    if feature_name != "auto":
        train_set.feature_name = feature_name
    if categorical_feature != "auto":
        train_set.categorical_feature = categorical_feature

    booster = Booster(params=params, train_set=train_set)  # the `booster_init` stage
    tracer.event(
        "train_begin", num_boost_round=num_boost_round,
        objective=str(params.get("objective", "")),
        num_leaves=str(params.get("num_leaves", "")),
        num_data=train_set.num_data(),
        mode="out_of_core" if getattr(booster.boosting, "ooc", None)
        is not None else "in_memory",
    )
    if init_model is not None:
        _apply_init_model(booster, init_model, train_set)

    # valid sets
    valid_list: List[Dataset] = []
    name_list: List[str] = []
    if valid_sets is not None:
        if isinstance(valid_sets, Dataset):
            valid_sets = [valid_sets]
        for i, vs in enumerate(valid_sets):
            if vs is train_set:
                name_list.append("training")
                valid_list.append(None)  # marker: evaluate on train scores
                continue
            if valid_names is not None and i < len(valid_names):
                name = valid_names[i]
            else:
                name = f"valid_{i}"
            booster.add_valid(vs, name)
            valid_list.append(vs)
            name_list.append(name)

    eval_train = "training" in name_list

    # callbacks (engine.py:120-152)
    cbs = set(callbacks or [])
    if verbose_eval is True:
        cbs.add(callback_mod.print_evaluation())
    elif isinstance(verbose_eval, int) and verbose_eval is not False:
        cbs.add(callback_mod.print_evaluation(verbose_eval))
    if early_stopping_rounds is not None and early_stopping_rounds > 0:
        cbs.add(callback_mod.early_stopping(early_stopping_rounds,
                                            verbose=bool(verbose_eval)))
    if learning_rates is not None:
        cbs.add(callback_mod.reset_parameter(learning_rate=learning_rates))
    if evals_result is not None:
        cbs.add(callback_mod.record_evaluation(evals_result))
    cbs_before = {c for c in cbs if getattr(c, "before_iteration", False)}
    cbs_after = cbs - cbs_before
    cbs_before = sorted(cbs_before, key=lambda c: getattr(c, "order", 0))
    cbs_after = sorted(cbs_after, key=lambda c: getattr(c, "order", 0))

    # checkpoint/resume wiring (ckpt/, docs/CHECKPOINT.md): params may
    # carry the config-level knobs; explicit arguments win
    ckpt_mgr = checkpoint_manager
    own_mgr = False
    if ckpt_mgr is None:
        cdir = checkpoint_dir or str(canon.get("checkpoint_dir", "") or "")
        if cdir:
            from .ckpt import CheckpointManager

            cfreq = int(checkpoint_freq or canon.get("checkpoint_freq", 0) or 0)
            ckpt_mgr = CheckpointManager(
                cdir, freq=cfreq,
                keep_last=int(canon.get("checkpoint_keep", checkpoint_keep)),
            )
            own_mgr = True
    start_iter = 0
    if ckpt_mgr is not None:
        ckpt_mgr.track_callbacks(list(cbs_before) + list(cbs_after))
        cbs_after = sorted(cbs_after + [ckpt_mgr],
                           key=lambda c: getattr(c, "order", 0))
        resume = checkpoint_resume
        if isinstance(resume, str):
            resume = resume.lower()
        if resume not in (False, None, "false", "0", "none"):
            state = ckpt_mgr.try_restore(
                booster, require=(resume == "force"),
                ignore_complete=(resume == "force"),
            )
            if state is not None:
                start_iter = state.iteration

    def _net_abort(e: NetError) -> None:
        """Cooperative abort (docs/ROBUSTNESS.md): a peer died or a
        collective timed out.  Flush the last completed checkpoint so it
        is durable, then let the typed error propagate — the CLI maps it
        to a retryable exit code and the next ``task=train`` auto-resumes
        bit-identically from that boundary."""
        if ckpt_mgr is not None:
            try:
                ckpt_mgr.flush()
            except Exception:  # pragma: no cover - disk-full etc.
                pass
        Log.warning(
            "Training aborted by transport failure (%s): %s — latest "
            "completed checkpoint preserved; rerun to auto-resume",
            type(e).__name__, e,
        )

    def _finalize(b: Booster) -> Booster:
        if ckpt_mgr is not None:
            if ckpt_mgr.preempted:
                ckpt_mgr.flush()  # preempted: leave resumable state
            else:
                ckpt_mgr.mark_complete(b)
            if own_mgr:
                ckpt_mgr.close()
        return b

    def _ckpt_bounded(step: int, i: int) -> int:
        """Clip a fused-chunk length so chunk ends land on checkpoint
        boundaries (the manager can only capture between dispatches)."""
        if ckpt_mgr is not None and ckpt_mgr.freq > 0:
            step = min(step, ckpt_mgr.freq - (i % ckpt_mgr.freq))
        return max(step, 1)

    # Fused fast path: with no per-iteration host decisions (no valid
    # sets, no custom objective, no before-iteration callbacks, no early
    # stopping) the whole run executes as chunked device programs, with
    # no host round trip per iteration (its cost: not measured on this
    # machine).
    ptrainer = getattr(booster.boosting, "ptrainer", None)
    if (
        ptrainer is not None
        and fobj is None
        and not name_list
        and not cbs_before
        and not (early_stopping_rounds and early_stopping_rounds > 0)
    ):
        i = start_iter
        stopped = False
        while i < num_boost_round and not stopped:
            step = _ckpt_bounded(num_boost_round - i, i)
            iter_before = booster.boosting.iter
            stopped = booster.boosting.train_iters_partitioned(step, is_eval=False)
            done = booster.boosting.iter - iter_before
            try:
                for t in range(done):
                    for cb in cbs_after:
                        cb(callback_mod.CallbackEnv(
                            booster, params, i + t, 0, num_boost_round, []))
            except PreemptionExit:
                booster.best_iteration = booster.current_iteration()
                return _finalize(booster)
            except NetError as ne:
                _net_abort(ne)
                raise
            i += done
            if done < step:
                Log.info("Finished training with %d iterations", i)
                break
        booster.best_iteration = booster.current_iteration()
        return _finalize(booster)

    # Fused path WITH eval: when an eval period > 1 is configured
    # (output_freq, or an integer verbose_eval), run fused chunks of
    # ``period`` iterations between eval points instead of dropping to
    # one-dispatch-per-iteration; early stopping and the periodic
    # callbacks consume chunk-boundary metrics.  (The reference's CLI
    # evaluates at output_freq granularity the same way,
    # application.cpp:225-250; the python API's per-iteration eval is
    # preserved whenever period == 1.)
    # opt-in is output_freq ONLY: an integer verbose_eval controls PRINT
    # frequency in the reference API, never evaluation frequency, so it
    # must not change which iterations get evaluated
    period = int(canon.get("output_freq", 1))
    if (
        ptrainer is not None
        and fobj is None
        and not cbs_before
        and period > 1
    ):
        i = start_iter
        while i < num_boost_round:
            step = _ckpt_bounded(min(period, num_boost_round - i), i)
            iter_before = booster.boosting.iter
            booster.boosting.train_iters_partitioned(step, is_eval=False)
            done = booster.boosting.iter - iter_before
            i += done
            evaluation_result_list = []
            if valid_sets is not None or eval_train:
                with tracer.span("eval", iter=i):
                    if eval_train:
                        evaluation_result_list.extend(booster.eval_train(feval))
                    evaluation_result_list.extend(booster.eval_valid(feval))
            try:
                for cb in cbs_after:
                    cb(callback_mod.CallbackEnv(
                        booster, params, i - 1, 0, num_boost_round,
                        evaluation_result_list))
            except callback_mod.EarlyStopException as es:
                booster.best_iteration = es.best_iteration + 1
                _record_best_score(booster, es.best_score)
                break
            except PreemptionExit:
                break
            except NetError as ne:
                _net_abort(ne)
                raise
            if done < step:
                Log.info("Finished training with %d iterations", i)
                break
        if booster.best_iteration <= 0:
            booster.best_iteration = booster.current_iteration()
        return _finalize(booster)

    # training loop
    for i in range(start_iter, num_boost_round):
        for cb in cbs_before:
            cb(callback_mod.CallbackEnv(booster, params, i, 0, num_boost_round, None))
        finished = booster.update(fobj=fobj)
        evaluation_result_list = []
        if valid_sets is not None or eval_train:
            with tracer.span("eval", iter=i):
                if eval_train:
                    evaluation_result_list.extend(booster.eval_train(feval))
                evaluation_result_list.extend(booster.eval_valid(feval))
        try:
            for cb in cbs_after:
                cb(callback_mod.CallbackEnv(
                    booster, params, i, 0, num_boost_round, evaluation_result_list))
        except callback_mod.EarlyStopException as es:
            booster.best_iteration = es.best_iteration + 1
            _record_best_score(booster, es.best_score)
            break
        except PreemptionExit:
            break
        except NetError as ne:
            _net_abort(ne)
            raise
        if finished:
            Log.info("Finished training with %d iterations", i + 1)
            break
    if booster.best_iteration <= 0:
        booster.best_iteration = booster.current_iteration()
    return _finalize(booster)


def _metric_rank(name: str, params: Dict[str, Any]) -> int:
    """Position of a result metric in the configured metric list (prefix
    match tolerates decorated names like ndcg@5); unknown -> end."""
    metric = params.get("metric", "")
    if isinstance(metric, str):
        # Config._parse_list accepts comma OR whitespace separators
        metric = [m for m in metric.replace(",", " ").split() if m]
    for i, m in enumerate(metric or []):
        if name == m or name.startswith(str(m)):
            return i
    return 1 << 30


def _record_best_score(booster: Booster, best_score_list) -> None:
    if not best_score_list:
        return
    out: Dict[str, Dict[str, float]] = collections.defaultdict(dict)
    for item in best_score_list:
        out[item[0]][item[1]] = item[2]
    booster.best_score = dict(out)


def _apply_init_model(booster: Booster, init_model, train_set: Dataset) -> None:
    """Continued training (engine.py init_model / gbdt.cpp input_model):
    load the model and seed the training scores with its predictions."""
    if isinstance(init_model, Booster):
        model_str = init_model.model_to_string()
    else:
        with open(init_model) as f:
            model_str = f.read()
    prev = Booster(params=booster.params, model_str=model_str)
    b = booster.boosting
    # schema-drift guard: a feature-count mismatch used to surface as a
    # shape error deep in the trainer (or silent garbage predictions
    # when the new data happens to be wider).  The continuous-training
    # factory hits this whenever the watched data directory drifts, so
    # name the mismatch and the fix here instead.
    prev_nf = int(getattr(prev.boosting, "max_feature_idx", -1)) + 1
    new_nf = int(train_set.num_feature())
    if prev_nf > 0 and prev_nf != new_nf:
        Log.fatal(
            "init_model was trained on %d features but the new training "
            "data has %d — continued training requires the same feature "
            "schema (same columns, same order). Retrain from scratch, or "
            "fix the data source that drifted.", prev_nf, new_nf)
    prev_tpi = int(max(prev.boosting.num_tree_per_iteration, 1))
    new_tpi = int(max(b.num_tree_per_iteration, 1))
    if prev_tpi != new_tpi:
        Log.fatal(
            "init_model boosts %d tree(s) per iteration but the new "
            "training config boosts %d (different objective/num_class?) "
            "— continued training requires the same objective shape.",
            prev_tpi, new_tpi)
    b.models = prev.boosting.models + b.models
    b.num_init_iteration = len(prev.boosting.models) // max(
        prev.boosting.num_tree_per_iteration, 1
    )
    b.boost_from_average_ = prev.boosting.boost_from_average_
    raw = train_set.data
    if raw is None:
        Log.fatal("Continued training requires the raw training data")
    import jax.numpy as jnp

    init_scores = prev.boosting.predict_raw_scores(np.asarray(raw, np.float64))
    b.scores = b.scores + jnp.asarray(init_scores.astype(np.float32))


def cv(
    params: Dict[str, Any],
    train_set: Dataset,
    num_boost_round: int = 10,
    folds=None,
    nfold: int = 5,
    stratified: bool = False,
    shuffle: bool = True,
    metrics=None,
    fobj=None,
    feval=None,
    init_model=None,
    feature_name="auto",
    categorical_feature="auto",
    early_stopping_rounds: Optional[int] = None,
    fpreproc=None,
    verbose_eval=None,
    show_stdv: bool = True,
    seed: int = 0,
    callbacks=None,
) -> Dict[str, List[float]]:
    """lgb.cv (engine.py:~250-400): k-fold cross-validation returning
    {metric-mean: [...], metric-stdv: [...]}."""
    params = dict(params or {})
    if metrics is not None:
        params["metric"] = metrics
    canon = canonicalize_params(params)
    num_boost_round = int(canon.pop("num_iterations", num_boost_round))
    for alias in ("num_iterations", "num_iteration", "num_tree", "num_trees",
                  "num_round", "num_rounds", "num_boost_round"):
        params.pop(alias, None)

    full = train_set.construct()
    n = full.num_data
    label = np.asarray(full.metadata.label)

    # build folds (engine.py _make_n_folds)
    if folds is None:
        rng = np.random.RandomState(seed)
        if stratified:
            try:
                from sklearn.model_selection import StratifiedKFold

                skf = StratifiedKFold(n_splits=nfold, shuffle=shuffle,
                                      random_state=seed if shuffle else None)
                folds = list(skf.split(np.zeros(n), label))
            except ImportError:
                stratified = False
        if not stratified:
            idx = rng.permutation(n) if shuffle else np.arange(n)
            parts = np.array_split(idx, nfold)
            folds = [
                (np.concatenate([parts[j] for j in range(nfold) if j != i]), parts[i])
                for i in range(nfold)
            ]

    boosters = []
    for train_idx, test_idx in folds:
        tr = train_set.subset(np.sort(train_idx))
        te = train_set.subset(np.sort(test_idx))
        fold_params = params.copy()
        if fpreproc is not None:
            # per-fold params stay local (reference engine's tparam)
            tr, te, fold_params = fpreproc(tr, te, fold_params)
        bst = Booster(params=fold_params, train_set=tr)
        bst.add_valid(te, "valid")
        boosters.append(bst)

    results = collections.defaultdict(list)
    best_iter = num_boost_round
    history: List[Dict[str, float]] = []
    for i in range(num_boost_round):
        merged = collections.defaultdict(list)
        for bst in boosters:
            bst.update(fobj=fobj)
            for _, name, val, bigger in bst.eval_valid(feval):
                merged[(name, bigger)].append(val)
        one = {}
        for (name, bigger), vals in merged.items():
            mean, std = float(np.mean(vals)), float(np.std(vals))
            results[name + "-mean"].append(mean)
            results[name + "-stdv"].append(std)
            one[name] = (mean, bigger)
        history.append(one)
        if verbose_eval:
            msg = "\t".join(
                f"cv_agg {k}: {results[k + '-mean'][-1]:g} + {results[k + '-stdv'][-1]:g}"
                for k in {name for (name, _) in merged}
            )
            Log.info("[%d]\t%s", i + 1, msg)
        if early_stopping_rounds and len(history) > early_stopping_rounds:
            # stop on the FIRST configured metric (the reference keys
            # early stopping off config order, not dict iteration order)
            first = min(merged.keys(), key=lambda kb: _metric_rank(kb[0], params))
            (name, bigger) = first
            series = results[name + "-mean"]
            best = int(np.argmax(series) if bigger else np.argmin(series))
            if len(series) - 1 - best >= early_stopping_rounds:
                for k in list(results.keys()):
                    results[k] = results[k][: best + 1]
                break
    return dict(results)
