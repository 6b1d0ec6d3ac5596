"""Checkpoint/resume subsystem tests (ckpt/, docs/CHECKPOINT.md).

The acceptance contract: resuming from a checkpoint is **bit-identical**
to never having died — same trees, same leaf values, same early-stopping
decision — for every boosting driver, because the checkpoint carries the
full training state (score caches, every RNG stream, bests, the fused
trainer's row permutation).  Process-kill variants live in
test_ckpt_fault.py; the 2-process sharded variant in test_multihost.py.
"""

import os
import shutil
import tempfile

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.ckpt import CheckpointManager, CheckpointMismatch
from lightgbm_tpu.ckpt.state import (
    TrainState,
    capture,
    pack_trees,
    unpack_trees,
)
from lightgbm_tpu.ckpt.store import CheckpointStore
from lightgbm_tpu.utils.random import Random


@pytest.fixture(scope="module")
def xy():
    rng = np.random.RandomState(0)
    X = rng.randn(600, 8)
    y = (X[:, 0] + 0.5 * X[:, 1] ** 2 + 0.1 * rng.randn(600) > 0).astype(float)
    return X, y


def _kill_at(k):
    """Callback simulating sudden death at iteration ``k`` (the process
    variants use real SIGKILL; in-process a non-Exception throwable that
    nothing in the engine catches plays the same role)."""
    def cb(env):
        if env.iteration + 1 == k:
            raise KeyboardInterrupt
    cb.order = 99
    return cb


def _train(P, X, y, rounds, ckpt_dir=None, freq=3, callbacks=None, **kw):
    ds = lgb.Dataset(X, label=y, params=dict(P))
    mgr = None
    if ckpt_dir is not None:
        mgr = CheckpointManager(ckpt_dir, freq=freq)
    try:
        bst = lgb.train(dict(P), ds, rounds, verbose_eval=False,
                        checkpoint_manager=mgr, callbacks=callbacks, **kw)
    finally:
        if mgr is not None:
            mgr.close()
    return bst


def _train_killed(P, X, y, rounds, ckpt_dir, kill, freq=3, **kw):
    with pytest.raises(KeyboardInterrupt):
        _train(P, X, y, rounds, ckpt_dir=ckpt_dir, freq=freq,
               callbacks=[_kill_at(kill)], **kw)


# ----------------------------------------------------------------------
# RNG state round trips (satellite: model text cannot carry these)
# ----------------------------------------------------------------------
def test_random_state_roundtrip():
    a = Random(123)
    for _ in range(37):
        a.next_float()
    state = a.get_state()
    seq_a = [a.next_float() for _ in range(20)] + list(a.sample(50, 11))
    b = Random(999).set_state(state)
    seq_b = [b.next_float() for _ in range(20)] + list(b.sample(50, 11))
    assert seq_a == seq_b
    # the state is one LCG word — a fresh seed differs
    assert Random(123).get_state() != state


def test_goss_key_roundtrip():
    import io

    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(3)
    for _ in range(5):
        key, _ = jax.random.split(key)
    # the npz round trip GOSS's export/import hooks ride on
    buf = io.BytesIO()
    np.savez(buf, k=np.asarray(key))
    buf.seek(0)
    k2 = jnp.asarray(np.load(buf)["k"])
    a = jax.random.uniform(jax.random.split(key)[1], (8,))
    b = jax.random.uniform(jax.random.split(k2)[1], (8,))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ----------------------------------------------------------------------
# store: atomicity, CRC, retention, corrupt-tail discovery
# ----------------------------------------------------------------------
def test_store_save_latest_retention(tmp_path):
    st = CheckpointStore(str(tmp_path), keep_last=2)
    for step in (2, 4, 6, 8):
        st.save(step, f"blob-{step}".encode())
    assert st.steps() == [6, 8]  # rolling retention
    files = [f for f in os.listdir(tmp_path) if f.startswith("ckpt_")]
    assert len(files) == 2
    assert not [f for f in os.listdir(tmp_path) if ".tmp." in f]
    step, blob = st.latest_valid()
    assert step == 8 and blob == b"blob-8"


def test_store_corrupt_tail_skipped(tmp_path):
    st = CheckpointStore(str(tmp_path), keep_last=3)
    st.save(3, b"three")
    st.save(6, b"sixsix")
    # truncate the tail checkpoint (torn write after a SIGKILL)
    with open(st.path_for(6), "wb") as f:
        f.write(b"si")
    step, blob = st.latest_valid()
    assert step == 3 and blob == b"three"
    # CRC failure (size right, bits wrong) is also skipped
    with open(st.path_for(6), "wb") as f:
        f.write(b"sixsex")
    step, _ = st.latest_valid()
    assert step == 3


def test_store_complete_marker(tmp_path):
    st = CheckpointStore(str(tmp_path))
    st.save(5, b"five")
    assert st.complete_step() is None
    st.mark_complete(7)
    assert st.complete_step() == 7
    st.save(9, b"nine")  # a new save voids the marker (run is live)
    assert st.complete_step() is None


# ----------------------------------------------------------------------
# TrainState: binary tree pack/unpack + capture fidelity
# ----------------------------------------------------------------------
def test_tree_pack_unpack_bit_exact(xy):
    X, y = xy
    P = dict(objective="binary", num_leaves=7, learning_rate=0.2, verbose=-1)
    bst = _train(P, X, y, 5)
    models = bst.boosting.models
    back = unpack_trees(pack_trees(models))
    assert len(back) == len(models)
    for a, b in zip(models, back):
        assert a.num_leaves == b.num_leaves
        assert a.to_string() == b.to_string()
        n, m = a.num_leaves, max(a.num_leaves - 1, 1)
        np.testing.assert_array_equal(a.leaf_value[:n], b.leaf_value[:n])
        np.testing.assert_array_equal(a.threshold[:m], b.threshold[:m])
        np.testing.assert_array_equal(a.threshold_in_bin[:m],
                                      b.threshold_in_bin[:m])


def test_trainstate_bytes_roundtrip(xy):
    X, y = xy
    P = dict(objective="binary", num_leaves=7, verbose=-1,
             bagging_fraction=0.7, bagging_freq=2)
    bst = _train(P, X, y, 6)
    state = capture(bst)
    back = TrainState.from_bytes(state.to_bytes())
    assert back.iteration == state.iteration == 6
    assert back.meta == state.meta
    for k, v in state.arrays.items():
        np.testing.assert_array_equal(back.arrays[k], np.asarray(v), err_msg=k)


def test_restore_refuses_config_and_data_mismatch(xy, tmp_path):
    X, y = xy
    P = dict(objective="binary", num_leaves=7, verbose=-1)
    d = str(tmp_path)
    _train_killed(P, X, y, 10, d, kill=6)
    # different math-relevant config -> refused
    P2 = dict(P, num_leaves=15)
    with pytest.raises(CheckpointMismatch):
        _train(P2, X, y, 10, ckpt_dir=d)
    # different dataset -> refused
    with pytest.raises(CheckpointMismatch):
        _train(P, X[:500], y[:500], 10, ckpt_dir=d)
    # volatile knobs (run length) do NOT refuse
    bst = _train(P, X, y, 12, ckpt_dir=d)
    assert bst.current_iteration() == 12


# ----------------------------------------------------------------------
# resume bit-identity across the boosting drivers
# ----------------------------------------------------------------------
def _assert_resume_bit_identical(P, X, y, rounds=10, kill=6, freq=3,
                                 monkeypatch=None, env=None):
    for k, v in (env or {}).items():
        monkeypatch.setenv(k, v)
    ref = _train(P, X, y, rounds).model_to_string()
    d = tempfile.mkdtemp()
    try:
        _train_killed(P, X, y, rounds, d, kill=kill, freq=freq)
        assert CheckpointStore(d).steps(), "no checkpoint written before kill"
        resumed = _train(P, X, y, rounds, ckpt_dir=d, freq=freq)
        assert resumed.model_to_string() == ref
    finally:
        shutil.rmtree(d, ignore_errors=True)


def test_resume_bit_identical_gbdt_bagging(xy, monkeypatch):
    X, y = xy
    _assert_resume_bit_identical(
        dict(objective="binary", num_leaves=7, learning_rate=0.2, verbose=-1,
             bagging_fraction=0.7, bagging_freq=2, feature_fraction=0.8),
        X, y, monkeypatch=monkeypatch,
    )


def test_resume_bit_identical_goss(xy, monkeypatch):
    # learning_rate=0.3 ends the GOSS warmup (1/lr ~ 3 iters) before the
    # kill, so the chained PRNGKey is live state when the run dies
    X, y = xy
    _assert_resume_bit_identical(
        dict(objective="binary", boosting="goss", num_leaves=7, verbose=-1,
             learning_rate=0.3, top_rate=0.3, other_rate=0.2),
        X, y, monkeypatch=monkeypatch,
    )


def test_resume_bit_identical_dart(xy, monkeypatch):
    X, y = xy
    _assert_resume_bit_identical(
        dict(objective="binary", boosting="dart", num_leaves=7, verbose=-1,
             learning_rate=0.2, drop_rate=0.4, drop_seed=7),
        X, y, monkeypatch=monkeypatch,
    )


def test_resume_bit_identical_fused_partitioned(xy, monkeypatch):
    """Serial fused trainer (LIGHTGBM_TPU_PGROW=force on CPU interpret):
    the checkpoint must carry the physical row permutation — histogram
    summation order follows the partition layout."""
    X, y = xy
    _assert_resume_bit_identical(
        dict(objective="binary", num_leaves=7, learning_rate=0.2,
             min_data_in_leaf=20, verbose=-1),
        X, y, monkeypatch=monkeypatch, env={"LIGHTGBM_TPU_PGROW": "force"},
    )


def test_resume_bit_identical_fused_bagged_mid_period(xy, monkeypatch):
    """The fused trainer's bag is a function of (seed, bagging period, row
    id), so a run killed inside a period (checkpoint at iteration 4 of a
    period of 3 iterations that began at 3) resumes into the SAME bag of
    original rows, in the restored layout: byte-equal to the uninterrupted
    run.  (Since PR 30 no tree start puts the rows back in original order;
    the draw reads the ROWID channel instead.)"""
    X, y = xy
    _assert_resume_bit_identical(
        dict(objective="binary", num_leaves=7, learning_rate=0.2,
             min_data_in_leaf=20, verbose=-1, bagging_fraction=0.6,
             bagging_freq=3, feature_fraction=0.8),
        X, y, rounds=10, kill=6, freq=4, monkeypatch=monkeypatch,
        env={"LIGHTGBM_TPU_PGROW": "force"},
    )


def test_resume_bit_identical_fused_goss(xy, monkeypatch):
    X, y = xy
    _assert_resume_bit_identical(
        dict(objective="binary", boosting="goss", num_leaves=7, verbose=-1,
             learning_rate=0.3, top_rate=0.3, other_rate=0.2),
        X, y, monkeypatch=monkeypatch, env={"LIGHTGBM_TPU_PGROW": "force"},
    )


def test_resume_bit_identical_sharded_partitioned(monkeypatch):
    """Sharded fused trainer over the 8-device CPU mesh (single
    controller): the checkpoint carries every shard's physical row
    permutation; resume is bit-identical.  (The 2-process variant —
    cross-process barrier + host-0 container write — is the slow
    test_multihost.py::test_two_process_ckpt_resume_bit_identical.)"""
    monkeypatch.setenv("LIGHTGBM_TPU_PGROW", "force")
    rng = np.random.RandomState(5)
    X = rng.randint(0, 12, size=(3000, 6)).astype(np.float64)
    w = rng.randn(6)
    y = (1.0 / (1.0 + np.exp(-((X - 6) @ w * 0.3))) > rng.rand(3000)).astype(float)
    P = dict(objective="binary", tree_learner="data", num_leaves=15,
             learning_rate=0.2, max_bin=31, min_data_in_leaf=20, verbose=-1)
    ref = _train(P, X, y, 8)
    from lightgbm_tpu.boosting.ptrainer import ShardedPartitionedTrainer

    assert isinstance(ref.boosting.ptrainer, ShardedPartitionedTrainer)
    d = tempfile.mkdtemp()
    try:
        _train_killed(P, X, y, 8, d, kill=5, freq=2)
        resumed = _train(P, X, y, 8, ckpt_dir=d, freq=2)
        assert resumed.model_to_string() == ref.model_to_string()
    finally:
        shutil.rmtree(d, ignore_errors=True)


def test_corrupt_tail_checkpoint_falls_back(xy):
    """Kill, corrupt the newest checkpoint, resume: discovery skips the
    torn tail and resumes from the previous one — still bit-identical."""
    X, y = xy
    P = dict(objective="binary", num_leaves=7, learning_rate=0.2, verbose=-1,
             bagging_fraction=0.7, bagging_freq=2)
    ref = _train(P, X, y, 10).model_to_string()
    d = tempfile.mkdtemp()
    try:
        _train_killed(P, X, y, 10, d, kill=8, freq=3)
        st = CheckpointStore(d)
        steps = st.steps()
        assert len(steps) >= 2, steps
        with open(st.path_for(steps[-1]), "r+b") as f:
            f.truncate(128)  # torn write
        resumed = _train(P, X, y, 10, ckpt_dir=d, freq=3)
        assert resumed.model_to_string() == ref
    finally:
        shutil.rmtree(d, ignore_errors=True)


# ----------------------------------------------------------------------
# early stopping across a mid-patience-window kill
# ----------------------------------------------------------------------
def test_early_stopping_patience_survives_kill(xy):
    """Kill inside the patience window: the resumed run must count
    no-improvement rounds from the restored bests, stopping at the SAME
    iteration with the SAME best_iteration as the uninterrupted run."""
    rng = np.random.RandomState(3)
    X, y = xy
    Xv = X[:200] + 0.35 * rng.randn(200, X.shape[1])  # noisy valid set
    yv = y[:200]
    P = dict(objective="binary", metric="binary_logloss", num_leaves=15,
             learning_rate=0.3, verbose=-1)

    def run(ckpt_dir=None, callbacks=None, freq=2, expect_kill=False):
        ds = lgb.Dataset(X, label=y, params=dict(P))
        dv = lgb.Dataset(Xv, label=yv, reference=ds)
        mgr = CheckpointManager(ckpt_dir, freq=freq) if ckpt_dir else None
        hist = {}
        bst = None
        try:
            # evals_result is passed in EVERY leg so the tracked-callback
            # lists line up between the killed and the resumed run
            if expect_kill:
                with pytest.raises(KeyboardInterrupt):
                    lgb.train(dict(P), ds, 40, valid_sets=[dv],
                              early_stopping_rounds=5, evals_result=hist,
                              verbose_eval=False, checkpoint_manager=mgr,
                              callbacks=callbacks)
            else:
                bst = lgb.train(dict(P), ds, 40, valid_sets=[dv],
                                early_stopping_rounds=5, evals_result=hist,
                                verbose_eval=False, checkpoint_manager=mgr,
                                callbacks=callbacks)
        finally:
            if mgr is not None:
                mgr.close()
        return bst, hist

    ref, ref_hist = run()
    stop_iter = ref.current_iteration()
    best = ref.best_iteration
    assert 0 < best < stop_iter < 40, (best, stop_iter)

    # kill mid-patience-window (after the best, before the stop)
    kill = best + 2
    assert kill < stop_iter
    d = tempfile.mkdtemp()
    try:
        run(ckpt_dir=d, callbacks=[_kill_at(kill)], expect_kill=True)
        resumed, res_hist = run(ckpt_dir=d)
        assert resumed.best_iteration == best
        assert resumed.current_iteration() == stop_iter
        assert resumed.model_to_string() == ref.model_to_string()
        # eval history restored through the kill point, identical after
        k = list(ref_hist)[0]
        m = list(ref_hist[k])[0]
        assert res_hist[k][m] == ref_hist[k][m]
    finally:
        shutil.rmtree(d, ignore_errors=True)


# ----------------------------------------------------------------------
# checkpoint-resume vs init_model continued training (parity pin)
# ----------------------------------------------------------------------
def test_checkpoint_resume_vs_init_model_semantics(xy):
    """Pins the semantic difference: checkpoint resume restores the
    score caches and RNG streams (bit-identical); init_model continued
    training (gbdt.cpp input-model semantics) RECOMPUTES scores via
    predict and restarts the RNG streams — statistically equivalent,
    not bit-guaranteed."""
    X, y = xy
    P = dict(objective="binary", num_leaves=7, learning_rate=0.2, verbose=-1,
             bagging_fraction=0.7, bagging_freq=2)
    ref = _train(P, X, y, 10)
    ref_str = ref.model_to_string()

    # checkpoint resume: bit-identical
    d = tempfile.mkdtemp()
    try:
        _train_killed(P, X, y, 10, d, kill=7, freq=5)
        resumed = _train(P, X, y, 10, ckpt_dir=d, freq=5)
        assert resumed.model_to_string() == ref_str
    finally:
        shutil.rmtree(d, ignore_errors=True)

    # init_model continuation: same tree COUNT and the first 5 trees are
    # the reference's own (the text round trip preserves them verbatim
    # in the continued model), but the run is NOT bit-guaranteed —
    # scores are re-seeded via predict, the bagging RNG restarts
    first = _train(P, X, y, 5)
    first_str = first.model_to_string()
    cont = lgb.train(dict(P), lgb.Dataset(X, label=y, params=dict(P)),
                     5, init_model=first, verbose_eval=False)
    assert cont.current_iteration() == 10
    assert cont.num_trees == ref.num_trees
    cont_str = cont.model_to_string()
    for blk in first_str.split("Tree=")[1:3]:
        body = blk.partition("\n")[2].split("\nTree=")[0]
        assert body.split("feature importances")[0].strip() in cont_str
    # predictions agree statistically (same algorithm), not bitwise:
    # the continuation replays different bagging draws after iter 5
    pr, pc = ref.predict(X[:200]), cont.predict(X[:200])
    assert np.mean(np.abs(pr - pc)) < 0.1
    assert np.corrcoef(pr, pc)[0, 1] > 0.9


# ----------------------------------------------------------------------
# manager behaviors
# ----------------------------------------------------------------------
def test_preemption_flush_and_exit(xy, tmp_path):
    """request_preemption (the SIGTERM handler's effect) makes the next
    iteration boundary write a checkpoint and end training cleanly; a
    fresh run resumes from it bit-identically."""
    X, y = xy
    P = dict(objective="binary", num_leaves=7, learning_rate=0.2, verbose=-1)
    ref = _train(P, X, y, 10).model_to_string()
    d = str(tmp_path)
    mgr = CheckpointManager(d, freq=3)

    def preempt(env):
        if env.iteration + 1 == 5:
            mgr.request_preemption()
    preempt.order = 5  # before the manager's boundary check

    ds = lgb.Dataset(X, label=y, params=dict(P))
    bst = lgb.train(dict(P), ds, 10, verbose_eval=False,
                    checkpoint_manager=mgr, callbacks=[preempt])
    mgr.close()
    assert bst.current_iteration() == 5  # stopped at the boundary
    st = CheckpointStore(d)
    assert st.steps()[-1] == 5  # flushed the preemption checkpoint
    assert st.complete_step() is None  # NOT marked complete
    resumed = _train(P, X, y, 10, ckpt_dir=d, freq=3)
    assert resumed.model_to_string() == ref


def test_completed_run_not_auto_resumed(xy, tmp_path):
    """auto resume must not hijack a FRESH run after a prior run in the
    same directory completed normally (the CLI reruns-in-place case)."""
    X, y = xy
    P = dict(objective="binary", num_leaves=7, verbose=-1)
    d = str(tmp_path)
    b1 = _train(P, X, y, 6, ckpt_dir=d, freq=2)
    assert CheckpointStore(d).complete_step() == 6
    b2 = _train(P, X, y, 3, ckpt_dir=d, freq=2)  # shorter fresh run
    assert b2.current_iteration() == 3
    assert b2.num_trees < b1.num_trees


def test_ckpt_obs_spans(xy, tmp_path, monkeypatch):
    """Checkpoint activity shows up in the run trace (docs/OBSERVABILITY.md):
    capture/serialize spans + ckpt.saved events with byte counts."""
    import json

    trace = str(tmp_path / "trace.jsonl")
    monkeypatch.setenv("LIGHTGBM_TPU_TRACE", trace)
    X, y = xy
    P = dict(objective="binary", num_leaves=7, verbose=-1)
    _train(P, X, y, 6, ckpt_dir=str(tmp_path / "ck"), freq=3)
    from lightgbm_tpu.obs import tracer

    tracer.close()
    recs = [json.loads(ln) for ln in open(trace)]
    spans = {r["name"] for r in recs if r["ev"] == "span"}
    assert "ckpt.capture" in spans and "ckpt.serialize" in spans
    saved = [r for r in recs if r["ev"] == "event" and r["name"] == "ckpt.saved"]
    assert saved and all(r["bytes"] > 0 for r in saved)
    assert any(r["ev"] == "counter" and r["name"] == "ckpt.bytes" for r in recs)
