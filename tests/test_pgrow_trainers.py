"""The fused trainers end to end on the CPU (Pallas interpret mode) where
they go past one class or one device: data-parallel (``tree_learner=data``
under ``shard_map``), multiclass and GOSS, against the default path and
the mask grower.  Split from tests/test_pgrow.py (ROADMAP D9: under
``--dist loadfile`` a file runs on one worker)."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest


class TestShardedPartitioned:
    """Data-parallel partitioned trainer (shard_map + hist psum) must
    reproduce the serial partitioned trainer tree-for-tree."""

    def test_dp_matches_serial(self, monkeypatch):
        import lightgbm_tpu as lgb

        if len(jax.devices()) < 4:
            pytest.skip("needs multi-device mesh")
        rng = np.random.default_rng(3)
        X = rng.standard_normal((3000, 8)).astype(np.float32)
        w = rng.standard_normal(8)
        y = (rng.random(3000) < 1 / (1 + np.exp(-(X @ w)))).astype(np.float32)
        params = dict(objective="binary", num_leaves=15, learning_rate=0.2,
                      max_bin=31, min_data_in_leaf=20, verbose=-1)
        monkeypatch.setenv("LIGHTGBM_TPU_PGROW", "force")
        preds, models = {}, {}
        for mode in ("serial", "data"):
            p = dict(params, tree_learner=mode)
            bst = lgb.train(p, lgb.Dataset(X, label=y, params=dict(p)), 3)
            if mode == "data":
                from lightgbm_tpu.boosting.ptrainer import ShardedPartitionedTrainer
                assert isinstance(bst.boosting.ptrainer, ShardedPartitionedTrainer)
            preds[mode] = bst.predict(X)
            models[mode] = bst.boosting.save_model_to_string()
        # identical split structure (same hist sums to f32 tolerance)
        np.testing.assert_allclose(preds["data"], preds["serial"], rtol=3e-3, atol=3e-4)

    def test_dp_multiclass_matches_serial(self, monkeypatch):
        """K > 1 under the sharded trainer: K score channels in the
        sharded layout, one multi-hist psum per iteration."""
        import lightgbm_tpu as lgb

        if len(jax.devices()) < 4:
            pytest.skip("needs multi-device mesh")
        rng = np.random.default_rng(13)
        n, f, K = 2400, 6, 3
        X = rng.standard_normal((n, f)).astype(np.float32)
        w = rng.standard_normal((f, K))
        y = np.argmax(X @ w + 0.3 * rng.standard_normal((n, K)), axis=1).astype(np.float32)
        params = dict(objective="multiclass", num_class=K, num_leaves=7,
                      learning_rate=0.2, max_bin=31, min_data_in_leaf=20,
                      verbose=-1)
        monkeypatch.setenv("LIGHTGBM_TPU_PGROW", "force")
        from unittest import mock

        from lightgbm_tpu.boosting import ptrainer

        preds = {}
        for mode in ("serial", "data"):
            p = dict(params, tree_learner=mode)
            # both chunk programs trace their K trees through the one helper
            with mock.patch.object(ptrainer, "class_trees", wraps=ptrainer.class_trees) as spy:
                bst = lgb.train(p, lgb.Dataset(X, label=y, params=dict(p)), 3)
            assert spy.call_count == 1 and spy.call_args.kwargs["lay"].num_score == K
            if mode == "data":
                from lightgbm_tpu.boosting.ptrainer import ShardedPartitionedTrainer
                assert isinstance(bst.boosting.ptrainer, ShardedPartitionedTrainer)
                assert bst.boosting.ptrainer.K == K
            preds[mode] = bst.predict(X)
        np.testing.assert_allclose(preds["data"], preds["serial"], rtol=4e-3, atol=5e-4)

    def test_dp_goss_trains(self, monkeypatch):
        """GOSS under the sharded trainer: per-shard local top-k (the
        reference's distributed GOSS is also per-machine local).  Sampling
        draws differ from serial by design, so assert training quality
        rather than tree equality."""
        import lightgbm_tpu as lgb

        if len(jax.devices()) < 4:
            pytest.skip("needs multi-device mesh")
        rng = np.random.default_rng(14)
        n, f = 3000, 8
        X = rng.standard_normal((n, f)).astype(np.float32)
        w = rng.standard_normal(f)
        y = (rng.random(n) < 1 / (1 + np.exp(-(X @ w)))).astype(np.float32)
        params = dict(objective="binary", boosting="goss", num_leaves=15,
                      learning_rate=0.5, max_bin=31, min_data_in_leaf=20,
                      tree_learner="data", verbose=-1)
        monkeypatch.setenv("LIGHTGBM_TPU_PGROW", "force")
        bst = lgb.train(params, lgb.Dataset(X, label=y, params=dict(params)), 6)
        from lightgbm_tpu.boosting.ptrainer import ShardedPartitionedTrainer
        assert isinstance(bst.boosting.ptrainer, ShardedPartitionedTrainer)
        from sklearn.metrics import roc_auc_score
        auc = roc_auc_score(y, bst.predict(X))
        # The fused trainers once read `config.boosting`, which does not
        # exist (the parameter is `boosting_type`), and trained plain GBDT here:
        # AUC 0.872, over the 0.85 this test asked.  GOSS proper (4 of the 6
        # trees from 30% of the rows) reads 0.833-0.845 on this table on the
        # mask grower, the serial and the sharded fused trainer alike, so the
        # quality is held against the serial fused trainer's.
        serial = dict(params, tree_learner="serial")
        ref = lgb.train(serial, lgb.Dataset(X, label=y, params=dict(serial)), 6)
        ref_auc = roc_auc_score(y, ref.predict(X))
        assert auc > 0.8 and abs(auc - ref_auc) < 0.03, (auc, ref_auc)
        # and GOSS really sampled: the last iteration selected under half the rows
        pt, lay = bst.boosting.ptrainer, bst.boosting.ptrainer.layout
        sel = np.asarray(pt.p)[:, lay.SEL, :pt.num_rows].view(np.float32)
        assert 0.2 * n < sel.sum() < 0.5 * n


def _multiclass_ref():
    """benchmarks/multiclass_ref.py, the benchmark's float64 softmax reference,
    loaded by path (benchmarks/ is no package)."""
    import importlib.util

    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "benchmarks", "multiclass_ref.py")
    spec = importlib.util.spec_from_file_location("multiclass_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _covtype_shaped(n, K, seed):
    """n rows in the Covertype cell's shape: 10 integer-valued numeric columns,
    exactly one of 4 and one of 40 one-hot columns set a row (Zipf-skewed, so
    EFB bundles each set into one column), K classes from a noisy argmax."""
    rng = np.random.default_rng(seed)
    num = np.round(rng.standard_normal((n, 10)) * 200 + 1000).astype(np.float32)

    def zipf(card):
        p = 1.0 / np.arange(1, card + 1)
        return rng.choice(card, n, p=p / p.sum())

    w, s = zipf(4), zipf(40)
    X = np.zeros((n, 54), np.float32)
    X[:, :10] = num
    X[np.arange(n), 10 + w] = 1.0
    X[np.arange(n), 14 + s] = 1.0
    logit = ((num[:, :K] - 1000) / 200 + 0.8 * (s[:, None] % K == np.arange(K))
             + 0.5 * (w[:, None] % K == np.arange(K)))
    y = np.argmax(logit + rng.gumbel(size=(n, K)), axis=1).astype(np.float32)
    return X, y


def _dense(n, K, seed):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, 6)).astype(np.float32)
    y = np.argmax(X @ rng.standard_normal((6, K)) + 0.3 * rng.standard_normal((n, K)),
                  axis=1).astype(np.float32)
    return X, y


# the cell's case; and K = 3, both objectives, on dense columns (what
# TestMulticlassFused's two prediction checks read too)
MULTI_CASES = [("multiclass", 7, "covtype"), ("multiclass", 3, "dense"),
               ("multiclassova", 3, "dense")]
_MULTI_TRAINED = {}


def _multi_trained(objective, K, shape, bundle=True):
    """(fused booster, mask booster or None, the first iteration's (2K, n)
    g and h planes as the chunk program wrote them, original order, y):
    3 iterations of 31 leaves on 4,096 rows, trained once a worker.  With
    EFB off, the fused booster alone and its first iteration only, which
    is what the bundled trees are compared with."""
    import lightgbm_tpu as lgb
    from unittest import mock

    key = (objective, K, shape, bundle)
    if key not in _MULTI_TRAINED:
        X, y = (_covtype_shaped if shape == "covtype" else _dense)(4096, K, 3)
        params = dict(objective=objective, num_class=K, num_leaves=31, learning_rate=0.1,
                      max_bin=63, min_data_in_leaf=20,
                      enable_bundle=bundle, verbose=-1)
        with mock.patch.dict(os.environ, LIGHTGBM_TPU_PGROW="force"):
            fused = lgb.Booster(params=params,
                                train_set=lgb.Dataset(X, label=y, params=dict(params)))
            gb, pt = fused.boosting, fused.boosting.ptrainer
            gb.train_iters_partitioned(1, is_eval=False)
            lay, n = pt.layout, pt.num_rows
            rows = [lay.g_row(k) for k in range(K)] + [lay.h_row(k) for k in range(K)]
            got = np.asarray(pt.p[jnp.asarray(rows + [lay.ROWID]), :n])
            planes = np.empty((2 * K, n), np.float32)
            planes[:, got[-1]] = got[:-1].view(np.float32)
            if bundle:
                gb.train_iters_partitioned(2, is_eval=False)
        plain = None
        if bundle:
            with mock.patch.dict(os.environ, LIGHTGBM_TPU_PGROW="0"):
                plain = lgb.train(params, lgb.Dataset(X, label=y, params=dict(params)), 3)
        _MULTI_TRAINED[key] = (fused, plain, planes, y)
    return _MULTI_TRAINED[key]


def _splits(tree):
    ns = int(tree.num_leaves) - 1
    return (tree.split_feature[:ns].tolist(), tree.threshold_in_bin[:ns].tolist(),
            tree.left_child[:ns].tolist(), tree.right_child[:ns].tolist())


def _sides(tree, X):
    """The rows of X each split of ``tree`` sends left and right, split by split."""
    leaf = tree.predict_leaf_index(X)

    def under(c):
        out, stack = [], [c]
        while stack:
            j = stack.pop()
            if j < 0:
                out.append(~j)
            else:
                stack += [tree.left_child[j], tree.right_child[j]]
        return np.isin(leaf, out)

    return [(under(tree.left_child[j]), under(tree.right_child[j]))
            for j in range(int(tree.num_leaves) - 1)]


def _partings(fused, plain, X, K, grad):
    """The two models' trees compared an iteration at a time as the rows each
    split sends either way: [] if all agree, else (tree, split, (fused gain,
    terms), (plain gain, terms)) at the first parting split of EVERY tree of
    the first iteration in which any tree parts.  The K trees of an iteration
    grow from one snapshot of the scores, so each class tree is compared on
    its own; the iterations after a parting grow from other scores.  The gains
    are float64 from ``grad(iteration)``'s (K, n) planes, the float32
    gradients both engines grew that iteration's trees on."""
    out = []
    for it in range(len(fused.boosting.models) // K):
        for t in range(it * K, (it + 1) * K):
            a, b = fused.boosting.models[t], plain.boosting.models[t]
            sa, sb = _sides(a, X), _sides(b, X)
            for j in range(max(len(sa), len(sb))):
                if j < min(len(sa), len(sb)) and all(map(np.array_equal, sa[j], sb[j])):
                    continue
                g, h = (np.asarray(v, np.float64)[t % K] for v in grad(it))

                def gain(sides):
                    if j >= len(sides):
                        return 0.0, 0.0
                    (gl, hl), (gr, hr) = ((g[m].sum(), h[m].sum()) for m in sides[j])
                    terms = (gl * gl / hl, gr * gr / hr, (gl + gr) ** 2 / (hl + hr))
                    return terms[0] + terms[1] - terms[2], sum(terms)

                out.append((t, j, gain(sa), gain(sb)))
                break
        if out:
            return out
    return out


class TestMulticlassFused:
    """K > 1 on the fused trainer: the K trees of an iteration from ONE traced
    grower looped over the classes (``ptrainer.class_trees``).  The K = 3
    predictions against the default path's; and, as the Covertype cell runs
    it, at K = 3 and 7, softmax and one-vs-all, on dense columns and on the
    cell's shape (10 numeric, 4 + 40 one-hot, which EFB bundles into 2
    columns): 31 leaves, 3 iterations of 4,096 rows, each case trained once a
    worker and shared by the tests that read it."""

    def test_multiclass_matches_default(self):
        fused, plain, _, _ = _multi_trained("multiclass", 3, "dense")
        assert fused.boosting.ptrainer is not None and plain.boosting.ptrainer is None
        assert fused.boosting.ptrainer.K == 3
        X, _ = _dense(4096, 3, 3)
        np.testing.assert_allclose(fused.predict(X), plain.predict(X), rtol=4e-3, atol=5e-4)

    def test_multiclassova_matches_default(self):
        fused, plain, _, _ = _multi_trained("multiclassova", 3, "dense")
        assert fused.boosting.ptrainer.K == 3 and plain.boosting.ptrainer is None
        X, _ = _dense(4096, 3, 3)
        np.testing.assert_allclose(fused.predict(X), plain.predict(X), rtol=4e-3, atol=5e-4)

    @pytest.mark.parametrize("objective,K,shape", MULTI_CASES)
    def test_trees_split_for_split_equal_the_mask_growers(self, objective, K, shape):
        """All 3K trees send the training rows the same way split for split;
        in the first iteration where a class tree parts, every class tree is
        compared up to its own parting, a split where float32 sums part two
        choices whose float64 gains tie (within 1e-5 of the terms they are
        made of, the cell's TIE_RTOL): every class at 1/K makes iteration 0's
        softmax planes two values a class, and ties between other partitions
        common."""
        fused, plain, _, _ = _multi_trained(objective, K, shape)
        pt = fused.boosting.ptrainer
        assert type(pt).__name__ == "PartitionedTrainer" and pt.K == K
        assert pt.params.num_cols == (12 if shape == "covtype" else 0)
        a, b = fused.boosting.models, plain.boosting.models
        assert len(a) == len(b) == 3 * K
        assert all(int(t.num_leaves) == 31 for t in a)
        X, y = (_covtype_shaped if shape == "covtype" else _dense)(4096, K, 3)

        def grad(it):
            s = plain.predict(X, raw_score=True, num_iteration=it).T if it else np.zeros((K, len(y)))
            return fused.boosting.objective.get_gradients(jnp.asarray(s, jnp.float32))

        for parting in _partings(fused, plain, X, K, grad):
            t, j, (ga, sa), (gb, sb) = parting
            assert ga >= gb - 1e-5 * max(sa, sb), parting

    @pytest.mark.parametrize("objective,K,shape", MULTI_CASES)
    def test_the_first_iterations_planes_equal_the_reference(self, objective, K, shape):
        """g and h of every class as the chunk program's one pass wrote them,
        from the scores before the first tree (all 0): against the benchmark's
        float64 softmax (multiclass_ref.py), and for one-vs-all against the
        objective's own K binary planes."""
        fused, _, planes, y = _multi_trained(objective, K, shape)
        if objective == "multiclass":
            ref = _multiclass_ref().gradients(np.zeros((K, len(y))), y)
        else:
            g, h = fused.boosting.objective.get_gradients(jnp.zeros((K, len(y)), jnp.float32))
            ref = {"g": np.asarray(g, np.float64), "h": np.asarray(h, np.float64)}
        assert np.any(ref["g"] != 0)
        assert np.max(np.abs(planes[:K] - ref["g"])) < 1e-6
        assert np.max(np.abs(planes[K:] - ref["h"])) < 1e-6

    def test_bundled_trees_equal_unbundled(self):
        """The cell's shape with EFB off (54 columns streamed as they are)
        grows the very trees the bundled matrix (12 columns) grows: the
        first iteration's 7."""
        bundled = _multi_trained("multiclass", 7, "covtype")[0]
        unbundled = _multi_trained("multiclass", 7, "covtype", bundle=False)[0]
        assert unbundled.boosting.ptrainer.params.num_cols == 0
        assert bundled.boosting.ptrainer.params.num_cols == 12
        assert len(unbundled.boosting.models) == 7
        assert [_splits(t) for t in bundled.boosting.models[:7]] == \
            [_splits(t) for t in unbundled.boosting.models]


class TestGossFused:
    def test_goss_matches_mask_path(self, monkeypatch):
        import lightgbm_tpu as lgb

        rng = np.random.default_rng(4)
        n, f = 3000, 8
        X = rng.standard_normal((n, f)).astype(np.float32)
        w = rng.standard_normal(f)
        y = (rng.random(n) < 1 / (1 + np.exp(-(X @ w)))).astype(np.float32)
        # learning_rate 0.5 -> GOSS sampling kicks in from iteration 2
        params = dict(objective="binary", boosting="goss", num_leaves=15,
                      learning_rate=0.5, max_bin=31, min_data_in_leaf=20,
                      top_rate=0.3, other_rate=0.2, verbose=-1)
        aucs = {}
        for mode, env in [("pgrow", "force"), ("default", "0")]:
            monkeypatch.setenv("LIGHTGBM_TPU_PGROW", env)
            bst = lgb.train(params, lgb.Dataset(X, label=y, params=dict(params)), 6)
            if mode == "pgrow":
                assert bst.boosting.ptrainer is not None
            pred = bst.predict(X)
            # RNG streams differ (threefry key vs split) -> compare
            # quality, not per-row predictions
            from sklearn.metrics import roc_auc_score
            aucs[mode] = roc_auc_score(y, pred)
        assert aucs["pgrow"] > 0.8 and aucs["default"] > 0.8
        assert abs(aucs["pgrow"] - aucs["default"]) < 0.05

    def test_goss_warm_iters_identical(self, monkeypatch):
        """Before 1/learning_rate iterations GOSS does no sampling, so
        fused and mask paths must agree exactly (to f32 tolerance)."""
        import lightgbm_tpu as lgb

        rng = np.random.default_rng(5)
        n, f = 2500, 6
        X = rng.standard_normal((n, f)).astype(np.float32)
        y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float32)
        params = dict(objective="binary", boosting="goss", num_leaves=7,
                      learning_rate=0.1, max_bin=31, min_data_in_leaf=20,
                      verbose=-1)  # warm window = 10 iters > 3 trained
        preds = {}
        for mode, env in [("pgrow", "force"), ("default", "0")]:
            monkeypatch.setenv("LIGHTGBM_TPU_PGROW", env)
            bst = lgb.train(params, lgb.Dataset(X, label=y, params=dict(params)), 3)
            preds[mode] = bst.predict(X)
        np.testing.assert_allclose(preds["pgrow"], preds["default"], rtol=3e-3, atol=3e-4)
