"""What the canonical reorder at a tree's start used to guarantee, asserted
without it (PR 30 deleted the step: a tree of the fused trainers starts in the
row order the previous tree's partition left).

- the bagging and GOSS draws follow ROWS, not positions: they are functions of
  (key, row id) through ``ptrainer.rowid_uniform``, so ``bagging_freq = k``
  holds one bag of original rows for k iterations on the serial and on the
  sharded trainer, however the rows were partitioned in between;
- the positional carries (pending delta, rollback snapshot) need no remap: a
  chunk of 4 is four chunks of 1 byte for byte, a rollback after a multi-tree
  chunk restores the scores of the shorter run;
- what partition history may move is float summation order alone: each
  LEVELGROW mode is bit-deterministic, the first tree is byte-equal across the
  modes, later trees agree in structure and to rounding.

Kernels interpreted on the CPU (LIGHTGBM_TPU_PGROW=force)."""

import os

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import lightgbm_tpu as lgb  # noqa: E402
from lightgbm_tpu.boosting.ptrainer import (  # noqa: E402
    PartitionedTrainer,
    ShardedPartitionedTrainer,
    rowid_uniform,
)


def _problem(n=2000, f=6, seed=21, objective="binary"):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, f)).astype(np.float32)
    w = rng.standard_normal(f)
    if objective == "binary":
        y = (rng.random(n) < 1 / (1 + np.exp(-(X @ w)))).astype(np.float32)
    else:
        y = (X @ w + 0.3 * rng.standard_normal(n)).astype(np.float32)
    return X, y


BASE = dict(objective="binary", num_leaves=15, learning_rate=0.2, max_bin=31,
            min_data_in_leaf=20, verbose=-1)
BAGGED = dict(BASE, bagging_fraction=0.5, bagging_freq=3)


def _booster(params, X, y):
    return lgb.Booster(params=dict(params), train_set=lgb.Dataset(X, label=y, params=dict(params)))


@pytest.fixture
def force_fused(monkeypatch):
    monkeypatch.setenv("LIGHTGBM_TPU_PGROW", "force")
    monkeypatch.delenv("LIGHTGBM_TPU_LEVELGROW", raising=False)


# -- (a) the draw ---------------------------------------------------------------
def test_rowid_draw_is_permutation_equivariant():
    """A row's draw depends on its id and the key, not on where it lies."""
    n = 5000
    key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(7), 0), 4)
    rowid = jnp.arange(n, dtype=jnp.int32)
    perm = jnp.asarray(np.random.default_rng(0).permutation(n), jnp.int32)
    u = np.asarray(rowid_uniform(key, rowid))
    np.testing.assert_array_equal(np.asarray(rowid_uniform(key, rowid[perm])), u[np.asarray(perm)])
    # a slice of the ids draws the same values as the whole: no draw looks at n
    np.testing.assert_array_equal(np.asarray(rowid_uniform(key, rowid[100:200])), u[100:200])
    # jitted or eager, the traced twin's draw is the fused program's
    np.testing.assert_array_equal(np.asarray(jax.jit(rowid_uniform)(key, rowid)), u)
    # another iteration's key is another draw
    other = rowid_uniform(jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(7), 0), 5), rowid)
    assert (np.asarray(other) != u).mean() > 0.99


@pytest.mark.parametrize("frac", [0.5, 0.2])
def test_rowid_draw_has_the_bagging_fraction(frac):
    """Bernoulli(frac) by row id: the mean is frac within 3 sigma at 1e5 rows,
    the values are uniform on [0, 1), and neighbouring ids are uncorrelated."""
    n = 100_000
    u = np.asarray(rowid_uniform(jax.random.PRNGKey(123), jnp.arange(n, dtype=jnp.int32)))
    assert u.min() >= 0.0 and u.max() < 1.0
    sel = u < frac
    assert abs(sel.mean() - frac) < 3 * np.sqrt(frac * (1 - frac) / n)
    assert abs(u.mean() - 0.5) < 3 * np.sqrt(1 / 12 / n)
    assert abs(np.corrcoef(u[:-1], u[1:])[0, 1]) < 4 / np.sqrt(n)


# -- (b) one bag of original rows for bagging_freq iterations ---------------------
def _selected_original_rows(pt):
    """Original ids of the rows the last iteration selected: the SEL and ROWID
    channels travel with the row through every partition."""
    lay = pt.layout
    p = np.asarray(pt.p)
    if isinstance(pt, ShardedPartitionedTrainer):
        nl = pt.num_rows
        rows = []
        for s in range(pt.d):
            sel = p[s, lay.SEL, :nl].view(np.float32)
            rows.append(s * nl + p[s, lay.ROWID, :nl][sel > 0])
        return np.sort(np.concatenate(rows))
    n = pt.num_rows
    return np.sort(p[lay.ROWID, :n][p[lay.SEL, :n].view(np.float32) > 0])


def _bags_by_iteration(b, iters):
    bags, perms = [], []
    for _ in range(iters):
        assert b.train_iters_partitioned(1, is_eval=False) is False
        bags.append(_selected_original_rows(b.ptrainer))
        perms.append(np.asarray(b.ptrainer.export_perm()).copy())
    return bags, perms


def test_serial_bagging_holds_one_bag_for_a_period(force_fused):
    X, y = _problem()
    b = _booster(BAGGED, X, y).boosting
    assert type(b.ptrainer) is PartitionedTrainer
    bags, perms = _bags_by_iteration(b, 7)
    n = len(y)
    # the rows really are somewhere else after every tree, and stay there
    assert not np.array_equal(perms[0], np.arange(n))
    assert all(not np.array_equal(perms[i], perms[i + 1]) for i in range(6))
    assert all(np.array_equal(np.sort(p), np.arange(n)) for p in perms)
    for period in ((0, 1, 2), (3, 4, 5)):
        for i in period[1:]:
            np.testing.assert_array_equal(bags[i], bags[period[0]])
    assert not np.array_equal(bags[2], bags[3]) and not np.array_equal(bags[5], bags[6])
    for bag in (bags[0], bags[3], bags[6]):
        assert abs(len(bag) / n - 0.5) < 3 * np.sqrt(0.25 / n)
    # two periods' bags are independent draws: they share about a quarter of the rows
    assert abs(len(np.intersect1d(bags[0], bags[3])) / n - 0.25) < 0.05


def test_sharded_bagging_holds_one_bag_for_a_period(force_fused):
    """The data-parallel program never reordered, so its positional draw
    re-bagged every iteration whatever `bagging_freq` said; it now draws by the
    shard's local row id with the shard index in the key.  2,003 rows over four
    shards leave padding rows, which no bag may hold."""
    from unittest import mock

    import lightgbm_tpu.parallel as par

    if len(jax.devices()) < 4:
        pytest.skip("needs a multi-device mesh")
    X, y = _problem(n=2003)
    mesh4 = par.make_mesh(4)
    with mock.patch.object(par, "make_mesh", lambda n_devices=None: mesh4):
        b = _booster(dict(BAGGED, tree_learner="data"), X, y).boosting
    pt = b.ptrainer
    assert type(pt) is ShardedPartitionedTrainer and pt.d == 4
    bags, perms = _bags_by_iteration(b, 7)
    assert all(not np.array_equal(perms[i], perms[i + 1]) for i in range(6))
    for period in ((0, 1, 2), (3, 4, 5)):
        for i in period[1:]:
            np.testing.assert_array_equal(bags[i], bags[period[0]])
    assert not np.array_equal(bags[2], bags[3]) and not np.array_equal(bags[5], bags[6])
    nl = pt.num_rows
    for bag in bags:
        shard, local = bag // nl, bag % nl
        assert (shard * nl + local < 2003).all() and (local < np.minimum(nl, 2003 - shard * nl)).all()
        assert abs(len(bag) / 2003 - 0.5) < 3 * np.sqrt(0.25 / 2003)
    # shards draw differently from the same local ids
    first = [set((bags[0][bags[0] // nl == s] % nl).tolist()) for s in range(4)]
    assert first[0] != first[1] and first[1] != first[2]


# -- (c) GOSS ------------------------------------------------------------------
def test_goss_selects_the_top_rows_and_upweights_only_the_sampled(force_fused):
    """After the warm-up: exactly `top_cnt` rows are kept for their |g*h| and
    every one of them outranks every other row; of the rest a Bernoulli sample
    by row id is kept and only it carries the (n - top) / other factor in g."""
    n = 3000
    X, y = _problem(n=n, f=8, seed=4, objective="regression")
    params = dict(objective="regression", boosting="goss", num_leaves=15, learning_rate=0.5,
                  max_bin=31, min_data_in_leaf=20, top_rate=0.3, other_rate=0.2, verbose=-1)
    b = _booster(params, X, y).boosting
    pt = b.ptrainer
    lay = pt.layout
    top_cnt, other_cnt = int(n * 0.3), int(n * 0.2)
    mult = np.float32((n - top_cnt) / other_cnt)
    assert b.train_iters_partitioned(2, is_eval=False) is False  # 1 / learning_rate warm iterations
    p = np.asarray(pt.p)
    np.testing.assert_array_equal(p[lay.SEL, :n].view(np.float32), 1.0)
    sampled_sets = []
    for _ in range(2):
        before = np.asarray(pt.scores_original_order())
        assert not np.array_equal(np.asarray(pt.export_perm()), np.arange(n))
        assert b.train_iters_partitioned(1, is_eval=False) is False
        p = np.asarray(pt.p)
        rowid = p[lay.ROWID, :n]
        sel = p[lay.SEL, :n].view(np.float32) > 0
        g_chan = p[lay.G, :n].view(np.float32)
        fresh = (before - y)[rowid]  # L2: g = score - label, h = 1, so |g*h| = |g|
        ratio = g_chan / fresh
        up = np.isclose(ratio, mult, rtol=1e-5)
        assert (up | np.isclose(ratio, 1.0, rtol=1e-5)).all()
        top = sel & ~up
        assert top.sum() == top_cnt
        assert np.abs(fresh[top]).min() >= np.abs(fresh[~top]).max()
        assert (sel[up]).all(), "an up-weighted row is not selected"
        # the rest-sample: Bernoulli(other / (n - top)) over the n - top rows
        rest, prob = n - top_cnt, other_cnt / (n - top_cnt)
        assert abs(up.sum() - rest * prob) < 4 * np.sqrt(rest * prob * (1 - prob))
        sampled_sets.append(set(rowid[up].tolist()))
    assert sampled_sets[0] != sampled_sets[1]  # keyed by the iteration too


# -- (d) the positional carries need no remap -----------------------------------
@pytest.mark.parametrize("params", [BASE, BAGGED], ids=["plain", "bagged"])
def test_a_chunk_of_4_is_four_chunks_of_1(force_fused, params):
    """Models and scores byte for byte: inside a chunk the delta stays pending
    across a tree boundary and lands in the layout the tree left; between
    chunks it is settled by the epilogue.  Neither needs the rows put back."""
    X, y = _problem()
    whole, parts = _booster(params, X, y), _booster(params, X, y)
    assert whole.boosting.train_iters_partitioned(4, is_eval=False) is False
    for _ in range(4):
        assert parts.boosting.train_iters_partitioned(1, is_eval=False) is False
    assert whole.model_to_string() == parts.model_to_string()
    for a, c in ((whole.boosting.scores, parts.boosting.scores),
                 (whole.boosting.ptrainer.scores_original_order(),
                  parts.boosting.ptrainer.scores_original_order())):
        assert np.asarray(a).tobytes() == np.asarray(c).tobytes()
    np.testing.assert_array_equal(whole.boosting.ptrainer.export_perm(),
                                  parts.boosting.ptrainer.export_perm())
    assert not np.array_equal(whole.boosting.ptrainer.export_perm(), np.arange(len(y)))


@pytest.mark.parametrize("params", [BASE, BAGGED], ids=["plain", "bagged"])
def test_rollback_after_a_multi_tree_chunk(force_fused, params):
    """`rollback_last` subtracts the chunk's last kept delta positionally, in
    the layout its tree left: the scores are those of a run one tree shorter,
    and the next tree grown from them is that run's next tree."""
    X, y = _problem()
    long, short = _booster(params, X, y), _booster(params, X, y)
    assert long.boosting.train_iters_partitioned(3, is_eval=False) is False
    long.rollback_one_iter()
    assert long.boosting.iter == 2 and not long.boosting.ptrainer.score_dirty
    assert short.boosting.train_iters_partitioned(2, is_eval=False) is False
    for got, want in ((long.boosting.ptrainer.scores_original_order(),
                       short.boosting.ptrainer.scores_original_order()),
                      (long.boosting.scores, short.boosting.scores)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)
    assert long.model_to_string() == short.model_to_string()
    for b in (long, short):
        assert b.boosting.train_iters_partitioned(1, is_eval=False) is False
    np.testing.assert_allclose(long.predict(X), short.predict(X), rtol=3e-4, atol=3e-5)


# -- what partition history may move: summation order alone ----------------------
def _trees(bst):
    return bst.model_to_string().split("\nTree=")[1:]


def test_levelgrow_modes_agree_as_far_as_the_contract_says(monkeypatch):
    """LEVELGROW=1 and =0 build the same tree and leave different layouts
    behind it.  Guaranteed: each mode is byte-equal to itself on a second run;
    the first tree (same starting order) is byte-equal across the modes; later
    trees have equal leaf counts, and predictions agree to rtol 1e-5."""
    monkeypatch.setenv("LIGHTGBM_TPU_PGROW", "force")
    X, y = _problem(n=3000, f=8, seed=3)
    runs = {}
    for mode in ("1", "0"):
        monkeypatch.setenv("LIGHTGBM_TPU_LEVELGROW", mode)
        pair = []
        for _ in range(2):
            bst = _booster(BASE, X, y)
            assert bst.boosting.ptrainer.params.levelwise == (mode == "1")
            assert bst.boosting.train_iters_partitioned(5, is_eval=False) is False
            pair.append(bst)
        assert pair[0].model_to_string() == pair[1].model_to_string()
        np.testing.assert_array_equal(pair[0].boosting.ptrainer.export_perm(),
                                      pair[1].boosting.ptrainer.export_perm())
        runs[mode] = pair[0]
    # the modes do leave different layouts (what made the reorder look necessary)
    assert not np.array_equal(runs["1"].boosting.ptrainer.export_perm(),
                              runs["0"].boosting.ptrainer.export_perm())
    level, classic = _trees(runs["1"]), _trees(runs["0"])
    assert len(level) == len(classic) == 5
    assert level[0] == classic[0]
    leaves = [[m.num_leaves for m in runs[mode].boosting.models] for mode in ("1", "0")]
    assert leaves[0] == leaves[1]
    np.testing.assert_allclose(runs["1"].predict(X), runs["0"].predict(X), rtol=1e-5)
