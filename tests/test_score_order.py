"""The fused trainers hand back scores in original row order through the
ROWID channel (``pgrow.in_row_order``): a scatter into zeros below
``pgrow.SORT_MIN_ROWS`` rows, one key-value sort on ROWID from there on.  The channel is a permutation of the rows, so the sort must give, byte
for byte, what the scatter gives: on its own, in the serial and sharded chunk
programs' epilogues and in both ``scores_original_order`` paths, and through
a whole training run.  The trainers' tests lower ``SORT_MIN_ROWS`` to 0, so
that the sort runs at a CPU's sizes.

Kernels interpreted on the CPU (LIGHTGBM_TPU_PGROW=force)."""

import os

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import lightgbm_tpu as lgb  # noqa: E402
from lightgbm_tpu.boosting import ptrainer  # noqa: E402
from lightgbm_tpu.boosting.ptrainer import (  # noqa: E402
    PartitionedTrainer,
    ShardedPartitionedTrainer,
)
from lightgbm_tpu.ops import pgrow  # noqa: E402
from lightgbm_tpu.ops.pgrow import in_row_order  # noqa: E402


def _scattered(rowid, planes):
    """The reference: one scatter into zeros a plane."""
    return jnp.stack([jnp.zeros(p.shape, p.dtype).at[rowid].set(p) for p in planes])


def _bits(x):
    return np.asarray(x, np.float32).view(np.int32)


@pytest.fixture
def sort_always(monkeypatch):
    monkeypatch.setattr(pgrow, "SORT_MIN_ROWS", 0)


@pytest.mark.parametrize("sorts", [True, False], ids=["sort", "scatter"])
@pytest.mark.parametrize("n", [1, 130, 1000, 4099])
@pytest.mark.parametrize("K", [1, 3])
def test_the_sort_is_the_scatter(K, n, sorts, monkeypatch):
    """Random permutations of n rows (none a multiple of 128 but 1's trivial
    one), float planes holding -0.0, infinities and NaN: the same bytes and
    shape, (K, n), jitted or not, by either form; the form is the one the
    row count asks for (one sort of all K planes, or a scatter a plane)."""
    if sorts:
        monkeypatch.setattr(pgrow, "SORT_MIN_ROWS", n)
    else:
        assert n < pgrow.SORT_MIN_ROWS
    rng = np.random.default_rng(n * 10 + K)
    rowid = jnp.asarray(rng.permutation(n).astype(np.int32))
    planes = []
    for _ in range(K):
        v = rng.standard_normal(n).astype(np.float32)
        v[: min(n, 4)] = np.array([-0.0, np.inf, -np.inf, np.nan], np.float32)[: min(n, 4)]
        planes.append(jnp.asarray(rng.permutation(v)))
    want = _scattered(rowid, planes)
    got = in_row_order(rowid, planes)
    assert got.shape == want.shape == (K, n)
    assert np.array_equal(_bits(got), _bits(want))
    assert np.array_equal(_bits(jax.jit(lambda r, p: in_row_order(r, p))(rowid, planes)),
                          _bits(want))
    jaxpr = jax.make_jaxpr(lambda r, p: in_row_order(r, p))(rowid, planes)
    prims = [e.primitive.name for e in jaxpr.eqns]
    assert prims.count("sort") == (1 if sorts else 0)
    assert prims.count("scatter") == (0 if sorts else K)


def _problem(n, f=6, seed=21):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, f)).astype(np.float32)
    w = rng.standard_normal(f)
    y = (rng.random(n) < 1 / (1 + np.exp(-(X @ w)))).astype(np.float32)
    return X, y


BAGGED = dict(objective="binary", num_leaves=15, learning_rate=0.2, max_bin=31,
              min_data_in_leaf=20, bagging_fraction=0.5, bagging_freq=2, verbose=-1)


def _booster(params, X, y):
    return lgb.Booster(params=dict(params), train_set=lgb.Dataset(X, label=y, params=dict(params)))


@pytest.fixture
def force_fused(monkeypatch):
    monkeypatch.setenv("LIGHTGBM_TPU_PGROW", "force")
    monkeypatch.delenv("LIGHTGBM_TPU_LEVELGROW", raising=False)


def _channels(pt, p, lo=0):
    lay = pt.layout
    rowid = p[lay.ROWID, lo: lo + pt.num_rows]
    planes = [p[lay.SCORE + k, lo: lo + pt.num_rows].view(np.float32) for k in range(pt.K)]
    return jnp.asarray(rowid), [jnp.asarray(x) for x in planes]


@pytest.mark.parametrize("K", [1, 3])
def test_serial_chunk_and_scores_original_order_give_the_scatters_bytes(force_fused, sort_always, K):
    """1,999 rows, bagging on, binary and three classes: after each chunk the
    rows lie elsewhere, and what `train_chunk` returns and what
    `scores_original_order` returns ((n,), or (K, n)) are the scatter of the
    score channels through ROWID, byte for byte."""
    n = 1999
    X, y = _problem(n)
    params = BAGGED
    if K > 1:
        y = np.digitize(X[:, 0], [-0.5, 0.5]).astype(np.float32)
        params = dict(BAGGED, objective="multiclass", num_class=K)
    b = _booster(params, X, y).boosting
    pt = b.ptrainer
    assert type(pt) is PartitionedTrainer and pt.K == K
    for it0 in (0, 3):
        _, scores, n_done = pt.train_chunk(3, 0.2, it0)
        assert n_done == 3
        p = np.asarray(pt.p)
        rowid, planes = _channels(pt, p)
        assert not np.array_equal(np.asarray(rowid), np.arange(n))
        assert np.array_equal(np.sort(np.asarray(rowid)), np.arange(n))
        want = _scattered(rowid, planes)
        want = want[0] if K == 1 else want
        assert np.array_equal(_bits(scores), _bits(want))
        assert np.array_equal(_bits(pt.scores_original_order()), _bits(want))


def test_sharded_chunk_and_scores_give_the_scatters_bytes_padding_included(force_fused, sort_always):
    """2,003 rows over four shards of 501 leave the last shard one padding
    row.  Its ROWID is its own local id (500 >= the shard's 500 real rows),
    so each shard's key is a permutation of 0..nl-1 and the sort orders the
    padding row after the real ones: the global array `_scores_global`
    returns equals the per-shard scatter in EVERY position, and what
    `train_chunk` and `scores_original_order` keep of it too."""
    from unittest import mock

    import lightgbm_tpu.parallel as par

    if len(jax.devices()) < 4:
        pytest.skip("needs a multi-device mesh")
    n = 2003
    X, y = _problem(n)
    mesh4 = par.make_mesh(4)
    with mock.patch.object(par, "make_mesh", lambda n_devices=None: mesh4):
        b = _booster(dict(BAGGED, tree_learner="data"), X, y).boosting
    pt = b.ptrainer
    assert type(pt) is ShardedPartitionedTrainer and pt.d == 4
    nl = pt.num_rows
    assert nl * 4 > n
    _, scores, n_done = pt.train_chunk(3, 0.2, 0)
    assert n_done == 3
    p = np.asarray(pt.p)
    want = []
    for s in range(pt.d):
        rowid, planes = _channels(pt, p[s])
        ids = np.asarray(rowid)
        assert np.array_equal(np.sort(ids), np.arange(nl))
        real = max(0, min(nl, n - s * nl))
        # a padding row keeps the id it was packed with, past the real ones
        pos = np.nonzero(ids >= real)[0]
        assert len(pos) == nl - real
        want.append(np.asarray(_scattered(rowid, planes)))
    want = np.concatenate(want, axis=1)  # (K, d * nl)
    assert np.array_equal(_bits(pt._scores_global()), _bits(want))
    kept = pt._gather_rows(jnp.asarray(want))[0]
    assert kept.shape == (n,)
    assert np.array_equal(_bits(scores), _bits(kept))
    assert np.array_equal(_bits(pt.scores_original_order()), _bits(kept))


def test_a_training_run_is_the_scatters_byte_for_byte(force_fused, sort_always, monkeypatch):
    """The same run twice, once through the sort and once with the trainer's
    original-order helper swapped here for the scatter: the same model string
    and the same training-set AUC at every iteration."""
    X, y = _problem(1500, seed=5)
    params = dict(BAGGED, metric="auc", is_training_metric=True)

    def run():
        ds = lgb.Dataset(X, label=y, params=dict(params))
        evals = {}
        bst = lgb.train(dict(params), ds, num_boost_round=6, valid_sets=[ds],
                        callbacks=[lgb.record_evaluation(evals)])
        assert bst.boosting.ptrainer is not None
        return bst.model_to_string(), evals["training"]["auc"]

    model, auc = run()
    assert len(auc) == 6
    monkeypatch.setattr(ptrainer, "in_row_order", _scattered)
    model_scattered, auc_scattered = run()
    assert model == model_scattered
    assert auc == auc_scattered
