"""The level grower (ops/pgrow.py; CPU via Pallas interpret mode): its
static caps against the per-split grower, the chunk program's records
against their goldens byte for byte, and the level phase's batched split
search.  Split from tests/test_pgrow.py (ROADMAP D9: under ``--dist
loadfile`` a file runs on one worker)."""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lightgbm_tpu.ops import pkernels as pk
from lightgbm_tpu.ops.pgrow import PGrowParams
from test_pgrow import _plain_meta_hyper


class TestLevelGrowerCaps:
    """Stress the level grower where its static caps bind (VERDICT item
    7): num_leaves=1023 exceeds the 512-slot frontier and the level
    budget it implies, and the level-batched path must stay
    tree-identical to the per-split grower."""

    def test_num_leaves_1023_parity_with_levelgrow_off(self, monkeypatch):
        import lightgbm_tpu as lgb

        rng = np.random.default_rng(3)
        n, f = 5000, 8
        X = rng.standard_normal((n, f)).astype(np.float32)
        w = rng.standard_normal(f)
        y = (rng.random(n) < 1 / (1 + np.exp(-(X @ w)))).astype(np.float32)
        params = dict(objective="binary", num_leaves=1023, learning_rate=0.2,
                      max_bin=31, min_data_in_leaf=1, verbose=-1)
        monkeypatch.setenv("LIGHTGBM_TPU_PGROW", "force")
        preds = {}
        leaves = {}
        for mode in ("1", "0"):
            monkeypatch.setenv("LIGHTGBM_TPU_LEVELGROW", mode)
            bst = lgb.train(params, lgb.Dataset(X, label=y, params=dict(params)), 2)
            assert bst.boosting.ptrainer is not None
            assert bst.boosting.ptrainer.params.levelwise == (mode == "1")
            preds[mode] = bst.predict(X)
            leaves[mode] = [t.num_leaves for t in bst.boosting.models]
        # with min_data_in_leaf=1 and 5000 rows the 1023-leaf cap BINDS
        assert leaves["1"] == leaves["0"]
        assert max(leaves["1"]) == 1023, leaves
        # level-batched growth is tree-identical to per-split growth
        np.testing.assert_array_equal(preds["1"], preds["0"])

    @pytest.mark.parametrize("num_leaves,rows,min_hess,binds", [
        (31, 6000, 1e-3, "table"), (63, 3000, 4.0, "levels")])
    def test_level_phase_is_bounded_by_its_table(self, monkeypatch, num_leaves, rows,
                                                 min_hess, binds):
        """The level phase runs at most log2(SMAX) + 1 levels and none on a
        full candidate table (each one streams all SMAX slots' histograms
        whatever it holds); what it leaves is the replay's tail, and the trees
        are those of the per-split grower."""
        import lightgbm_tpu as lgb

        rng = np.random.default_rng(5)
        X = rng.standard_normal((rows, 6)).astype(np.float32)
        w = rng.standard_normal(6)
        y = (rng.random(rows) < 1 / (1 + np.exp(-(X @ w)))).astype(np.float32)
        params = dict(objective="binary", num_leaves=num_leaves, learning_rate=0.3,
                      max_bin=31, min_data_in_leaf=1, min_sum_hessian_in_leaf=min_hess,
                      verbose=-1)
        monkeypatch.setenv("LIGHTGBM_TPU_PGROW", "force")
        counts, preds, leaves = [], {}, {}
        for mode in ("1", "0"):
            monkeypatch.setenv("LIGHTGBM_TPU_LEVELGROW", mode)
            bst = lgb.Booster(params=params,
                              train_set=lgb.Dataset(X, label=y, params=dict(params)))
            pt = bst.boosting.ptrainer
            chunk = pt.train_chunk

            def keep(*a, _chunk=chunk, **k):
                out = _chunk(*a, **k)
                counts.extend(out[0]["levels"][:, 0].tolist())
                return out

            pt.train_chunk = keep
            bst.boosting.train_iters_partitioned(6, is_eval=False)
            preds[mode] = bst.predict(X)
            leaves[mode] = [t.num_leaves for t in bst.boosting.models]
        np.testing.assert_array_equal(preds["1"], preds["0"])
        assert leaves["1"] == leaves["0"] and max(leaves["1"]) == num_leaves
        levels, _, segments, scanned = np.array(counts[:6]).T  # LEVELGROW=0 counts none
        assert not np.array(counts[6:]).any()
        smax = -(-(num_leaves + 1) // 8) * 8
        assert (scanned == levels * smax).all()  # 6 columns: the search has no loop
        cap = (smax - 1).bit_length() + 1
        assert levels.max() <= cap and segments.max() <= smax - 1, counts
        if binds == "table":
            # balanced trees fill the 2 * SMAX slots in the doubling levels:
            # the phase ends there, with no level that holds no segment
            assert (levels == cap - 1).all() and (segments == smax - 1).all(), counts
        else:
            # unbalanced trees leave slots free after the last level: the
            # tail takes those splits, and the trees above are still full
            assert (levels == cap).all() and segments.min() < smax - 1, counts


GOLDEN_RECS = os.path.join(os.path.dirname(__file__), "golden", "pgrow_recs.npz")


def _captured_chunk(X, y, params, sharded: bool, iterations: int, env=None):
    """(records, trainer) of one chunk of ``iterations`` iterations, as the
    chunk program returns them; four shards under ``sharded``.
    LIGHTGBM_TPU_PGROW=force must be set."""
    from unittest import mock

    import lightgbm_tpu as lgb
    import lightgbm_tpu.parallel as par

    params = dict(params, tree_learner="data" if sharded else "serial")
    mesh4 = par.make_mesh(4) if sharded else None
    with mock.patch.dict(os.environ, env or {}), \
            mock.patch.object(par, "make_mesh", lambda n_devices=None: mesh4):
        b = lgb.Booster(params=params,
                        train_set=lgb.Dataset(X, label=y, params=dict(params))).boosting
    pt = b.ptrainer
    assert getattr(pt, "d", 1) == (4 if sharded else 1)
    seen = {}
    run = pt.train_chunk

    def capture(*a, **k):
        out = run(*a, **k)
        seen["recs"] = out[0]
        return out

    with mock.patch.object(pt, "train_chunk", capture):
        b.train_iters_partitioned(iterations, is_eval=False)
    return seen["recs"], pt


def chunk_records(levelgrow: str, sharded: bool):
    """Split records of 3 iterations on a seeded 65,536-row table, as the
    chunk program returns them.  LIGHTGBM_TPU_PGROW=force must be set."""
    rng = np.random.default_rng(20270927)
    n, f = 65536, 10
    X = rng.standard_normal((n, f)).astype(np.float32)
    w = rng.standard_normal(f)
    y = (rng.random(n) < 1 / (1 + np.exp(-(X @ w + X[:, 0] * X[:, 1])))).astype(np.float32)
    params = dict(objective="binary", num_leaves=31, learning_rate=0.1, max_bin=63,
                  min_data_in_leaf=20, verbose=-1)
    recs, pt = _captured_chunk(X, y, params, sharded, 3,
                               env={"LIGHTGBM_TPU_LEVELGROW": levelgrow})
    assert pt.params.levelwise == (levelgrow == "1")
    assert recs["num_splits"].tolist() == [[30]] * 3
    return recs["raw"]


GOLDEN_CASES = [("serial_lg1", "1", False), ("serial_lg0", "0", False),
                ("dp4_lg1", "1", True), ("dp4_lg0", "0", True)]


class TestRecordsGolden:
    """tests/golden/pgrow_recs.npz was written by
    tests/golden/make_pgrow_recs.py from commit 028fc30, whose change to the chunk program and the replay is
    control flow alone: of 90 replayed splits in the level-batched runs
    78 take precomputed children and 12 the ``split_stream`` tail, and
    not one byte of a record may differ.  A later PR that changes the
    arithmetic on purpose rewrites the file with that script and says so."""

    @pytest.mark.parametrize("name,levelgrow,sharded", GOLDEN_CASES)
    def test_records_byte_equal(self, monkeypatch, name, levelgrow, sharded):
        if sharded and len(jax.devices()) < 4:
            pytest.skip("needs 4 devices")
        monkeypatch.setenv("LIGHTGBM_TPU_PGROW", "force")
        want = np.load(GOLDEN_RECS)[name]
        got = chunk_records(levelgrow, sharded)
        assert got.dtype == want.dtype and got.shape == want.shape == (3, 1, 30, 12)
        assert got.tobytes() == want.tobytes()


def _wide_chunk(sharded: bool):
    """(records, trainer) of a chunk of 2 iterations, 63 leaves, on a seeded
    2,048 x 600 table.  LIGHTGBM_TPU_PGROW=force must be set."""
    rng = np.random.default_rng(20340600)
    n, f = 2048, 600
    X = rng.standard_normal((n, f)).astype(np.float32)
    w = rng.standard_normal(6)
    y = (rng.random(n) < 1 / (1 + np.exp(-(X[:, [0, 5, 299, 300, 598, 599]] @ w)))
         ).astype(np.float32)
    params = dict(objective="binary", num_leaves=63, learning_rate=0.1, max_bin=63,
                  min_data_in_leaf=1, min_sum_hessian_in_leaf=1e-3, verbose=-1)
    return _captured_chunk(X, y, params, sharded, 2)


class TestLevelSplitScan:
    """The level phase's split search visits ``scan_batch`` slots at a
    time up to the level's active count, and a slot's search is the same
    ``find2`` on the same histogram whatever the batch: every field of every
    slot below ``n_act`` bit for bit."""

    SMAX, COLS, BINS = 32, 7, 32

    @pytest.fixture(scope="class")
    def level(self):
        from lightgbm_tpu.ops import pgrow

        s, f, b = self.SMAX, self.COLS, self.BINS
        rng = np.random.default_rng(20340034)
        lanes = pk.hist_lanes(f, b)
        rows = rng.standard_normal((s, 16, lanes)).astype(np.float32)
        rows[:, [3, 4, 5, 10, 11, 12]] = np.abs(rows[:, [3, 4, 5, 10, 11, 12]])  # hessians
        rows[:, [6, 13]] = rng.integers(0, 40, (s, 2, lanes))  # counts
        rows[:, [7, 14, 15]] = 0.0
        hists = jnp.asarray(rows)
        hist2 = jnp.stack([jax.vmap(lambda h, r=r: pk._hist_from_rows(h, f, b, row0=r))(hists)
                           for r in (0, 7)], axis=1)
        sums2 = jnp.sum(hist2[:, :, 0], axis=2)  # (SMAX, 2, 3): totals via feature 0
        dok2 = jnp.asarray(rng.random((s, 2)) < 0.8)
        find2 = pgrow.sibling_split_search(
            PGrowParams(2 * s - 1, b, f, 1000, -1, True, False), *_plain_meta_hyper(f, b),
            jnp.ones((f,), jnp.float32))

        @functools.partial(jax.jit, static_argnums=1)
        def search(n_act, batch):
            return pgrow.level_split_scan(find2, hists, sums2, dok2, n_act, batch, f, b)

        whole, visited = search(jnp.int32(s // 2), s)
        assert int(visited) == s
        gains = np.asarray(whole.gain)
        assert (gains > 0).any() and (gains == -np.inf).any()  # both kinds compared
        return search, whole

    @pytest.mark.parametrize("n_act", [0, 1, 7, 8, 9, SMAX // 2])
    @pytest.mark.parametrize("batch", [4, 8])
    def test_batched_search_is_the_whole_one_bit_for_bit(self, level, batch, n_act):
        search, whole = level
        got, visited = search(jnp.int32(n_act), batch)
        assert int(visited) == -(-n_act // batch) * batch
        for name, want, have in zip(whole._fields, whole, got):
            want, have = np.asarray(want), np.asarray(have)
            assert have.shape == want.shape == (self.SMAX, 2) and have.dtype == want.dtype
            # whole batches are searched (n_act <= visited); what no batch
            # reached was never written
            assert have[:int(visited)].tobytes() == want[:int(visited)].tobytes(), name
            assert not have[int(visited):].any(), name

    def test_the_batch_is_a_function_of_the_shape(self):
        from lightgbm_tpu.ops.pgrow import level_slots, scan_batch

        for cols, want in ((28, 256), (32, 256), (33, 128), (200, 32), (600, 8), (2000, 4),
                           (4000, 4)):
            assert scan_batch(255, pk.hist_lanes(cols, 63)) == want, cols
        assert scan_batch(31, pk.hist_lanes(28, 63)) == level_slots(31) == 32
        assert scan_batch(15, pk.hist_lanes(2000, 63)) == 4 < level_slots(15)

    @pytest.mark.parametrize("sharded", [False, True], ids=["serial", "four-shards"])
    def test_records_byte_equal_to_the_straight_search(self, monkeypatch, sharded):
        """End to end at a width where the shape rule itself picks a batch
        under SMAX (63 leaves x 600 columns: 8 of 64 slots, four trips at the
        fullest level): the chunk's records are those of the straight-line
        search byte for byte, serial and under a 4-device ``shard_map``, and
        ``scan_slots`` says which of the two ran."""
        from lightgbm_tpu.ops import pgrow

        if sharded and len(jax.devices()) < 4:
            pytest.skip("needs 4 devices")
        monkeypatch.setenv("LIGHTGBM_TPU_PGROW", "force")
        batched, pt = _wide_chunk(sharded)
        smax = pgrow.level_slots(63)
        sb = pgrow.scan_batch(63, pk.hist_lanes(600, 63))
        assert (smax, sb) == (64, 8)
        counts = pt.stream_counts(batched, 2)
        assert counts["levels"] * sb < counts["scan_slots"] < counts["levels"] * smax
        assert counts["level_segments"] <= counts["scan_slots"] <= (
            counts["level_segments"] + counts["levels"] * (sb - 1))
        # the same program with the straight-line search: the grower is traced
        # anew, under a budget that holds all SMAX slots in one batch
        monkeypatch.setattr(pgrow, "SCAN_BATCH_BYTES", 1 << 40)
        jax.clear_caches()
        straight, pt = _wide_chunk(sharded)
        jax.clear_caches()
        assert pt.stream_counts(straight, 2)["scan_slots"] == counts["levels"] * smax
        assert batched["num_splits"].tolist() == straight["num_splits"].tolist() == [[62]] * 2
        assert batched["raw"].tobytes() == straight["raw"].tobytes()
        assert batched["levels"][..., :3].tolist() == straight["levels"][..., :3].tolist()
