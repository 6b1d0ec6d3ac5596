"""Split-decision audit-trail tests (obs/audit.py + ``report diff``).

The acceptance contract: audit trails from a LEVELGROW=0 and a
LEVELGROW=1 run of the same config are BYTE-identical — both at the
original known-parity config and at the formerly-divergent one (ROADMAP
item 1: 15 leaves / min_data_in_leaf=20 / 6 rounds).  That config used
to diverge by ONE leaf value of iteration 2's tree (1 ULP).  Root
cause: the two modes leave different physical row orders behind (the
level grower speculatively partitions candidate levels best-first
acceptance never takes), and ``segment_values``' float range-add
cumsum carried position-dependent 1-ULP residue — so training scores,
and from round 2 on the gradients, depended on partition history.
Fixed by an exact lookup in ``segment_values`` (a value is selected by
its bits, never computed: first by integer rank and gather, since PR 32
by comparing the position with the sorted segment bounds), so the
repro class asserts parity; ``report diff`` localization is covered on
synthetic trails in TestReportDiff.

Since PR 30 a tree starts in the row order the previous tree left (the
canonical reorder that also went in with that fix is gone: it was 40%
of a 21M-row iteration), so past the first tree the two modes are
guaranteed equal in structure and to an ulp in values, no longer byte
for byte (tests/test_row_order.py asserts that contract).  At these
configurations the trails ARE still byte-identical with the reorder
gone, so the assertions below stand as they were.
"""

import json
import os

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.obs.audit import AuditWriter, audit

# the ROADMAP-pinned shape: 15 leaves / min_data_in_leaf=20 / 6 rounds.
# Seed 0 of this generator is a measured-parity config; seed 1 is the
# measured-divergent config (reproduced at PR 7 time on this tree).
PARAMS = {"objective": "binary", "num_leaves": 15, "verbose": -1,
          "min_data_in_leaf": 20}
PARITY_SEED = 0
DIVERGENT_SEED = 1


def _data(seed):
    rng = np.random.RandomState(seed)
    X = rng.randn(1200, 8)
    y = (X[:, 0] + X[:, 1] > 0).astype(float)
    return X, y


def _train_audited(tmp_path, tag, levelgrow, seed, monkeypatch):
    path = str(tmp_path / f"{tag}.jsonl")
    monkeypatch.setenv("LIGHTGBM_TPU_PGROW", "force")
    monkeypatch.setenv("LIGHTGBM_TPU_LEVELGROW", levelgrow)
    monkeypatch.setenv("LIGHTGBM_TPU_AUDIT", path)
    X, y = _data(seed)
    try:
        bst = lgb.train(dict(PARAMS),
                        lgb.Dataset(X, label=y, params=dict(PARAMS)),
                        num_boost_round=6, verbose_eval=False)
        model = bst.model_to_string()
    finally:
        audit.close()
        audit.path = None
    return path, model


class TestAuditStream:
    def test_disabled_by_default(self, monkeypatch):
        monkeypatch.delenv("LIGHTGBM_TPU_AUDIT", raising=False)
        w = AuditWriter()
        w.refresh_from_env()
        assert not w.enabled
        w.record_tree(0, 0, None, None)  # no-op, must not touch view/tree

    def test_records_schema_and_split_count(self, tmp_path, monkeypatch):
        path, model = _train_audited(tmp_path, "schema", "0",
                                     PARITY_SEED, monkeypatch)
        recs = [json.loads(l) for l in open(path)]
        splits = [r for r in recs if r["ev"] == "split"]
        trees = [r for r in recs if r["ev"] == "tree"]
        assert trees and splits
        assert len(trees) == 6  # one per boosting round (single class)
        # per-tree: leaves == splits + 1, and the leaf-value vector
        # length matches
        for t in trees:
            n_splits = sum(1 for s in splits if s["it"] == t["it"]
                           and s["k"] == t["k"])
            assert t["leaves"] == n_splits + 1
            assert len(t["values"]) == t["leaves"]
        # split fields: the full decision
        for s in splits:
            assert {"ev", "it", "k", "s", "leaf", "feat", "bin", "thr",
                    "gain", "dl", "dbz", "lcnt", "rcnt"} <= set(s)
            assert s["gain"] > 0
            assert s["lcnt"] > 0 and s["rcnt"] > 0
        # deterministic: records carry NO timestamps
        assert all("ts" not in r for r in recs)

    def test_mask_and_fused_paths_both_emit(self, tmp_path, monkeypatch):
        """The audit hook covers every trainer path: the mask grower
        (PGROW off) emits the same schema as the fused path."""
        path = str(tmp_path / "mask.jsonl")
        monkeypatch.setenv("LIGHTGBM_TPU_PGROW", "0")
        monkeypatch.setenv("LIGHTGBM_TPU_AUDIT", path)
        X, y = _data(PARITY_SEED)
        try:
            lgb.train(dict(PARAMS),
                      lgb.Dataset(X, label=y, params=dict(PARAMS)),
                      num_boost_round=2, verbose_eval=False)
        finally:
            audit.close()
            audit.path = None
        recs = [json.loads(l) for l in open(path)]
        assert any(r["ev"] == "split" for r in recs)
        assert any(r["ev"] == "tree" for r in recs)

    def test_levelgrow_parity_config_byte_identical(self, tmp_path,
                                                    monkeypatch):
        """At the known-parity config the two LEVELGROW modes must
        produce BYTE-identical audit trails (the determinism contract:
        repr floats, no timestamps, acceptance order)."""
        p0, m0 = _train_audited(tmp_path, "p0", "0", PARITY_SEED,
                                monkeypatch)
        p1, m1 = _train_audited(tmp_path, "p1", "1", PARITY_SEED,
                                monkeypatch)
        assert m0 == m1, "parity config regressed: models differ"
        with open(p0, "rb") as a, open(p1, "rb") as b:
            assert a.read() == b.read()
        from lightgbm_tpu.cli import main

        assert main(["report", "diff", p0, p1]) == 0


class TestLevelgrowDivergenceRepro:
    """The formerly-divergent LEVELGROW=1 vs =0 config (ROADMAP item 1).

    The two modes leave different within-segment row orders (the level
    grower partitions speculative candidates), and the old
    ``segment_values`` float-cumsum range-add gave different rows
    1-ULP-different score deltas depending on position — so from round
    2 on, gradients (hence one leaf value of tree 2) diverged.  Fixed
    by ``segment_values`` selecting each row's value bit for bit (by
    range compare since PR 32); this class pins the parity, which holds
    here without the canonical reorder PR 30 deleted (module docstring;
    the synthetic-trail localization coverage lives in
    TestReportDiff)."""

    @pytest.fixture(scope="class")
    def trails(self, tmp_path_factory):
        td = tmp_path_factory.mktemp("audit_div")
        mp = pytest.MonkeyPatch()
        try:
            p0, m0 = _train_audited(td, "d0", "0", DIVERGENT_SEED, mp)
            p1, m1 = _train_audited(td, "d1", "1", DIVERGENT_SEED, mp)
        finally:
            mp.undo()
        return p0, m0, p1, m1

    def test_levelgrow_models_match_at_divergent_config(self, trails):
        p0, m0, p1, m1 = trails
        assert m0 == m1

    def test_trails_byte_identical_at_divergent_config(self, trails):
        """Beyond the model string: the full audit trails (every split
        decision, every leaf value) must be byte-identical, and
        ``report diff`` must agree."""
        p0, m0, p1, m1 = trails
        with open(p0, "rb") as a, open(p1, "rb") as b:
            assert a.read() == b.read()
        from lightgbm_tpu.cli import main

        assert main(["report", "diff", p0, p1]) == 0
