import numpy as np

import data


def test_same_seed_same_table_other_seed_other_rows():
    X1, y1 = data.make_higgs_shaped(5000, seed=3)
    X2, y2 = data.make_higgs_shaped(5000, seed=3)
    X3, _ = data.make_higgs_shaped(5000, seed=4)
    assert X1.dtype == np.float32 and X1.shape == (5000, 28) and set(np.unique(y1)) == {0.0, 1.0}
    assert np.array_equal(X1, X2) and np.array_equal(y1, y2) and not np.array_equal(X1, X3)


def test_label_seed_redraws_the_labels_over_the_same_features():
    X1, y1 = data.make_higgs_shaped(5000, seed=3)
    Xa, ya = data.make_higgs_shaped(5000, seed=3, label_seed=11)
    Xb, yb = data.make_higgs_shaped(5000, seed=3, label_seed=11)
    _, yc = data.make_higgs_shaped(5000, seed=3, label_seed=12)
    assert np.array_equal(X1, Xa) and np.array_equal(ya, yb)
    # two draws of the same coins disagree where p(1-p) is large: about 2 rows in 5
    assert 0.3 < np.mean(ya != yc) < 0.5 and 0.3 < np.mean(ya != y1) < 0.5


def test_task_is_learnable_and_scale_is_the_margins():
    X, y = data.make_higgs_shaped(200_000, seed=1)
    w = data.task_weights()
    margin = X[:, :8] @ w + 0.5 * X[:, 0] * X[:, 1] - 0.3 * X[:, 2] ** 2
    assert abs(margin.std() / data._margin_std(w) - 1) < 0.01
    assert 0.73 < data.auc(y, margin) < 0.75  # the margin is the best score there is


def test_auc_against_the_pair_count():
    rng = np.random.RandomState(0)
    y = (rng.rand(300) < 0.4).astype(np.float32)
    s = np.round(rng.randn(300) + y, 1)  # rounding makes ties
    pos, neg = s[y > 0], s[y <= 0]
    pairs = (pos[:, None] > neg[None, :]).sum() + 0.5 * (pos[:, None] == neg[None, :]).sum()
    assert abs(data.auc(y, s) - pairs / (len(pos) * len(neg))) < 1e-12
