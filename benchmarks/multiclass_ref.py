"""The benchmark's own multiclass reference, in float64 numpy: the softmax
objective as the reference states it (src/objective/multiclass_objective.hpp,
what lightgbm_tpu/objective/multiclass.py follows), written apart from the
program so that no later PR to it can move the yardstick.

One boosting iteration of the reference (gbdt.cpp:445-480): every class's
gradient plane from ONE snapshot of the K score planes,

    p_k = softmax(scores)_k,   g_k = p_k - 1[y = k],   h_k = 2 p_k (1 - p_k),

then K trees, tree k grown on (g_k, h_k) and adding its leaves to class k's
score (`add_class_trees`).  A prediction is the softmax of the summed class
scores.  The trees themselves are the mask grower's (ops/grow.py), the
program's oracle.
"""

import numpy as np


def softmax(scores) -> np.ndarray:
    """(K, n) scores -> (K, n) class probabilities, float64."""
    z = np.asarray(scores, np.float64)
    e = np.exp(z - z.max(axis=0, keepdims=True))
    return e / e.sum(axis=0, keepdims=True)


def gradients(scores, y) -> dict:
    """{"g", "h"}: (K, n) float64 planes of one iteration from the (K, n)
    score snapshot and the class labels (n,)."""
    p = softmax(scores)
    onehot = np.asarray(y, np.int64)[None, :] == np.arange(p.shape[0])[:, None]
    return {"g": p - onehot, "h": 2.0 * p * (1.0 - p)}


def add_class_trees(scores, tree_outputs) -> np.ndarray:
    """The scores after trees whose (n,) outputs are `tree_outputs`, in the
    model's order: tree t adds to class t % K."""
    out = np.array(scores, np.float64)
    for t, v in enumerate(tree_outputs):
        out[t % out.shape[0]] += v
    return out


def macro_auc(y, prob, auc) -> float:
    """The mean over the classes of one-vs-rest AUC of each class's
    probability (`auc(labels01, score)`: data.auc); a class absent from `y`
    has none and is left out."""
    y = np.asarray(y, np.int64)
    got = [auc((y == k).astype(np.float64), prob[k]) for k in range(prob.shape[0])
           if 0 < np.sum(y == k) < len(y)]
    return float(np.mean(got))


def logloss(y, prob) -> float:
    y = np.asarray(y, np.int64)
    return float(-np.mean(np.log(np.maximum(prob[y, np.arange(len(y))], 1e-15))))


def error(y, prob) -> float:
    return float(np.mean(np.argmax(prob, axis=0) != np.asarray(y, np.int64)))
