"""Shared harness for the strategy-parity pin (tests/test_strategy_parity.py).

The tentpole refactor (lightgbm_tpu/tree/strategy.py) must be INVISIBLE:
model bytes and split-decision audit trails at the PR-7 parity configs
are captured from the pre-refactor tree into tests/golden/strategy_parity/
and every later session re-derives them byte-for-byte.  This module
holds the config matrix and the runner so the capture script and the
test cannot drift apart.

Run ``python tests/strategy_parity_lib.py <outdir>`` to (re)capture.
"""

import hashlib
import json
import os
import sys
import threading

import numpy as np

# the PR-7 audit shape: 15 leaves / min_data_in_leaf=20 / 6 rounds
_BASE = {"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 20,
         "verbose": -1, "seed": 7}

# name -> extra params for the booster-level configs (all trained with
# lgb.train; hostlearner feature/voting modes run below via LocalGroup)
BOOSTER_CONFIGS = {
    "bagging": {"bagging_fraction": 0.7, "bagging_freq": 1,
                "bagging_seed": 3},
    # learning_rate 0.5 -> GOSS's 1/lr warmup ends at round 2, so the
    # top-k/other-k sampling really runs inside the 6-round window
    "goss": {"boosting": "goss", "top_rate": 0.3, "other_rate": 0.2,
             "learning_rate": 0.5},
    "sharded": {"tree_learner": "data"},
    "ooc": {"out_of_core": "true", "ooc_chunk_rows": 512},
}

ROUNDS = 6


def _data(seed=11, n=1200, f=8):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = (X[:, 0] + X[:, 1] > 0).astype(float)
    return X, y


def run_booster_config(name, audit_path):
    """Train one named config with the audit trail armed; returns
    (model_string, audit_bytes)."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.obs.audit import audit

    params = dict(_BASE)
    params.update(BOOSTER_CONFIGS[name])
    os.environ["LIGHTGBM_TPU_AUDIT"] = audit_path
    X, y = _data()
    try:
        bst = lgb.train(params, lgb.Dataset(X, label=y, params=dict(params)),
                        num_boost_round=ROUNDS, verbose_eval=False)
        model = bst.model_to_string()
    finally:
        audit.close()
        audit.path = None
        os.environ.pop("LIGHTGBM_TPU_AUDIT", None)
    with open(audit_path, "rb") as fh:
        trail = fh.read()
    return model, trail


def run_hostlearner_mode(mode, nproc=2):
    """Grow one tree on an in-process LocalGroup; returns a stable
    digest of rank 0's GrowResult arrays."""
    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.ops.grow import GrowParams
    from lightgbm_tpu.ops.split import FeatureMeta, SplitHyper
    from lightgbm_tpu.parallel import HostParallelLearner, LocalGroup

    rng = np.random.default_rng(5)
    n, f, B = 2000, 24, 16
    bins = rng.integers(0, B, size=(n, f)).astype(np.uint8)
    grad = rng.normal(size=n).astype(np.float32)
    hess = np.ones(n, np.float32)
    meta = FeatureMeta(jnp.full((f,), B, jnp.int32),
                       jnp.zeros((f,), jnp.int32), jnp.zeros((f,), bool))
    hyper = SplitHyper(jnp.float32(0.0), jnp.float32(0.1), jnp.float32(20.0),
                       jnp.float32(1e-3), jnp.float32(0.0))
    params = GrowParams(num_leaves=15, num_bins=B,
                        top_k=f if mode == "voting" else 20)
    fmask = jnp.ones((f,), jnp.float32)
    rows = np.array_split(np.arange(n), nproc)
    grp = LocalGroup(nproc)
    out = [None] * nproc
    errs = []

    def worker(r, comm):
        try:
            idx = rows[r]
            learner = HostParallelLearner(mode, comm, params)
            gr = learner.grow(jnp.asarray(bins[idx]), jnp.asarray(grad[idx]),
                              jnp.asarray(hess[idx]),
                              jnp.ones((len(idx),), jnp.float32),
                              fmask, meta, hyper)
            out[r] = jax.tree_util.tree_map(np.asarray, gr)
        except BaseException as e:
            errs.append((r, e))

    ts = [threading.Thread(target=worker, args=(r, c))
          for r, c in enumerate(grp.comms())]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    if errs:
        raise errs[0][1]
    h = hashlib.sha256()
    for name, arr in zip(out[0]._fields, out[0]):
        h.update(name.encode())
        h.update(np.ascontiguousarray(np.asarray(arr)).tobytes())
    return h.hexdigest()


def capture(outdir):
    import jax

    os.makedirs(outdir, exist_ok=True)
    # the goldens are byte pins of f32 arithmetic: they hold for the XLA
    # that recorded them (PR 22 re-recorded after 0.4.37 -> 0.9.0 moved
    # gains by 1 ULP from the second tree on, see CHANGES.md)
    digests = {"_recorded_under": {"jax": jax.__version__,
                                   "backend": jax.default_backend()}}
    for name in BOOSTER_CONFIGS:
        audit_path = os.path.join(outdir, f"{name}.audit.jsonl")
        model, trail = run_booster_config(name, audit_path)
        with open(os.path.join(outdir, f"{name}.model.txt"), "w") as fh:
            fh.write(model)
        digests[name] = {
            "model_sha256": hashlib.sha256(model.encode()).hexdigest(),
            "audit_sha256": hashlib.sha256(trail).hexdigest(),
        }
    for mode in ("feature", "voting"):
        digests[f"hostlearner_{mode}"] = {
            "grow_sha256": run_hostlearner_mode(mode)}
    with open(os.path.join(outdir, "digests.json"), "w") as fh:
        json.dump(digests, fh, indent=2, sort_keys=True)
        fh.write("\n")
    return digests


if __name__ == "__main__":
    out = sys.argv[1] if len(sys.argv) > 1 else os.path.join(
        os.path.dirname(__file__), "golden", "strategy_parity")
    print(json.dumps(capture(out), indent=2, sort_keys=True))
