"""Worker for the 2-process distributed parity test (run via subprocess).

Each process: CPU platform with 4 virtual devices, rank from argv,
jax.distributed over localhost.  Grows one data-parallel tree on its
row half and (rank 0) writes the replicated split records to an npz.
"""

import os
import sys

rank = int(sys.argv[1])
port = sys.argv[2]
out = sys.argv[3]
mode = sys.argv[4] if len(sys.argv) > 4 else "grow"

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
)
os.environ["LIGHTGBM_TPU_COORDINATOR"] = f"127.0.0.1:{port}"
os.environ["LIGHTGBM_TPU_NUM_PROCESSES"] = "2"
os.environ["LIGHTGBM_TPU_PROCESS_ID"] = str(rank)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from lightgbm_tpu.parallel.distributed import ensure_initialized  # noqa: E402

assert ensure_initialized() is True
import jax  # noqa: E402

import jax.numpy as jnp  # noqa: E402

assert jax.process_count() == 2, jax.process_count()
assert len(jax.devices()) == 8

from lightgbm_tpu.ops.grow import GrowParams  # noqa: E402
from lightgbm_tpu.ops.split import FeatureMeta, SplitHyper  # noqa: E402
from lightgbm_tpu.parallel import ShardedLearner, make_mesh  # noqa: E402

if mode == "sketchmerge":
    # streaming-ingest sketch merge across hosts: each rank folds a
    # DIFFERENT row half into its sketch bank chunk-by-chunk, then
    # merge_across_hosts allgathers + merges.  Exact (unspilled)
    # sketches must come back bit-identical to a single-process sketch
    # of the full data, on BOTH ranks.
    import pickle

    from lightgbm_tpu.data.stats import SketchCollector

    rng = np.random.default_rng(17)
    X = rng.integers(-4, 9, size=(6000, 5)).astype(np.float64)
    X[rng.random((6000, 5)) < 0.05] = np.nan
    half = X[:3000] if rank == 0 else X[3000:]
    coll = SketchCollector(categorical={4}, cap=100_000)
    for lo in range(0, 3000, 700):
        coll.update(half[lo : lo + 700])
    coll.merge_across_hosts()
    if rank == 0:
        banks = [sk.to_distinct_counts() for sk in coll.sketches]
        extras = [(sk.total_cnt, getattr(sk, "zero_cnt", -1),
                   getattr(sk, "nan_cnt", -1)) for sk in coll.sketches]
        with open(out, "wb") as fh:
            pickle.dump({"banks": banks, "extras": extras}, fh)
    print(f"rank {rank} sketchmerge done: {coll.rows_seen} rows")
    sys.exit(0)

if mode == "findbin":
    # distributed find-bin parity: both ranks hold the SAME data; the
    # feature mappers (each found by exactly one rank, then allgathered)
    # must be bit-identical to the single-process mappers
    import pickle

    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import BinnedDataset

    rng = np.random.default_rng(9)
    X = rng.standard_normal((5000, 13))
    X[:, 3] = np.round(X[:, 3] * 2)  # low-cardinality column
    y = rng.standard_normal(5000)
    cfg_params = {"max_bin": 31, "tree_learner": "data", "num_machines": 2,
                  "verbose": -1}
    cfg = Config.from_params(dict(cfg_params))
    assert cfg.is_parallel_find_bin, "expected parallel find-bin to engage"
    ds = BinnedDataset.from_raw(X, cfg, label=y)
    if rank == 0:
        states = [m.state() for m in ds.bin_mappers]
        with open(out, "wb") as fh:
            pickle.dump({"states": states, "binned": ds.binned,
                         "used": ds.used_feature_map}, fh)
    print(f"rank {rank} findbin done: {len(ds.bin_mappers)} mappers")
    sys.exit(0)

if mode == "ckptresume":
    # 2-process sharded-ptrainer checkpoint/resume: train uninterrupted
    # for 6 iters (reference hash), then a second run that "dies" at
    # iteration 3 (KeyboardInterrupt from a callback — both ranks throw
    # at the same boundary, so no collective is left half-entered), then
    # a third run that auto-resumes from the rank-0-written checkpoint.
    # The resumed model must be BIT-identical to the uninterrupted one
    # on both ranks (exercises the multihost barrier, the host-0 write,
    # the per-rank container unwrap, and the sharded perm export/import).
    import json

    os.environ["LIGHTGBM_TPU_PGROW"] = "force"
    import lightgbm_tpu as lgb
    from lightgbm_tpu.boosting.ptrainer import ShardedPartitionedTrainer
    from lightgbm_tpu.ckpt import CheckpointManager

    rng = np.random.default_rng(5)
    N, F = 3000, 6
    X = rng.integers(0, 12, size=(N, F)).astype(np.float32)
    wv = rng.standard_normal(F)
    yp = 1.0 / (1.0 + np.exp(-((X - 6) @ wv * 0.3)))
    y = (rng.random(N) < yp).astype(np.float32)
    cut = 1700
    sl = slice(0, cut) if rank == 0 else slice(cut, N)
    p = dict(objective="binary", tree_learner="data", num_machines=2,
             pre_partition=True, num_leaves=15, learning_rate=0.2,
             max_bin=31, min_data_in_leaf=20, verbose=-1)

    def mk():
        return lgb.Dataset(X[sl], label=y[sl], params=dict(p))

    ref = lgb.train(dict(p), mk(), 6, verbose_eval=False)
    assert isinstance(ref.boosting.ptrainer, ShardedPartitionedTrainer)
    ref_str = ref.model_to_string()

    ckdir = out + f".ckpt"  # shared tmp dir: both ranks see the same files

    def killer(env):
        if env.iteration + 1 == 3:
            raise KeyboardInterrupt
    killer.order = 99

    mgr = CheckpointManager(ckdir, freq=2)
    try:
        lgb.train(dict(p), mk(), 6, verbose_eval=False,
                  checkpoint_manager=mgr, callbacks=[killer])
        raise AssertionError("expected the simulated death")
    except KeyboardInterrupt:
        pass
    mgr.close()

    mgr2 = CheckpointManager(ckdir, freq=2)
    resumed = lgb.train(dict(p), mk(), 6, verbose_eval=False,
                        checkpoint_manager=mgr2)
    mgr2.close()
    match = resumed.model_to_string() == ref_str
    if rank == 0:
        with open(out, "w") as fh:
            json.dump({"match": bool(match), "trees": resumed.num_trees,
                       "model": resumed.model_to_string()}, fh)
    assert match, f"rank {rank}: resumed model diverged from uninterrupted"
    print(f"rank {rank} ckptresume done: match={match}")
    sys.exit(0)

if mode == "ptrainer":
    # fused data-parallel trainer (ShardedPartitionedTrainer) across two
    # processes: each rank holds a DIFFERENT row half (pre_partition);
    # integer-valued features make the distributed find-bin mappers
    # bit-identical to single-process full-data mappers, so the test can
    # assert tree-for-tree parity against the serial fused trainer.
    os.environ["LIGHTGBM_TPU_PGROW"] = "force"
    import lightgbm_tpu as lgb

    rng = np.random.default_rng(5)
    N, F = 3000, 6
    X = rng.integers(0, 12, size=(N, F)).astype(np.float32)
    wv = rng.standard_normal(F)
    yp = 1.0 / (1.0 + np.exp(-((X - 6) @ wv * 0.3)))
    y = (rng.random(N) < yp).astype(np.float32)
    cut = 1700  # unequal halves exercise the shard-padding branches
    sl = slice(0, cut) if rank == 0 else slice(cut, N)
    p = dict(objective="binary", tree_learner="data", num_machines=2,
             pre_partition=True, num_leaves=15, learning_rate=0.2,
             max_bin=31, min_data_in_leaf=20, verbose=-1)
    ds = lgb.Dataset(X[sl], label=y[sl], params=dict(p))
    bst = lgb.train(p, ds, 4, verbose_eval=False)
    from lightgbm_tpu.boosting.ptrainer import ShardedPartitionedTrainer

    assert isinstance(bst.boosting.ptrainer, ShardedPartitionedTrainer), (
        type(bst.boosting.ptrainer)
    )
    if rank == 0:
        with open(out, "w") as fh:
            fh.write(bst.model_to_string())
    print(f"rank {rank} ptrainer done: {bst.num_trees} trees")
    sys.exit(0)

# identical synthetic dataset on both ranks; each passes its own half
rng = np.random.default_rng(42)
N, F, B = 4096, 6, 16
bins = rng.integers(0, B, size=(N, F), dtype=np.uint8)
grad = rng.standard_normal(N).astype(np.float32)
hess = np.abs(rng.standard_normal(N)).astype(np.float32) + 0.1
# deliberately UNEQUAL shards: exercises the pad-to-global-max path
cut = 2200
sl = slice(0, cut) if rank == 0 else slice(cut, N)
half = sl.stop - sl.start

meta = FeatureMeta(
    num_bins=jnp.full((F,), B, jnp.int32),
    default_bin=jnp.zeros((F,), jnp.int32),
    is_categorical=jnp.zeros((F,), bool),
)
hyper = SplitHyper(
    lambda_l1=jnp.float32(0.0), lambda_l2=jnp.float32(0.01),
    min_data_in_leaf=jnp.float32(20), min_sum_hessian_in_leaf=jnp.float32(1e-3),
    min_gain_to_split=jnp.float32(0.0),
)
params = GrowParams(num_leaves=15, num_bins=B)
learner = ShardedLearner("data", make_mesh(), params)
gr = learner.grow(
    jnp.asarray(bins[sl]), jnp.asarray(grad[sl]), jnp.asarray(hess[sl]),
    jnp.ones((half,), jnp.float32), jnp.ones((F,), jnp.float32), meta, hyper,
)
ns = int(gr.num_splits)
if rank == 0:
    np.savez(
        out,
        num_splits=ns,
        rec_feat=np.asarray(gr.rec_feat[:ns]),
        rec_thr=np.asarray(gr.rec_thr[:ns]),
        rec_leaf=np.asarray(gr.rec_leaf[:ns]),
        rec_lval=np.asarray(gr.rec_lval[:ns]),
        leaf_id_local=np.asarray(gr.leaf_id),
    )
print(f"rank {rank} done: {ns} splits")
