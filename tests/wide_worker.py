"""Worker for the wide-data distributed learner parity tests (run via
subprocess).  Each process: CPU platform with 4 virtual devices, rank and
world size from argv, jax.distributed over localhost.

Modes:
  serial   -- single process, tree_learner=serial on the full data; the
              byte-identity REFERENCE.  It must run under the same
              XLA_FLAGS as the parallel workers: XLA:CPU partitions its
              thread pool by device count and f32 matmul accumulation
              order follows it, so histograms are only bitwise
              reproducible within one environment.
  feature  -- rows REPLICATED on every rank, columns sharded inside the
              learner; full lgb.train; rank 0 writes the model string.
  voting   -- rows pre-partitioned; tree_learner=voting with top_k=F
              (2k >= F, exact data-parallel recovery).
  datahost -- rows pre-partitioned; tree_learner=data over the
              host-driven learner (same shards as voting mode).
"""

import os
import sys

rank = int(sys.argv[1])
port = sys.argv[2]
out = sys.argv[3]
mode = sys.argv[4]
nproc = int(sys.argv[5]) if len(sys.argv) > 5 else 2

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
)
if mode != "serial":
    os.environ["LIGHTGBM_TPU_COORDINATOR"] = f"127.0.0.1:{port}"
    os.environ["LIGHTGBM_TPU_NUM_PROCESSES"] = str(nproc)
    os.environ["LIGHTGBM_TPU_PROCESS_ID"] = str(rank)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

if mode != "serial":
    from lightgbm_tpu.parallel.distributed import ensure_initialized

    assert ensure_initialized() is True
import jax  # noqa: E402


if mode != "serial":
    assert jax.process_count() == nproc, jax.process_count()

import lightgbm_tpu as lgb  # noqa: E402
from lightgbm_tpu.parallel import HostParallelLearner  # noqa: E402

# integer features -> distributed find-bin mappers are bit-identical to
# the single-process mappers, so model strings can be compared bytewise
rng = np.random.default_rng(29)
N, F = 3000, 30
X = rng.integers(0, 12, size=(N, F)).astype(np.float32)
wv = rng.standard_normal(F)
yp = 1.0 / (1.0 + np.exp(-((X - 6) @ wv * 0.1)))
y = (rng.random(N) < yp).astype(np.float32)

# boost_from_average off everywhere: the distributed label average is an
# allreduce of per-rank partials, which rounds differently from the
# single-process mean even on replicated data
base = dict(objective="binary", boost_from_average=False, num_leaves=15,
            learning_rate=0.2, max_bin=31, min_data_in_leaf=20, verbose=-1)

if mode == "serial":
    p = dict(base, tree_learner="serial")
    ds = lgb.Dataset(X, label=y, params=dict(p))
elif mode == "feature":
    # every rank sees the full matrix; the learner shards its columns
    p = dict(base, tree_learner="feature", num_machines=nproc)
    ds = lgb.Dataset(X, label=y, params=dict(p))
else:
    # unequal row shards via pre_partition
    cuts = [0] + [N * (r + 1) // nproc + (7 if r == 0 else 0)
                  for r in range(nproc - 1)] + [N]
    sl = slice(cuts[rank], cuts[rank + 1])
    learner = "voting" if mode == "voting" else "data"
    p = dict(base, tree_learner=learner, num_machines=nproc,
             pre_partition=True, top_k=F)
    ds = lgb.Dataset(X[sl], label=y[sl], params=dict(p))

bst = lgb.train(p, ds, 4, verbose_eval=False)

if mode != "serial":
    want = {"feature": "feature", "voting": "voting", "datahost": "data"}[mode]
    learner_obj = bst.boosting.learner
    assert isinstance(learner_obj, HostParallelLearner), type(learner_obj)
    assert learner_obj.mode == want, learner_obj.mode
    assert learner_obj.comm.ledger_total() > 0

if rank == 0:
    with open(out, "w") as fh:
        fh.write(bst.model_to_string())
print(f"rank {rank} {mode} done: {bst.num_trees} trees")
sys.exit(0)
