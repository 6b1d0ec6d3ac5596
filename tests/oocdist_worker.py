"""Worker for the distributed out-of-core matrix (tests/test_oocdist.py).

argv: ``rank nproc port out mode ckdir`` — the same shape as
elastic_worker.py, and the same world-invariant data recipe: the GLOBAL
dataset is generated identically on every rank from a fixed seed
(few-valued integer features so the bin mappers are bit-identical at
any world size) and each rank keeps its contiguous
``[rank*N/W, (rank+1)*N/W)`` slice under the pre_partition contract.
The difference: ``tree_learner=data`` PLUS out-of-core streaming, so
every rank streams its own shard through the prefetch ring and the node
histograms merge over the byte collectives
(boosting/oocdist.py DistributedOocTrainer).

Env knobs (set by the parent):
  OOCDIST_ROWS / OOCDIST_TREES / OOCDIST_FREQ — problem size
  OOCDIST_CHUNK_ROWS  — ooc_chunk_rows (0 = auto; rounded up to
      ROW_BLOCK per rank)
  OOCDIST_OOC         — out_of_core mode (default "true"; pass "auto"
      with LIGHTGBM_TPU_DEVICE_BUDGET to exercise the budget routing)
  OOCDIST_QUANT       — "1" turns quantized_training on (the
      grid/world byte-identity contract)
  OOCDIST_KILL_ITER=i — every rank SIGKILLs itself in the 0-based
      iteration-``i`` callback (whole-job preemption)
  OOCDIST_LEAVES      — num_leaves

Writes ``out.rankR.json`` (learner class, schedule fingerprint, stream
stats) and ``out.rankR.txt`` (final model) on clean completion.
"""

import json
import os
import signal
import sys

rank = int(sys.argv[1])
nproc = int(sys.argv[2])
port = sys.argv[3]
out = sys.argv[4]
mode = sys.argv[5]
ckdir = sys.argv[6]

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["LIGHTGBM_TPU_COORDINATOR"] = f"127.0.0.1:{port}"
os.environ["LIGHTGBM_TPU_NUM_PROCESSES"] = str(nproc)
os.environ["LIGHTGBM_TPU_PROCESS_ID"] = str(rank)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lightgbm_tpu.parallel import net  # noqa: E402
from lightgbm_tpu.parallel.distributed import ensure_initialized  # noqa: E402

assert ensure_initialized() is True
import jax  # noqa: E402

assert jax.process_count() == nproc

import numpy as np  # noqa: E402

import lightgbm_tpu as lgb  # noqa: E402
from lightgbm_tpu.ckpt import CheckpointManager  # noqa: E402
from lightgbm_tpu.ckpt.store import CheckpointStore  # noqa: E402
from lightgbm_tpu.cli import EXIT_PEER_FAILURE  # noqa: E402

N = int(os.environ.get("OOCDIST_ROWS", "16384"))
TREES = int(os.environ.get("OOCDIST_TREES", "4"))
FREQ = int(os.environ.get("OOCDIST_FREQ", "0"))
KILL_ITER = int(os.environ.get("OOCDIST_KILL_ITER", "-1"))
CHUNK_ROWS = int(os.environ.get("OOCDIST_CHUNK_ROWS", "0"))
OOC_MODE = os.environ.get("OOCDIST_OOC", "true")
QUANT = os.environ.get("OOCDIST_QUANT", "1") == "1"
LEAVES = int(os.environ.get("OOCDIST_LEAVES", "15"))


def _write(payload: dict) -> None:
    with open(out + f".rank{rank}.json", "w") as fh:
        json.dump(payload, fh)


def make_data(n):
    """The GLOBAL dataset, identical on every rank (see
    elastic_worker.make_data: few-valued integer features keep the
    locally-computed bin mappers bit-identical at any world size)."""
    rng = np.random.default_rng(42)
    F = 10
    X = rng.integers(0, 5, size=(n, F)).astype(np.float32)
    w = rng.standard_normal(F)
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-((X - 2.0) @ w * 0.35)))
         ).astype(np.float32)
    return X, y


if mode != "train":
    print(f"unknown mode {mode}")
    sys.exit(2)

X, y = make_data(N)
lo, hi = rank * N // nproc, (rank + 1) * N // nproc
p = dict(objective="binary", tree_learner="data", num_machines=nproc,
         pre_partition=True, num_leaves=LEAVES, learning_rate=0.2,
         max_bin=31, min_data_in_leaf=20, verbose=-1,
         out_of_core=OOC_MODE, ooc_chunk_rows=CHUNK_ROWS,
         quantized_training=QUANT)
ds = lgb.Dataset(X[lo:hi], label=y[lo:hi], params=dict(p))

latest = CheckpointStore(ckdir).latest_valid() if ckdir != "-" else None
resume_from = latest[0] if latest is not None else None


def _kill(env):
    if KILL_ITER >= 0 and env.iteration >= KILL_ITER:
        # whole-job preemption: iteration KILL_ITER's collectives are
        # complete on every rank before any after-iteration callback
        # runs, so every rank reaches this line and dies here
        os.kill(os.getpid(), signal.SIGKILL)


_kill.order = 100  # after the CheckpointManager (order 40)

mgr = CheckpointManager(ckdir, freq=FREQ) if ckdir != "-" and FREQ > 0 \
    else None
booster = None
try:
    booster = lgb.train(
        dict(p), ds, TREES, verbose_eval=False,
        **({"checkpoint_manager": mgr} if mgr is not None else {}),
        callbacks=[_kill])
except net.PeerFailureError as e:
    if mgr is not None:
        mgr.flush()
    _write({"error": "PeerFailureError", "ranks": list(e.ranks),
            "resume_from": resume_from})
    print(f"rank {rank} detected peer failure after {e.elapsed_s:.1f}s")
    net.hard_exit(EXIT_PEER_FAILURE)
if mgr is not None:
    mgr.close()

ooc = booster.boosting.ooc
with open(out + f".rank{rank}.txt", "w") as fh:
    fh.write(booster.model_to_string())
_write({
    "error": None,
    "resume_from": resume_from,
    "trees": booster.num_trees,
    "iters": booster.current_iteration(),
    "world": nproc,
    "rows": [lo, hi],
    "learner": type(booster.boosting.learner).__name__,
    "ooc": ooc is not None,
    "schedule": ooc.schedule_fingerprint() if ooc is not None else None,
    "chunks_per_pass": ooc.plan.num_chunks if ooc is not None else None,
    "stream_stats": dict(ooc.stats.as_dict()) if ooc is not None else None,
})
print(f"rank {rank} oocdist train done (world={nproc}, "
      f"resume_from={resume_from})")
sys.exit(0)
