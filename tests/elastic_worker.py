"""Worker for the elastic-training matrix (test_ckpt_fault.py topology
legs, test_rebalance.py).

argv: ``rank nproc port out mode ckdir``.  Every rank of one phase runs
this script; the parent varies ``nproc`` between phases — that is the
whole point: a checkpoint written at one world size is resumed at
another through the canonical global layout (ckpt/state.py,
docs/CHECKPOINT.md).

The global dataset is generated IDENTICALLY on every rank from a fixed
seed (integer-valued features so the distributed find-bin mappers are
bit-identical regardless of world size) and each rank keeps its
contiguous ``[rank*N/W, (rank+1)*N/W)`` row slice — the pre_partition
contract, so the concatenated shards are byte-for-byte the same global
matrix at every world size and the fingerprint handshake accepts the
resume.

modes:
  train — lgb.train over the host-driven data-parallel learner with a
          shared CheckpointManager; auto-resumes from ``ckdir`` when a
          valid checkpoint exists.  Env knobs (set by the parent):
            ELASTIC_ROWS / ELASTIC_TREES / ELASTIC_FREQ — problem size
            ELASTIC_KILL_ITER=i  — every rank SIGKILLs itself in the
                0-based iteration-``i`` callback (whole-job preemption:
                collectives for iteration i are complete, so nobody is
                left mid-barrier; the freq-boundary checkpoint is
                already durable two iterations back)
            ELASTIC_REBALANCE=1  — arm straggler-aware shard
                rebalancing (config knobs rebalance_*)
            ELASTIC_OBJECTIVE=lambdarank — ranking data: relevance
                labels, query groups, and GROUP-ALIGNED shard edges (a
                query group never spans ranks; rebalance must keep it
                that way via cut-point snapping)
            ELASTIC_QUANTIZED=1 — quantized training (world-invariant
                integer histograms -> byte-identical across worlds)
          plus the standard LIGHTGBM_TPU_FAULT / _FAULT_RANK / _TRACE /
          _AUDIT hooks.  Writes ``out.rankR.json`` (audit fields below)
          and ``out.rankR.txt`` (final model) on clean completion.
"""

import json
import os
import signal
import sys
import time

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

assert ensure_initialized() is (nproc > 1)  # world 1 = serial reference
import jax  # noqa: E402

assert jax.process_count() == nproc

import numpy as np  # noqa: E402

import lightgbm_tpu as lgb  # noqa: E402
from lightgbm_tpu.ckpt import CheckpointManager  # noqa: E402
from lightgbm_tpu.ckpt.store import CheckpointStore  # noqa: E402
from lightgbm_tpu.cli import EXIT_PEER_FAILURE  # noqa: E402

N = int(os.environ.get("ELASTIC_ROWS", "1024"))
TREES = int(os.environ.get("ELASTIC_TREES", "16"))
FREQ = int(os.environ.get("ELASTIC_FREQ", "4"))
KILL_ITER = int(os.environ.get("ELASTIC_KILL_ITER", "-1"))
REBALANCE = os.environ.get("ELASTIC_REBALANCE", "0") == "1"
LEAVES = int(os.environ.get("ELASTIC_LEAVES", "15"))
OBJECTIVE = os.environ.get("ELASTIC_OBJECTIVE", "binary")
QUANTIZED = os.environ.get("ELASTIC_QUANTIZED", "0") == "1"


def _write(payload: dict) -> None:
    with open(out + f".rank{rank}.json", "w") as fh:
        json.dump(payload, fh)


def make_data(n):
    """The GLOBAL dataset, identical on every rank.  Few-valued integer
    features (5 distinct values) so EVERY shard of every world size sees
    the full value set and the locally-computed bin mappers — and hence
    the binned bytes the elastic fingerprint handshake covers — are
    bit-identical at any world."""
    rng = np.random.default_rng(42)
    F = 10
    X = rng.integers(0, 5, size=(n, F)).astype(np.float32)
    w = rng.standard_normal(F)
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-((X - 2.0) @ w * 0.35)))
         ).astype(np.float32)
    return X, y


def make_rank_data(n):
    """Ranking data, identical on every rank: variable-size query groups
    (8..24 rows) with relevance 0..3 assigned by within-group score
    rank.  Returns (X, y, group_sizes)."""
    rng = np.random.default_rng(43)
    F = 10
    X = rng.integers(0, 5, size=(n, F)).astype(np.float32)
    sizes = []
    while sum(sizes) < n - 24:
        sizes.append(int(rng.integers(8, 25)))
    sizes.append(n - sum(sizes))
    w = rng.standard_normal(F)
    score = (X - 2.0) @ w * 0.3 + rng.standard_normal(n) * 0.5
    y = np.zeros(n, np.float32)
    off = 0
    for s in sizes:
        order = score[off:off + s].argsort().argsort()
        y[off:off + s] = np.minimum(3, (order * 4) // s)
        off += s
    return X, y, np.asarray(sizes, np.int64)


if mode != "train":
    print(f"unknown mode {mode}")
    sys.exit(2)

group_cum = None
if OBJECTIVE == "lambdarank":
    X, y, group_sizes = make_rank_data(N)
    group_cum = np.concatenate([[0], np.cumsum(group_sizes)])
    # pre_partition contract for ranking: every shard edge IS a group
    # boundary — each rank snaps the ideal even split to the nearest
    # cumulative boundary (identical arithmetic on every rank)
    lo = int(group_cum[np.abs(group_cum - rank * N // nproc).argmin()])
    hi = int(group_cum[np.abs(group_cum - (rank + 1) * N // nproc).argmin()])
    local_sizes = np.diff(group_cum[(group_cum >= lo) & (group_cum <= hi)])
else:
    X, y = make_data(N)
    lo, hi = rank * N // nproc, (rank + 1) * N // nproc
    local_sizes = None
p = dict(objective=OBJECTIVE, tree_learner="data", num_machines=nproc,
         pre_partition=True, num_leaves=LEAVES, learning_rate=0.2,
         max_bin=31, min_data_in_leaf=20, verbose=-1)
if QUANTIZED:
    p.update(quantized_training=True, seed=7)
if REBALANCE:
    p.update(rebalance=True, rebalance_threshold=1.5, rebalance_patience=3,
             rebalance_max_move_frac=float(
                 os.environ.get("ELASTIC_MOVE_FRAC", "0.25")))
ds = lgb.Dataset(X[lo:hi], label=y[lo:hi], group=local_sizes,
                 params=dict(p))

latest = CheckpointStore(ckdir).latest_valid()
resume_from = latest[0] if latest is not None else None

it_marks = []


def _clock(env):
    it_marks.append((env.iteration, time.perf_counter()))


_clock.order = 90


def _kill(env):
    if KILL_ITER >= 0 and env.iteration >= KILL_ITER:
        # whole-job preemption: iteration KILL_ITER's collectives are
        # complete on every rank before any after-iteration callback
        # runs, so every rank reaches this line and dies here
        os.kill(os.getpid(), signal.SIGKILL)


_kill.order = 100  # after the CheckpointManager (order 40)

mgr = CheckpointManager(ckdir, freq=FREQ)
booster = None
try:
    booster = lgb.train(dict(p), ds, TREES, verbose_eval=False,
                        checkpoint_manager=mgr, callbacks=[_clock, _kill])
except net.PeerFailureError as e:
    mgr.flush()
    _write({"error": "PeerFailureError", "ranks": list(e.ranks),
            "resume_from": resume_from})
    print(f"rank {rank} detected peer failure after {e.elapsed_s:.1f}s")
    net.hard_exit(EXIT_PEER_FAILURE)
mgr.close()

it_times = [round(b - a, 6)
            for (_, a), (_, b) in zip(it_marks, it_marks[1:])]
reb = getattr(booster.boosting, "_rebalance", None)
final_counts = list(reb["plan"].counts) if reb else None
_qb = booster.boosting.train_set.metadata.query_boundaries
group_aligned = None
if group_cum is not None and reb:
    edges = set(int(g) for g in group_cum)
    group_aligned = all(int(s) in edges
                        for s in reb["plan"].starts) and reb["plan"].total in edges
with open(out + f".rank{rank}.txt", "w") as fh:
    fh.write(booster.model_to_string())
_write({
    "error": None,
    "resume_from": resume_from,
    "trees": booster.num_trees,
    "iters": booster.current_iteration(),
    "world": nproc,
    "rows": [lo, hi],
    "rows_end": int(booster.boosting.num_data),
    "final_counts": final_counts,
    "group_aligned": group_aligned,
    "n_local_groups": (None if _qb is None else int(len(_qb) - 1)),
    "it_times": it_times,
})
print(f"rank {rank} train done (world={nproc}, resume_from={resume_from})")
sys.exit(0)
