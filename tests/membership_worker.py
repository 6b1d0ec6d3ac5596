"""Worker for the live-membership matrix (test_membership.py,
factory/spot.py fleets).

argv: ``member_id fleet_dir out`` — unlike elastic_worker.py there is
NO jax.distributed bootstrap: every member runs single-process JAX and
ALL coordination rides the fleet directory's FileKVClient
(parallel/membership.py).  ``member_id`` of ``join`` means mid-run
arrival (the id is allocated from the store).

The global dataset is generated IDENTICALLY on every member from a
fixed seed (integer-valued features, so bin mappers are bit-identical
on any slice) and doubles as the ``row_provider`` seam: transitions
regenerate row slices in RAM instead of exchanging them.

Env knobs (set by the parent):
  MEMBER_NPROC      — bootstrap world size (launch-time members)
  MEMBER_ROWS / MEMBER_TREES / MEMBER_LEAVES — problem size
  MEMBER_KILL_ITER=i — SIGKILL self in the 0-based iteration-i callback
      (an eviction target: survivors detect the stale heartbeat and
      resize instead of exiting 75)
  MEMBER_LEAVE_ITER=i — request a clean leave at iteration i (same path
      a SIGTERM takes, but deterministic for byte-identity tests)
  MEMBER_SIGTERM_ITER=i — SIGTERM *self* at iteration i: exercises the
      real signal handler -> request_leave path with deterministic timing
  MEMBER_ITER_SLEEP=s — sleep s seconds per finished iteration (paces
      the fleet so a mid-run joiner reliably lands before completion)
  MEMBER_REBALANCE=1 — arm straggler-aware shard rebalancing
  MEMBER_QUANTIZED=0 — disable quantized training (default on)
  MEMBER_PROGRESS=1 — publish write-once ``progress/<iter>`` KV records
      (first finisher claims the slot) plus per-attempt
      ``attempts/<iter>.m<id>.e<epoch>`` keys for the spot cost ledger
plus the standard LIGHTGBM_TPU_FAULT / _TRACE / _NET_* hooks.

Exit codes: 0 on completed model OR clean leave; EXIT_PEER_FAILURE (75)
when membership recovery itself fails.  Writes ``out.mM.json`` always
and ``out.mM.txt`` (final model) on completed training.
"""

import json
import os
import signal
import sys
import time

member_arg = sys.argv[1]
fleet_dir = sys.argv[2]
out = sys.argv[3]

os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

import lightgbm_tpu as lgb  # noqa: E402
from lightgbm_tpu.cli import EXIT_PEER_FAILURE  # noqa: E402
from lightgbm_tpu.parallel import membership, net  # noqa: E402
from lightgbm_tpu.parallel.shardplan import ShardPlan  # noqa: E402

N = int(os.environ.get("MEMBER_ROWS", "600"))
TREES = int(os.environ.get("MEMBER_TREES", "12"))
LEAVES = int(os.environ.get("MEMBER_LEAVES", "7"))
KILL_ITER = int(os.environ.get("MEMBER_KILL_ITER", "-1"))
LEAVE_ITER = int(os.environ.get("MEMBER_LEAVE_ITER", "-1"))
SIGTERM_ITER = int(os.environ.get("MEMBER_SIGTERM_ITER", "-1"))
ITER_SLEEP = float(os.environ.get("MEMBER_ITER_SLEEP", "0"))
REBALANCE = os.environ.get("MEMBER_REBALANCE", "0") == "1"
QUANTIZED = os.environ.get("MEMBER_QUANTIZED", "1") == "1"
PROGRESS = os.environ.get("MEMBER_PROGRESS", "0") == "1"


def make_data(n):
    """The GLOBAL dataset, identical on every member (few-valued integer
    features: every contiguous slice sees the full value set, so the
    locally-built bin mappers are bit-identical at any world)."""
    rng = np.random.default_rng(42)
    F = 10
    X = rng.integers(0, 5, size=(n, F)).astype(np.float32)
    w = rng.standard_normal(F)
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-((X - 2.0) @ w * 0.35)))
         ).astype(np.float32)
    return X, y


X, y = make_data(N)

rt = membership.MembershipRuntime(
    fleet_dir, None if member_arg == "join" else int(member_arg))
rt.row_provider = lambda lo, hi: (X[lo:hi], y[lo:hi])

signal.signal(signal.SIGTERM, lambda *_a: rt.request_leave())

if member_arg == "join":
    rt.join()
else:
    nproc = int(os.environ["MEMBER_NPROC"])
    counts = [(r + 1) * N // nproc - r * N // nproc for r in range(nproc)]
    rt.bootstrap(nproc, counts)

mid = rt.id


def _write(payload: dict) -> None:
    with open(out + f".m{mid}.json", "w") as fh:
        json.dump(payload, fh)


lo, hi = ShardPlan.from_counts(rt.counts).rank_range(rt.rank)
membership.set_runtime(rt)

p = dict(objective="binary", tree_learner="data", pre_partition=True,
         elastic_membership=True, num_leaves=LEAVES, learning_rate=0.2,
         max_bin=31, min_data_in_leaf=20, boost_from_average=False,
         quantized_training=QUANTIZED, seed=7, verbose=-1)
if REBALANCE:
    p.update(rebalance=True, rebalance_threshold=1.5, rebalance_patience=3,
             rebalance_max_move_frac=0.25)
ds = lgb.Dataset(X[lo:hi], label=y[lo:hi], params=dict(p))

epochs_seen = []

try:
    # explicit loop on current_iteration(): a mid-run joiner restores at
    # the fleet's iteration and must train only the REMAINING rounds
    # (lgb.train's range(start, rounds) loop has no notion of that)
    booster = lgb.Booster(params=dict(p), train_set=ds)
    while booster.current_iteration() < TREES:
        booster.update()
        it = booster.current_iteration() - 1
        epochs_seen.append(rt.epoch)
        if PROGRESS:
            # write-once fleet-wide iteration record for the spot cost
            # ledger (factory/spot.py): the FIRST member to finish the
            # iteration claims its slot, so a redone iteration cannot
            # re-claim it and zero_lost_iterations() stays provable
            rt.client.try_create(
                f"progress/{it}",
                json.dumps({"epoch": rt.epoch, "member": mid}).encode())
            # per-attempt record: epoch-keyed, so the SAME member
            # completing the SAME iteration twice (a redo — resizes
            # always bump the epoch) leaves two keys the ledger can see;
            # this is what upgrades "no iteration lost" to "none redone"
            rt.client.try_create(f"attempts/{it}.m{mid}.e{rt.epoch}", b"1")
        if LEAVE_ITER >= 0 and it >= LEAVE_ITER:
            rt.request_leave()
        if SIGTERM_ITER >= 0 and it >= SIGTERM_ITER:
            os.kill(os.getpid(), signal.SIGTERM)
        if KILL_ITER >= 0 and it >= KILL_ITER:
            os.kill(os.getpid(), signal.SIGKILL)
        if ITER_SLEEP > 0:
            time.sleep(ITER_SLEEP)
except membership.CleanLeave as e:
    rt.stop()
    _write({"error": None, "left_at_epoch": e.epoch, "member": mid,
            "epochs_seen": epochs_seen})
    print(f"member {mid} left cleanly at epoch {e.epoch}")
    sys.exit(0)
except net.PeerFailureError as e:
    rt.stop()
    _write({"error": "PeerFailureError", "ranks": list(e.ranks),
            "member": mid, "epochs_seen": epochs_seen})
    print(f"member {mid} unrecoverable peer failure: {e}")
    net.hard_exit(EXIT_PEER_FAILURE)

rt.stop()
with open(out + f".m{mid}.txt", "w") as fh:
    fh.write(booster.model_to_string())
b = booster.boosting
_write({
    "error": None,
    "member": mid,
    "trees": booster.num_trees,
    "iters": booster.current_iteration(),
    "final_epoch": rt.epoch,
    "final_members": list(rt.members),
    "final_counts": list(rt.counts),
    "rows_end": int(b.num_data),
    "epochs_seen": epochs_seen,
    "resize_pauses": [round(s, 4) for s in
                      getattr(b, "_membership_pauses", [])],
})
print(f"member {mid} train done (epoch={rt.epoch}, members={list(rt.members)})")
sys.exit(0)
