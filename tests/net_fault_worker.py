"""Worker for the real-subprocess fault matrix (test_net_fault.py).

argv: ``rank nproc port out mode [ckdir]``.  The harness sets
``LIGHTGBM_TPU_NET_TIMEOUT`` (the deadline under test) for every rank
and ``LIGHTGBM_TPU_FAULT`` only in the target rank's environment.

modes:
  gather   — loop ``allgather_bytes``; the faulted rank dies (SIGKILL
             itself) or wedges at call N; every survivor records the
             typed error + elapsed time and leaves via ``net.hard_exit``
  barrier  — the same loop over ``collect.barrier``
  init     — bounded-bootstrap probe: the coordinator address never
             answers; the watchdogged ``jax.distributed.initialize``
             must fail loudly within the retry budget instead of
             hanging (the unreachable-coordinator class)
  train    — both ranks train the SAME data with a shared
             ``CheckpointManager`` (the multihost ckpt barrier is the
             collective under test); used for the kill -> detect ->
             flush -> auto-resume acceptance proof.  Survivors of a
             peer failure exit with code 75 (cli.EXIT_PEER_FAILURE).
             With ``LIGHTGBM_TPU_TRACE`` set, the survivor's typed
             failure additionally flushes the crash flight recorder
             (obs/flight.py) — the ``report merge``/crash-dump
             acceptance legs ride this mode.
  wfeature / wvoting — full lgb.train over the host-driven
             feature-parallel / voting-parallel learner
             (parallel/hostlearner.py); the faulted rank dies mid-
             collective and every survivor must classify a typed
             PeerFailureError within the bound and leave with exit
             code 75 — the wide learners share the hardened
             transport's failure semantics unchanged.
  mergetrace — clean 2-rank "training" loop (compute span + hardened
             barrier per iteration, KV transport) with per-rank traces;
             MERGETRACE_COMPUTE_S skews one rank into a straggler so
             the test can assert ``report merge`` attribution.
"""

import json
import os
import sys
import time

rank = int(sys.argv[1])
nproc = int(sys.argv[2])
port = sys.argv[3]
out = sys.argv[4]
mode = sys.argv[5]

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["LIGHTGBM_TPU_COORDINATOR"] = f"127.0.0.1:{port}"
os.environ["LIGHTGBM_TPU_NUM_PROCESSES"] = str(nproc)
os.environ["LIGHTGBM_TPU_PROCESS_ID"] = str(rank)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lightgbm_tpu.parallel import net  # noqa: E402
from lightgbm_tpu.parallel.distributed import ensure_initialized  # noqa: E402

DEADLINE = float(os.environ.get("LIGHTGBM_TPU_NET_TIMEOUT", "4"))


def _write(payload: dict) -> None:
    with open(out + f".rank{rank}.json", "w") as fh:
        json.dump(payload, fh)


if mode == "init":
    # nothing listens on the coordinator port: the bootstrap must fail
    # LOUDLY and bounded, not hang
    t0 = time.time()
    try:
        ensure_initialized()
        print("UNEXPECTED: bootstrap succeeded")
        sys.exit(2)
    except net.CollectiveTimeoutError as e:
        _write({"error": "CollectiveTimeoutError",
                "wall": time.time() - t0, "msg": str(e)})
        sys.exit(0)

assert ensure_initialized() is True
import jax  # noqa: E402

assert jax.process_count() == nproc

from lightgbm_tpu.parallel import collect  # noqa: E402

if mode == "mergetrace":
    # clean run: per-iteration compute (skewed per rank via
    # MERGETRACE_COMPUTE_S) + the hardened KV barrier, traced per rank —
    # the `report merge` straggler-attribution acceptance leg
    from lightgbm_tpu.obs import tracer

    tracer.refresh_from_env()  # LIGHTGBM_TPU_TRACE + rank/world identity
    assert tracer.enabled, "mergetrace mode needs LIGHTGBM_TPU_TRACE"
    compute_s = float(os.environ.get("MERGETRACE_COMPUTE_S", "0.02"))
    for i in range(4):
        with tracer.iteration(i):
            with tracer.span("histogram"):
                time.sleep(compute_s)
            collect.barrier(tag=f"it{i}")
    tracer.close()
    _write({"error": None, "iters": 4})
    print(f"rank {rank} mergetrace done")
    sys.exit(0)

if mode in ("gather", "barrier"):
    t_enter = time.time()
    try:
        for i in range(5):
            t_enter = time.time()
            if mode == "barrier":
                collect.barrier(tag=f"iter{i}")
            else:
                blobs = collect.allgather_bytes(f"r{rank}i{i}".encode())
                assert len(blobs) == nproc
        print(f"rank {rank} UNEXPECTED: all collectives completed")
        _write({"error": None})
        sys.exit(2)
    except net.PeerFailureError as e:
        _write({"error": "PeerFailureError", "ranks": list(e.ranks),
                "elapsed": e.elapsed_s, "wall": time.time() - t_enter})
    except net.CollectiveTimeoutError as e:
        _write({"error": "CollectiveTimeoutError",
                "elapsed": e.elapsed_s, "wall": time.time() - t_enter})
    print(f"rank {rank} {mode} recorded failure; hard exit")
    net.hard_exit(0)  # the atexit shutdown barrier would hang on the corpse

if mode in ("wfeature", "wvoting"):
    # wide-data learners ride the same hardened collect.allgather_bytes
    # path, so die:N lands inside a histogram/best-split/vote exchange
    import numpy as np

    import lightgbm_tpu as lgb
    from lightgbm_tpu.cli import EXIT_PEER_FAILURE

    learner = "feature" if mode == "wfeature" else "voting"
    rng = np.random.default_rng(13)
    N, F = 1200, 20
    X = rng.integers(0, 12, size=(N, F)).astype(np.float32)
    w = rng.standard_normal(F)
    y = (rng.random(N) < 1.0 / (1.0 + np.exp(-((X - 6) @ w * 0.2)))
         ).astype(np.float32)
    p = dict(objective="binary", tree_learner=learner, num_machines=nproc,
             boost_from_average=False, num_leaves=15, min_data_in_leaf=20,
             top_k=4, verbose=-1)
    if learner == "voting":
        p["pre_partition"] = True
        sl = slice(rank * N // nproc, (rank + 1) * N // nproc)
        ds = lgb.Dataset(X[sl], label=y[sl], params=dict(p))
    else:
        ds = lgb.Dataset(X, label=y, params=dict(p))
    t0 = time.time()
    try:
        bst = lgb.train(dict(p), ds, 10, verbose_eval=False)
    except net.PeerFailureError as e:
        _write({"error": "PeerFailureError", "ranks": list(e.ranks),
                "elapsed": e.elapsed_s, "wall": time.time() - t0})
        print(f"rank {rank} {mode}: peer failure after {e.elapsed_s:.1f}s")
        net.hard_exit(EXIT_PEER_FAILURE)
    _write({"error": None, "trees": bst.num_trees})
    print(f"rank {rank} {mode} UNEXPECTED clean finish")
    sys.exit(2)

if mode == "train":
    # acceptance leg (ISSUE 5): each rank trains the SAME data locally
    # (no multi-process XLA — this environment's CPU backend rejects
    # it); the ONLY collective is the multihost checkpoint barrier, so a
    # rank SIGKILLed by die:N dies exactly mid-barrier.
    import numpy as np

    import lightgbm_tpu as lgb
    from lightgbm_tpu.ckpt import CheckpointManager
    from lightgbm_tpu.ckpt.store import CheckpointStore
    from lightgbm_tpu.cli import EXIT_PEER_FAILURE

    ckdir = sys.argv[6]
    rng = np.random.default_rng(7)
    N, F = 900, 8
    X = rng.standard_normal((N, F)).astype(np.float32)
    w = rng.standard_normal(F)
    y = (rng.random(N) < 1.0 / (1.0 + np.exp(-(X @ w)))).astype(np.float32)
    p = dict(objective="binary", num_leaves=15, learning_rate=0.2,
             min_data_in_leaf=20, verbose=-1)

    latest = CheckpointStore(ckdir).latest_valid()
    resume_from = latest[0] if latest is not None else None

    mgr = CheckpointManager(ckdir, freq=3)
    try:
        bst = lgb.train(dict(p), lgb.Dataset(X, label=y, params=dict(p)),
                        12, verbose_eval=False, checkpoint_manager=mgr)
    except net.PeerFailureError as e:
        mgr.flush()
        _write({"error": "PeerFailureError", "ranks": list(e.ranks),
                "elapsed": e.elapsed_s, "resume_from": resume_from})
        print(f"rank {rank} detected peer failure after {e.elapsed_s:.1f}s")
        net.hard_exit(EXIT_PEER_FAILURE)
    mgr.close()
    with open(out + f".rank{rank}.txt", "w") as fh:
        fh.write(bst.model_to_string())
    _write({"error": None, "trees": bst.num_trees,
            "resume_from": resume_from})
    print(f"rank {rank} train done (resume_from={resume_from})")
    sys.exit(0)  # clean exit: every rank alive, shutdown barrier passes

print(f"unknown mode {mode}")
sys.exit(2)
