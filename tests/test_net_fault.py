"""Real-subprocess fault-injection matrix for the hardened transport
(parallel/net.py, docs/ROBUSTNESS.md).

Acceptance contract (ISSUE 5): a SIGKILLed peer mid-collective is
detected by EVERY survivor as a typed ``PeerFailureError`` within ~2x
the configured deadline (no indefinite hang), survivors leave through
the checkpoint-flush path with the retryable exit code, and rerunning
the job auto-resumes to a byte-identical final model.

Tier-1 runs the smoke legs (3-rank SIGKILL mid-allgather, the bounded
bootstrap probe, and the kill -> flush -> resume training proof); the
wider matrix (mid-barrier kill, wedged-peer timeout, coordinator death)
is marked ``slow``.  Faults are injected via ``LIGHTGBM_TPU_FAULT`` in
the target rank's environment only (die:N = SIGKILL self at the Nth
collective; drop_collective:N = wedge while heartbeats keep beating).
"""

import json
import os
import signal
import socket
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "net_fault_worker.py")
DEADLINE = 4.0
# detection bound under test: wait window + staleness window (~2x the
# deadline) plus scheduling slack for a loaded CI box
DETECT_BOUND = 2 * DEADLINE + 1.5


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def _spawn(rank, nproc, port, out, mode, extra_env=None, args=()):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS", "LIGHTGBM_TPU_FAULT",
                        "LIGHTGBM_TPU_FAULT_RANK")}
    env["LIGHTGBM_TPU_NET_TIMEOUT"] = str(DEADLINE)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.update(extra_env or {})
    return subprocess.Popen(
        [sys.executable, WORKER, str(rank), str(nproc), str(port), out,
         mode, *map(str, args)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, env=env,
    )


def _result(out, rank):
    with open(out + f".rank{rank}.json") as fh:
        return json.load(fh)


def _assert_terminated_by_xla_poller(rc, log):
    """The survivor was stopped from C++ by XLA's coordination-service error
    poller, not by an exception of ours: SIGABRT, or exit code 1 (jaxlib
    0.9.0 ends the process with a quiet fatal log, which is `_exit(1)`), and
    in either case the poller's own line is in the log."""
    assert rc in (-signal.SIGABRT, 1), log[-2000:]
    lines = (("JAX distributed service detected fatal errors",) if rc == 1
             else ("another task died", "UNAVAILABLE"))
    assert any(line in log for line in lines), log[-2000:]


# ----------------------------------------------------------------------
# tier-1 smoke legs
# ----------------------------------------------------------------------
@pytest.mark.faultinject
@pytest.mark.netfault
def test_sigkill_mid_allgather_detected_by_all_survivors(tmp_path):
    """Rank 2 of 3 SIGKILLs itself entering the 3rd allgather; BOTH
    survivors must stop PROMPTLY — neither may hang.  Per survivor the
    same two legitimate outcomes as coordinator death
    (docs/ROBUSTNESS.md): our sweeper classifies a typed
    PeerFailureError naming rank 2 within the detection bound, or XLA's
    in-process error poller wins the race and fail-fast terminates the
    survivor from C++ (SIGABRT or exit 1, "another task died") — that
    poller is not interceptable from Python and outruns the sweeper on a
    loaded box: rank 0 hosts the coordination service, so once it has
    classified the failure and left, rank 1's poller sees the service
    gone (5 runs of 10 beside seven busy processes, PR 31)."""
    import time

    out = str(tmp_path / "g")
    port = _free_port()
    procs = [
        _spawn(r, 3, port, out, "gather",
               extra_env={"LIGHTGBM_TPU_FAULT": "die:3"} if r == 2 else None)
        for r in range(3)
    ]
    t0 = time.monotonic()
    logs = [p.communicate(timeout=240)[0] for p in procs]
    wall = time.monotonic() - t0
    assert procs[2].returncode == -signal.SIGKILL, logs[2][-2000:]
    typed = 0
    for r in (0, 1):
        rc = procs[r].returncode
        if rc == 0:  # sweeper classified before XLA's poller fired
            res = _result(out, r)
            assert res["error"] == "PeerFailureError", res
            assert 2 in res["ranks"], res
            assert res["wall"] <= DETECT_BOUND, res
            typed += 1
        else:  # XLA's fail-fast poller stopped the survivor from C++
            _assert_terminated_by_xla_poller(rc, logs[r])
    # the whole point: nobody hangs on the dead peer
    assert wall <= DETECT_BOUND + 30.0


@pytest.mark.faultinject
@pytest.mark.netfault
def test_bootstrap_timeout_is_loud_and_bounded(tmp_path):
    """Nothing listens at the coordinator address: the watchdogged initialize must raise a typed timeout
    within the retry budget instead of hanging forever."""
    out = str(tmp_path / "i")
    port = _free_port()  # bound+closed: nothing will ever listen
    p = _spawn(1, 2, port, out, "init",
               extra_env={"LIGHTGBM_TPU_NET_RETRIES": "0"})
    log = p.communicate(timeout=180)[0]
    assert p.returncode == 0, log[-2000:]
    res = _result(out, 1)
    assert res["error"] == "CollectiveTimeoutError", res
    # one attempt bounded by the RPC timeout plus the watchdog budget
    assert res["wall"] <= 3 * DEADLINE + 3.0, res


@pytest.mark.faultinject
@pytest.mark.netfault
def test_sigkill_mid_ckpt_barrier_flush_exit_and_bitidentical_resume(tmp_path):
    """The ISSUE-5 acceptance proof, on real subprocesses:

    1. reference: 2 ranks train to completion through the multihost
       checkpoint barrier — models byte-identical across ranks;
    2. kill: rank 1 SIGKILLs itself entering the 2nd checkpoint barrier
       (iteration 6); rank 0 detects PeerFailureError within the bound,
       flushes, and exits with the retryable code 75;
    3. resume: rerunning both ranks auto-resumes from the surviving
       iteration-3 checkpoint and the final model is byte-identical to
       the uninterrupted reference."""
    def run_pair(tag, ckdir, fault_rank=None):
        out = str(tmp_path / tag)
        port = _free_port()
        procs = []
        for r in range(2):
            # per-rank run trace: the survivor's typed failure must
            # flush the crash flight recorder next to it
            extra = {"LIGHTGBM_TPU_TRACE": out + f".rank{r}.trace.jsonl"}
            if r == fault_rank:
                extra["LIGHTGBM_TPU_FAULT"] = "die:2"
            procs.append(_spawn(r, 2, port, out, "train", args=(ckdir,),
                                extra_env=extra))
        logs = [p.communicate(timeout=420)[0] for p in procs]
        return out, procs, logs

    ck_ref = str(tmp_path / "ck_ref")
    ck = str(tmp_path / "ck")

    out_ref, procs, logs = run_pair("ref", ck_ref)
    assert all(p.returncode == 0 for p in procs), "\n".join(logs)
    with open(out_ref + ".rank0.txt") as fh:
        ref_model = fh.read()
    with open(out_ref + ".rank1.txt") as fh:
        assert fh.read() == ref_model
    assert _result(out_ref, 0)["resume_from"] is None

    assert not os.path.exists(out_ref + ".rank0.trace.crash.jsonl"), \
        "clean run must not leave a crash dump"

    out_k, procs, logs = run_pair("kill", ck, fault_rank=1)
    assert procs[1].returncode == -signal.SIGKILL, logs[1][-2000:]
    assert procs[0].returncode == 75, logs[0][-2000:]  # EXIT_PEER_FAILURE
    res = _result(out_k, 0)
    assert res["error"] == "PeerFailureError" and res["ranks"] == [1], res
    assert res["elapsed"] <= DETECT_BOUND, res
    assert not os.path.exists(out_k + ".rank0.txt"), \
        "killed run must not have produced a model"
    # crash flight recorder (ISSUE 7 acceptance): the survivor's typed
    # failure left a flushed .crash.jsonl containing the final spans
    # before the failure and the net.peer_failure event
    crash = out_k + ".rank0.trace.crash.jsonl"
    assert os.path.exists(crash), \
        "survivor left no flight-recorder dump"
    recs = [json.loads(l) for l in open(crash) if l.strip()]
    assert recs[0]["kind"] == "flight", recs[0]
    assert recs[0]["reason"] == "peer_failure", recs[0]
    assert recs[0]["rank"] == 0 and recs[0]["world"] == 2, recs[0]
    assert any(r.get("ev") == "span" for r in recs[1:]), \
        "crash dump carries no spans"
    assert any(r.get("ev") == "event"
               and r.get("name") == "net.peer_failure"
               and 1 in r.get("ranks", []) for r in recs[1:]), \
        "crash dump missing the net.peer_failure event"

    out_r, procs, logs = run_pair("resume", ck)
    assert all(p.returncode == 0 for p in procs), "\n".join(logs)
    for r in (0, 1):
        res = _result(out_r, r)
        assert res["resume_from"] == 3, res  # iter-3 ckpt survived the kill
        with open(out_r + f".rank{r}.txt") as fh:
            assert fh.read() == ref_model, f"rank {r} diverged after resume"


@pytest.mark.netfault
def test_report_merge_attributes_straggler_on_real_2rank_run(tmp_path):
    """ISSUE 7 acceptance: `report merge` over a REAL 2-rank run
    (subprocess pair, KV transport) produces a per-rank per-phase
    timeline and names the straggler rank with barrier-wait
    attribution.  Rank 1's per-iteration compute is ~6x rank 0's, so
    rank 0 parks in the hardened barrier behind it."""
    out = str(tmp_path / "m")
    port = _free_port()
    procs = [
        _spawn(r, 2, port, out, "mergetrace",
               extra_env={
                   "LIGHTGBM_TPU_TRACE": out + f".rank{r}.trace.jsonl",
                   "MERGETRACE_COMPUTE_S": "0.3" if r == 1 else "0.05",
               })
        for r in range(2)
    ]
    logs = [p.communicate(timeout=240)[0] for p in procs]
    assert all(p.returncode == 0 for p in procs), "\n".join(
        l[-2000:] for l in logs)
    assert all(_result(out, r)["error"] is None for r in (0, 1))

    from lightgbm_tpu.obs import report

    paths = [out + f".rank{r}.trace.jsonl" for r in (0, 1)]
    by_rank = report.load_rank_traces(paths)
    assert set(by_rank) == {0, 1}, "rank identity missing from records"
    m = report.merge_summary(by_rank)
    assert m["aligned_iterations"] == 4
    assert m["world_size"] == 2
    assert m["run_id"], "run_id missing (coordinator address fallback)"
    # straggler attribution: rank 1 computes, rank 0 waits
    st = m["straggler"]
    assert st["rank"] == 1, m
    assert st["slowest_rank_share"] > 0.5, m
    assert st["wait_behind_straggler_s"] > 0, m
    assert (m["per_rank"][0]["barrier_wait_s"]
            > m["per_rank"][1]["barrier_wait_s"]), m
    # per-phase per-rank timeline: the compute phase and the barrier
    # phase are both attributed per rank
    assert "histogram" in m["phases"] and "net.barrier" in m["phases"], m
    assert m["phases"]["histogram"][1] > m["phases"]["histogram"][0], m
    rendered = report.render_merge(m)
    assert "straggler: rank 1" in rendered
    assert "barrier wait" in rendered


# ----------------------------------------------------------------------
# wider matrix (slow)
# ----------------------------------------------------------------------
@pytest.mark.slow
@pytest.mark.faultinject
@pytest.mark.netfault
@pytest.mark.parametrize("mode", ["wfeature", "wvoting"])
def test_sigkill_during_wide_learner_training(tmp_path, mode):
    """The feature-parallel and voting-parallel learners inherit the
    hardened transport's failure semantics unchanged: SIGKILL one rank
    mid-training and the survivor classifies a typed PeerFailureError
    naming the corpse within the detection bound, then leaves with the
    retryable exit code 75 (docs/ROBUSTNESS.md)."""
    out = str(tmp_path / mode)
    port = _free_port()
    procs = [
        _spawn(r, 2, port, out, mode,
               extra_env={"LIGHTGBM_TPU_FAULT": "die:6"} if r == 1 else None)
        for r in range(2)
    ]
    logs = [p.communicate(timeout=240)[0] for p in procs]
    assert procs[1].returncode == -signal.SIGKILL, logs[1][-2000:]
    assert procs[0].returncode == 75, logs[0][-2000:]  # EXIT_PEER_FAILURE
    res = _result(out, 0)
    assert res["error"] == "PeerFailureError" and res["ranks"] == [1], res
    assert res["elapsed"] <= DETECT_BOUND, res


@pytest.mark.slow
@pytest.mark.faultinject
@pytest.mark.netfault
def test_sigkill_mid_barrier(tmp_path):
    """Same detection contract when the collective is a bare barrier."""
    out = str(tmp_path / "b")
    port = _free_port()
    procs = [
        _spawn(r, 2, port, out, "barrier",
               extra_env={"LIGHTGBM_TPU_FAULT": "die:3"} if r == 1 else None)
        for r in range(2)
    ]
    logs = [p.communicate(timeout=240)[0] for p in procs]
    assert procs[1].returncode == -signal.SIGKILL, logs[1][-2000:]
    assert procs[0].returncode == 0, logs[0][-2000:]
    res = _result(out, 0)
    assert res["error"] == "PeerFailureError" and res["ranks"] == [1], res
    assert res["wall"] <= DETECT_BOUND, res


@pytest.mark.slow
@pytest.mark.faultinject
@pytest.mark.netfault
def test_wedged_peer_is_timeout_not_peer_failure(tmp_path):
    """drop_collective wedges rank 1 while its heartbeat keeps beating:
    the survivor must classify a *lost collective with a live peer* as
    CollectiveTimeoutError, bounded by the budget."""
    out = str(tmp_path / "d")
    port = _free_port()
    procs = [
        _spawn(r, 2, port, out, "gather",
               extra_env={"LIGHTGBM_TPU_FAULT": "drop_collective:3"}
               if r == 1 else None)
        for r in range(2)
    ]
    log0 = procs[0].communicate(timeout=240)[0]
    procs[1].kill()  # the wedged rank sleeps forever by design
    procs[1].communicate()
    assert procs[0].returncode == 0, log0[-2000:]
    res = _result(out, 0)
    assert res["error"] == "CollectiveTimeoutError", res
    assert res["wall"] <= DETECT_BOUND, res


@pytest.mark.slow
@pytest.mark.faultinject
@pytest.mark.netfault
def test_coordinator_death_is_bounded_not_a_hang(tmp_path):
    """Killing rank 0 — the process hosting the coordination service —
    must stop the survivor PROMPTLY.  Two legitimate outcomes
    (docs/ROBUSTNESS.md): our sweeper classifies PeerFailureError and
    exits 0 through the flush path, or XLA's in-process error poller
    wins the race and fail-fast terminates the survivor from C++ (SIGABRT
    or exit 1).
    Either way nothing hangs, and the atomic checkpoint store means the
    last durable checkpoint survives for auto-resume."""
    import time

    out = str(tmp_path / "c")
    port = _free_port()
    procs = [
        _spawn(r, 2, port, out, "gather",
               extra_env={"LIGHTGBM_TPU_FAULT": "die:3"} if r == 0 else None)
        for r in range(2)
    ]
    t0 = time.monotonic()
    logs = [p.communicate(timeout=240)[0] for p in procs]
    wall = time.monotonic() - t0
    assert procs[0].returncode == -signal.SIGKILL, logs[0][-2000:]
    rc1 = procs[1].returncode
    if rc1 == 0:  # our sweeper classified before XLA's poller fired
        res = _result(out, 1)
        assert res["error"] == "PeerFailureError", res
        assert res["wall"] <= DETECT_BOUND, res
    else:  # XLA's fail-fast poller stopped the survivor from C++
        _assert_terminated_by_xla_poller(rc1, logs[1])
    # the whole point: no indefinite hang on a dead coordinator
    assert wall <= DETECT_BOUND + 30.0


# ----------------------------------------------------------------------
# elastic counterpart (ISSUE 19): armed membership re-elects, the
# default keeps every fail-fast contract above byte-for-byte
# ----------------------------------------------------------------------
@pytest.mark.netfault
@pytest.mark.membership
def test_membership_armed_reelects_deterministically(tmp_path):
    """The elastic counterpart to
    test_coordinator_death_is_bounded_not_a_hang: with a membership
    runtime armed, the coordinator's death is NOT a job-fatal transport
    error.  The survivors converge on the identical eviction decision
    and the new coordinator is DETERMINISTIC — the lowest surviving
    member id, by construction rather than by vote — so any two runs of
    the same churn re-elect the same member.  The default
    (``elastic_membership=false``, every other test in this file) keeps
    the bounded fail-fast semantics unchanged."""
    import threading

    from lightgbm_tpu.config import Config
    from lightgbm_tpu.parallel.membership import MembershipRuntime

    # the knob defaults OFF: nothing in this file runs elastic code
    assert Config().elastic_membership is False

    rts = [MembershipRuntime(str(tmp_path), m) for m in range(3)]
    threads = [threading.Thread(target=rt.bootstrap,
                                args=(3, (200, 200, 200))) for rt in rts]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
        assert not t.is_alive()
    try:
        rts[0].stop()  # the coordinator freezes — SIGKILL equivalent
        decisions = [None, None]
        ts = [threading.Thread(target=lambda i=i: decisions.__setitem__(
            i - 1, rts[i].sync(known_dead=(0,)))) for i in (1, 2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
            assert not t.is_alive()
        # both survivors derived the IDENTICAL decision independently
        for d in decisions:
            assert d is not None
            assert d.dead == (0,) and d.new_members == (1, 2)
        for rt, d in zip(rts[1:], decisions):
            rt.commit_epoch(d, (300, 300), iteration=3, num_data=600)
        # re-election is positional: lowest surviving id — member 1
        assert rts[1].is_coordinator and not rts[2].is_coordinator
        assert min(rts[1].members) == 1
        assert rts[1].rank == 0 and rts[2].rank == 1
    finally:
        for rt in rts[1:]:
            rt.stop()
