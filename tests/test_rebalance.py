"""Straggler-aware shard rebalancing (parallel/shardplan.py,
docs/ROBUSTNESS.md).

Unit legs pin the pure controller policy — EWMA trigger at exactly
``rebalance_patience``, the ``rebalance_max_move_frac`` clamp,
heartbeat-staleness suppression, largest-remainder conservation — which
must be deterministic because every rank runs it independently on the
identical allgathered table and the plans have to agree.

The integration leg is a REAL 2-rank subprocess run with an injected
per-collective delay on rank 0 (``delay:ms:after:N`` +
``LIGHTGBM_TPU_FAULT_RANK``): the controller must fire, move rows off
the slow rank through the canonical gather/reshard exchange, keep the
data-parallel ranks bit-identical, and leave ``rebalance.plan`` events
that ``report merge`` renders with the rows-owned / barrier-wait-share
trend (docs/OBSERVABILITY.md).
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from lightgbm_tpu.parallel.shardplan import (RebalanceController, ShardPlan,
                                             _apply_floor, _largest_remainder)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EWORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "elastic_worker.py")


# ----------------------------------------------------------------------
# ShardPlan
# ----------------------------------------------------------------------
def test_shard_plan_ranges():
    p = ShardPlan.from_counts([300, 500, 200])
    assert p.world == 3 and p.total == 1000
    assert p.starts == (0, 300, 800)
    assert p.rank_range(0) == (0, 300)
    assert p.rank_range(1) == (300, 800)
    assert p.rank_range(2) == (800, 1000)


def test_shard_plan_rejects_bad_counts():
    with pytest.raises(ValueError):
        ShardPlan(())
    with pytest.raises(ValueError):
        ShardPlan((100, -1))


def test_largest_remainder_conserves_total():
    for shares, total in [([333.4, 333.3, 333.3], 1000),
                          ([0.5, 0.5], 7), ([10.9, 0.1], 11)]:
        out = _largest_remainder(shares, total)
        assert sum(out) == total
        assert all(c >= 0 for c in out)


def test_apply_floor_takes_from_largest():
    out = _apply_floor([0, 990, 10], 32, 1000)
    assert sum(out) == 1000
    assert all(c >= 32 for c in out)
    assert out[1] == max(out)


# ----------------------------------------------------------------------
# RebalanceController policy
# ----------------------------------------------------------------------
def _steady(ctl, plan, compute, n):
    fired = []
    for _ in range(n):
        fired.append(ctl.observe(plan, compute))
    return fired


def test_controller_fires_at_exactly_patience():
    ctl = RebalanceController(threshold=1.5, patience=3, max_move_frac=0.25)
    plan = ShardPlan.from_counts([600, 600])
    fired = _steady(ctl, plan, [4.0, 1.0], 5)
    assert fired[0] is None and fired[1] is None  # hot=1, hot=2
    assert fired[2] is not None                   # hot=3 == patience
    new = fired[2]
    assert new.total == 1200 and new.world == 2
    assert new.counts[0] < 600 < new.counts[1]
    # max_move_frac=0.25 bounds the displaced rows to 300
    assert 600 - new.counts[0] <= 300


def test_controller_quiet_fleet_never_fires():
    ctl = RebalanceController(threshold=1.5, patience=3, max_move_frac=0.25)
    plan = ShardPlan.from_counts([512, 512])
    assert all(f is None for f in _steady(ctl, plan, [1.0, 1.1], 10))


def test_controller_transient_spike_resets_patience():
    ctl = RebalanceController(threshold=1.5, patience=3, max_move_frac=0.25)
    plan = ShardPlan.from_counts([512, 512])
    assert ctl.observe(plan, [4.0, 1.0]) is None   # hot=1
    # one-iteration blip (GC pause, page-cache miss) clears: the EWMA
    # decays back under threshold before patience is reached and the
    # hot counter resets — no rows move for transients
    for _ in range(8):
        assert ctl.observe(plan, [1.0, 1.0]) is None


def test_controller_stale_heartbeat_suppresses_move():
    ctl = RebalanceController(threshold=1.5, patience=3, max_move_frac=0.25,
                              stale_s=10.0)
    plan = ShardPlan.from_counts([600, 600])
    for _ in range(6):
        # persistent straggler, but a peer heartbeat is stale: the rank
        # may be dying, not merely slow — never move rows while the
        # failure detector might fire
        assert ctl.observe(plan, [4.0, 1.0], hb_ages=[0.1, 20.0]) is None


def test_controller_deterministic_across_replicas():
    """Two controllers fed the identical table must emit the identical
    plan — ranks never exchange plans, only measurements."""
    plans = []
    for _ in range(2):
        ctl = RebalanceController(threshold=1.5, patience=3,
                                  max_move_frac=0.25)
        plan = ShardPlan.from_counts([700, 500, 600])
        out = _steady(ctl, plan, [3.0, 1.0, 1.2], 6)
        plans.append([p.counts for p in out if p is not None])
    assert plans[0] == plans[1] and plans[0]


def test_rebalance_off_by_default_and_single_process_skips():
    """rebalance=False is the default (exact pre-PR behavior: the
    controller never runs, zero extra collectives); arming it on a
    single-process run downgrades to a warning skip."""
    import lightgbm_tpu as lgb

    rng = np.random.RandomState(3)
    X = rng.randint(0, 8, size=(400, 5)).astype(np.float32)
    y = (X[:, 0] > 3).astype(np.float32)
    p = dict(objective="binary", num_leaves=7, min_data_in_leaf=20,
             verbose=-1)
    bst = lgb.train(dict(p), lgb.Dataset(X, label=y, params=dict(p)), 3,
                    verbose_eval=False)
    assert getattr(bst.boosting, "_rebalance", None) is None
    p2 = dict(p, rebalance=True)
    bst2 = lgb.train(dict(p2), lgb.Dataset(X, label=y, params=dict(p2)), 3,
                     verbose_eval=False)
    assert getattr(bst2.boosting, "_rebalance", None) is None
    assert bst2.num_trees == 3


# ----------------------------------------------------------------------
# integration: real 2-rank run, injected straggler, rebalance ON
# ----------------------------------------------------------------------
def _free_port():
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


@pytest.mark.faultinject
@pytest.mark.netfault
def test_rebalance_moves_rows_off_injected_straggler(tmp_path):
    """Rank 0 of 2 sleeps 40 ms at every hardened collective from the
    5th on (the new ``delay:ms:after:N`` form, scaled by the rank's
    row-count ratio).  The controller must detect the persistent
    straggler, shift rows to rank 1 at an iteration boundary, finish
    training with both ranks bit-identical, and leave ``rebalance.plan``
    trace events that ``report merge`` summarizes.

    The delay is 40 ms (not the historical 10 ms) so the injected
    straggle dominates scheduler noise on a loaded CI machine — at
    10 ms, OS jitter occasionally swamped the EWMA signal and the
    controller (correctly) never fired, flaking the assertion that
    rows moved."""
    out = str(tmp_path / "rb")
    port = _free_port()
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS", "LIGHTGBM_TPU_FAULT",
                        "LIGHTGBM_TPU_FAULT_RANK", "LIGHTGBM_TPU_TRACE")}
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.update(ELASTIC_ROWS="512", ELASTIC_TREES="12", ELASTIC_FREQ="6",
               ELASTIC_REBALANCE="1",
               LIGHTGBM_TPU_FAULT="delay:40:after:5",
               LIGHTGBM_TPU_FAULT_RANK="0")
    procs = []
    for r in range(2):
        renv = dict(env)
        renv["LIGHTGBM_TPU_TRACE"] = out + f".rank{r}.trace.jsonl"
        procs.append(subprocess.Popen(
            [sys.executable, EWORKER, str(r), "2", str(port), out, "train",
             str(tmp_path / "ck")],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=renv))
    logs = [p.communicate(timeout=420)[0] for p in procs]
    assert all(p.returncode == 0 for p in procs), "\n".join(
        l[-2000:] for l in logs)

    res = [json.load(open(out + f".rank{r}.json")) for r in range(2)]
    counts = res[0]["final_counts"]
    assert counts == res[1]["final_counts"], res
    assert counts is not None and sum(counts) == 512, res
    # rows moved OFF the slow rank
    assert counts[0] < 256 < counts[1], res
    assert res[0]["rows_end"] == counts[0], res
    assert res[1]["rows_end"] == counts[1], res
    # data-parallel ranks stay bit-identical through the move
    models = [open(out + f".rank{r}.txt").read() for r in range(2)]
    assert models[0] == models[1], "ranks diverged after rebalance"

    # report merge (satellite: obs/report.py) — the rebalance section
    from lightgbm_tpu.obs import report

    by_rank = report.load_rank_traces(
        [out + f".rank{r}.trace.jsonl" for r in range(2)])
    m = report.merge_summary(by_rank)
    reb = m.get("rebalance")
    assert reb, "merge_summary carries no rebalance events"
    assert reb[0]["rows_before"] == [256, 256], reb
    assert reb[-1]["rows_after"] == counts, reb
    assert reb[0]["wait_share_before"] is not None, reb
    rendered = report.render_merge(m)
    assert "rebalance" in rendered and "->" in rendered, rendered


# ----------------------------------------------------------------------
# row-block wire (framed raw-numpy bytes, no pickle — docs/ROBUSTNESS.md)
# ----------------------------------------------------------------------
def _wire_example():
    from lightgbm_tpu.parallel.shardplan import _pack_row_wire
    out = {
        (5, 9): {"bins": np.arange(8, dtype=np.int8).reshape(4, 2),
                 "label": np.array([0.0, 1.0, 1.0, 0.0], np.float32)},
        (20, 22): {"bins": np.array([[7, 7]], np.int8).repeat(2, 0),
                   "label": np.array([1.0, 0.5], np.float32)},
    }
    return out, _pack_row_wire(out)


# the exact frame for _wire_example(): magic, little-endian headers,
# sorted spans/names, C-order payloads, CRC32 per array.  Pinned so wire
# compatibility breaks loudly (mixed-version fleets exchange this blob).
_WIRE_PIN = (
    "5242310002000000050000000000000009000000000000000200000004000300"
    "000262696e737c69310400000000000000020000000000000008000000000000"
    "009f68aa8800010203040506070500030000016c6162656c3c66340400000000"
    "0000001000000000000000d876f7c6000000000000803f0000803f0000000014"
    "0000000000000016000000000000000200000004000300000262696e737c6931"
    "02000000000000000200000000000000040000000000000044f2f96807070707"
    "0500030000016c6162656c3c663402000000000000000800000000000000dbc9"
    "85ee0000803f0000003f"
)


def test_row_wire_pins_exact_bytes():
    _out, blob = _wire_example()
    assert blob.hex() == _WIRE_PIN.replace("\n", "")


def test_row_wire_roundtrip_exact():
    from lightgbm_tpu.parallel.shardplan import _unpack_row_wire
    out, blob = _wire_example()
    back = _unpack_row_wire(blob)
    assert set(back) == set(out)
    for span, blocks in out.items():
        assert set(back[span]) == set(blocks)
        for name, arr in blocks.items():
            got = back[span][name]
            assert got.dtype == arr.dtype and got.shape == arr.shape
            assert got.tobytes() == arr.tobytes()


def test_row_wire_rejects_corruption():
    from lightgbm_tpu.parallel.shardplan import _unpack_row_wire
    _out, blob = _wire_example()
    with pytest.raises(ValueError, match="bad magic"):
        _unpack_row_wire(b"XX" + blob[2:])
    flipped = bytearray(blob)
    flipped[-1] ^= 0xFF  # corrupt the last payload byte
    with pytest.raises(ValueError, match="CRC"):
        _unpack_row_wire(bytes(flipped))
    with pytest.raises(ValueError, match="CRC|length"):
        _unpack_row_wire(blob[:-1])  # truncated mid-payload


# ----------------------------------------------------------------------
# query-group boundary snapping (whole-group moves for lambdarank)
# ----------------------------------------------------------------------
def test_snap_to_groups_basic():
    from lightgbm_tpu.parallel.shardplan import snap_to_groups
    gb = np.array([0, 10, 30, 60, 100], np.int64)
    # each ideal cut snaps to the nearest group boundary
    assert snap_to_groups([28], gb) == (30,)
    assert snap_to_groups([45, 80], gb) == (30, 60)
    # ties break toward the lower boundary
    assert snap_to_groups([20], gb) == (10,)


def test_snap_to_groups_collision_pushes_forward():
    from lightgbm_tpu.parallel.shardplan import snap_to_groups
    gb = np.array([0, 10, 30, 60, 100], np.int64)
    # both ideals want 30; the second cut must move past it
    assert snap_to_groups([29, 31], gb) == (30, 60)


def test_snap_to_groups_returns_none_when_groups_run_out():
    from lightgbm_tpu.parallel.shardplan import snap_to_groups
    gb = np.array([0, 50, 100], np.int64)  # one interior boundary
    assert snap_to_groups([40, 70], gb) is None  # 2 cuts, 1 boundary


def test_controller_group_bounds_moves_whole_groups():
    from lightgbm_tpu.parallel.shardplan import RebalanceController
    gb = np.array([0, 40, 80, 130, 180, 256], np.int64)
    ctl = RebalanceController(threshold=1.2, patience=1,
                              max_move_frac=0.5, group_bounds=gb)
    plan = ShardPlan.from_counts([128, 128])
    newp = None
    for _ in range(4):
        newp = ctl.observe(plan, [3.0, 1.0]) or newp
    assert newp is not None
    # the cut lands exactly on a group boundary, never mid-group
    assert newp.starts[1] in set(int(g) for g in gb)
    assert newp.counts[0] < newp.counts[1]
    assert sum(newp.counts) == 256


# ----------------------------------------------------------------------
# distributed lambdarank (group-aligned shards; whole-group rebalance)
# ----------------------------------------------------------------------
def _lambdarank_fleet(tmp_path, tag, world, extra_env=None):
    out = str(tmp_path / tag)
    port = _free_port()
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS", "LIGHTGBM_TPU_FAULT",
                        "LIGHTGBM_TPU_FAULT_RANK", "LIGHTGBM_TPU_TRACE")}
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.update(ELASTIC_OBJECTIVE="lambdarank", ELASTIC_QUANTIZED="1",
               ELASTIC_ROWS="512", ELASTIC_TREES="10", ELASTIC_FREQ="100",
               ELASTIC_LEAVES="7")
    env.update(extra_env or {})
    procs = [subprocess.Popen(
        [sys.executable, EWORKER, str(r), str(world), str(port), out,
         "train", str(tmp_path / f"ck_{tag}")],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=dict(env)) for r in range(world)]
    logs = [p.communicate(timeout=420)[0] for p in procs]
    assert all(p.returncode == 0 for p in procs), "\n".join(
        l[-2500:] for l in logs)
    res = [json.load(open(out + f".rank{r}.json")) for r in range(world)]
    models = [open(out + f".rank{r}.txt").read() for r in range(world)]
    return res, models


def test_lambdarank_two_rank_parity(tmp_path):
    """First distributed lambdarank coverage: data-parallel ranks hold
    whole query groups and train in lockstep; quantized integer
    histograms make the result byte-identical ACROSS world sizes (the
    same world-invariance the binary oocdist tests pin — serial-vs-
    distributed stays structural parity per test_multihost.py)."""
    res2, models2 = _lambdarank_fleet(tmp_path, "w2", 2)
    res4, models4 = _lambdarank_fleet(tmp_path, "w4", 4)
    assert res2[0]["trees"] == res4[0]["trees"] == 10
    # no query group is split: the shard group counts add up to the
    # global group count at every world
    n2 = sum(r["n_local_groups"] for r in res2)
    n4 = sum(r["n_local_groups"] for r in res4)
    assert n2 == n4 > 4
    assert all(r["n_local_groups"] > 0 for r in res2 + res4)
    assert models2[0] == models2[1], "data-parallel ranks diverged"
    assert len(set(models4)) == 1, "world-4 ranks diverged"
    assert models2[0] == models4[0], \
        "lambdarank bytes changed with world size"


def test_lambdarank_rebalance_moves_whole_groups(tmp_path):
    """Rebalance leg: rank 0 is an injected straggler; the controller
    must move load at QUERY-GROUP granularity — every shard edge of the
    final plan is a group boundary and no group spans ranks.

    The delay is 120 ms: at 40, a four-device benchmark rehearsal that
    tier-1 ran beside this test starved rank 1 more than the injected
    straggle slowed rank 0, and the one group that moved went the wrong
    way (263 / 249) in every whole run of PR 33's tree."""
    res, models = _lambdarank_fleet(
        tmp_path, "rb", 2,
        {"ELASTIC_REBALANCE": "1", "ELASTIC_TREES": "12",
         "LIGHTGBM_TPU_FAULT": "delay:120:after:5",
         "LIGHTGBM_TPU_FAULT_RANK": "0"})
    counts = res[0]["final_counts"]
    assert counts == res[1]["final_counts"], res
    assert counts is not None and sum(counts) == 512, res
    assert counts[0] < counts[1], "rows did not move off the straggler"
    # whole-group invariant, asserted by each rank against the global
    # cumulative group boundaries
    assert res[0]["group_aligned"] is True, res
    assert res[1]["group_aligned"] is True, res
    assert res[0]["rows_end"] == counts[0] and res[1]["rows_end"] == counts[1]
    assert models[0] == models[1], "ranks diverged after group rebalance"
