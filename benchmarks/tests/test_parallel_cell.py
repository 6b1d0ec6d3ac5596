"""The four-chip cell's own pieces: harness/collective_ops.py on shapes, the
four `parallel` readers on a hand-made record (and their silence, None and no
raise, on a record of a program older than the counters, or off the chip), and
the cell's rehearsal on four CPU devices."""

import json
import os
import subprocess
import sys

import pytest

from harness import collective_ops
from harness.spec import BENCH_DIR, ROOT, Spec, load_module

CELL = "epsilon-dp4.train-2000f-split4"
READERS = ("collective_ms_per_iter", "collective_exposed_share", "allreduce_gb_per_iter",
           "hist_allreduce_ici_roofline")
# one tree of the cell's shape: the root's (2000, 63, 3), 9 levels of
# (256, 16, 128000) and 90 tail splits of two histograms as (6, 128000)
# planes, all float32
ROOT_BYTES, LEVEL_BYTES, TAIL_BYTES = 4 * 2000 * 63 * 3, 4 * 256 * 16 * 128_000, 4 * 6 * 128_000
TREE = dict(allreduce_calls=1 + 9 + 90,
            allreduce_bytes=ROOT_BYTES + 9 * LEVEL_BYTES + 90 * TAIL_BYTES, shards=4)


def _span(**counts):
    return {"name": "trees_from_records", "trees": 1, "splits": 254, **counts}


def _record(spans, collective_s=2.0, exposed_s=1.5, iters=4):
    return {"driver": "train", "chips": 4, "iters": iters,
            "program_spans": spans + [{"name": "chunk_program"}],
            "device": {"window_s": 8.0, "busy_s": 7.9, "collective_s": collective_s,
                       "collective_exposed_s": exposed_s}}


@pytest.fixture
def on_a_v5e(monkeypatch):
    monkeypatch.setattr(collective_ops, "device_kind", lambda: "TPU v5 lite")


def test_counters_sum_over_the_windows_spans():
    c = collective_ops.counters(_record([_span(**TREE)] * 4))
    assert c == {"allreduce_calls": 400, "allreduce_bytes": 4 * TREE["allreduce_bytes"],
                 "shards": 4}
    assert LEVEL_BYTES == 2_097_152_000 and 18.8e9 < 9 * LEVEL_BYTES < 18.9e9


@pytest.mark.parametrize("shards,factor", [(1, 0.0), (2, 1.0), (4, 1.5), (16, 1.875)])
def test_link_bytes_of_a_bandwidth_optimal_all_reduce(shards, factor):
    assert collective_ops.link_bytes({"allreduce_bytes": 1000, "shards": shards}) == 1000 * factor


def test_the_peak_is_by_device_kind_and_unknown_is_an_error():
    assert collective_ops.ici_bytes_per_s("TPU v5 lite") == 200e9  # 1,600 Gbit/s
    with pytest.raises(KeyError):
        collective_ops.ici_bytes_per_s("TPU v9 imaginary")


def test_the_four_readers_on_a_hand_made_record(on_a_v5e):
    rec = _record([_span(**TREE)] * 4)
    got = {name: load_module("layer_metrics", name).read(rec) for name in READERS}
    assert got["collective_ms_per_iter"] == pytest.approx(500.0)
    assert got["collective_exposed_share"] == pytest.approx(100 * 1.5 / 8.0)
    assert got["allreduce_gb_per_iter"] == pytest.approx(TREE["allreduce_bytes"] / 1e9)
    sent = 1.5 * 4 * TREE["allreduce_bytes"]
    assert got["hist_allreduce_ici_roofline"] == pytest.approx(100 * sent / 2.0 / 200e9)
    assert 0 < got["hist_allreduce_ici_roofline"] < 100


def test_a_program_without_the_counters_reads_nothing(on_a_v5e):
    """The parent of PR 33 runs the cell (the trainer is there) and writes
    `trees` and `splits` alone: the two counter readers are left out, the two
    trace readers still read."""
    old = _record([_span()])
    assert collective_ops.counters(old) is None and collective_ops.share(old) is None
    got = {name: load_module("layer_metrics", name).read(old) for name in READERS}
    assert got["allreduce_gb_per_iter"] is None and got["hist_allreduce_ici_roofline"] is None
    assert got["collective_ms_per_iter"] == pytest.approx(500.0)
    assert got["collective_exposed_share"] == pytest.approx(18.75)


def test_off_the_chip_and_on_one_chip():
    rehearsal = {"driver": "train", "iters": 2, "program_spans": [_span(**TREE)], "device": None}
    got = {name: load_module("layer_metrics", name).read(rehearsal) for name in READERS}
    assert got == {"collective_ms_per_iter": None, "collective_exposed_share": None,
                   "allreduce_gb_per_iter": pytest.approx(TREE["allreduce_bytes"] / 2e9),
                   "hist_allreduce_ici_roofline": None}
    # a serial program counts zeros and its trace has no collective: no share
    serial = _record([_span(allreduce_calls=0, allreduce_bytes=0, shards=1)], 0.0, 0.0)
    assert collective_ops.share(serial) is None
    assert load_module("layer_metrics", "allreduce_gb_per_iter").read(serial) == 0.0


def test_the_cell_and_its_files_agree():
    spec = Spec()
    cell = spec.cell(CELL)
    cfg, mix = spec.config(cell["config"]), spec.mix(cell["traffic"])
    one = spec.config("epsilon")
    assert cell["chips"] == cfg["chips"] == 4 and cfg["trainer"] == "ShardedPartitionedTrainer"
    assert cfg["params"] == {**one["params"], "tree_learner": "data"}
    assert cfg["published"] == one["published"] and cfg["reduced"] == ["num_iterations"]
    assert mix["rows_per_chip"] * cell["chips"] == cfg["published"]["num_data"]
    assert cfg["per_chip_bytes"]["total"] == (cfg["per_chip_bytes"]["arguments"]
                                              + cfg["per_chip_bytes"]["temporaries"])
    same = ("driver", "features", "features_seed", "heldout_rows", "chunk_iters", "warmup_iters",
            "auc_iters", "auc_floor", "trace_chunks", "parity_rows", "parity_iters")
    assert {k: mix[k] for k in same} == {k: spec.mix("train-2000f")[k] for k in same}
    mine = {m["name"] for m in spec.metrics("per_layer", CELL)}
    assert set(READERS) <= mine and "pack_upload_s" in mine and "eval_ms_per_iter" not in mine
    assert not set(READERS) & {m["name"] for m in spec.metrics("per_layer", "epsilon.train-2000f")}


def test_the_cells_rehearsal_on_four_cpu_devices_ends_correct():
    env = dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    for k in ("LIGHTGBM_TPU_PGROW", "LIGHTGBM_TPU_TRACE"):
        env.pop(k, None)
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload", CELL,
         "--seed", "3300000033", "--seconds", "1", "--trace", "1", "--rehearse"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["failed"] == 0 and result["metrics"] == {}
    assert result["device"]["platform"] == "cpu" and result["device"]["count"] == 4
    assert not [ln for ln in lines if ln.startswith("[bench] FAILED")]
    assert any("ok: trained on ShardedPartitionedTrainer over 4 device(s)" in ln for ln in lines)
    found = next(ln for ln in lines if "found: [" in ln)
    assert "'allreduce_gb_per_iter'" in found and "'pack_upload_s'" in found
    assert "'matrix_copy_ms_per_iter'" in found
