"""Set-up read from the program's kept stages: harness/stages.py and the eight
readers that split `setup_s`, on a hand-made record and stage list (present, and
None with no raise on a program older than `tracer.stages`), the cut at the
traced window's start, the same tree with the program's sink on, the share of a
stage its children close, the entries against PERF.md's layers, and the rehearsals
of the three cells whose set-up took the three ways in (chunked, `lgb.train`,
four devices)."""

import json
import os
import re
import subprocess
import sys

import pytest

from harness import stages
from harness.spec import BENCH_DIR, ROOT, Spec, load_module

WINDOW = 1_000_000.0  # wall clock at which the traced window began
EVERYWHERE = ("dataset_construct_s", "booster_init_s", "pack_matrix_s", "program_build_s",
              "trace_lower_s", "setup_program_s")
READERS = EVERYWHERE + ("find_bundles_s", "shard_pack_s")
DENSE = ("higgs.train-21m", "higgs.train-21m-valid", "epsilon.train-2000f",
         "epsilon-dp4.train-2000f-split4")


def _stage(name, dur_s, end, depth=0, parent=None, **attrs):
    # `t0` on a clock of its own (perf_counter's), here the window's start as 0
    return {"name": name, "t0": end - WINDOW - dur_s, "ts": end, "dur_s": dur_s, "depth": depth,
            "parent": parent, **attrs}


# one Booster of the four-chip cell under `lgb.train`, a validation set after
# it, the chunk program's first call; then, after the window, the parity
# check's Booster, which no reader may count
KEPT = [
    _stage("load_binary", 2.0, WINDOW - 90, 1, "dataset_construct", bytes=800_000_000),
    _stage("dataset_construct", 2.5, WINDOW - 89.5, source="binary"),
    _stage("bins_upload", 3.0, WINDOW - 80, 1, "booster_init"),
    _stage("find_bundles", 31.0, WINDOW - 49, 1, "booster_init", columns=2000, bundles=0),
    _stage("shard_pack", 16.0, WINDOW - 33, 1, "booster_init", shards=4),
    _stage("booster_init", 51.0, WINDOW - 32.5),
    _stage("dataset_construct", 0.25, WINDOW - 32, source="matrix"),
    _stage("program_build", 6.5, WINDOW - 20, program="ptrainer.sharded_chunk(bag=0,ff=2000)",
           backend_s=0.5, cache_hit=True),
    _stage("program_build", 0.5, WINDOW - 19, program="ops.predict", backend_s=0.125,
           cache_hit=True),
    _stage("find_bundles", 9.0, WINDOW + 40, 1, "booster_init"),
    _stage("pack_matrix", 7.0, WINDOW + 45, 1, "booster_init"),
    _stage("booster_init", 20.0, WINDOW + 46),
    _stage("program_build", 30.0, WINDOW + 80),
]
EXPECTED = {"dataset_construct_s": 2.75, "booster_init_s": 51.0, "find_bundles_s": 31.0,
            "pack_matrix_s": 3.0, "shard_pack_s": 16.0, "program_build_s": 7.0,
            "trace_lower_s": 6.0 + 0.375, "setup_program_s": 2.5 + 51.0 + 0.25 + 6.5 + 0.5}


def _record():
    # a span that began 1.5 s into the window and took 2 s: `ts` is its end
    return {"driver": "train", "chips": 4, "iters": 4,
            "program_spans": [{"name": "chunk_program", "ts": WINDOW + 3.5, "dur_s": 2.0,
                               "start_s": 1.5}],
            "compile_setup": {"backend_compile_secs": 0.6, "cache_misses": 0}}


@pytest.fixture
def kept(monkeypatch):
    from lightgbm_tpu.obs import tracer

    monkeypatch.setattr(tracer, "stages", list(KEPT), raising=False)
    return tracer


@pytest.mark.parametrize("name", READERS)
def test_a_reader_sums_its_stages_before_the_window(kept, name):
    assert load_module("layer_metrics", name).read(_record()) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_stages_reads_nothing(monkeypatch, name):
    """The parent of PR 38: no `tracer.stages`.  None, and no raise."""
    from lightgbm_tpu.obs import tracer

    monkeypatch.setattr(tracer, "stages", None, raising=False)
    old = _record()
    assert load_module("layer_metrics", name).read(old) is None
    monkeypatch.delattr(tracer, "stages")
    assert load_module("layer_metrics", name).read(old) is None


def test_the_cut_is_where_the_window_began(kept):
    rec = _record()
    assert stages.window_start(rec) == pytest.approx(WINDOW)
    before = stages.setup_stages(rec)
    assert before == KEPT[:9] and all(s["ts"] <= WINDOW for s in before)
    assert stages.total(rec, "pack_matrix") is None  # kept, but after the window began
    assert stages.total(rec, "booster_init") == 51.0
    assert stages.total(rec, "program_build") == 7.0
    # a window that began later takes in the parity check's stages too
    rec["program_spans"][0]["ts"] += 60
    assert stages.total(rec, "booster_init") == 71.0
    # no program span in the record: nothing to cut by, so nothing is said
    assert stages.setup_stages({"program_spans": []}) is None
    assert stages.total({"program_spans": []}, "booster_init") is None


def test_the_tree_is_the_same_with_the_programs_sink_on(tmp_path, monkeypatch):
    """The one-hot driver sets its table up with the sink on, where the trainer's
    `chunk_program` span is around the call that builds the program: a kept
    stage's depth counts stages only, so `setup_program_s` still takes it in."""
    import collections
    import time

    from lightgbm_tpu.obs import tracer

    monkeypatch.setattr(tracer, "stages", collections.deque(maxlen=8))
    tracer.configure(str(tmp_path / "on.jsonl"))
    try:
        with tracer.span("sparse_table"):
            with tracer.stage("dataset_construct"):
                with tracer.stage("load_binary"):
                    pass
        with tracer.span("chunk_program"):
            tracer.record_stage("program_build", time.perf_counter() - 6.5, 6.5,
                                program="ptrainer.chunk", backend_s=0.5, cache_hit=True)
    finally:
        tracer.close()
        tracer.path = None
        tracer.reset_aggregates()
    assert [(s["name"], s["depth"], s["parent"]) for s in tracer.stages] == [
        ("load_binary", 1, "dataset_construct"), ("dataset_construct", 0, None),
        ("program_build", 0, None)]
    rec = _record()
    rec["program_spans"][0]["ts"] = time.time() + 60  # the window began after all this
    ds = tracer.stages[1]["dur_s"]
    assert load_module("layer_metrics", "setup_program_s").read(rec) == pytest.approx(6.5 + ds)
    assert load_module("layer_metrics", "trace_lower_s").read(rec) == pytest.approx(6.0)


def test_the_share_of_a_stage_its_children_close():
    """What `stages_tree.py` prints beside a parent: the children of THIS
    instance (the parity check's second `booster_init` has its own)."""
    import stages_tree  # benchmarks/ is on the path, as for run.py

    rows = {(s["name"], s["ts"]): s["children_share"] for s in stages_tree.tree(KEPT)}
    assert rows[("booster_init", WINDOW - 32.5)] == pytest.approx(50.0 / 51.0)
    assert rows[("booster_init", WINDOW + 46)] == pytest.approx(16.0 / 20.0)
    assert rows[("dataset_construct", WINDOW - 89.5)] == pytest.approx(0.8)
    assert rows[("dataset_construct", WINDOW - 32)] is None  # no stage inside it
    assert rows[("shard_pack", WINDOW - 33)] is None
    assert [s["t0"] for s in stages_tree.tree(KEPT)] == sorted(s["t0"] for s in KEPT)


def test_a_name_nobody_kept_reads_nothing(kept):
    kept.stages = [s for s in KEPT if s["name"] not in ("shard_pack", "find_bundles")]
    rec = _record()
    assert load_module("layer_metrics", "shard_pack_s").read(rec) is None
    assert load_module("layer_metrics", "find_bundles_s").read(rec) is None
    assert load_module("layer_metrics", "booster_init_s").read(rec) == 51.0


def test_every_new_entry_has_its_file_and_a_layer_perf_md_names():
    spec = Spec()
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    layers = set(re.findall(r"^\| (\w+) \| `", perf[perf.index("## 3. Layers"):perf.index("## 4.")],
                            re.M))
    cells = [c["name"] for c in spec.doc["workloads"]]
    by_name = {m["name"]: m for m in spec.doc["per_layer"]}
    assert [m["name"] for m in spec.doc["per_layer"][-8:]] == [
        "dataset_construct_s", "booster_init_s", "find_bundles_s", "pack_matrix_s",
        "shard_pack_s", "program_build_s", "trace_lower_s", "setup_program_s"]
    for name in READERS:
        entry, reader = by_name[name], load_module("layer_metrics", name)
        assert (entry["unit"], entry["better"], entry["moves"]) == ("s", "lower", "setup_s")
        assert (reader.LAYER, reader.MOVES, reader.SOURCE) == (entry["layer"], "setup_s",
                                                               entry["source"])
        assert reader.DRIVERS == ("train",) and reader.LAYER in layers, (name, layers)
        assert f"`{name}`" in perf
    for name in EVERYWHERE:
        assert "workloads" not in by_name[name]
    assert by_name["shard_pack_s"]["workloads"] == ["epsilon-dp4.train-2000f-split4"]
    # the one-hot cell's bundles come with its binary file: no search on a warm run
    assert by_name["find_bundles_s"]["workloads"] == list(DENSE)
    assert all(c in cells for c in DENSE)


@pytest.mark.parametrize("cell,devices,also", [
    ("higgs.train-21m", 1, ("find_bundles_s", "pack_upload_s")),
    ("higgs.train-21m-valid", 1, ("find_bundles_s", "eval_ms_per_iter")),
    ("epsilon-dp4.train-2000f-split4", 4, ("find_bundles_s", "shard_pack_s", "pack_upload_s")),
])
def test_a_rehearsal_finds_the_new_metrics(cell, devices, also):
    env = dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    for k in ("LIGHTGBM_TPU_PGROW", "LIGHTGBM_TPU_TRACE"):
        env.pop(k, None)
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload", cell,
         "--seed", "3800000033", "--seconds", "1", "--trace", "1", "--rehearse"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["failed"] == 0 and result["metrics"] == {}
    assert not [ln for ln in lines if ln.startswith("[bench] FAILED")]
    found = next(ln for ln in lines if "found: [" in ln)
    for name in EVERYWHERE + also:
        assert f"'{name}'" in found, (name, found)
    if devices == 1:
        assert "'shard_pack_s'" not in found
