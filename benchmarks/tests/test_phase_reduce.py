import copy
import json
import os

import pytest

from harness import phase_reduce as pr
from harness.spec import Spec, load_module

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
XPLANE = os.path.join(FIXTURES, "v5e_chunk_tail.xplane.pb")
NS = 1e-9
NEW_READERS = ("replay_ms_per_iter", "level_phase_ms_per_iter", "matrix_copy_ms_per_iter",
               "replay_launches_per_split", "canon_reorder_ms_per_iter",
               "leaf_delta_ms_per_iter", "chunk_epilogue_ms_per_chunk",
               "device_wait_ms_per_iter", "phase_unattributed_share")


@pytest.fixture(scope="module")
def phase_map():
    with open(os.path.join(FIXTURES, "v5e_chunk_tail.phase_map.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def table(phase_map):
    """fixtures/v5e_chunk_tail.xplane.pb: device 0's `XLA Ops` and `XLA
    Modules` lines and the host's `bench:`/`lgbm:` annotations from a traced
    chunk (2 iterations, 20,000 rows) on a TPU v5e (chip run of PR 25's tree),
    cut to 55 events: the main `while` and two `cond` events (parents), all
    28 launches of the whole-matrix copy `copy.2398`, the last 15 operations
    of the second tree's leaf delta, the 9 of the chunk epilogue, and the one
    operation of the next program.  The map beside it is
    `parse_hlo_phases` of that program's compiled text, cut to those
    instructions and the six static copy sites."""
    return pr.reduce(pr.read(XPLANE), [phase_map])


def test_window_busy_and_program(table):
    # bench:window: 45,202,949 .. 74,422,008 ns
    assert table["window_s"] == pytest.approx(29_219_059 * NS)
    assert table["chips"] == 1 and table["device_plane"] is True
    # the 53 leaves do not overlap; their durations sum to 208,148 ns
    assert table["busy_s"] == pytest.approx(208_148 * NS)
    assert table["idle_s"] == pytest.approx((29_219_059 - 208_148) * NS)
    prog = table["chunk_program"]
    assert prog["module"] == ["jit_prog"] and prog["matrix"] == ["s32[16,21024]"]
    assert prog["name"] == ["ptrainer.chunk(bag=0,ff=28)"]
    # all but the next program's one operation (896 ns)
    assert prog["launches"] == 52 and prog["busy_s"] == pytest.approx(207_252 * NS)
    assert table["other_programs"] == {
        "jit_broadcast_in_dim": {"busy_s": pytest.approx(896 * NS), "launches": 1}}


@pytest.mark.parametrize("phase,ns,launches,top", [
    ("chunk_epilogue", 128_564, 9, "fusion f32[20000]"),
    ("replay", 62_350, 28, "copy s32[16,21024]"),
    ("leaf_delta", 16_338, 15, "broadcast_select_fusion (f32[20000], f32[20000])"),
])
def test_phase_rows_are_the_hand_computed_sums(table, phase, ns, launches, top):
    row = table["phases"][phase]
    assert row["busy_s"] == pytest.approx(ns * NS)
    assert row["launches"] == launches
    assert row["top"][0][0] == top
    # the parents (`while`, `cond`) are no launches of any phase
    assert all("while" not in label and "cond " not in label for label, _ in row["top"])


def test_phases_sum_to_the_program_and_nothing_is_unattributed(table):
    assert list(table["phases"]) == ["chunk_epilogue", "replay", "leaf_delta"]
    assert sum(r["busy_s"] for r in table["phases"].values()) == \
        pytest.approx(table["chunk_program"]["busy_s"])
    assert pr.NO_PHASE not in table["phases"]


def test_matrix_copy_sites(table):
    sites = {s["instruction"]: s for s in table["matrix_copies"]}
    assert sorted(sites) == ["copy.2308", "copy.2359", "copy.2369", "copy.2389", "copy.2398",
                             "copy.2417"]
    assert sites["copy.2398"] == {"instruction": "copy.2398", "phase": "replay", "launches": 28,
                                  "busy_s": pytest.approx(62_350 * NS)}
    assert table["matrix_copies"][0]["instruction"] == "copy.2398"  # most expensive first
    # a static site the window never ran is listed with nothing: the stopped
    # no-op branch's copy, which no phase owns
    assert sites["copy.2417"] == {"instruction": "copy.2417", "phase": None, "launches": 0,
                                  "busy_s": 0}
    assert sites["copy.2359"]["phase"] == "level_phase"


def test_idle_gaps_go_to_the_innermost_lgbm_span(table):
    # idle before, between and after the program's operations, up to the next
    # program: the host sat in device_wait (inside records_fetch inside tree);
    # after that program's one operation it was in train_score
    assert table["idle_gaps_s"] == {"lgbm:device_wait": pytest.approx(26_158_040 * NS),
                                    "lgbm:train_score": pytest.approx(2_852_871 * NS)}
    assert sum(table["idle_gaps_s"].values()) == pytest.approx(table["idle_s"])


def test_a_map_of_another_executable_shows_as_unattributed(phase_map):
    """Instructions the map does not know (a stale executable, a missing
    scope) keep their time, under "(no phase)"."""
    stale = copy.deepcopy(phase_map)
    for name in ("pad.46", "score_add.1"):  # 589 + 3,165 ns
        del stale["ops"][name]
    table = pr.reduce(pr.read(XPLANE), [stale])
    assert table["phases"][pr.NO_PHASE]["busy_s"] == pytest.approx(3_754 * NS)
    assert table["phases"][pr.NO_PHASE]["launches"] == 2
    assert table["phases"]["chunk_epilogue"]["busy_s"] == pytest.approx((128_564 - 3_754) * NS)


def test_of_two_maps_with_one_module_name_the_one_that_knows_the_trace_wins(phase_map):
    other = {"name": "ptrainer.chunk(parity)", "module": "jit_prog", "matrix": "s32[16,4608]",
             "ops": {"copy.7": "replay", "score_add.1": "chunk_epilogue"}, "matrix_copies": ["copy.7"]}
    for maps in ([other, phase_map], [phase_map, other]):
        table = pr.reduce(pr.read(XPLANE), maps)
        assert table["chunk_program"]["name"] == ["ptrainer.chunk(bag=0,ff=28)"]


def test_without_maps_every_program_is_another_program():
    table = pr.reduce(pr.read(XPLANE), [])
    assert "chunk_program" not in table and table["phases"] == {} and table["matrix_copies"] == []
    assert table["other_programs"]["jit_prog"]["launches"] == 52


def test_no_device_operation_gives_no_table():
    assert pr.reduce(pr.Reading({}, {}, [], True), []) is None


# -- the readers -------------------------------------------------------------
def _record(**over):
    spans = [{"name": "chunk_program", "dur_s": 0.002}, {"name": "records_fetch", "dur_s": 21.4},
             {"name": "device_wait", "dur_s": 21.3}, {"name": "records_d2h", "dur_s": 0.001},
             {"name": "trees_from_records", "dur_s": 0.01, "trees": 4, "splits": 1016}]
    return {"driver": "train", "chips": 1, "iters": 4, "laps": 1, "window_s": 21.5,
            "program_spans": spans, "device": None, **over}


@pytest.fixture
def no_trace(monkeypatch, tmp_path):
    """The program's tracer points at a directory that holds no trace, as
    after a run that was not profiled."""
    from lightgbm_tpu.obs import tracer

    monkeypatch.setattr(tracer, "path", str(tmp_path / "program.jsonl"))
    monkeypatch.setattr(pr, "_tables", {})


@pytest.mark.parametrize("name", NEW_READERS)
def test_reader_is_declared_as_benchmark_json_has_it(name):
    entry = [m for m in Spec().doc["per_layer"] if m["name"] == name]
    reader = load_module("layer_metrics", name)
    assert len(entry) == 1
    assert (reader.LAYER, reader.MOVES, reader.SOURCE) == \
        (entry[0]["layer"], entry[0]["moves"], entry[0]["source"])
    assert "train" in reader.DRIVERS and "workloads" not in entry[0]


@pytest.mark.parametrize("name", [n for n in NEW_READERS if n != "device_wait_ms_per_iter"])
def test_trace_reader_returns_none_without_a_device_plane(no_trace, name):
    assert load_module("layer_metrics", name).read(_record()) is None


def test_span_reader_reads_device_wait_alone_and_none_from_an_older_program(no_trace):
    reader = load_module("layer_metrics", "device_wait_ms_per_iter")
    assert reader.read(_record()) == pytest.approx(1e3 * 21.3 / 4)
    old = _record(program_spans=[{"name": "chunk_program", "dur_s": 0.002},
                                 {"name": "records_fetch", "dur_s": 21.4}])
    assert reader.read(old) is None


@pytest.fixture
def with_table(monkeypatch, table, tmp_path):
    from lightgbm_tpu.obs import tracer

    monkeypatch.setattr(tracer, "path", str(tmp_path / "program.jsonl"))
    monkeypatch.setattr(pr, "_tables", {str(tmp_path): table})


@pytest.mark.parametrize("name,value", [
    ("replay_ms_per_iter", 1e3 * 62_350 * NS / 4),
    ("level_phase_ms_per_iter", 0.0),
    ("matrix_copy_ms_per_iter", 1e3 * 62_350 * NS / 4),
    ("replay_launches_per_split", 28 / 1016),
    ("canon_reorder_ms_per_iter", 0.0),
    ("leaf_delta_ms_per_iter", 1e3 * 16_338 * NS / 4),
    ("chunk_epilogue_ms_per_chunk", 1e3 * 128_564 * NS / 1),
    ("phase_unattributed_share", 0.0),
])
def test_trace_reader_reads_the_table(with_table, name, value):
    assert load_module("layer_metrics", name).read(_record()) == pytest.approx(value)


def test_table_is_none_for_a_program_without_phase_maps(monkeypatch, no_trace):
    from lightgbm_tpu.obs import compilewatch

    monkeypatch.delattr(compilewatch, "phase_maps")
    assert pr.table() is None
