"""Device time by phase (obs/phases.py, JitWatch.phase_map, the nested host
spans): the names the chunk programs give themselves, the join from a compiled
module's text to them, and what all of it costs with tracing off (nothing)."""

import ast
import json
import os
import pathlib
import re

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import lightgbm_tpu as lgb  # noqa: E402
from lightgbm_tpu.obs import PHASES, compilewatch, tracer  # noqa: E402
from lightgbm_tpu.obs.compilewatch import JitWatch  # noqa: E402
from lightgbm_tpu.obs.phases import ENCLOSING, parse_hlo_phases, phase_of  # noqa: E402

REPO = pathlib.Path(__file__).resolve().parent.parent
FIXTURE = REPO / "tests" / "fixtures" / "v5e_chunk_hlo_cut.txt"
KERNEL_FILES = ("lightgbm_tpu/ops/pkernels.py", "lightgbm_tpu/ops/histogram_pallas.py")
KERNELS = ("hist_dyn", "update_and_root_hist", "update_multi_and_hists", "score_add",
           "level_stream", "split_stream", "update_channels",
           "hist_segment", "hist_segments", "hist_segment_q")


def _toy(n=300, f=4, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    return X, (X[:, 0] + X[:, 1] > 0).astype(float), rng.randint(0, 3, n).astype(float)


def _one_hot_csr(n=300, fields=(5, 7, 4), seed=0):
    """Three categorical fields one-hot encoded, as scipy CSR: EFB bundles each
    field's columns, so the fused trainer streams bundles."""
    import scipy.sparse

    rng = np.random.RandomState(seed)
    cols = np.stack([rng.randint(0, c, n) for c in fields], 1) + np.cumsum((0,) + fields[:-1])
    return scipy.sparse.csr_matrix(
        (np.ones(cols.size), cols.reshape(-1), np.arange(0, cols.size + 1, len(fields))),
        shape=(n, sum(fields)))


# -- the names inside the programs -------------------------------------------
def _lowered_chunk_program(params, y, X):
    """StableHLO text, with locations, of the fused chunk program for this
    configuration (kernels interpreted; nothing runs)."""
    bst = lgb.Booster(params=params, train_set=lgb.Dataset(X, label=y, params=params))
    pt = bst.boosting.ptrainer
    cfg = pt.config
    used = pt.params.num_features
    if cfg.feature_fraction < 1.0:
        used = max(1, int(used * cfg.feature_fraction))
    prog = pt._build_program(pt.CHUNK_ALLOC, cfg.bagging_fraction < 1.0 and cfg.bagging_freq > 0,
                             max(1, int(cfg.bagging_freq)), used)
    return prog.lower(pt.p, jnp.float32(0.1), pt._base_key, jnp.int32(0),
                      jnp.int32(2)).as_text(debug_info=True)


@pytest.fixture(scope="module")
def lowered():
    old = os.environ.get("LIGHTGBM_TPU_PGROW")
    os.environ["LIGHTGBM_TPU_PGROW"] = "force"
    try:
        X, yb, ym = _toy()
        base = {"num_leaves": 7, "verbose": -1}
        return {
            "binary": _lowered_chunk_program(
                {**base, "objective": "binary", "bagging_fraction": 0.8, "bagging_freq": 1,
                 "feature_fraction": 0.75}, yb, X),
            "multiclass": _lowered_chunk_program(
                {**base, "objective": "multiclass", "num_class": 3}, ym, X),
            "bundled": _lowered_chunk_program(
                {**base, "objective": "binary", "min_data_in_leaf": 1}, yb, _one_hot_csr()),
        }
    finally:
        if old is None:
            del os.environ["LIGHTGBM_TPU_PGROW"]
        else:
            os.environ["LIGHTGBM_TPU_PGROW"] = old


def _loc_names(text):
    return set(re.findall(r'loc\("([^"]*)"', text))


@pytest.mark.parametrize("phase", PHASES)
def test_chunk_program_carries_every_phase_word(lowered, phase):
    """Every word of the vocabulary is a scope of the lowered chunk program
    (`score_add` as a scope of its own only where K > 1 lands deltas in the
    loop), but for `canon_reorder`: the word stays for the benchmark's reader,
    and since PR 30 no program, bagged binary or multiclass, opens it.
    `bundle_expand` is a scope of a program that streams EFB bundles alone,
    keyed with the phase it sits in: the root's search, a level's, the tail's."""
    if phase == "canon_reorder":
        for text in lowered.values():
            assert phase not in {phase_of(n) for n in _loc_names(text)}
        return
    if phase == "bundle_expand":
        assert not any("bundle_expand" in n for n in _loc_names(lowered["binary"]))
        keys = {phase_of(n) for n in _loc_names(lowered["bundled"])}
        assert {"update_root_hist/bundle_expand", "split_scan/bundle_expand",
                "replay/bundle_expand"} <= keys and "bundle_expand" not in keys
        assert all(ENCLOSING[k] in PHASES for k in keys if k and "/" in k)
        return
    text = lowered["multiclass" if phase == "score_add" else "binary"]
    if phase == "score_add":
        assert re.search(r"score_add/jit\(score_add\)", text), \
            "the per-class score_add call has no scope around it"
    assert phase in {phase_of(n) for n in _loc_names(text)}


@pytest.mark.parametrize("kernel,program", [
    ("update_and_root_hist", "binary"), ("level_stream", "binary"),
    ("split_stream", "binary"), ("score_add", "binary"),
    ("update_multi_and_hists", "multiclass")])
def test_chunk_program_names_its_kernels(lowered, kernel, program):
    """`pallas_call(name=...)` reaches the program text as a scope of the
    kernel's own name, whatever branch or loop it is called from."""
    assert any(re.search(rf"(^|/){kernel}(/|$)", n) for n in _loc_names(lowered[program]))


def _pallas_call_sites():
    sites = {}
    for rel in KERNEL_FILES:
        tree = ast.parse((REPO / rel).read_text())
        for fn in tree.body:
            if not isinstance(fn, ast.FunctionDef):
                continue
            for node in ast.walk(fn):
                if (isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute)
                        and node.func.attr == "pallas_call"):
                    name = [k.value for k in node.keywords if k.arg == "name"]
                    sites[fn.name] = name[0].value if name and isinstance(name[0], ast.Constant) else None
    return sites


def test_every_pallas_call_site_is_known():
    assert sorted(_pallas_call_sites()) == sorted(KERNELS)


@pytest.mark.parametrize("kernel", KERNELS)
def test_pallas_call_is_named_after_its_function(kernel):
    assert _pallas_call_sites()[kernel] == kernel


# -- the parser ---------------------------------------------------------------
@pytest.mark.parametrize("op_name,phase", [
    ("jit(prog)/while/body/cond/branch_1_fun/jit(grow_tree_partitioned)/level_phase/while/body/add",
     "level_phase"),
    ("jit(prog)/while/body/jit(grow_tree_partitioned)/replay/while/body/cond/branch_1_fun/"
     "replay_tail/jit(split_stream)/split_stream/pallas_call", "replay_tail"),
    ("jit(prog)/chunk_epilogue/jit(score_add)/score_add/pallas_call", "chunk_epilogue"),
    ("jit(prog)/while/body/score_add/jit(score_add)/score_add/pallas_call", "score_add"),
    ("jit(prog)/while/body/cond", None),
    ("jit(prog)/while/body/my_replay_helper/add", None),  # a word is a whole component
])
def test_phase_of(op_name, phase):
    assert phase_of(op_name) == phase


# what the fixture's instructions must map to, worked out by hand from the text:
# own word; no metadata -> next/previous word of its computation, else the
# caller's; metadata without a word -> the caller's alone
FIXTURE_MAP = {
    # ENTRY: the main loop sits outside every scope (parameters are no events)
    "while.79": None,
    "score_add.1": "chunk_epilogue",  # a Mosaic custom call; its own name is no scope
    # the main loop's body and condition, the one_iter conditional
    "cond.127": None, "add.1545": None, "lt.551": None,
    # the live iteration
    "fusion.200": "canon_reorder", "update_and_root_hist.1": "update_root_hist",
    "while.78": "level_phase", "while.77": "replay",
    # the fixture is the program as it was before PR 27, when each iteration sat
    # in lax.cond(stopped, no-op, live): the no-op branch copies the matrix and
    # nothing names it (the parser's rules are what this pins, not the program)
    "get-tuple-element.2390": None, "copy.2417": None,
    # the replay loop's body: two metadata-less matrix copies around named work
    "get-tuple-element.2355": "replay", "copy.2389": "replay",
    "slice_reduce_fusion.19": "replay", "cond.129": "replay", "copy.2398": "replay",
    "cond.98": "replay",
    # take_classic: the kernel and what feeds it are the tail's; a derived
    # instruction whose path was cut back takes the conditional's phase
    "concatenate.315": "replay_tail", "split_stream.5": "replay_tail",
    "get-tuple-element.1123": "replay",
    # take_pre: a metadata-less matrix copy in a conditional's branch
    "get-tuple-element.2254": "replay", "copy.2308": "replay", "copy.2144": "replay",
    "constant_dynamic-slice_fusion.4": "replay", "copy.2313": "replay",
}


@pytest.fixture(scope="module")
def fixture_map():
    return parse_hlo_phases(FIXTURE.read_text())


def test_parser_module_matrix_and_copy_sites(fixture_map):
    """tests/fixtures/v5e_chunk_hlo_cut.txt: 8 KB cut from the compiled text of
    a chunk program on a TPU v5e (chip run of PR 25's tree, 20,000 rows): real
    lines, less their backend_config and with long tuple types shortened."""
    assert fixture_map["module"] == "jit_prog"
    assert fixture_map["matrix"] == "s32[16,21024]"
    assert sorted(fixture_map["matrix_copies"]) == ["copy.2308", "copy.2389", "copy.2398",
                                                    "copy.2417"]
    # the insides of a fusion are no events of their own
    assert "dynamic_slice.38" not in fixture_map["ops"]
    assert set(fixture_map["ops"]) == set(FIXTURE_MAP)


@pytest.mark.parametrize("instruction", sorted(FIXTURE_MAP))
def test_parser_gives_the_hand_written_map(fixture_map, instruction):
    assert fixture_map["ops"][instruction] == FIXTURE_MAP[instruction]


def test_parser_on_text_without_a_module():
    assert parse_hlo_phases("") == {"module": None, "matrix": None, "ops": {},
                                    "matrix_copies": []}


def test_vocabulary_is_flat_and_enclosing_is_inside_it():
    assert len(set(PHASES)) == len(PHASES) == 11
    # an inner phase is a word, or `bundle_expand` keyed with the word around it
    assert all(outer in PHASES and all(w in PHASES for w in inner.split("/"))
               and inner.split("/")[1:] in ([], ["bundle_expand"])
               for inner, outer in ENCLOSING.items())
    assert phase_of("jit(prog)/while/body/level_phase/while/body/split_scan/vmap()/"
                    "bundle_expand/gather") == "split_scan/bundle_expand"
    assert phase_of("jit(f)/bundle_expand/gather") == "bundle_expand"


# -- JitWatch.phase_map -------------------------------------------------------
def test_jitwatch_phase_map_round_trips():
    @jax.jit
    def toy_two_scopes(x):
        with jax.named_scope("replay"):
            y = jnp.sort(jnp.sin(x))
        with jax.named_scope("leaf_delta"):  # a cumsum's expansion loses its path
            return jnp.cumsum(y)

    w = JitWatch(toy_two_scopes, "test.toy_two_scopes")
    assert w.phase_map() is None and w.module == "jit_toy_two_scopes"
    out = w(jnp.arange(4096, dtype=jnp.float32))
    m = w.phase_map()
    assert m["name"] == "test.toy_two_scopes" and m["module"] == "jit_toy_two_scopes"
    assert m["matrix"] == "f32[4096]"
    assert set(m["ops"].values()) == {"replay", "leaf_delta"}
    # the remembered signature holds no buffer, and the map is a pure re-run
    spec = jax.tree_util.tree_leaves(w._compiled_spec)
    assert all(isinstance(s, jax.ShapeDtypeStruct) for s in spec)
    assert w.phase_map() == m
    maps = compilewatch.phase_maps(modules={"jit_toy_two_scopes"})
    assert [p["name"] for p in maps] == ["test.toy_two_scopes"]
    np.testing.assert_allclose(out, w(jnp.arange(4096, dtype=jnp.float32)))
    assert w.compiles == 1 and w.retraces == 0  # asking for the map is no retrace


def test_sharded_chunk_program_maps_too(monkeypatch):
    """The data-parallel chunk program (`shard_map`, arguments sharded over a
    mesh) rebuilds from its remembered signature like the serial one."""
    if len(jax.devices()) < 4:
        pytest.skip("needs a multi-device mesh")
    monkeypatch.setenv("LIGHTGBM_TPU_PGROW", "force")
    X, y, _ = _toy(1200, 6)
    params = {"objective": "binary", "num_leaves": 7, "verbose": -1, "tree_learner": "data"}
    bst = lgb.train(params, lgb.Dataset(X, label=y, params=dict(params)), 2, verbose_eval=False)
    pt = bst.boosting.ptrainer
    assert type(pt).__name__ == "ShardedPartitionedTrainer"
    (watch,) = pt._progs.values()
    m = watch.phase_map()
    assert m["module"] == watch.module == "jit_shard_body"
    assert {"update_root_hist", "level_phase", "replay", "leaf_delta",
            "chunk_epilogue"} <= set(m["ops"].values())
    assert "canon_reorder" not in m["ops"].values()  # the sharded program has none
    # the matrix is one device's block of the (shards, C, N) array, which the
    # program squeezes before its loops: a copy of either form is a copy of
    # the matrix (ROADMAP S9; until PR 33 `matrix_copy_ms_per_iter` could
    # only read 0.0 in a data-parallel cell)
    c, n = pt.p.shape[1:]
    # (on the CPU the interpreted kernels alias nothing, so this program has
    # copy sites of its own; the v5e compile test holds the chip's to none)
    assert m["matrix"] == f"s32[1,{c},{n}]"
    args, kwargs = watch._compiled_spec
    lines = watch._fn.lower(*args, **kwargs).compile().as_text().splitlines()
    # beside an instruction of the replay loop's body, and at the entry's end
    inside = next(k for k, v in m["ops"].items() if v == "replay_tail")
    body = next(i for i, ln in enumerate(lines) if re.match(rf"\s+(ROOT )?%{re.escape(inside)} = ", ln))
    root = max(i for i, ln in enumerate(lines) if ln.lstrip().startswith("ROOT "))
    planted = list(lines)
    planted.insert(root, f"  %copy.9001 = s32[1,{c},{n}]{{2,1,0}} copy(%p)")
    planted.insert(body, f"  %copy.9002 = s32[{c},{n}]{{1,0}} copy(%p)")
    planted.insert(body, f"  %copy.9003 = s32[{c},{n - 1}]{{1,0}} copy(%p)")  # not the matrix
    got = parse_hlo_phases("\n".join(planted))
    assert set(got["matrix_copies"]) - set(m["matrix_copies"]) == {"copy.9001", "copy.9002"}
    assert got["ops"]["copy.9002"] == "replay_tail" and "copy.9003" in got["ops"]


# -- the host spans -----------------------------------------------------------
@pytest.fixture
def tracing_off(monkeypatch):
    monkeypatch.delenv("LIGHTGBM_TPU_TRACE", raising=False)
    monkeypatch.delenv("LIGHTGBM_TPU_AUDIT", raising=False)
    monkeypatch.setenv("LIGHTGBM_TPU_PGROW", "force")
    tracer.close()
    tracer.path = None
    tracer.refresh_from_env()
    yield
    tracer.close()
    tracer.path = None


@pytest.fixture
def counted(monkeypatch):
    """Counts of the calls the instrumentation may only make while tracing."""
    calls = {"annotation": 0, "block": 0, "phase_map": 0}
    real_ann, real_block, real_map = (jax.profiler.TraceAnnotation, jax.block_until_ready,
                                      JitWatch.phase_map)

    def annotation(*a, **k):
        calls["annotation"] += 1
        return real_ann(*a, **k)

    def block(x):
        calls["block"] += 1
        return real_block(x)

    def phase_map(self):
        calls["phase_map"] += 1
        return real_map(self)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", annotation)
    monkeypatch.setattr(jax, "block_until_ready", block)
    monkeypatch.setattr(JitWatch, "phase_map", phase_map)
    return calls


def _booster(X, y, learner="serial"):
    params = {"objective": "binary", "num_leaves": 7, "verbose": -1, "tree_learner": learner}
    bst = lgb.Booster(params=params, train_set=lgb.Dataset(X, label=y, params=params))
    assert bst.boosting.ptrainer is not None
    return bst


@pytest.fixture
def fresh_stages(monkeypatch, tracing_off):
    """`tracer.stages` empty, whatever earlier tests of this worker kept."""
    monkeypatch.setattr(tracer, "stages", [])
    return tracer.stages


# the stages that end in work handed to the device, each with ONE wait
DEVICE_STAGES = {"bins_upload", "pack_matrix", "shard_pack"}


@pytest.mark.parametrize("learner", ["serial", "data"])
def test_tracing_off_costs_nothing(fresh_stages, counted, learner, monkeypatch):
    """No record, no TraceAnnotation, no re-lowering, and no block_until_ready
    but the one that ends each stage that hands work to the device, in
    construction: the untraced path issues the calls it issued before.  The
    data-parallel trainer's `shard_pack` stage and all-reduce counters too:
    construction is inside what is counted.  Nor is a shape count made for a
    span nobody writes (`perm_tiles`, PR 37, stands for `stream_counts`).  A
    stage is opened once a Dataset, a Booster or a program: the first chunk
    adds its program's `program_build`, the chunks after it add no stage."""
    from unittest import mock

    from lightgbm_tpu.boosting import ptrainer as ptrainer_mod

    if learner == "data" and len(jax.devices()) < 4:
        pytest.skip("needs a multi-device mesh")
    tiles = mock.Mock(wraps=ptrainer_mod.perm_tiles)
    monkeypatch.setattr(ptrainer_mod, "perm_tiles", tiles)
    X, y, _ = _toy()
    work = tracer.work_ops
    bst = _booster(X, y, learner)
    assert type(bst.boosting.ptrainer).__name__ == (
        "ShardedPartitionedTrainer" if learner == "data" else "PartitionedTrainer")
    built = [s["name"] for s in fresh_stages]
    waits = [n for n in built if n in DEVICE_STAGES]
    assert waits == ["bins_upload", "shard_pack" if learner == "data" else "pack_matrix"]
    assert counted == {"annotation": 0, "block": len(waits), "phase_map": 0}
    assert len(built) < 32 and "program_build" not in built
    bst.boosting.train_iters_partitioned(2, is_eval=False)
    first = fresh_stages[len(built):]
    assert [s["name"] for s in first] == ["program_build"]
    assert first[0]["program"].startswith("ptrainer.") and first[0]["depth"] == 0
    assert 0 < first[0]["backend_s"] < first[0]["dur_s"]
    for _ in range(2):
        bst.boosting.train_iters_partitioned(2, is_eval=False)
    assert len(fresh_stages) == len(built) + 1  # the second and third chunk: no stage
    assert tracer.work_ops == work
    assert counted == {"annotation": 0, "block": len(waits), "phase_map": 0}
    assert not tiles.called


def _tree_of(stages):
    """{parent name: [children, oldest first]} of the kept stages."""
    kids = {}
    for s in stages:
        kids.setdefault(s["parent"], []).append(s)
    return kids


@pytest.mark.parametrize("source", ["matrix", "binary", "sparse"])
def test_a_booster_leaves_its_setup_as_nested_stages(fresh_stages, counted, source, tmp_path):
    """With the sink off: `dataset_construct` and `booster_init` with the
    children of that way in, nested, their seconds inside their parent's."""
    params = {"objective": "binary", "num_leaves": 7, "verbose": -1}
    X, y, _ = _toy()
    if source == "sparse":
        table = _one_hot_csr()
    elif source == "binary":
        path = str(tmp_path / "table.bin")
        lgb.Dataset(X, label=y, params=params).construct().save_binary(path)
        del fresh_stages[:]
        table = path
    else:
        table = X
    work = tracer.work_ops
    bst = lgb.Booster(params=params, train_set=lgb.Dataset(table, label=y, params=params))
    assert bst.boosting.ptrainer is not None
    assert tracer.work_ops == work and counted["annotation"] == 0
    kids = _tree_of(fresh_stages)
    (booster,) = kids[None]
    assert booster["name"] == "booster_init" and booster["depth"] == 0
    under_booster = [s["name"] for s in kids["booster_init"]]
    (ds,) = [s for s in kids["booster_init"] if s["name"] == "dataset_construct"]
    assert (ds["source"], ds["rows"], ds["depth"]) == (source, 300, 1)
    under_ds = [s["name"] for s in kids["dataset_construct"]]
    if source == "sparse":  # the bundles are made at ingest and uploaded by the trainer
        assert under_ds == ["sparse_ingest"]
        assert [s["name"] for s in kids["sparse_ingest"]] == ["csr_bin", "find_bundles",
                                                              "build_bundled"]
        (found,) = [s for s in kids["sparse_ingest"] if s["name"] == "find_bundles"]
        assert found["columns"] == 16 and found["bundles"] == 3 and found["depth"] == 3
        assert under_booster == ["dataset_construct", "objective_init", "trainer_import",
                                 "pack_matrix"]
    else:
        assert under_ds == (["load_binary"] if source == "binary" else ["find_bins", "bin_rows"])
        if source == "binary":
            assert kids["dataset_construct"][0]["bytes"] == os.path.getsize(table)
        assert under_booster == ["dataset_construct", "objective_init", "bins_upload",
                                 "trainer_import", "find_bundles", "pack_matrix"]
        upload, found = kids["booster_init"][2], kids["booster_init"][4]
        assert upload["bytes"] == 300 * 4 and found["columns"] == 4 and found["bundles"] == 0
    pack = kids["booster_init"][-1]
    assert pack["rows"] == 300 and pack["channels"] == 16 and pack["bytes"] == bst.boosting.ptrainer.p.nbytes
    for parent, children in kids.items():
        if parent is not None:
            (p,) = [s for s in fresh_stages if s["name"] == parent]
            assert sum(c["dur_s"] for c in children) <= p["dur_s"]
            assert all(c["depth"] == p["depth"] + 1 and p["t0"] <= c["t0"] for c in children)
    assert counted["block"] == sum(s["name"] in DEVICE_STAGES for s in fresh_stages)
    # an already constructed Dataset opens nothing
    n = len(fresh_stages)
    assert bst.train_dataset.construct() is bst.train_dataset.construct()
    assert len(fresh_stages) == n


def test_shard_pack_is_a_stage_with_its_shards(fresh_stages):
    if len(jax.devices()) < 4:
        pytest.skip("needs a multi-device mesh")
    X, y, _ = _toy()
    bst = _booster(X, y, "data")
    (pack,) = [s for s in fresh_stages if s["name"] == "shard_pack"]
    assert pack["shards"] == bst.boosting.ptrainer.d == len(jax.devices())
    assert (pack["rows"], pack["parent"], pack["depth"]) == (300, "booster_init", 1)
    assert "pack_matrix" not in [s["name"] for s in fresh_stages]


@pytest.fixture
def traced_chunks(tmp_path, monkeypatch, counted):
    """Two fused chunks of two iterations with the JSONL sink on."""
    monkeypatch.setenv("LIGHTGBM_TPU_PGROW", "force")
    X, y, _ = _toy()
    bst = _booster(X, y)
    path = str(tmp_path / "trace.jsonl")
    tracer.configure(path)
    try:
        for _ in range(2):
            bst.boosting.train_iters_partitioned(2, is_eval=False)
        n_maps = tracer.write_program_maps(modules={"jit_prog"})
    finally:
        tracer.close()
        tracer.path = None
    with open(path) as f:
        recs = [json.loads(line) for line in f]
    return bst, recs, n_maps, dict(counted)


def test_nested_spans_are_each_right_alone(traced_chunks):
    bst, recs, _, calls = traced_chunks
    spans = [r for r in recs if r["ev"] == "span"]
    by = {}
    for s in spans:
        by.setdefault(s["name"], []).append(s)
    assert len(by["records_fetch"]) == len(by["device_wait"]) == len(by["records_d2h"]) == 2
    for fetch, wait, d2h in zip(by["records_fetch"], by["device_wait"], by["records_d2h"]):
        assert wait["dur_s"] + d2h["dur_s"] <= fetch["dur_s"]
        assert wait["parent"] == d2h["parent"] == "records_fetch"
        assert wait["depth"] == d2h["depth"] == fetch["depth"] + 1
    # the fence ran (tracing is on) and every span was an annotation too, but
    # `program_build`: a call is known to have built a program once it is over
    assert [s["parent"] for s in by["program_build"]] == ["chunk_program"]
    assert calls["block"] >= 2 and calls["annotation"] >= len(spans) - 1
    trees = by["trees_from_records"]
    assert [t["trees"] for t in trees] == [2, 2]
    models = bst.boosting.models
    assert sum(t["splits"] for t in trees) == sum(int(m.num_leaves) - 1 for m in models)


def test_trees_from_records_carries_the_stream_counts(traced_chunks):
    """Beside `trees` and `splits`: what the operation and byte models of the
    streaming kernels are made of (benchmarks/harness/hist_ops.py)."""
    from lightgbm_tpu.ops.pgrow import level_slots
    from lightgbm_tpu.ops.pkernels import hist_lanes

    bst, recs, _, _ = traced_chunks
    pt = bst.boosting.ptrainer
    n, f, b = pt.num_rows, pt.params.num_features, pt.params.num_bins
    for t in (r for r in recs if r["ev"] == "span" and r["name"] == "trees_from_records"):
        assert t["channels"] == pt.layout.C == 16 and t["col_groups"] == 1
        # PR 37: the tiles a block's compaction multiplies, of the dense form's 192
        assert t["perm_tiles"] == 33
        assert t["hist_cells"] == hist_lanes(f, b) and t["hist_cells"] % 128 == 0
        assert t["hist_cells"] >= f * b
        # two trees a chunk: every tree's first level streams every row once,
        # no level streams a row twice, and a tree's level phase is bounded by
        # its candidate table (log2(SMAX) + 1 levels, ops/pgrow.py)
        smax = level_slots(pt.params.num_leaves)
        assert 2 <= t["levels"] <= 2 * ((smax - 1).bit_length() + 1)
        assert 2 * n <= t["level_rows"] <= t["levels"] * n
        assert t["levels"] <= t["level_segments"] <= 2 * (pt.params.num_leaves - 1) * t["levels"]


@pytest.mark.parametrize("objective,rows,loop", [
    ("binary", 300, False), ("binary", 310, True), ("multiclass", 320, False)])
def test_scan_slots_counts_what_the_split_search_visited(tmp_path, monkeypatch, objective, rows,
                                                         loop):
    """`scan_slots` (PR 34), beside `level_segments` on the same span: all
    `level_slots` of every level where the search has no loop (any shape up to
    32 columns), whole batches of `scan_batch` slots up to the level's active
    count where it has one, summed over trees and classes like its neighbours.
    The loop is steered in the test, by a budget no 4 slots fit; each case has
    a row count of its own, because a grower traced once for a shape stays
    traced."""
    from lightgbm_tpu.ops import pgrow

    monkeypatch.setenv("LIGHTGBM_TPU_PGROW", "force")
    if loop:
        monkeypatch.setattr(pgrow, "SCAN_BATCH_BYTES", 1)
    X, yb, ym = _toy(rows)
    params = {"objective": objective, "num_leaves": 15, "min_data_in_leaf": 2, "verbose": -1}
    if objective == "multiclass":
        params["num_class"] = 3
    bst = lgb.Booster(params=params, train_set=lgb.Dataset(
        X, label=ym if objective == "multiclass" else yb, params=params))
    pt = bst.boosting.ptrainer
    path = str(tmp_path / "trace.jsonl")
    tracer.configure(path)
    try:
        bst.boosting.train_iters_partitioned(2, is_eval=False)
    finally:
        tracer.close()
        tracer.path = None
    with open(path) as f:
        (t,) = [r for r in map(json.loads, f)
                if r["ev"] == "span" and r["name"] == "trees_from_records"]
    smax = pgrow.level_slots(15)
    sb = pgrow.scan_batch(15, t["hist_cells"])
    assert t["trees"] == 2 * pt.K and t["levels"] >= 3 * t["trees"]
    if loop:
        assert sb == 4 < smax
        assert t["level_segments"] <= t["scan_slots"] <= t["level_segments"] + t["levels"] * (sb - 1)
        assert t["scan_slots"] % sb == 0 and t["scan_slots"] < t["levels"] * smax
    else:
        assert sb == smax == 16
        assert t["scan_slots"] == t["levels"] * smax


def test_serial_trees_from_records_reduce_nothing(traced_chunks):
    _, recs, _, _ = traced_chunks
    trees = [r for r in recs if r["ev"] == "span" and r["name"] == "trees_from_records"]
    assert [(t["shards"], t["allreduce_calls"], t["allreduce_bytes"]) for t in trees] == [(1, 0, 0)] * 2
    assert not [r for r in recs if r["ev"] == "span" and r["name"] == "shard_pack"]


@pytest.mark.parametrize("levelgrow", ["1", "0"])
def test_sharded_trees_from_records_count_the_allreduces(tmp_path, monkeypatch, levelgrow):
    """`allreduce_calls` and `allreduce_bytes`: what ONE chip handed to the
    histogram `psum`s over the span's trees (the root's, one a level, two
    histograms a tail split as six planes of `hist_lanes` lanes), out of the
    device program's own counts; `shards`;
    and the host span around the numpy packing.  Under LEVELGROW=0 no level is
    reduced and every split takes the tail, so the count is known from the
    trees alone."""
    from lightgbm_tpu.ops.pgrow import level_slots
    from lightgbm_tpu.ops.pkernels import hist_lanes

    if len(jax.devices()) < 4:
        pytest.skip("needs a multi-device mesh")
    monkeypatch.setenv("LIGHTGBM_TPU_PGROW", "force")
    monkeypatch.setenv("LIGHTGBM_TPU_LEVELGROW", levelgrow)
    X, y, _ = _toy(1203, 6)
    path = str(tmp_path / "trace.jsonl")
    tracer.configure(path)
    try:
        bst = _booster(X, y, "data")
        bst.boosting.train_iters_partitioned(3, is_eval=False)
    finally:
        tracer.close()
        tracer.path = None
    with open(path) as f:
        spans = [r for r in map(json.loads, f) if r["ev"] == "span"]
    pt = bst.boosting.ptrainer
    (pack,) = [s for s in spans if s["name"] == "shard_pack"]
    assert (pack["rows"], pack["shards"]) == (1203, pt.d) and pack["dur_s"] > 0
    (t,) = [s for s in spans if s["name"] == "trees_from_records"]
    f_, b = pt.params.num_features, pt.params.num_bins
    root = 4 * f_ * b * 3
    level = 4 * level_slots(pt.params.num_leaves) * 16 * hist_lanes(f_, b)
    assert t["shards"] == pt.d == len(jax.devices()) and t["trees"] == 3
    tails = t["allreduce_calls"] - t["trees"] - t["levels"]
    tail = 4 * 6 * hist_lanes(f_, b)
    assert t["allreduce_bytes"] == t["trees"] * root + t["levels"] * level + tails * tail
    if levelgrow == "0":
        assert t["levels"] == 0 and tails == t["splits"] == 3 * 6
    else:
        # 7 leaves: the level phase holds whole trees of 3 levels, so a tree
        # takes its 6 splits from the candidate tables unless it grew deeper
        assert 3 <= t["levels"] <= 3 * 4 and 0 <= tails < t["splits"]


@pytest.mark.parametrize("name", ["chunk_program", "records_fetch"])
def test_old_readers_inputs_keep_name_and_nesting(traced_chunks, name):
    """`chunk_device_wait_ms_per_iter`, `driver_host_ms_per_iter` and
    `host_ms_per_chunk` sum these two spans by name: both still come once a
    chunk, side by side under `tree`, neither inside the other."""
    _, recs, _, _ = traced_chunks
    got = [r for r in recs if r["ev"] == "span" and r["name"] == name]
    assert len(got) == 2
    assert all(r["parent"] == "tree" and r["depth"] == 1 for r in got)
    if name == "chunk_program":
        assert all(r["iters"] == 2 for r in got)


def test_program_records_only_on_demand(traced_chunks):
    """`write_program_maps` puts the chunk program's map into the sink; the
    training path and `close()` never build one."""
    _, recs, n_maps, calls = traced_chunks
    programs = [r for r in recs if r["ev"] == "program"]
    assert n_maps == len(programs) == calls["phase_map"] >= 1
    chunk = [p for p in programs if p["name"].startswith("ptrainer.chunk")]
    assert chunk and chunk[0]["module"] == "jit_prog"
    # (every live chunk program of the process is written: this booster's is the
    # one with a level phase if an earlier test left a LEVELGROW=0 program alive)
    assert any({"level_phase", "replay", "leaf_delta"} <= set(p["ops"].values()) for p in chunk)
    assert all("canon_reorder" not in p["ops"].values() for p in chunk)
    assert recs.index(programs[0]) > max(i for i, r in enumerate(recs) if r["ev"] == "span")


def test_trees_and_scores_bit_equal_with_and_without_tracing(tmp_path, monkeypatch):
    monkeypatch.setenv("LIGHTGBM_TPU_PGROW", "force")
    monkeypatch.delenv("LIGHTGBM_TPU_TRACE", raising=False)
    X, y, _ = _toy(400, 5)
    out = {}
    try:
        for mode in ("off", "on"):
            tracer.close()
            tracer.path = None
            if mode == "on":
                tracer.configure(str(tmp_path / "t.jsonl"))
            bst = _booster(X, y)
            bst.boosting.train_iters_partitioned(3, is_eval=False)
            out[mode] = (bst.model_to_string(), np.asarray(bst.boosting.scores))
    finally:
        tracer.close()
        tracer.path = None
    assert out["off"][0] == out["on"][0]
    np.testing.assert_array_equal(out["off"][1], out["on"][1])
