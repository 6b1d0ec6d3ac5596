"""The chunk programs of the benchmark's configurations (21M x 28 and 400,000 x
2,000 on one chip; 400,000 x 2,000 split over four), compiled for a
described (not attached) TPU v5e: what the chip's own compiler leaves of the
names the program gives itself, and where it copies the whole packed matrix.
Counts from a compile, never speeds; nothing runs.

The topology is described inside a fixture (never at import: one process at a
time may load libtpu, and every xdist worker imports this file), and this is
the only test file that does so."""

import collections
import contextlib
import os
import re

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from lightgbm_tpu.obs.phases import PHASES, parse_hlo_phases  # noqa: E402
from lightgbm_tpu.ops.pkernels import hist_lanes  # noqa: E402

ROWS = 21_000_000
# the cells' configuration (benchmarks/configs/higgs.json)
PARAMS = {"objective": "binary", "max_bin": 63, "num_leaves": 255, "learning_rate": 0.1,
          "min_data_in_leaf": 1, "min_sum_hessian_in_leaf": 100, "verbose": -1}
NOT_LAUNCHED = ("get-tuple-element", "tuple", "constant", "bitcast", "while", "conditional")
EIGHTH_OF_THE_CHIP = 2.15e9  # the contract's floor for a cell whose device is busy


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@contextlib.contextmanager
def _compiling_for_a_described_chip():
    """Kernels through Mosaic (the trainer is built under PGROW=force), and no
    compile cache: a compile for a described chip is written to it and cannot
    be read back."""
    from jax.experimental.compilation_cache import compilation_cache

    old = os.environ.get("LIGHTGBM_TPU_PGROW")
    os.environ["LIGHTGBM_TPU_PGROW"] = "force"
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()
        if old is None:
            del os.environ["LIGHTGBM_TPU_PGROW"]
        else:
            os.environ["LIGHTGBM_TPU_PGROW"] = old


def _small_trainer(rows=ROWS, cols=28, small_rows=20_000, **more):
    """A fused trainer on a small table: its closures do not depend on the row
    count, so it is then told the real one."""
    import lightgbm_tpu as lgb

    params = dict(PARAMS, **more)
    rng = np.random.RandomState(7)
    X = rng.randn(small_rows, cols)
    y = (X[:, 0] + X[:, 1] * X[:, 2] > 0).astype(float)
    bst = lgb.Booster(params=params, train_set=lgb.Dataset(X, label=y, params=dict(params)))
    pt = bst.boosting.ptrainer
    pt.num_rows = rows
    pt.params = pt.params._replace(num_rows=rows)
    pt.interpret = False
    return pt


def _compiled_chunk_program(pt, one_chip, rows, cols):
    """(text, argument bytes, temporary bytes) of ``pt``'s serial chunk
    program at ``rows`` x ``cols``, compiled for the described chip."""
    prog = pt._build_program(pt.CHUNK_ALLOC, False, 1, cols)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    key = pt._base_key
    compiled = prog.lower(
        spec((pt.p.shape[0], rows + 1024), jnp.int32), spec((), jnp.float32),
        spec(key.shape, key.dtype), spec((), jnp.int32), spec((), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    return compiled.as_text(), mem.argument_size_in_bytes, mem.temp_size_in_bytes


@pytest.fixture(scope="module")
def higgs_compiled(one_chip):
    """The serial chunk program at 21M rows x 28 features: (text, argument
    bytes, temporary bytes)."""
    with _compiling_for_a_described_chip():
        return _compiled_chunk_program(_small_trainer(), one_chip, ROWS, 28)


@pytest.fixture(scope="module")
def compiled_text(higgs_compiled):
    return higgs_compiled[0]


# rows a chip, columns, rows of the small table the trainer is built on
SHARDED_SHAPES = {"higgs_21m_x_28_a_chip": (ROWS, 28, 20_000),
                  "epsilon_100k_x_2000_a_chip": (100_000, 2_000, 4096)}


@pytest.fixture(scope="module", params=sorted(SHARDED_SHAPES))
def sharded_compiled(request, topo):
    """The data-parallel chunk program (``tree_learner=data``) compiled for the
    four described chips, at 21M rows a chip (which no cell of the benchmark
    runs) and at the benchmark's ``epsilon-dp4`` shape, 400,000 x 2,000 split
    100,000 rows a chip: the trainer is built on four CPU devices and then
    handed the chips' mesh.  (text, argument bytes and temporary bytes of ONE
    chip, rows a chip, channel rows, the width of a shard's block, the shape's
    name in SHARDED_SHAPES)."""
    from unittest import mock

    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import lightgbm_tpu.parallel as par

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices to build the sharded trainer on")
    rows, cols, small_rows = SHARDED_SHAPES[request.param]
    cpu_mesh = par.make_mesh(4)
    with _compiling_for_a_described_chip():
        with mock.patch.object(par, "make_mesh", lambda n_devices=None: cpu_mesh):
            pt = _small_trainer(rows=rows, cols=cols, small_rows=small_rows, tree_learner="data")
        assert type(pt).__name__ == "ShardedPartitionedTrainer" and pt.d == 4
        pt.mesh = mesh = Mesh(np.array(topo.devices[:4]), ("data",))
        prog = pt._build_program(pt.CHUNK_ALLOC, False, 1, cols)

        def spec(shape, dtype, *axes):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(mesh, P(*axes)))

        key = pt._base_key
        channels = pt.p.shape[1]
        width = -(-(rows + 1024) // 128) * 128  # as ShardedPartitionedTrainer.__init__ packs it
        compiled = prog.lower(
            spec((4, channels, width), jnp.int32, "data"), spec((4,), jnp.int32, "data"),
            spec((), jnp.float32), spec(key.shape, key.dtype), spec((), jnp.int32),
            spec((), jnp.int32)).compile()
        mem = compiled.memory_analysis()
        return (compiled.as_text(), mem.argument_size_in_bytes, mem.temp_size_in_bytes,
                rows, channels, width, request.param)


@pytest.fixture(scope="module")
def sharded_text(sharded_compiled):
    return sharded_compiled[0]


@pytest.fixture(scope="module")
def phase_map(compiled_text):
    return parse_hlo_phases(compiled_text)


def test_module_and_matrix(phase_map):
    assert phase_map["module"] == "jit_prog"
    assert phase_map["matrix"] == "s32[16,21001024]"


@pytest.mark.parametrize("phase", [p for p in PHASES
                                   if p not in ("canon_reorder", "sample", "bundle_expand",
                                                "rank_grad")])
def test_the_compiler_keeps_the_phase(request, phase_map, phase):
    """Every phase the cells' programs run (they draw no sample and, since PR
    30, reorder nothing at a tree's start; a bundle's expansion is keyed with
    the phase around it) still owns instructions after XLA's passes: in the
    Higgs program, and the two that only K > 1 opens (a class's delta and the
    K-class gradient pass) in the Covertype one."""
    if phase in ("score_add", "multi_update"):
        pm = parse_hlo_phases(request.getfixturevalue("covtype_compiled")[0])
        assert phase in pm["ops"].values()
        return
    assert phase in phase_map["ops"].values()


_RESULT = re.compile(r"^\s+(?:ROOT )?%(\S+) = (\(.*?\)|\S+) ([\w-]+)\(", re.M)
_LAYOUT = re.compile(r"\{[^{}]*\}")
_ARRAY = re.compile(r"s32\[([\d,]*)\]")  # the packed matrix is int32 words


def _moves_of_matrix_size(text, elements):
    """Instructions anywhere in the module (fusion bodies included) that
    gather, scatter, sort, transpose or copy an int32 array of at least
    ``elements`` elements: what a reorder of the packed matrix would compile
    to."""
    found = []
    for name, shape, opcode in _RESULT.findall(text):
        if opcode not in ("gather", "scatter", "sort", "transpose", "copy"):
            continue
        sizes = [int(np.prod([int(d) for d in dims.split(",") if d] or [1]))
                 for dims in _ARRAY.findall(shape)]
        if sizes and max(sizes) >= elements:
            found.append((name, opcode, shape))
    return found


@pytest.mark.parametrize("cell", ["higgs_21m_x_28", "epsilon_400k_x_2000"])
def test_no_program_reorders_the_matrix(request, cell):
    """PR 30 deleted the canonical reorder at a tree's start: the serial chunk
    programs of both configurations own no `canon_reorder` instruction, and
    nothing in them gathers, scatters, sorts, transposes or copies an array of
    the matrix's size (the parent's: `fusion s32[21000000,16]`, 469 ms a tree,
    and at 512 channel rows a transposing copy around a gather of 2 KB rows).
    Row vectors of `n` elements still move: the epilogue puts the score
    plane back in row order through ROWID, by a sort or a scatter as the row
    count asks (the epilogue's own tests, below); `leaf_delta` moves none
    (the test after this one)."""
    if cell == "higgs_21m_x_28":
        text, rows, channels = request.getfixturevalue("compiled_text"), ROWS, 16
    else:
        text, rows, channels = request.getfixturevalue("epsilon_compiled")[0], EPS_ROWS, 512
    pm = parse_hlo_phases(text)
    assert pm["matrix"] == f"s32[{channels},{rows + 1024}]"
    assert "canon_reorder" not in pm["ops"].values()
    assert "canon_reorder" not in text
    assert pm["matrix_copies"] == []
    assert _moves_of_matrix_size(text, channels * rows) == []
    # the check sees such a move where there is one
    planted = f"  %fusion.1 = s32[{rows},{channels}]{{1,0:T(8,128)}} gather(%p, %i), offset_dims={{1}}\n"
    assert _moves_of_matrix_size(planted, channels * rows) == [
        ("fusion.1", "gather", f"s32[{rows},{channels}]{{1,0:T(8,128)}}")]


_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) \(.*\{$")
_ANY_ARRAY = re.compile(r"\b[a-z]+\d*\[([\d,]*)\]")
_CALLS = re.compile(r"\bcalls=%([\w.-]+)")


_Inst = collections.namedtuple("_Inst", "name shape opcode calls")


def _by_computation(text):
    """computation -> its instructions (``calls``: the body of a fusion)"""
    comps, cur = {}, None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            cur = comps.setdefault(m.group(1), [])
        elif cur is not None and (m := _RESULT.match(line)):
            called = _CALLS.search(line)
            cur.append(_Inst(*m.groups(), called.group(1) if called else None))
    return comps


def _dims(shape):
    """The dimensions of every array in a result shape (a tuple has several)."""
    return [[int(d) for d in dims.split(",") if d] for dims in _ANY_ARRAY.findall(shape)]


@pytest.mark.parametrize("cell", ["higgs_21m_x_28", "epsilon_400k_x_2000"])
def test_leaf_delta_looks_values_up_by_compare(request, cell):
    """PR 32: a row's leaf value comes from comparing its position with the
    255 sorted segment bounds in ONE fusion (``ops/pgrow.py::segment_values``).
    Nothing the phase map gives to `leaf_delta`, fusion bodies included,
    gathers, scatters, sorts or prefix-sums `n` or more elements (the parent's:
    a scatter into ``s32[n+1]``, a ``reduce-window`` cumsum and two gathers of
    ``[n]``, 377 of the phase's 383 ms at 21M rows); the ``(255, n)`` operand
    of the lookup's reduction exists inside its fusion only; and the program's
    temporaries are no more than the parent's."""
    if cell == "higgs_21m_x_28":
        (text, _, temps), rows, parent_temps = request.getfixturevalue("higgs_compiled"), ROWS, 845_272_064
    else:
        (text, _, temps), rows, parent_temps = (
            request.getfixturevalue("epsilon_compiled"), EPS_ROWS, 6_976_412_160)
    leaves = PARAMS["num_leaves"]
    ops = parse_hlo_phases(text)["ops"]
    comps = _by_computation(text)
    launched = [i for body in comps.values() for i in body if i.name in ops]
    mine = [i for i in launched if ops[i.name] == "leaf_delta"]
    fusions = [i for i in mine if i.opcode == "fusion"]
    assert fusions
    inside = mine + [i for f in fusions for i in comps[f.calls]]
    assert [i[:3] for i in inside
            if i.opcode in ("gather", "scatter", "sort", "reduce-window")
            and max(map(np.prod, _dims(i.shape))) >= rows] == []
    lookups = [f for f in fusions
               if any(i.opcode == "reduce" for i in comps[f.calls])
               and any(sorted(d) == [leaves, rows] for i in comps[f.calls] for d in _dims(i.shape))]
    assert len(lookups) == 1 and _dims(lookups[0].shape) == [[rows]]
    assert [i[:3] for i in launched
            if any({leaves, rows} <= set(d) for d in _dims(i.shape))] == []
    assert temps <= parent_temps


def _inside(comps, insts):
    """``insts`` and what their fusions call, at any depth (XLA nests the
    scatter's kCustom fusion inside a loop fusion)."""
    out, todo = [], list(insts)
    while todo:
        i = todo.pop()
        out.append(i)
        if i.calls:
            todo.extend(comps[i.calls])
    return out


def _epilogue_moves(text, rows):
    """(the scatters of ``rows`` or more elements, the shapes of the sorts)
    under the `chunk_epilogue` scope, fusion bodies included."""
    ops = parse_hlo_phases(text)["ops"]
    comps = _by_computation(text)
    mine = [i for body in comps.values() for i in body if ops.get(i.name) == "chunk_epilogue"]
    inside = _inside(comps, mine)
    scatters = [i[:3] for i in inside
                if i.opcode == "scatter" and max(map(np.prod, _dims(i.shape))) >= rows]
    return scatters, [_LAYOUT.sub("", i.shape) for i in inside if i.opcode == "sort"]


def test_the_epilogue_sorts_and_scatters_nothing(compiled_text):
    """From ``pgrow.SORT_MIN_ROWS`` rows on the epilogue puts the score
    channel back in row order with ONE key-value sort on ROWID
    (``pgrow.in_row_order``): under `chunk_epilogue` no scatter of `n` or
    more elements, and exactly one sort, over ``(s32[n], f32[n])``.  The
    scatter ``zeros(n).at[rowid].set(score)`` compiles at 21M rows to an
    index select, this same sort and a kCustom scatter fusion through the
    sorted ids (0.0515 and 0.1249 s a chunk on a v5e).  The
    sort is unstable because the ids are distinct: a stable one carries an
    ``s32[n]`` iota as a third operand."""
    assert _epilogue_moves(compiled_text, ROWS) == ([], [f"(s32[{ROWS}], f32[{ROWS}])"])


def test_below_the_sort_size_the_epilogue_scatters(epsilon_compiled):
    """At Epsilon's 400,000 rows, under ``SORT_MIN_ROWS``, the compiler makes
    the scatter a direct one, and the epilogue keeps it: one scatter of `n`,
    no sort (a sort would add ~8 s to a cold compile on the v5e's host, for
    2 ms a chunk)."""
    from lightgbm_tpu.ops.pgrow import SORT_MIN_ROWS

    assert EPS_ROWS < SORT_MIN_ROWS
    scatters, sorts = _epilogue_moves(epsilon_compiled[0], EPS_ROWS)
    assert [op for _, _, op in scatters] == ["scatter"] and sorts == []


def test_the_sharded_epilogue_sorts_at_its_size(sharded_compiled):
    """The same on one chip of the four-chip program, at a chip's rows: the
    sort at 21M rows a chip (a shard's padding rows keep their own ids, so
    the key is a permutation), the direct scatter at 100,000."""
    from lightgbm_tpu.ops.pgrow import SORT_MIN_ROWS

    text, rows = sharded_compiled[0], sharded_compiled[3]
    scatters, sorts = _epilogue_moves(text, rows)
    if rows >= SORT_MIN_ROWS:
        assert (scatters, sorts) == ([], [f"(s32[{rows}], f32[{rows}])"])
    else:
        assert [op for _, _, op in scatters] == ["scatter"] and sorts == []


def test_the_epilogue_check_sees_a_scatter(one_chip):
    """The scatter into zeros, compiled alone at 21M rows under the same scope: the
    check finds its scatter and its sort, and the helper's one sort."""
    from lightgbm_tpu.ops.pgrow import in_row_order
    from lightgbm_tpu.obs.phases import CHUNK_EPILOGUE

    def scattered(rowid, score):
        with jax.named_scope(CHUNK_EPILOGUE):
            return jnp.zeros((ROWS,), jnp.float32).at[rowid].set(score)

    def sorted_(rowid, score):
        with jax.named_scope(CHUNK_EPILOGUE):
            return in_row_order(rowid, [score])

    args = (jax.ShapeDtypeStruct((ROWS,), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((ROWS,), jnp.float32, sharding=one_chip))
    with _compiling_for_a_described_chip():
        texts = [jax.jit(f).lower(*args).compile().as_text() for f in (scattered, sorted_)]
    scatters, sorts = _epilogue_moves(texts[0], ROWS)
    assert [op for _, _, op in scatters] == ["scatter"]
    assert sorts == [f"(s32[{ROWS}], f32[{ROWS}])"]
    assert _epilogue_moves(texts[1], ROWS) == ([], [f"(s32[{ROWS}], f32[{ROWS}])"])


@pytest.mark.parametrize("kernel,phase", [
    ("update_and_root_hist", "update_root_hist"), ("level_stream", "level_phase"),
    ("split_stream", "replay_tail"), ("score_add", "chunk_epilogue")])
def test_mosaic_kernels_carry_their_names_and_phases(compiled_text, phase_map, kernel, phase):
    calls = re.findall(rf'%({kernel}(?:\.\d+)?) = .*custom_call_target="tpu_custom_call"',
                       compiled_text)
    assert calls, f"no Mosaic custom call named {kernel}"
    assert {phase_map["ops"][c] for c in calls} == {phase}


def test_whole_matrix_copy_sites(phase_map):
    """None (PR 27).  The packed matrix goes loop carry -> aliased kernel ->
    loop carry and through no conditional: the parent's six static sites (three
    in the replay, two in the level phase, one in the stopped no-op branch) were
    all copy-insertion's answer to a ``lax.cond`` that carried the matrix.  A
    site that comes back inside a loop costs 1.34 GB of traffic a launch: read
    its operand and users in the compiled text before guessing (PERF.md
    section 5 has the table of causes)."""
    sites = collections.Counter(phase_map["ops"][c] for c in phase_map["matrix_copies"])
    assert sites == {}


def test_no_conditional_carries_the_matrix(compiled_text, phase_map):
    """The contract behind the count above, read off the compiled text: of the
    parent's three conditionals (the ``stopped`` test around every iteration,
    ``gain > 0`` and ``has_pre`` in the replay) only ``has_pre`` is left, and
    no conditional's operands or result hold an array of the matrix's shape;
    ``split_stream`` is still one kernel, launched outside any branch."""
    types = {m.group(1): m.group(2) for m in re.finditer(
        r"^\s+(?:ROOT )?%(\S+) = (\(.*?\)|\S+) [\w-]+\(", compiled_text, re.M)}
    conds = re.findall(r"%(\S+) = .* conditional\((.*?)\), branch_computations", compiled_text)
    assert len(conds) == 1
    (name, operands), = conds
    assert phase_map["ops"][name] == "replay"
    for inst in [name] + re.findall(r"%([\w.-]+)", operands):
        assert phase_map["matrix"] not in types[inst], inst
    calls = re.findall(r'%(split_stream(?:\.\d+)?) = .*custom_call_target="tpu_custom_call"',
                       compiled_text)
    assert len(calls) == 1


def test_what_no_phase_claims_is_bookkeeping(compiled_text, phase_map):
    """Outside every scope: the main loop with its counter and stop test, and
    the record stores.  Nothing the size of the table."""
    unclaimed = {k for k, v in phase_map["ops"].items() if v is None}
    table_sized = []
    for line in compiled_text.splitlines():
        m = re.match(r"^\s+(?:ROOT )?%(\S+) = (\(.*?\)|\S+) ([\w-]+)\(", line)
        if not m or m.group(1) not in unclaimed or m.group(3) in NOT_LAUNCHED:
            continue
        if "21000000" in m.group(2) or "21001024" in m.group(2):
            table_sized.append(m.group(1))
    assert table_sized == []
    assert len(unclaimed) < 0.1 * len(phase_map["ops"])


_ALL_REDUCE = re.compile(r"^\s+(?:ROOT )?%\S+ = (\S+) all-reduce(?:-start)?\(", re.M)


def test_sharded_program_copies_no_shard(sharded_compiled):
    """The same contract in the data-parallel program, at both shapes (the
    parent of PR 27 compiled to six ``copy s32[16,...]`` sites and three
    conditionals here too, and its one trace on record had the copies at 66%
    of the time): no copy of a shard of the matrix, with or without the
    leading 1 of a device's block, and nothing that gathers, scatters, sorts or
    transposes an int32 array of its size; one conditional.  At 512 channel
    rows this also holds the width of a shard's block to whole 128-lane tiles:
    at 101,024 the TPU's default layout for the block put the channels on the
    lanes and the program transposed the shard in and out of every chunk (two
    ``copy s32[1,512,101024]``, 397 MB more temporaries; PR 33)."""
    text, _, _, rows, channels, width, _ = sharded_compiled
    assert "tpu_custom_call" in text
    pm = parse_hlo_phases(text)
    assert pm["module"] == "jit_shard_body"
    assert pm["matrix"] == f"s32[1,{channels},{width}]" and width % 128 == 0
    assert pm["matrix_copies"] == []
    assert re.findall(rf" = s32\[(?:1,)?{channels},{width}\]\S* copy\(", text) == []
    assert _moves_of_matrix_size(text, channels * rows) == []
    assert len(re.findall(r" conditional\(", text)) == 1


def test_what_the_sharded_program_all_reduces(sharded_compiled):
    """WHAT is reduced and WHO searches, pinned: three ``all-reduce`` sites and
    no other collective (the root histogram, a level's, a tail split's two
    children as six planes of ``hist_lanes`` lanes, so that no lane is padding:
    the tail's stays inside the ``has_pre`` conditional's classic branch), and
    the largest operand is a level's histograms of ALL 256 slots
    in the kernel's layout, 16 rows of ``hist_lanes`` lanes a slot: 2.097 GB at
    2,000 columns x 63 bins.  A change to reduce-scatter by column group, to
    the active slots alone or to re-summed rows shows here, and is the
    ``perf_opt`` that has this program for its parent (ISSUE 33)."""
    text, name = sharded_compiled[0], sharded_compiled[-1]
    cols = SHARDED_SHAPES[name][1]
    lanes = hist_lanes(cols, PARAMS["max_bin"])  # 1,792 and 128,000
    shapes = [_LAYOUT.sub("", sh) for sh in _ALL_REDUCE.findall(text)]
    assert sorted(shapes) == sorted([f"f32[256,16,{lanes}]", f"f32[{cols},63,3]", f"f32[6,{lanes}]"])
    assert max(shapes, key=lambda sh: max(map(np.prod, _dims(sh)))) == f"f32[256,16,{lanes}]"
    for other in ("all-gather", "reduce-scatter", "all-to-all", "collective-permute"):
        assert f" {other}(" not in text and f" {other}-start(" not in text


def test_what_one_chip_of_the_sharded_program_holds(sharded_compiled):
    """Arguments + temporaries of ONE chip.  At the ``epsilon-dp4`` shape: its
    207 MB quarter of the matrix but the WHOLE level histogram and its
    all-reduced copy, 4.41 GB.  The split search's whole-array temporaries
    over them (2.77 GB more, 7.18 in all) went in PR 34: it holds 4 slots at a
    time.  The floor that applies is the busy device's eighth of the chip,
    2.15e9 (the device idles 0.4-0.5% of a window), and the pinned number
    lives HERE: benchmarks/configs/epsilon-dp4.json (``per_chip_bytes``,
    ``reduced_detail``) quotes the 7.18 GB of PR 33's program until a
    ``benchmark`` PR rewrites it.  A change that moves the number says so in
    PERF.md."""
    _, args, temps, _, channels, width, name = sharded_compiled
    assert 4 * channels * width <= args < 4 * channels * width + 4096
    if name == "epsilon_100k_x_2000_a_chip":
        assert abs(args + temps - 4_408_907_776) < 2 ** 20
        assert EIGHTH_OF_THE_CHIP < args + temps < 14e9
    else:  # 104 bytes a row, as the serial program at 21M rows
        assert EIGHTH_OF_THE_CHIP < args + temps < 2.3e9


# -- the wide configuration (benchmarks/configs/epsilon.json) ----------------
EPS_ROWS, EPS_COLS = 400_000, 2_000
EPS_MATRIX = f"s32[512,{EPS_ROWS + 1024}]"


@pytest.fixture(scope="module")
def epsilon_compiled(one_chip):
    """The serial chunk program at 400,000 rows x 2,000 columns (the parameters
    are the same published ones), compiled for the described chip: (text,
    argument bytes, temporary bytes)."""
    with _compiling_for_a_described_chip():
        pt = _small_trainer(rows=EPS_ROWS, cols=EPS_COLS, small_rows=4096)
        assert type(pt).__name__ == "PartitionedTrainer" and pt.p.shape[0] == 512
        return _compiled_chunk_program(pt, one_chip, EPS_ROWS, EPS_COLS)


@pytest.mark.parametrize("kernel", ["update_and_root_hist", "level_stream", "split_stream",
                                    "score_add"])
def test_epsilon_kernels_compile_through_mosaic(epsilon_compiled, kernel):
    """Every kernel the K = 1 chunk program reaches is a Mosaic custom call at
    512 channel rows and 128,000 histogram lanes: the compiler accepted the
    rolled column groups and the VMEM each kernel asks for."""
    text = epsilon_compiled[0]
    assert re.findall(rf'%({kernel}(?:\.\d+)?) = .*custom_call_target="tpu_custom_call"', text)


def test_epsilon_program_keeps_the_carry_contract(epsilon_compiled):
    """PR 27's rule at width: no copy of the matrix, neither one that keeps its
    layout (what copy insertion makes for a conditional that carries it) nor
    the transposing one the canonical reorder's gather made once a tree until
    PR 30 deleted the step; the one conditional is the replay's over the small
    tables."""
    text = epsilon_compiled[0]
    pm = parse_hlo_phases(text)
    assert pm["matrix"] == EPS_MATRIX
    assert re.findall(rf" = s32\[512,{EPS_ROWS + 1024}\]\S* copy\(", text) == []
    assert pm["matrix_copies"] == []
    assert len(re.findall(r" conditional\(", text)) == 1
    assert "split_scan" in pm["ops"].values()


def test_epsilon_training_fills_the_chip(epsilon_compiled):
    """What training holds, the program's arguments and temporaries: 821 MB of
    matrix and 2,103 MB of temporaries, of which a level's histograms are
    2,097, 2.92 GB (2,924,763,648 bytes; 2,939,331,584 until PR 40, whose
    search holds a batch's planes one lane a cell).  The split search's
    whole-array temporaries (4.86 GB more, 7.80 in all) went in PR 34.  The floor that applies is the busy
    device's eighth of the chip (the device idles 0.4% of a window), and the
    number is pinned here: benchmarks/configs/epsilon.json's
    ``reduced_detail`` quotes PR 30's 7.80 GB until a ``benchmark`` PR
    rewrites it.  The configuration's rows stay the published 400,000."""
    _, args, temps = epsilon_compiled
    assert abs(args + temps - 2_924_763_648) < 2 ** 20
    assert EIGHTH_OF_THE_CHIP < args + temps < 14e9
    assert 0.8e9 < args < 0.9e9  # the packed matrix, 2,048 bytes a row


def _split_scan_instructions(text):
    """(launched, not launched) instructions the phase map gives to
    `split_scan`, as _Inst; fusion bodies are not entered."""
    ops = parse_hlo_phases(text)["ops"]
    mine = [i for body in _by_computation(text).values() for i in body
            if ops.get(i.name) == "split_scan"]
    return ([i for i in mine if i.opcode not in NOT_LAUNCHED],
            [i for i in mine if i.opcode in NOT_LAUNCHED])


def _search_is_batched(text):
    from lightgbm_tpu.ops.pgrow import scan_batch

    lanes = hist_lanes(EPS_COLS, PARAMS["max_bin"])
    batch = scan_batch(PARAMS["num_leaves"], lanes)
    assert batch == 4
    launched, rest = _split_scan_instructions(text)
    assert [i.opcode for i in rest].count("while") == 1
    whole = [(i.opcode, d) for i in launched for d in _dims(i.shape) if d[:1] == [256]]
    assert whole and max(np.prod(d) for _, d in whole) < lanes, whole  # tables, no histogram
    large = [d for i in launched for d in _dims(i.shape) if np.prod(d) >= 2 * lanes]
    assert large and {d[0] for d in large} == {batch}, large
    assert any(d == [256, 16, lanes] for i in rest for d in _dims(i.shape))  # carried, not made
    assert re.findall(rf" = f32\[256,16,{lanes}\]\S* copy(?:-start)?\(", text) == []


def _search_is_straight(text, count=None):
    launched, rest = _split_scan_instructions(text)
    assert "while" not in [i.opcode for i in rest]
    assert count is None or len(launched) + len(rest) == count
    large = [d for i in launched for d in _dims(i.shape)
             if np.prod(d) >= 256 * hist_lanes(28, PARAMS["max_bin"])]
    assert large and {d[0] for d in large} == {256}, large


_ARRAY_WITH_LAYOUT = re.compile(r"\b[a-z]+\d*\[([\d,]*)\](?:\{([\d,]*)(?::T\((\d+)(?:,(\d+))?\))?)?")


def _arrays(shape):
    """(dimensions, lanes and sublanes the tiled layout pads them to / cells)
    of every array in a result shape.  `f32[2,2000,63,3]{3,2,1,0:T(8,128)}`
    has its 3 on the lanes, padded to 128, and reads 42.7; `f32[4,2,2000,63]
    {2,3,1,0:T(8,128)}` has the 2,000 there and reads 1.04."""
    found = []
    for dims, order, a, b in _ARRAY_WITH_LAYOUT.findall(shape):
        dims = [int(d) for d in dims.split(",") if d]
        order = [int(d) for d in order.split(",") if d]
        if not dims or not order or not a:
            found.append((dims, 1.0))
            continue
        tile = (int(a), int(b)) if b else (1, int(a))
        minor = dims[order[0]]
        second = dims[order[1]] if len(order) > 1 else 1
        padded = -(-minor // tile[1]) * tile[1] * -(-second // tile[0]) * tile[0]
        found.append((dims, padded / (minor * second)))
    return found


def _search_instructions(text):
    """Launched instructions of the phases a level's or a tail split's search
    runs under: `split_scan`; `replay_tail` and, around it, `replay`.  Not the
    root's search, once a tree under `update_root_hist`: it is handed the
    (F, B, 3) histogram that the chunk program builds and, sharded,
    all-reduces (``test_what_the_sharded_program_all_reduces`` pins that
    operand's shape)."""
    ops = parse_hlo_phases(text)["ops"]
    return [i for body in _by_computation(text).values() for i in body
            if i.opcode not in NOT_LAUNCHED
            and ops.get(i.name) in ("split_scan", "replay_tail", "replay")]


def _no_histogram_with_its_3_on_the_lanes(text, cols, bins=63):
    search = _search_instructions(text)
    assert len(search) > 100
    # a child's cells: (..., columns, bins | thresholds | three placements'
    # thresholds | a column's pitch [, 3]) or (..., the kernel's lanes).  The
    # search's RESULT is (..., columns, 3), a cell a column, and stays.
    binlike = {bins, bins - 1, 3 * (bins - 1), -(-bins // 8) * 8}
    sized = [(i, dims, pad) for i in search for dims, pad in _arrays(i.shape)
             if (cols in dims and binlike & set(dims)) or hist_lanes(cols, bins) in dims]
    assert len(sized) > 10
    assert [(i.name, i.opcode, i.shape) for i, dims, _ in sized if dims[-1] == 3] == []
    # nor mostly padding for another reason (a gather's operand with the BATCH on
    # the lanes read 32: `ops/split.py::_pick`)
    assert [(i.name, i.opcode, i.shape) for i, _, pad in sized if pad >= 8] == []


@pytest.mark.parametrize("cell", ["higgs_21m_x_28", "epsilon_400k_x_2000"])
def test_the_split_search_reads_planes(request, cell):
    """PR 40: ``find2`` takes the kernels' g, h and count planes, the bins (or
    whatever the compiler prefers of columns and bins) on the lanes, in a
    level's search and in a tail split's: nothing under `split_scan`,
    `replay_tail` or `replay` makes an array the size of a child's histogram
    whose last dimension is 3 (the parent's: `pad_maximum_fusion
    f32[2,2000,63,3]`, `copy f32[1,2000,63,3]`, `copy f32[2,2000,63,3]`,
    `reduce_window_sum f32[4,2,2000,63,3]`: 129 MB for 3 MB of cells each, 114
    ms an iteration at Epsilon in seven ops), or that is padded 8 times over
    for any other reason."""
    if cell == "higgs_21m_x_28":
        _no_histogram_with_its_3_on_the_lanes(request.getfixturevalue("compiled_text"), 28)
    else:
        _no_histogram_with_its_3_on_the_lanes(request.getfixturevalue("epsilon_compiled")[0],
                                              EPS_COLS)


def test_the_sharded_split_search_reads_planes(sharded_compiled):
    """The same under ``shard_map``, where the tail differs from the serial
    one by the all-reduce of the six planes and nothing else."""
    text, name = sharded_compiled[0], sharded_compiled[-1]
    _no_histogram_with_its_3_on_the_lanes(text, SHARDED_SHAPES[name][1])


def test_the_layout_check_reads_a_layout():
    assert _arrays("f32[2,2000,63,3]{3,2,1,0:T(8,128)}") == [([2, 2000, 63, 3], 128 / 3 * 64 / 63)]
    assert _arrays("f32[4,2,1,2000,63]{0,1,4,3,2:T(2,128)S(1)}")[0][1] == 32.0
    (_, dense), (_, flat) = _arrays("(f32[4,2,2000,63]{2,3,1,0:T(8,128)S(1)}, s32[2000]{0:T(1024)})")
    assert dense == 2048 / 2000 * 64 / 63 and flat == 2048 / 2000


def test_the_split_search_visits_a_batch_of_slots(epsilon_compiled):
    """PR 34: at 2,000 columns the level's split search is a loop over
    ``scan_batch`` = 4 slots, ceil(n_act / 4) trips (``ops/pgrow.py::
    level_split_scan``), where the parent searched all 256 slots of every
    level: `add_add_fusion f32[256,1,128000]`, `pad_maximum_fusion
    f32[256,2,2000,186,3]` and their like, 953 ms an iteration.  Nothing
    `split_scan` launches makes an array with a leading 256 but the small
    result tables the loop carries; its large arrays lead with 4; the level's
    histograms enter the loop as its invariant, and nothing anywhere copies
    them."""
    _search_is_batched(epsilon_compiled[0])


def test_the_narrow_split_search_is_the_parents(compiled_text):
    """At 28 columns ``scan_batch`` is all 256 slots and the search is the
    straight-line one: `split_scan` owns no loop and 98 instructions (150 at
    the parent of PR 34 and until PR 40, whose search builds no (..., B, 3)
    array and gathers nothing), and its arrays lead with 256."""
    _search_is_straight(compiled_text, count=98)


def test_the_sharded_split_search_follows_the_width(sharded_compiled):
    """The same two under ``shard_map``: every shard takes the same trips,
    because the active count comes from tables every shard holds."""
    text, name = sharded_compiled[0], sharded_compiled[-1]
    if name == "epsilon_100k_x_2000_a_chip":
        _search_is_batched(text)
    else:
        _search_is_straight(text)



# -- the ranking configuration (benchmarks/configs/mslr.json) -----------------
MSLR_ROWS, MSLR_COLS, MSLR_QUERIES = 2_270_296, 137, 18_919
MSLR_MATRIX = f"s32[48,{MSLR_ROWS + 1024}]"
PAIR_STEP_BUDGET = 512 * 2 ** 20


def _mslr_rank_specs(one_chip):
    """The bucket operands of the cell's table as shapes: the query lengths of
    benchmarks/data_rank.py at the mix's seed, bucketed by objective/rank.py."""
    import json
    import sys

    from lightgbm_tpu.objective import rank

    bench = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                         "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    import data_rank

    with open(os.path.join(bench, "traffic", "train-2m-lambdarank.json")) as f:
        mix = json.load(f)
    sizes = data_rank.query_sizes(MSLR_QUERIES, mix["features_seed"], total=MSLR_ROWS,
                                  max_query=mix["max_query"])
    n = int(sizes.sum())
    host = rank.build_buckets(np.concatenate([[0], np.cumsum(sizes)]), np.zeros(n),
                              np.zeros(n), np.ones(len(sizes)))
    specs = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        {"buckets": [{k: b[k] for k in ("label", "gain", "inv")} for b in host["buckets"]],
         "flat_pos": host["flat_pos"]})
    return specs, host


def _mslr_compiled_parts(one_chip):
    """(text, argument bytes, temporary bytes) of the serial lambdarank chunk
    program at 2,270,296 rows x 137 columns with the cell's buckets, and the
    argument and temporary bytes of the grouped gradient alone at that size."""
    import lightgbm_tpu as lgb

    params = dict(PARAMS, objective="lambdarank")
    rng = np.random.RandomState(7)
    sizes = [100] * 40
    X = rng.randn(sum(sizes), MSLR_COLS)
    y = rng.randint(0, 5, sum(sizes)).astype(float)
    bst = lgb.Booster(params=params, train_set=lgb.Dataset(X, label=y, group=sizes,
                                                           params=dict(params)))
    pt = bst.boosting.ptrainer
    assert pt.p.shape[0] == 48 and pt._rank_ops is not None
    pt.num_rows = MSLR_ROWS
    pt.params = pt.params._replace(num_rows=MSLR_ROWS)
    pt.interpret = False
    specs, _ = _mslr_rank_specs(one_chip)
    prog = pt._build_program(pt.CHUNK_ALLOC, False, 1, MSLR_COLS)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    key = pt._base_key
    compiled = prog.lower(
        spec((48, MSLR_ROWS + 1024), jnp.int32), spec((), jnp.float32),
        spec(key.shape, key.dtype), spec((), jnp.int32), spec((), jnp.int32), specs).compile()
    mem = compiled.memory_analysis()
    grad = jax.jit(pt.objective.grouped_gradients).lower(
        spec((MSLR_ROWS,), jnp.float32), spec((MSLR_ROWS,), jnp.int32), specs).compile()
    gmem = grad.memory_analysis()
    return (compiled.as_text(), mem.argument_size_in_bytes, mem.temp_size_in_bytes,
            gmem.argument_size_in_bytes, gmem.temp_size_in_bytes)


@pytest.fixture(scope="module")
def mslr_compiled(one_chip):
    with _compiling_for_a_described_chip():
        return _mslr_compiled_parts(one_chip)


def test_mslr_program_holds_what_the_configuration_says(mslr_compiled):
    """What lambdarank training at MS LTR's size holds: the packed matrix (48
    channel rows), the buckets' operands and the chunk program's temporaries,
    633,615,360 bytes (compiled here for the v5e, PR 41;
    benchmarks/configs/mslr.json reckons 0.7-1.2 GB).  The run's
    memory_peak_bytes, set-up's packing included, is what the contract's
    floor is read against (PERF.md section 4)."""
    _, args, temps, _, _ = mslr_compiled
    assert abs(args + temps - 633_615_360) < 2 ** 20
    assert 0.43e9 < args < 0.5e9  # the matrix, 192 bytes a row, and the bucket operands


def test_mslr_pair_step_stays_inside_its_budget(mslr_compiled):
    """The grouped gradient alone, at the cell's 14 length buckets: its
    temporaries stay under the 512 MiB the pair step may hold (slices of at
    most objective/rank.PAIR_SLICE_CELLS cells), where the padded (Q, S_max,
    S_max) form would need 118 GB a float32 plane."""
    _, _, _, _, grad_temps = mslr_compiled
    assert 0 < grad_temps <= PAIR_STEP_BUDGET


_PAIR_ARRAY = re.compile(r"\b(?:f32|s32|pred|bf16|u32)\[([\d,]+)\]")


def test_mslr_program_builds_no_padded_pair_program(mslr_compiled):
    """No array anywhere in the module (fusion bodies included) of a pair
    shape, two equal sides of at least 8, holds more than a slice's cells; the
    largest are the slices', 4,194,304 cells."""
    from lightgbm_tpu.objective.rank import PAIR_SLICE_CELLS

    text = mslr_compiled[0]
    sizes = []
    for m in _PAIR_ARRAY.finditer(text):
        dims = [int(d) for d in m.group(1).split(",") if d]
        if len(dims) >= 3 and any(dims[i] == dims[j] >= 8 for i in range(len(dims))
                                  for j in range(i + 1, len(dims))):
            sizes.append(int(np.prod(dims)))
    assert sizes and max(sizes) == PAIR_SLICE_CELLS


def test_mslr_program_moves_only_row_vectors(mslr_compiled):
    """The grouped gradient moves (N,) vectors (the score to original order,
    the buckets' gathers, g and h back through ROWID) and nothing of the
    matrix's size is gathered, scattered, sorted, transposed or copied; the
    carry contract holds; the kernels are Mosaic's and `rank_grad` owns
    instructions."""
    text = mslr_compiled[0]
    pm = parse_hlo_phases(text)
    assert pm["matrix"] == MSLR_MATRIX and pm["matrix_copies"] == []
    assert _moves_of_matrix_size(text, 48 * MSLR_ROWS) == []
    assert "rank_grad" in pm["ops"].values()
    for kernel in ("update_and_root_hist", "level_stream", "split_stream", "score_add"):
        assert re.findall(rf'%({kernel}(?:\.\d+)?) = .*custom_call_target="tpu_custom_call"', text)


# -- the multiclass configuration (benchmarks/configs/covtype.json) ----------
COV_ROWS, COV_CLASSES = 4_648_096, 7
COV_MATRIX = f"s32[40,{COV_ROWS + 1024}]"  # 12 columns (10 + 2 EFB bundles): 8 word rows + a 32-row band


def _covtype_trainer(small_rows=8192):
    """A K = 7 fused trainer on a small table of the cell's shape (10 numeric
    columns, exactly one of 4 and one of 40 one-hot columns a row, which EFB
    bundles into 2), then told the cell's row count."""
    import lightgbm_tpu as lgb

    params = dict(PARAMS, objective="multiclass", num_class=COV_CLASSES)
    rng = np.random.RandomState(7)
    X = np.zeros((small_rows, 54))
    X[:, :10] = np.round(rng.randn(small_rows, 10) * 200)
    X[np.arange(small_rows), 10 + rng.randint(0, 4, small_rows)] = 1.0
    X[np.arange(small_rows), 14 + rng.randint(0, 40, small_rows)] = 1.0
    y = rng.randint(0, COV_CLASSES, small_rows).astype(float)
    bst = lgb.Booster(params=params, train_set=lgb.Dataset(X, label=y, params=dict(params)))
    pt = bst.boosting.ptrainer
    assert pt.K == COV_CLASSES and pt.params.num_cols == 12 and pt.p.shape[0] == 40
    pt.num_rows = COV_ROWS
    pt.params = pt.params._replace(num_rows=COV_ROWS)
    pt.interpret = False
    return pt


@pytest.fixture(scope="module")
def covtype_compiled(one_chip):
    """The serial K = 7 chunk program at the cell's shape, 4,648,096 rows x 54
    columns bundled to 12, compiled for the described chip: (text, argument
    bytes, temporary bytes)."""
    with _compiling_for_a_described_chip():
        return _compiled_chunk_program(_covtype_trainer(), one_chip, COV_ROWS, 54)


def _kernel_sites(text, kernel):
    return re.findall(rf'%({kernel}(?:\.\d+)?) = .*custom_call_target="tpu_custom_call"', text)


@pytest.mark.parametrize("kernel,k1", [("level_stream", "level_stream"),
                                       ("split_stream", "split_stream"),
                                       ("update_multi_and_hists", "update_and_root_hist")])
def test_the_seven_class_program_holds_one_grower(covtype_compiled, compiled_text, kernel, k1):
    """The K trees of an iteration are ONE traced grower in a loop over the
    classes (``ptrainer.class_trees``): the K = 7 program has as many call
    sites of each streaming kernel as the K = 1 program (the parent unrolled
    the loop, 7 of each), and one gradient pass where K = 1 has its own."""
    text = covtype_compiled[0]
    assert len(_kernel_sites(text, kernel)) == len(_kernel_sites(compiled_text, k1)) >= 1
    pm = parse_hlo_phases(text)
    assert {pm["ops"][c] for c in _kernel_sites(text, "update_multi_and_hists")} == {"multi_update"}
    # the class step: one band pass a class tree, in the loop, under its own word
    steps = [c for c in _kernel_sites(text, "score_add") if pm["ops"][c] == "score_add"]
    assert len(steps) == 1


def test_the_seven_class_program_moves_no_matrix(covtype_compiled):
    """The carry contract at K = 7: the matrix goes loop carry ->
    aliased kernel -> loop carry through the class loop too, no copy of it and
    nothing that gathers, scatters, sorts or transposes an array of its size,
    and one conditional (the replay's); the epilogue puts the 7 score planes
    back in row order with ONE sort of the planes end to end, no
    scatter."""
    text = covtype_compiled[0]
    pm = parse_hlo_phases(text)
    assert pm["matrix"] == COV_MATRIX and pm["matrix_copies"] == []
    assert re.findall(rf" = s32\[40,{COV_ROWS + 1024}\]\S* copy\(", text) == []
    assert _moves_of_matrix_size(text, 40 * COV_ROWS) == []
    assert len(re.findall(r" conditional\(", text)) == 1
    n = COV_CLASSES * COV_ROWS
    assert _epilogue_moves(text, COV_ROWS) == ([], [f"(s32[{n}], f32[{n}])"])


def test_what_seven_class_training_holds(covtype_compiled):
    """The program's arguments and temporaries at the cell's shape, pinned as
    the other configurations' are: the 744 MB matrix (40 channel rows) and
    the temporaries, 897,340,928 bytes in all (compiled for the v5e).
    benchmarks/configs/covtype.json reckons the run's memory_peak_bytes, set-up's
    packing included, against the 12.5% floor; PERF.md section 4 has what the
    chip read.  A change that moves the number says so in PERF.md."""
    _, args, temps = covtype_compiled
    assert 40 * (COV_ROWS + 1024) * 4 <= args < 40 * (COV_ROWS + 1024) * 4 + 2 ** 20
    assert abs(args + temps - 897_340_928) < 2 ** 20
