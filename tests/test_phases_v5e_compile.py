"""The 21M-row chunk program of the benchmark's configuration, compiled for a
described (not attached) TPU v5e: what the chip's own compiler leaves of the
names the program gives itself, and where it copies the whole packed matrix.
Counts from a compile, never speeds; nothing runs.

The topology is described inside a fixture (never at import: one process at a
time may load libtpu, and every xdist worker imports this file), and this is
the only test file that does so."""

import collections
import os
import re

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from lightgbm_tpu.obs.phases import PHASES, parse_hlo_phases  # noqa: E402

ROWS = 21_000_000
# the cells' configuration (benchmarks/configs/higgs.json)
PARAMS = {"objective": "binary", "max_bin": 63, "num_leaves": 255, "learning_rate": 0.1,
          "min_data_in_leaf": 1, "min_sum_hessian_in_leaf": 100, "verbose": -1}
NOT_LAUNCHED = ("get-tuple-element", "tuple", "constant", "bitcast", "while", "conditional")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def compiled_text(one_chip):
    """Compiled HLO text of the serial chunk program at 21M rows x 28 features:
    the trainer is built on a small table (its closures do not depend on the
    row count) and then told the real one; kernels go through Mosaic."""
    import lightgbm_tpu as lgb
    from jax.experimental.compilation_cache import compilation_cache

    old = os.environ.get("LIGHTGBM_TPU_PGROW")
    os.environ["LIGHTGBM_TPU_PGROW"] = "force"
    cache_was = jax.config.jax_enable_compilation_cache
    # a compile for a described chip is written to the cache and cannot be read back
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        rng = np.random.RandomState(7)
        X = rng.randn(20_000, 28)
        y = (X[:, 0] + X[:, 1] * X[:, 2] > 0).astype(float)
        bst = lgb.Booster(params=PARAMS, train_set=lgb.Dataset(X, label=y, params=dict(PARAMS)))
        pt = bst.boosting.ptrainer
        pt.num_rows = ROWS
        pt.params = pt.params._replace(num_rows=ROWS)
        pt.interpret = False
        prog = pt._build_program(pt.CHUNK_ALLOC, False, 1, 28)

        def spec(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        key = pt._base_key
        lowered = prog.lower(spec((pt.p.shape[0], ROWS + 1024), jnp.int32), spec((), jnp.float32),
                             spec(key.shape, key.dtype), spec((), jnp.int32), spec((), jnp.int32))
        return lowered.compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()
        if old is None:
            del os.environ["LIGHTGBM_TPU_PGROW"]
        else:
            os.environ["LIGHTGBM_TPU_PGROW"] = old


@pytest.fixture(scope="module")
def phase_map(compiled_text):
    return parse_hlo_phases(compiled_text)


def test_module_and_matrix(phase_map):
    assert phase_map["module"] == "jit_prog"
    assert phase_map["matrix"] == "s32[16,21001024]"


@pytest.mark.parametrize("phase", [p for p in PHASES if p not in ("sample", "score_add")])
def test_the_compiler_keeps_the_phase(phase_map, phase):
    """Every phase the cells' program runs (it draws no sample, and lands no
    per-class delta) still owns instructions after XLA's passes."""
    assert phase in phase_map["ops"].values()


@pytest.mark.parametrize("kernel,phase", [
    ("update_and_root_hist", "update_root_hist"), ("level_stream", "level_phase"),
    ("split_stream", "replay_tail"), ("score_add", "chunk_epilogue")])
def test_mosaic_kernels_carry_their_names_and_phases(compiled_text, phase_map, kernel, phase):
    calls = re.findall(rf'%({kernel}(?:\.\d+)?) = .*custom_call_target="tpu_custom_call"',
                       compiled_text)
    assert calls, f"no Mosaic custom call named {kernel}"
    assert {phase_map["ops"][c] for c in calls} == {phase}


def test_whole_matrix_copy_sites(phase_map):
    """ROADMAP S1's starting point: six static sites, three in the replay, two
    in the level phase, one in the stopped no-op branch (no phase), none in a
    phase of ptrainer.py.  A PR that removes copies lowers these counts."""
    sites = collections.Counter(phase_map["ops"][c] for c in phase_map["matrix_copies"])
    assert sites == {"replay": 3, "level_phase": 2, None: 1}


def test_what_no_phase_claims_is_bookkeeping(compiled_text, phase_map):
    """Outside every scope: the main loop, the record stores, and the stopped
    no-op branch with its copy.  Nothing else the size of the table."""
    unclaimed = {k for k, v in phase_map["ops"].items() if v is None}
    table_sized = []
    for line in compiled_text.splitlines():
        m = re.match(r"^\s+(?:ROOT )?%(\S+) = (\(.*?\)|\S+) ([\w-]+)\(", line)
        if not m or m.group(1) not in unclaimed or m.group(3) in NOT_LAUNCHED:
            continue
        if "21000000" in m.group(2) or "21001024" in m.group(2):
            table_sized.append(m.group(1))
    assert table_sized == [c for c in phase_map["matrix_copies"] if phase_map["ops"][c] is None]
    assert len(unclaimed) < 0.1 * len(phase_map["ops"])
