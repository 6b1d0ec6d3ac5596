"""Strategy-refactor invisibility pin (the tentpole contract).

The composable trainer core (lightgbm_tpu/tree/strategy.py) rewired
every learner through the SplitGain/LeafFit/HistAccum/StateExport seams.
These tests re-train the PR-7 parity configs and require the model bytes
AND the split-decision audit trails to match the pre-refactor goldens
captured in tests/golden/strategy_parity/ byte for byte — plus a
``report diff`` run over the audit streams returning rc 0 (identical).

Regenerate goldens (only when behaviour is INTENTIONALLY changed):
``python tests/strategy_parity_lib.py``.
"""

import hashlib
import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(__file__))
import strategy_parity_lib as lib  # noqa: E402

GOLDEN = os.path.join(os.path.dirname(__file__), "golden", "strategy_parity")


def _digests():
    with open(os.path.join(GOLDEN, "digests.json")) as fh:
        return json.load(fh)


def _recorded():
    import jax

    return (f" (goldens recorded under {_digests()['_recorded_under']}, "
            f"running jax {jax.__version__})")


@pytest.mark.parametrize("name", sorted(lib.BOOSTER_CONFIGS))
def test_booster_config_parity(name, tmp_path):
    audit_path = str(tmp_path / f"{name}.audit.jsonl")
    model, trail = lib.run_booster_config(name, audit_path)
    want = _digests()[name]
    assert hashlib.sha256(model.encode()).hexdigest() == \
        want["model_sha256"], f"{name}: model bytes drifted" + _recorded()
    assert hashlib.sha256(trail).hexdigest() == want["audit_sha256"], \
        f"{name}: split-decision audit trail drifted" + _recorded()
    # the user-facing check the issue names: `report diff` over the
    # golden trail and this run's trail must say identical (rc 0)
    proc = subprocess.run(
        [sys.executable, "-m", "lightgbm_tpu", "report", "diff",
         os.path.join(GOLDEN, f"{name}.audit.jsonl"), audit_path],
        capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, (
        f"report diff found divergence for {name}:\n{proc.stdout}"
        f"{proc.stderr}")


@pytest.mark.parametrize("mode", ["feature", "voting"])
def test_hostlearner_parity(mode):
    got = lib.run_hostlearner_mode(mode)
    assert got == _digests()[f"hostlearner_{mode}"]["grow_sha256"], \
        f"hostlearner {mode}: GrowResult bytes drifted" + _recorded()


def test_model_bytes_match_golden_files():
    """The stored .model.txt goldens themselves hash to the digests —
    guards against hand-edits of one without the other."""
    d = _digests()
    for name in lib.BOOSTER_CONFIGS:
        with open(os.path.join(GOLDEN, f"{name}.model.txt")) as fh:
            model = fh.read()
        assert hashlib.sha256(model.encode()).hexdigest() == \
            d[name]["model_sha256"]
