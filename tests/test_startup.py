"""Start-up rules (PR 22): the package places its compile cache from
outside, never picks a platform, and the chip smoke refuses to run
without a chip."""

import json
import os
import subprocess
import sys

import jax

import lightgbm_tpu as lgb

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = """
import json, sys
sys.path.insert(0, {repo!r})
import jax
from jax._src import xla_bridge
before = jax.config.jax_platforms
import lightgbm_tpu
up = xla_bridge.backends_are_initialized()
lightgbm_tpu.enable_compile_cache()
print(json.dumps({{
    "platforms_before": before,
    "platforms_after": jax.config.jax_platforms,
    "backend_up_after_import": up,
    "cache_dir": jax.config.jax_compilation_cache_dir,
}}))
"""


def _probe(cwd, **env_over):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", **env_over)
    out = subprocess.run(
        [sys.executable, "-c", _PROBE.format(repo=REPO)], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=120, check=True).stdout
    return json.loads(out.strip().splitlines()[-1])


def test_env_cache_dir_is_never_overridden(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    updated = []
    monkeypatch.setattr(jax.config, "update",
                        lambda key, val: updated.append(key))
    lgb.enable_compile_cache()
    assert "jax_compilation_cache_dir" not in updated


def test_default_cache_dir_and_untouched_platforms(tmp_path):
    # two fresh processes, different working directories, no .git test,
    # no host-name component: one fixed <checkout>/.jax_cache
    a = _probe(str(tmp_path))
    b = _probe(REPO)
    assert a["cache_dir"] == b["cache_dir"] == os.path.join(REPO, ".jax_cache")
    # importing the package neither sets jax_platforms nor starts a backend
    assert a["platforms_after"] == a["platforms_before"] == "cpu"
    assert a["backend_up_after_import"] is False
    # ... and an operator's cache directory rides through untouched
    c = _probe(str(tmp_path), JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"))
    assert c["cache_dir"] == str(tmp_path / "cc")


def test_chip_smoke_result_line_has_exactly_the_contract_keys():
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    line = chip_smoke.result_line(
        {"platform": "tpu", "kind": "TPU v5 lite", "count": 1, "extra": 0})
    assert "\n" not in line
    assert json.loads(line) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


def test_chip_smoke_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "platform 'cpu'" in r.stdout  # names what it found
    assert '"ok"' not in r.stdout  # and prints no result
