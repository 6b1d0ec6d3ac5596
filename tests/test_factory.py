"""Continuous-training factory tests (docs/FACTORY.md): the crash-safe
supervisor state file (CRC refusal, atomic round-trip), the data-dir
watcher (content fingerprints, debounce, touch is not a change), the
registry lifecycle extensions (publish dedupe, canary pin, quarantine,
lifecycle-aware GC), per-version serving metrics (/stats vs /metrics
parity, prune on swap), the init_model schema-drift guard, the
in-process factory cycle (cold promote -> warm-started promote), crash
replay (kill mid-publish never double-publishes), the eval-gate
rollback verdict, a subprocess SIGKILL mid-retrain that resumes from
its checkpoint, and the tier-1 e2e: a live subprocess fleet under
closed-loop traffic where a data append drives warm retrain -> publish
-> canary -> auto-promote with zero dropped or mis-versioned responses,
and a blind canary auto-rolls-back with a recorded verdict.
"""

import glob
import json
import os
import re
import signal
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.factory import FactoryState, FactorySupervisor
from lightgbm_tpu.factory import watch
from lightgbm_tpu.obs.metrics import registry as metrics_registry
from lightgbm_tpu.serve import (
    FleetProxy,
    ModelRegistry,
    PackedPredictor,
    PredictorArtifact,
)
from lightgbm_tpu.serve.fleet import _wait_ready, spawn_replicas
from lightgbm_tpu.utils.log import LightGBMError

N_FEATURES = 8
TRAIN_PARAMS = {"objective": "binary", "num_leaves": 7, "verbose": -1,
                "min_data_in_leaf": 5}
FACTORY_KNOBS = {"num_boost_round": 5, "checkpoint_freq": 2,
                 "debounce_ms": 0.0, "canary_fraction": 0.0}


def _write_chunk(data_dir, name, n, seed, backdate=True):
    """Append ``n`` CSV rows (label first, the parser default) drawn
    from one fixed rule, so every chunk is more signal for the same
    concept — warm starts should help, never regress."""
    rng = np.random.RandomState(seed)
    X = rng.randn(n, N_FEATURES)
    y = (X[:, 0] + 0.5 * X[:, 1] - X[:, 2] > 0).astype(int)
    path = os.path.join(data_dir, name)
    with open(path, "a") as f:
        for yy, row in zip(y, X):
            f.write(",".join([str(yy)] + [f"{v:.6f}" for v in row]) + "\n")
    if backdate:  # move mtime out of the debounce window
        t = time.time() - 60
        os.utime(path, (t, t))
    return path


def _supervisor(tmp_path, **over):
    data_dir = os.path.join(tmp_path, "data")
    os.makedirs(data_dir, exist_ok=True)
    knobs = dict(FACTORY_KNOBS)
    params = dict(TRAIN_PARAMS)
    for k in list(over):
        if k in ("proxy", "host"):
            continue
        knobs[k] = over.pop(k)
    return FactorySupervisor(
        data_dir, os.path.join(tmp_path, "work"),
        os.path.join(tmp_path, "reg"), params=params, **over, **knobs)


@pytest.fixture(scope="module")
def tiny_booster():
    rng = np.random.RandomState(7)
    X = rng.randn(400, N_FEATURES)
    y = (X[:, 0] + 0.5 * X[:, 1] - X[:, 2] > 0).astype(np.float32)
    ds = lgb.Dataset(X, label=y, params={"min_data_in_leaf": 5})
    bst = lgb.train(dict(TRAIN_PARAMS), ds, num_boost_round=8,
                    verbose_eval=False)
    return bst, X


def _scaled(art, scale):
    from lightgbm_tpu.ops.predict import TreeArrays

    fields = {f: np.asarray(getattr(art.arrays, f))
              for f in TreeArrays.FIELDS}
    fields["leaf_value"] = fields["leaf_value"] * scale
    return PredictorArtifact(TreeArrays(**fields), art.meta)


# ----------------------------------------------------------------------
# supervisor state file
# ----------------------------------------------------------------------
class TestFactoryState:
    def test_fresh_when_absent(self, tmp_path):
        st = FactoryState.load(str(tmp_path))
        assert st.ingested == {} and st.run is None
        assert st.history == [] and st.current is None

    def test_round_trip(self, tmp_path):
        st = FactoryState(str(tmp_path))
        st.ingested = {"a.csv": {"size": 3, "mtime_ns": 1, "crc32": 9}}
        st.run = {"run_id": "r000001-abc", "candidate_version": 2}
        st.current = {"version": 1, "model_path": "/x", "metric": 0.1}
        st.retrain_seq = 4
        st.record_verdict({"run_id": "r000001-abc", "verdict": "promoted"})
        st.save()
        back = FactoryState.load(str(tmp_path))
        assert back.ingested == st.ingested
        assert back.run == st.run
        assert back.current == st.current
        assert back.retrain_seq == 4
        assert back.history == st.history

    def test_crc_mismatch_refused(self, tmp_path):
        st = FactoryState(str(tmp_path))
        st.retrain_seq = 1
        st.save()
        with open(st.path) as f:
            doc = json.load(f)
        doc["payload"]["retrain_seq"] = 99  # tamper without re-CRC
        with open(st.path, "w") as f:
            json.dump(doc, f)
        with pytest.raises(LightGBMError, match="CRC"):
            FactoryState.load(str(tmp_path))

    def test_garbage_refused(self, tmp_path):
        st = FactoryState(str(tmp_path))
        with open(st.path, "w") as f:
            f.write("not json{")
        with pytest.raises(LightGBMError, match="unreadable"):
            FactoryState.load(str(tmp_path))

    def test_history_bounded(self, tmp_path):
        st = FactoryState(str(tmp_path))
        for i in range(60):
            st.record_verdict({"run_id": f"r{i}"}, keep=50)
        assert len(st.history) == 50
        assert st.history[-1]["run_id"] == "r59"


# ----------------------------------------------------------------------
# data-dir watcher
# ----------------------------------------------------------------------
class TestWatch:
    def test_scan_filters(self, tmp_path):
        d = str(tmp_path)
        _write_chunk(d, "a.csv", 3, 0)
        _write_chunk(d, ".hidden.csv", 3, 1)
        with open(os.path.join(d, "notes.md"), "w") as f:
            f.write("not data\n")
        os.makedirs(os.path.join(d, "sub.csv"))
        assert list(watch.scan(d)) == ["a.csv"]

    def test_append_changes_touch_does_not(self, tmp_path):
        d = str(tmp_path)
        _write_chunk(d, "a.csv", 5, 0)
        prev = watch.scan(d)
        # a bare touch (mtime only) must NOT retrain
        os.utime(os.path.join(d, "a.csv"))
        assert watch.changed(prev, watch.scan(d)) == []
        # an append moves size + tail CRC -> retrain
        _write_chunk(d, "a.csv", 5, 1)
        assert watch.changed(prev, watch.scan(d)) == ["a.csv"]
        # a new file is a change too
        _write_chunk(d, "b.csv", 2, 2)
        assert "b.csv" in watch.changed(prev, watch.scan(d))

    def test_debounce(self, tmp_path):
        d = str(tmp_path)
        _write_chunk(d, "a.csv", 3, 0, backdate=False)
        cur = watch.scan(d)
        assert not watch.stable(cur, debounce_s=30.0)
        assert watch.stable(cur, debounce_s=0.0)
        t = time.time() - 60
        os.utime(os.path.join(d, "a.csv"), (t, t))
        assert watch.stable(watch.scan(d), debounce_s=30.0)

    def test_combined_fingerprint_tracks_content(self, tmp_path):
        d = str(tmp_path)
        _write_chunk(d, "a.csv", 4, 0)
        fp1 = watch.combined_fingerprint(watch.scan(d))
        assert fp1 == watch.combined_fingerprint(watch.scan(d))
        _write_chunk(d, "a.csv", 1, 9)
        assert watch.combined_fingerprint(watch.scan(d)) != fp1


# ----------------------------------------------------------------------
# registry lifecycle (factory satellites)
# ----------------------------------------------------------------------
class TestRegistryLifecycle:
    def test_publish_dedupe_key(self, tiny_booster, tmp_path):
        bst, _ = tiny_booster
        art = PredictorArtifact.from_booster(bst)
        reg = ModelRegistry(str(tmp_path / "reg"))
        v1 = reg.publish(art, activate=False, dedupe_key="r000001-abc")
        # the replayed publish of a killed run gets the SAME version back
        v2 = reg.publish(_scaled(art, 1.1), activate=False,
                         dedupe_key="r000001-abc")
        assert v1 == v2 == 1
        assert [m["version"] for m in reg.list_models()] == [1]
        # a different run id is a genuinely new publish
        assert reg.publish(art, activate=False, dedupe_key="r2") == 2

    def test_canary_pin_and_clear(self, tiny_booster, tmp_path):
        bst, _ = tiny_booster
        art = PredictorArtifact.from_booster(bst)
        reg = ModelRegistry(str(tmp_path / "reg"))
        reg.publish(art)
        reg.publish(_scaled(art, 1.1), activate=False)
        assert reg.canary_version() is None
        reg.set_canary(2)
        assert reg.canary_version() == 2
        assert [m["canary"] for m in reg.list_models()] == [False, True]
        reg.clear_canary()
        assert reg.canary_version() is None
        with pytest.raises(LightGBMError, match="unknown version"):
            reg.set_canary(99)

    def test_quarantine_records_reason(self, tiny_booster, tmp_path):
        bst, _ = tiny_booster
        art = PredictorArtifact.from_booster(bst)
        reg = ModelRegistry(str(tmp_path / "reg"))
        reg.publish(art)
        reg.publish(_scaled(art, 1.1), activate=False)
        reg.set_canary(2)
        reg.quarantine(2, "canary error rate 0.5 > 0.02")
        assert reg.quarantined() == {2: "canary error rate 0.5 > 0.02"}
        # quarantining the canary clears the canary pin
        assert reg.canary_version() is None
        rows = {m["version"]: m for m in reg.list_models()}
        assert rows[2]["quarantined"] == "canary error rate 0.5 > 0.02"
        assert rows[1]["quarantined"] is None

    def test_gc_protects_lifecycle_versions(self, tiny_booster, tmp_path):
        """Retention must never collect the active version, the pinned
        canary, or the most recent quarantined version (the rollback
        investigation's evidence)."""
        bst, _ = tiny_booster
        art = PredictorArtifact.from_booster(bst)
        reg = ModelRegistry(str(tmp_path / "reg"), keep_last=2)
        reg.publish(art)                                 # v1 (active)
        reg.publish(_scaled(art, 1.1), activate=False)   # v2 -> canary
        reg.set_canary(2)
        reg.publish(_scaled(art, 1.2), activate=False)   # v3 -> quarantined
        reg.quarantine(3, "slo miss")
        reg.publish(_scaled(art, 1.3), activate=False)   # v4
        reg.publish(_scaled(art, 1.4), activate=False)   # v5
        versions = [m["version"] for m in reg.list_models()]
        assert versions == [1, 2, 3, 4, 5]  # all protected or in-window
        # once the canary pin is lifted, v2 becomes collectible
        reg.clear_canary()
        reg.publish(_scaled(art, 1.5), activate=False)   # v6 triggers GC
        versions = [m["version"] for m in reg.list_models()]
        assert 2 not in versions
        assert 1 in versions and 3 in versions  # active + quarantined stay


# ----------------------------------------------------------------------
# per-version serving metrics (satellite 2)
# ----------------------------------------------------------------------
class TestPerVersionMetrics:
    @pytest.fixture()
    def server(self, tiny_booster, tmp_path):
        from lightgbm_tpu.serve import server as serve_server
        from lightgbm_tpu.serve.server import make_server

        # the per-version families belong to the process, not to a server:
        # another test file's server in this xdist worker may have left
        # requests and errors under version "1" (which file shares the worker
        # depends on how loadfile deals the files out)
        for fam in (serve_server._M_VER_REQS, serve_server._M_VER_ERRS,
                    serve_server._M_VER_LATENCY):
            fam.prune(())
        bst, X = tiny_booster
        model = PredictorArtifact.from_booster(bst).save(str(tmp_path / "m"))
        srv = make_server(model, port=0, warmup_max_rows=64,
                          max_delay_ms=1.0,
                          registry_dir=str(tmp_path / "reg"),
                          registry_poll_ms=50.0)
        t = threading.Thread(target=srv.serve_forever, daemon=True)
        t.start()
        yield srv, bst, X
        srv.shutdown()
        srv.server_close()

    def _post(self, port, rows, query=""):
        body = "\n".join(json.dumps(list(map(float, r)))
                         for r in rows).encode()
        return urllib.request.urlopen(
            f"http://127.0.0.1:{port}/predict{query}", data=body,
            timeout=30)

    def _metric_value(self, port, line_prefix):
        text = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=30).read().decode()
        for line in text.splitlines():
            if line.startswith(line_prefix):
                return float(line.rsplit(" ", 1)[1]), text
        return None, text

    def test_stats_metrics_parity(self, server):
        srv, bst, X = server
        port = srv.server_address[1]
        for _ in range(3):
            assert self._post(port, X[:2]).status == 200
        st = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/stats", timeout=30).read())
        pv = st["per_version"]["1"]
        assert pv["requests"] >= 3 and pv["errors"] == 0
        assert pv["latency_p99_ms"] > 0
        # /metrics must tell the same story, labeled by model_version
        val, text = self._metric_value(
            port,
            'lightgbm_tpu_serve_version_requests_total{model_version="1"}')
        assert val == pv["requests"]
        assert ('lightgbm_tpu_serve_version_latency_seconds_bucket'
                '{model_version="1",le="') in text
        assert ('lightgbm_tpu_serve_version_latency_seconds_count'
                '{model_version="1"}') in text

    def test_swap_prunes_old_version_labels(self, server):
        srv, bst, X = server
        port = srv.server_address[1]
        self._post(port, X[:2])
        reg = ModelRegistry(srv.registry.dir)
        v = reg.publish(_scaled(PredictorArtifact.from_booster(bst), 1.5))
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if getattr(srv.predictor, "version", None) == v:
                break
            time.sleep(0.05)
        assert srv.predictor.version == v
        self._post(port, X[:2])
        st = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/stats", timeout=30).read())
        # bounded cardinality: only the live version's series remain
        assert list(st["per_version"]) == [str(v)]
        val, text = self._metric_value(
            port,
            f'lightgbm_tpu_serve_version_requests_total'
            f'{{model_version="{v}"}}')
        assert val >= 1
        assert 'model_version="1"' not in text

    def test_pin_version_never_swaps(self, tiny_booster, tmp_path):
        from lightgbm_tpu.serve.server import make_server

        bst, X = tiny_booster
        art = PredictorArtifact.from_booster(bst)
        reg = ModelRegistry(str(tmp_path / "reg"))
        reg.publish(art)                      # v1
        reg.publish(_scaled(art, 2.0))        # v2 active
        srv = make_server(port=0, warmup_max_rows=64, max_delay_ms=1.0,
                          registry_dir=str(tmp_path / "reg"),
                          registry_poll_ms=50.0, pin_version=1)
        t = threading.Thread(target=srv.serve_forever, daemon=True)
        t.start()
        try:
            port = srv.server_address[1]
            r = self._post(port, X[:3], query="?model_version=1")
            assert r.headers["X-Model-Version"] == "1"
            lines = [json.loads(l) for l in r.read().decode().splitlines()]
            assert all(l["model_version"] == 1 for l in lines)
            assert np.allclose([l["prediction"] for l in lines],
                               PackedPredictor(art).predict(X[:3]))
            # the active version moved on; the pinned replica must not
            reg.activate(1)
            reg.activate(2)
            time.sleep(0.3)  # several poll periods
            st = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{port}/stats", timeout=30).read())
            assert st["model_version"] == 1
            assert st["pin_version"] == 1
        finally:
            srv.shutdown()
            srv.server_close()


# ----------------------------------------------------------------------
# init_model schema-drift guard (satellite 6)
# ----------------------------------------------------------------------
class TestInitModelGuard:
    def test_feature_count_mismatch_is_actionable(self, tiny_booster,
                                                  tmp_path):
        bst, _ = tiny_booster
        model = str(tmp_path / "prev.txt")
        bst.save_model(model)
        rng = np.random.RandomState(11)
        X = rng.randn(200, N_FEATURES + 3)  # drifted schema: wider data
        y = (X[:, 0] > 0).astype(np.float32)
        with pytest.raises(LightGBMError,
                           match=r"trained on 8 features.*has 11"):
            lgb.train(dict(TRAIN_PARAMS),
                      lgb.Dataset(X, label=y,
                                  params={"min_data_in_leaf": 5}),
                      num_boost_round=2, init_model=model,
                      verbose_eval=False)


# ----------------------------------------------------------------------
# in-process factory cycles
# ----------------------------------------------------------------------
@pytest.mark.factory
class TestFactoryCycle:
    def test_cold_then_warm_promote(self, tmp_path):
        sup = _supervisor(str(tmp_path))
        assert sup.run_cycle() is None  # empty data dir -> nothing to do
        _write_chunk(sup.data_dir, "chunk-000.csv", 300, 0)
        v1 = sup.run_cycle()
        assert v1["verdict"] == "promoted" and v1["version"] == 1
        assert v1["warm_start"] is False
        assert v1["detail"]["eval"]["baseline"] is None
        assert sup.registry.active_version() == 1
        assert sup.run_cycle() is None  # unchanged data -> no run
        # appended rows + a new chunk trigger a WARM-started retrain
        _write_chunk(sup.data_dir, "chunk-000.csv", 100, 1)
        _write_chunk(sup.data_dir, "chunk-001.csv", 200, 2)
        v2 = sup.run_cycle()
        assert v2["verdict"] == "promoted" and v2["version"] == 2
        assert v2["warm_start"] is True
        assert v2["detail"]["eval"]["baseline"] is not None
        assert sup.registry.active_version() == 2
        # durable state: a fresh load sees the same world
        back = FactoryState.load(sup.workdir)
        assert back.run is None
        assert [h["verdict"] for h in back.history] == ["promoted"] * 2
        assert back.current["version"] == 2
        assert os.path.exists(back.current["model_path"])
        assert set(back.ingested) == {"chunk-000.csv", "chunk-001.csv"}
        # run scratch space is retired with the run
        assert glob.glob(os.path.join(sup.workdir, "r0*")) == []

    def test_debounce_defers_fresh_writes(self, tmp_path):
        sup = _supervisor(str(tmp_path), debounce_ms=60000.0)
        _write_chunk(sup.data_dir, "chunk-000.csv", 50, 0, backdate=False)
        assert sup.run_cycle() is None  # writer might still be appending
        assert FactoryState.load(sup.workdir).run is None


@pytest.mark.factory
class TestFactoryCrashReplay:
    def test_kill_after_publish_never_double_publishes(self, tmp_path,
                                                       monkeypatch):
        """A crash between publish and the verdict replays the run; the
        dedupe key hands the SAME version back and exactly one model
        enters the registry."""
        sup = _supervisor(str(tmp_path))
        _write_chunk(sup.data_dir, "chunk-000.csv", 300, 0)
        monkeypatch.setattr(
            sup, "_eval_gate",
            lambda *a, **k: (_ for _ in ()).throw(RuntimeError("killed")))
        with pytest.raises(RuntimeError, match="killed"):
            sup.run_cycle()
        # the candidate was published (inactive) and the run is durable
        assert sup.registry.latest_version() == 1
        assert sup.registry.active_version() is None
        mid = FactoryState.load(sup.workdir)
        assert mid.run is not None
        assert mid.run["candidate_version"] == 1
        # "restart": a fresh supervisor re-enters and finishes the run
        sup2 = FactorySupervisor(sup.data_dir, sup.workdir,
                                 sup.registry_dir, params=dict(TRAIN_PARAMS),
                                 **FACTORY_KNOBS)
        verdict = sup2.run_cycle()
        assert verdict["verdict"] == "promoted" and verdict["version"] == 1
        assert verdict["run_id"] == mid.run["run_id"]
        assert [m["version"] for m in sup2.registry.list_models()] == [1]
        assert sup2.registry.active_version() == 1
        assert FactoryState.load(sup.workdir).run is None

    def test_eval_gate_rollback_records_verdict(self, tmp_path,
                                                monkeypatch):
        """A regressed candidate is quarantined WITH the reason, the
        active version does not move, and the next retrain still warm
        starts from the last good model."""
        sup = _supervisor(str(tmp_path))
        _write_chunk(sup.data_dir, "chunk-000.csv", 300, 0)
        assert sup.run_cycle()["verdict"] == "promoted"
        _write_chunk(sup.data_dir, "chunk-001.csv", 150, 1)

        real = sup._eval_metric

        def scripted(model_path, data_path):
            if os.sep + "models" + os.sep in model_path:
                return {"name": "binary_error", "value": 0.02}  # baseline
            return {"name": "binary_error", "value": 0.40}      # candidate
        monkeypatch.setattr(sup, "_eval_metric", scripted)
        verdict = sup.run_cycle()
        monkeypatch.setattr(sup, "_eval_metric", real)
        assert verdict["verdict"] == "rolled_back"
        assert "regressed" in verdict["reason"]
        assert sup.registry.active_version() == 1  # rollback held the fort
        assert sup.registry.quarantined() == {2: verdict["reason"]}
        hist = FactoryState.load(sup.workdir).history
        assert [h["verdict"] for h in hist] == ["promoted", "rolled_back"]
        assert hist[-1]["detail"]["eval"]["reason"] == verdict["reason"]
        # the factory keeps going: the next change retrains from v1
        _write_chunk(sup.data_dir, "chunk-002.csv", 150, 2)
        v3 = sup.run_cycle()
        assert v3["verdict"] == "promoted" and v3["version"] == 3
        assert v3["warm_start"] is True
        assert sup.registry.active_version() == 3


# ----------------------------------------------------------------------
# subprocess SIGKILL mid-retrain (satellite 3)
# ----------------------------------------------------------------------
def _factory_cmd(data_dir, workdir, reg_dir, rounds):
    return [sys.executable, "-m", "lightgbm_tpu", "factory",
            f"data={data_dir}", f"workdir={workdir}", f"registry={reg_dir}",
            "max_cycles=1", "poll_ms=50", "debounce_ms=0",
            f"num_boost_round={rounds}", "checkpoint_freq=1",
            "canary_fraction=0", "objective=binary", "num_leaves=15",
            "min_data_in_leaf=5"]  # default verbosity: the resume
    # assertion greps the "Checkpoint saved at iteration" info lines


@pytest.mark.factory
@pytest.mark.faultinject
class TestFactorySigkill:
    def test_sigkill_mid_retrain_resumes_and_publishes_once(self, tmp_path):
        data_dir = str(tmp_path / "data")
        workdir = str(tmp_path / "work")
        reg_dir = str(tmp_path / "reg")
        os.makedirs(data_dir)
        _write_chunk(data_dir, "chunk-000.csv", 2000, 0)
        rounds = 60
        cmd = _factory_cmd(data_dir, workdir, reg_dir, rounds)
        env = dict(os.environ, JAX_PLATFORMS=os.environ.get(
            "JAX_PLATFORMS", "cpu"))
        proc = subprocess.Popen(cmd, env=env, stdout=subprocess.DEVNULL,
                                stderr=subprocess.DEVNULL)
        try:
            # wait for the retrain to be demonstrably mid-flight (>= 2
            # durable checkpoints), then SIGKILL with rounds to spare
            deadline = time.monotonic() + 240
            ckpts = []
            while time.monotonic() < deadline:
                if proc.poll() is not None:
                    pytest.fail("factory finished before the kill landed — "
                                "raise num_boost_round")
                ckpts = glob.glob(
                    os.path.join(workdir, "r*", "ckpt", "ckpt_*.npz"))
                if len(ckpts) >= 2:
                    break
                time.sleep(0.01)
            assert len(ckpts) >= 2, "no checkpoints before the deadline"
            proc.send_signal(signal.SIGKILL)
            assert proc.wait(timeout=30) == -signal.SIGKILL
        finally:
            if proc.poll() is None:
                proc.kill()
        # killed mid-retrain: run record durable, nothing published
        mid = FactoryState.load(workdir)
        assert mid.run is not None
        run_id = mid.run["run_id"]
        assert ModelRegistry(reg_dir).active_version() is None
        # restart: the SAME run resumes from its checkpoint and finishes
        out = subprocess.run(cmd, env=env, stdout=subprocess.PIPE,
                             stderr=subprocess.STDOUT, timeout=420)
        text = out.stdout.decode(errors="replace")
        assert out.returncode == 0, text[-2000:]
        saves = [int(m) for m in re.findall(
            r"Checkpoint saved at iteration (\d+)", text)]
        assert saves, "restart never checkpointed"
        assert saves[0] > 1, \
            f"restart checkpointed from iteration {saves[0]} — it " \
            "retrained from scratch instead of resuming"
        reg = ModelRegistry(reg_dir)
        assert [m["version"] for m in reg.list_models()] == [1]
        assert reg.active_version() == 1
        done = FactoryState.load(workdir)
        assert done.run is None
        assert [h["run_id"] for h in done.history] == [run_id]
        assert done.history[0]["verdict"] == "promoted"
        booster = reg.load(1)
        assert booster.meta["num_trees"] == rounds


# ----------------------------------------------------------------------
# e2e: live fleet + closed-loop traffic + canary promote / rollback
# ----------------------------------------------------------------------
def _traffic(port, rows, n_threads=2):
    """Closed-loop /predict traffic through the proxy.  Every reply must
    be 200 and stamped with exactly one version; (version, predictions)
    pairs are recorded for post-hoc verification against the registry's
    artifacts."""
    body = "\n".join(json.dumps(list(map(float, r))) for r in rows).encode()
    stop = threading.Event()
    lock = threading.Lock()
    stats = {"n": 0, "errors": [], "replies": []}

    def worker():
        while not stop.is_set():
            try:
                r = urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/predict?model_version=1",
                    data=body, timeout=60)
                lines = [json.loads(l)
                         for l in r.read().decode().splitlines()]
            except Exception as e:
                with lock:
                    stats["errors"].append(f"{type(e).__name__}: {e}")
                continue
            vers = {l["model_version"] for l in lines}
            with lock:
                stats["n"] += 1
                if len(vers) != 1:
                    stats["errors"].append(f"reply mixed versions {vers}")
                else:
                    stats["replies"].append(
                        (vers.pop(), [l["prediction"] for l in lines]))

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(n_threads)]
    for t in threads:
        t.start()
    return stop, threads, stats


@pytest.mark.factory
@pytest.mark.fleet
class TestFactoryFleetE2E:
    def test_append_canary_promote_then_blind_rollback(self, tmp_path):
        """The whole loop against a LIVE fleet: data append -> warm
        retrain -> inactive publish -> canary slice -> auto-promote,
        with zero dropped and zero mis-versioned responses; then a
        second run whose canary sees no traffic refuses to promote
        blind, auto-rolls-back, and records the verdict."""
        tmp = str(tmp_path)
        data_dir = os.path.join(tmp, "data")
        reg_dir = os.path.join(tmp, "reg")
        os.makedirs(data_dir)
        _write_chunk(data_dir, "chunk-000.csv", 300, 0)
        # bootstrap v1 (no fleet yet, canary off)
        boot = FactorySupervisor(data_dir, os.path.join(tmp, "work"),
                                 reg_dir, params=dict(TRAIN_PARAMS),
                                 **FACTORY_KNOBS)
        assert boot.run_cycle()["verdict"] == "promoted"

        procs = spawn_replicas(2, {
            "registry": reg_dir, "warmup_max_rows": "64",
            "max_delay_ms": "1", "registry_poll_ms": "100",
        })
        proxy = None
        stop = None
        try:
            for _, port in procs:
                assert _wait_ready("127.0.0.1", port, 120.0), \
                    f"replica on port {port} never became ready"
            proxy = FleetProxy(("127.0.0.1", 0),
                               [f"127.0.0.1:{p}" for _, p in procs],
                               health_poll_s=0.2, retry_deadline_s=20.0)
            threading.Thread(target=proxy.serve_forever,
                             daemon=True).start()
            port = proxy.server_address[1]
            rng = np.random.RandomState(21)
            rows = rng.randn(2, N_FEATURES)
            stop, threads, stats = _traffic(port, rows)
            canary_before = metrics_registry.counter(
                "lightgbm_tpu_proxy_canary_requests_total").value()

            # ---- run 2: append -> warm retrain -> canary -> promote
            _write_chunk(data_dir, "chunk-000.csv", 150, 1)
            _write_chunk(data_dir, "chunk-001.csv", 150, 2)
            sup = FactorySupervisor(
                data_dir, os.path.join(tmp, "work"), reg_dir,
                params=dict(TRAIN_PARAMS), proxy=f"127.0.0.1:{port}",
                num_boost_round=5, checkpoint_freq=2, debounce_ms=0.0,
                canary_fraction=0.5, observe_s=3.0, min_requests=5)
            verdict = sup.run_cycle()
            assert verdict is not None and verdict["verdict"] == "promoted"
            assert verdict["version"] == 2 and verdict["warm_start"]
            canary_obs = verdict["detail"]["canary"]
            assert canary_obs["requests"] >= 5
            assert canary_obs["errors"] == 0
            assert sup.registry.active_version() == 2
            # the canary route really carried proxy traffic...
            assert metrics_registry.counter(
                "lightgbm_tpu_proxy_canary_requests_total").value() \
                > canary_before
            # ...and was torn down after the verdict
            assert proxy.stats()["canary"] is None
            # keep traffic flowing until the fleet serves v2
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                if any(r[0] == 2 for r in stats["replies"][-20:]):
                    break
                time.sleep(0.1)

            # ---- run 3: canary sees NO traffic -> refuse to promote
            stop.set()
            for t in threads:
                t.join(timeout=60)
            _write_chunk(data_dir, "chunk-002.csv", 150, 3)
            sup3 = FactorySupervisor(
                data_dir, os.path.join(tmp, "work"), reg_dir,
                params=dict(TRAIN_PARAMS), proxy=f"127.0.0.1:{port}",
                num_boost_round=5, checkpoint_freq=2, debounce_ms=0.0,
                canary_fraction=0.5, observe_s=1.0, min_requests=1000)
            verdict3 = sup3.run_cycle()
            assert verdict3["verdict"] == "rolled_back"
            assert "refusing to promote blind" in verdict3["reason"]
            assert sup3.registry.active_version() == 2  # held the fort
            assert sup3.registry.quarantined() == {3: verdict3["reason"]}
            hist = FactoryState.load(sup3.workdir).history
            assert [h["verdict"] for h in hist] == \
                ["promoted", "promoted", "rolled_back"]

            # ---- zero dropped, zero mis-versioned, outputs bit-checked
            assert stats["errors"] == [], stats["errors"][:5]
            assert stats["n"] > 0
            seen = {v for v, _ in stats["replies"]}
            assert seen <= {1, 2}, seen
            assert 2 in seen, "promotion never reached fleet traffic"
            expected = {v: PackedPredictor(sup.registry.load(v)).predict(rows)
                        for v in seen}
            for ver, preds in stats["replies"]:
                assert np.allclose(preds, expected[ver]), \
                    f"v{ver} reply does not match v{ver} model"
        finally:
            if stop is not None:
                stop.set()
            if proxy is not None:
                proxy.shutdown()
                proxy.server_close()
            for p, _ in procs:
                p.kill()
                p.wait(timeout=30)


@pytest.mark.factory
@pytest.mark.fleet
@pytest.mark.slow
class TestFactorySustained:
    def test_repeated_appends_promote_under_traffic(self, tmp_path):
        """Sustained leg: three successive appends each drive a full
        warm-retrain -> canary -> promote cycle under continuous
        closed-loop traffic; the fleet ends on the last version with a
        clean reply ledger."""
        tmp = str(tmp_path)
        data_dir = os.path.join(tmp, "data")
        reg_dir = os.path.join(tmp, "reg")
        os.makedirs(data_dir)
        _write_chunk(data_dir, "chunk-000.csv", 300, 0)
        boot = FactorySupervisor(data_dir, os.path.join(tmp, "work"),
                                 reg_dir, params=dict(TRAIN_PARAMS),
                                 **FACTORY_KNOBS)
        assert boot.run_cycle()["verdict"] == "promoted"
        procs = spawn_replicas(2, {
            "registry": reg_dir, "warmup_max_rows": "64",
            "max_delay_ms": "1", "registry_poll_ms": "100",
        })
        proxy = None
        stop = None
        try:
            for _, port in procs:
                assert _wait_ready("127.0.0.1", port, 120.0)
            proxy = FleetProxy(("127.0.0.1", 0),
                               [f"127.0.0.1:{p}" for _, p in procs],
                               health_poll_s=0.2, retry_deadline_s=20.0)
            threading.Thread(target=proxy.serve_forever,
                             daemon=True).start()
            port = proxy.server_address[1]
            rng = np.random.RandomState(22)
            rows = rng.randn(2, N_FEATURES)
            stop, threads, stats = _traffic(port, rows, n_threads=3)
            sup = FactorySupervisor(
                data_dir, os.path.join(tmp, "work"), reg_dir,
                params=dict(TRAIN_PARAMS), proxy=f"127.0.0.1:{port}",
                num_boost_round=4, checkpoint_freq=2, debounce_ms=0.0,
                canary_fraction=0.5, observe_s=2.5, min_requests=5)
            for i in range(1, 4):
                _write_chunk(data_dir, f"chunk-{i:03d}.csv", 120, i)
                verdict = sup.run_cycle()
                assert verdict["verdict"] == "promoted", verdict
                assert verdict["version"] == 1 + i
            assert sup.registry.active_version() == 4
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                if any(r[0] == 4 for r in stats["replies"][-20:]):
                    break
                time.sleep(0.1)
            stop.set()
            for t in threads:
                t.join(timeout=60)
            assert stats["errors"] == [], stats["errors"][:5]
            seen = {v for v, _ in stats["replies"]}
            assert seen <= {1, 2, 3, 4}
            assert 4 in seen, "final promotion never reached traffic"
            expected = {v: PackedPredictor(sup.registry.load(v)).predict(rows)
                        for v in seen}
            for ver, preds in stats["replies"]:
                assert np.allclose(preds, expected[ver])
        finally:
            if stop is not None:
                stop.set()
            if proxy is not None:
                proxy.shutdown()
                proxy.server_close()
            for p, _ in procs:
                p.kill()
                p.wait(timeout=30)
